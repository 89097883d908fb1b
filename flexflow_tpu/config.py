"""Runtime configuration.

The analog of the reference's FFConfig (reference: include/config.h:88-140,
defaults src/runtime/model.cc:1917-1968, parse_args model.cc:1970-2071).
Legion's `-ll:*` processor/memory knobs become mesh-shape knobs; the strategy
table is a map op-name -> ParallelConfig, persisted in the reference's text
schema (src/runtime/strategy.cc).
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, List, Optional, Tuple

MAX_NUM_WORKERS = 1024  # reference: include/config.h:30-42
MAX_TENSOR_DIM = 5
MAX_NUM_INPUTS = 8
MAX_NUM_WEIGHTS = 4
MAX_NUM_OUTPUTS = 8


@dataclasses.dataclass
class FFConfig:
    # training flags (reference defaults model.cc:1917-1938)
    batch_size: int = 64
    epochs: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 1e-4
    iterations: int = 1  # per-epoch iteration override for synthetic runs

    # parallelism / machine shape (replaces -ll:gpu/-ll:cpu/numNodes)
    num_devices: Optional[int] = None  # default: all visible jax devices
    mesh_shape: Optional[Dict[str, int]] = None  # e.g. {"data": 8} or {"data": 4, "model": 2}
    ici_mesh_shape: Optional[Dict[str, int]] = None
    # axis -> number of hosts it spans; feeds the search's two-tier machine
    # model (collectives over these axes are priced at DCN bandwidth)
    dcn_mesh_shape: Optional[Dict[str, int]] = None

    # search flags (reference model.cc:1930-1932)
    search_budget: int = 0
    search_alpha: float = 0.05
    import_strategy_file: str = ""
    export_strategy_file: str = ""
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    # search cost-table fidelity: False/"" = analytic roofline; "analyze" =
    # compile-only XLA cost_analysis (flops/bytes through the machine model);
    # True/"measure" = real on-device fwd+bwd timing (reference:
    # measure_operator_cost, simulator.cc:296-316)
    measure_search_costs: object = False
    # persistent op-cost DB (search/cost_db.py): measured/analyzed entries
    # keyed by op signature + environment survive the process, so a
    # warm-started search re-measures zero already-keyed ops. "" = off
    # (hermetic in-process caches only); the FF_COST_DB env var also
    # activates it when this field is unset
    cost_db_path: str = ""

    # dataloader (native threaded gather/prefetch; reference's dataloader is
    # native too — flexflow_dataloader.cc)
    native_dataloader: bool = True   # fall back to Python slicing if no g++
    dataloader_shuffle: bool = False  # reference slices sequentially
    dataloader_threads: int = 2
    dataloader_prefetch_slots: int = 3

    # execution flags
    sp_mode: str = "ring"  # sequence-parallel lowering: "ring" | "ulysses"
    profiling: bool = False
    # write the simulated schedule as DOT after compile (reference
    # --taskgraph, model.cc:2066-2069)
    taskgraph_file: str = ""
    # graph-level FusedOp pass (ops/fused.py); XLA fuses kernels regardless
    perform_fusion: bool = False
    simulator_workspace_size: int = 2 * 1024 * 1024 * 1024
    compute_dtype: str = "float32"  # "bfloat16" for MXU-native training
    # storage dtype of master weights/optimizer state. "bfloat16" halves the
    # optimizer's HBM traffic and removes the per-step f32->bf16 cast pass
    # (the measured ~7ms/step non-layer overhead, round-2 notes); update
    # MATH stays f32 inside the optimizer regardless
    master_dtype: str = "float32"
    # fuse residual-add + layernorm into one Pallas kernel in models that
    # opt in (models/transformer.py encoder blocks)
    use_fused_ln: bool = False
    # single-fusion optimizer update over flattened param buckets
    # (runtime/optimizer.py FusedUpdate): one elementwise kernel per dtype
    # instead of one per weight. Applies only when every param is
    # replicated (single chip / pure DP); sharded strategies fall back
    fused_optimizer: bool = False
    use_flash_attention: bool = True  # Pallas flash kernel on the dense path
    # multi-step scanned training (executor.make_train_scan): fit() runs up
    # to this many steps per device dispatch via lax.scan — the TPU-native
    # analog of the reference's Legion tracing replay around each iteration
    # (base_model.py:408-418). 0 = one dispatch per step (per-step verbs
    # keep working either way). Requires device-resident data.
    scan_steps: int = 0
    # gradient accumulation: split each global batch into this many equal
    # microbatches scanned through fwd+bwd with ONE optimizer update —
    # numerically the full-batch step (losses are batch means), at a
    # microbatch's activation memory. 1 = off.
    grad_accum_steps: int = 1
    # FSDP / ZeRO-3 analog: shard every weight (and with it the optimizer
    # state) over this mesh axis in addition to any strategy sharding —
    # each weight's largest divisible un-sharded dim is split, GSPMD
    # all-gathers at use and reduce-scatters the gradient. Param + opt
    # HBM divides by the axis size. "" = off.
    fsdp_axis: str = ""
    # in-graph compute/communication overlap (runtime/executor.py +
    # runtime/optimizer.py Zero1Update): reduce each microbatch's
    # gradients into data-axis-scattered per-op buckets INSIDE the
    # accumulation scan (the collective for microbatch k overlaps the
    # backward of microbatch k+1) and run the optimizer update sharded
    # ZeRO-1 style — each data shard updates its slice of params and
    # optimizer state from the already-scattered grads, then params
    # all-gather ONCE. Optimizer-state HBM divides by the data degree;
    # composes with fsdp_axis (a weight the FSDP axis already shards
    # keeps its ZeRO-3 layout). No-op on meshes without a data axis > 1.
    overlap_grad_sync: bool = False
    # async checkpointing (runtime/checkpoint.py): save_checkpoint
    # snapshots params to host in-step and runs the atomic tmp-dir +
    # manifest + publish-rename path on ONE background publisher thread,
    # so checkpoint_every stops costing step time. The TrainSupervisor
    # quiesces pending saves at SIGTERM/rewind/final; single-controller
    # only (multihost saves are collective and stay synchronous).
    async_checkpointing: bool = False
    # fflint (flexflow_tpu/analysis): static strategy validation inside
    # compile(), after the table is final but before params/programs are
    # built. "warn" logs violations through fflogger; "strict" raises
    # StrategyLintError on any error-severity finding (a bad strategy file
    # is then rejected in milliseconds with the op + rule named, instead
    # of failing deep inside mesh construction or XLA compile); "off"
    # skips the analyzer entirely.
    strategy_lint: str = "warn"
    # label value excluded from token-level accuracy (count AND
    # denominator) — set to the pad id for causal-LM training so padded
    # positions don't dilute the metric; None counts every position
    metrics_ignore_index: int = None
    # keep datasets device-resident (next_batch = on-device slice, the
    # reference's ZC-resident design) when they fit the budget
    device_resident_data: bool = True
    device_data_budget_bytes: int = 2 << 30
    seed: int = 0

    # ---- host-overlap step engine (runtime/pipeline_loader.py) ----
    # bounded background prefetch for host-resident data in fit(): a
    # worker thread pulls batches and device_puts them (committed) up to
    # this many ahead, so the hot loop's batch is already on device.
    # 0 = synchronous staging (the old loop). Device-resident datasets
    # bypass this (their next_batch is already an on-device slice).
    prefetch_depth: int = 2
    # max training steps in flight before fit() blocks on the OLDEST
    # step's loss scalar (a device-progress wait, not a host sync on the
    # current step). Bounds queued work + host memory; losses/metrics
    # still drain asynchronously at epoch boundaries. 0 = wait for each
    # step's own loss (fully synchronous device progress, for debugging).
    dispatch_ahead: int = 2

    # ---- fault tolerance (runtime/resilience.py) ----
    # checkpoint directory for the TrainSupervisor / fit() auto-resume.
    # "" = no supervision (fit behaves exactly as before)
    checkpoint_dir: str = ""
    # periodic checkpoint cadence in steps (0 = only preemption/final
    # saves); atomic tmp-dir + rename writes, see runtime/checkpoint.py
    checkpoint_every: int = 0
    keep_checkpoints: int = 3  # retention: newest K step dirs survive
    # divergence guard compiled INTO the train step (one jnp.isfinite
    # reduction over loss + global grad-norm; skip/keep selected in-graph):
    #   "none"    — guard off, the step program is byte-identical to before
    #   "skip"    — non-finite steps leave params/opt state untouched
    #   "backoff" — skip + halve the loss scale on non-finite, regrow
    #               after loss_scale_growth_interval clean steps
    on_nonfinite: str = "none"
    # rewind-to-last-checkpoint after this many CONSECUTIVE non-finite
    # steps (0 = never rewind; requires a checkpoint_dir supervisor)
    nonfinite_rewind_after: int = 0
    # wall-clock watchdog per train step: dump all thread stacks and abort
    # when a step's host fetch blocks longer than this (0 = off). Hung
    # cross-host collectives otherwise block forever with no diagnostics.
    step_timeout_s: float = 0.0
    loss_scale: float = 1.0  # initial loss scale ("backoff" mode)
    loss_scale_growth_interval: int = 200

    # ---- elastic recovery (runtime/elastic.py) ----
    # what a resuming process does when its actual topology (visible
    # devices / mesh) differs from the checkpoint's:
    #   "resume_resharded" — refit the mesh to the surviving devices
    #       (csim-ranked candidates over the saved axes), re-shard the
    #       saved params/opt-state onto it, and preserve the GLOBAL batch
    #       by scaling grad_accum_steps with the data-degree change
    #   "research"        — same mesh refit, then re-run the MCMC strategy
    #       search at the new device count (budget: search_budget, else a
    #       small default) instead of re-deriving the saved strategy
    #   "abort"           — raise TopologyChangedError (the pre-elastic
    #       behavior, for jobs whose semantics pin the topology)
    on_topology_change: str = "resume_resharded"
    # verify the content-hash manifest (ff_manifest.json) of a checkpoint
    # before restoring, and fall back to the newest INTACT step when the
    # latest fails (torn write, bitrot, injected corruption)
    verify_checkpoints: bool = True
    # refuse to resume-reshard below this many devices (a 256-chip job
    # "recovering" onto 2 chips is an outage, not elasticity)
    elastic_min_devices: int = 1

    # ---- serving (runtime/serving.py: continuous batching) ----
    # decode slots in the ONE compiled slot-decode program; the host
    # scheduler admits/retires requests per slot
    serve_slots: int = 4
    # paged KV cache: pool of (kv_pages, kv_page_size, KVH, Dh) blocks
    # shared by all slots through per-slot page tables. kv_pages = 0
    # derives 1 (scratch) + serve_slots * ceil(max_seq_len /
    # kv_page_size) + prefix-cache slack (half the slot pages, at least
    # one slot's worth) when serve_prefix_cache is on — without the
    # slack the derived pool has zero free pages for refcount-0 cached
    # prefixes and the radix cache silently goes cold (ISSUE 18). The
    # engine logs the derived split at init.
    kv_page_size: int = 128
    kv_pages: int = 0
    # prompt-length admission buckets (ascending ints); None = powers of
    # two from 8 — warm prefill programs are reused within a bucket, and
    # ServingEngine.recompile_count proves it
    decode_buckets: Optional[List[int]] = None
    # radix prefix cache (runtime/kv_pool.py RadixPrefixCache): share KV
    # pages across requests whose prompts start with the same page-aligned
    # token prefix — admission mounts the cached pages read-only and
    # prefills only the tail (copy-on-write: shared pages are never
    # written). False = the PR-3 allocate-everything path.
    serve_prefix_cache: bool = True
    # speculative decoding: the draft model proposes this many greedy
    # tokens per slot per iteration; one fixed-shape verify program
    # scores all K+1 positions in a single dispatch. 0 = off. Greedy
    # streams stay token-identical to non-speculative decode.
    serve_speculate_k: int = 0
    # the compiled draft FFModel (same vocab as the target — validated at
    # engine construction). A runtime object, not a flag: pass it
    # programmatically or via make_serving_engine(draft_model=...)
    draft_model: Optional[object] = None
    # fleet router (runtime/router.py ServingRouter): bound on the router
    # queue — submissions past it are REJECTED immediately (state
    # "rejected") instead of queueing, so accepted-request p99 TTFT stays
    # bounded under overload while excess load fails fast at the front
    # door. 0 = unbounded (the pre-router behavior: the queue grows with
    # the backlog and every request's tail latency grows with it).
    serve_max_queue: int = 0
    # ---- quantized serving tier (ISSUE 11) ----
    # storage dtype of the paged KV pool (runtime/serving.py):
    #   "native" — the compute dtype (float32/bfloat16), the pre-quant
    #              behavior
    #   "bf16"   — store pages in bfloat16 regardless of compute dtype
    #              (plain cast, no scales): halves an f32 pool
    #   "int8"   — symmetric int8 pages with per-page-per-kv-head f32
    #              scales stored alongside the pool; ~2x the tokens per
    #              pool byte vs bf16. Dequantization happens in VMEM —
    #              inside the Pallas paged-attention kernel, or fused
    #              into the einsum gather — so wide KV is never
    #              materialized in HBM.
    #   "fp8"    — float8_e4m3fn pages, same scale layout (needs a jax
    #              build with jnp.float8_e4m3fn; validated at engine
    #              construction, not here, so config objects stay
    #              backend-free)
    # The page allocator, COW rule, radix trie, router affinity and
    # speculation are page-granular and unchanged — a page simply holds
    # more tokens per byte, multiplying prefix-cache capacity and
    # slots-per-chip at fixed HBM. Quantized KV is lossy: greedy streams
    # carry a per-dtype divergence budget vs the full-width path
    # (docs/serving.md "Quantized tier").
    kv_cache_dtype: str = "native"
    # serving-weight storage for the fixed-shape decode/prefill programs
    # (runtime/generation.py weight-only quantization, promoted to a
    # first-class serving mode): "native" | "int8" | "fp8". Quantization
    # happens ONCE at engine init (per-output-channel scales); dequant
    # fuses into each consuming matmul, so the HBM weight read per decode
    # step — the decode bottleneck — is the quantized bytes.
    serve_weight_dtype: str = "native"
    # ---- disaggregated fleet + tiered prefix cache (ISSUE 12) ----
    # pinned host-memory second tier under the radix prefix cache
    # (runtime/serving.py): refcount-0 KV pages evicted under pool
    # pressure DEMOTE to host RAM (async ordered D2H) instead of dying,
    # and a trie match against a host-resident edge PROMOTES the page
    # back (H2D, bitwise). Sized in pages of kv_page_size positions —
    # the effective shared-prefix corpus becomes host-RAM-sized instead
    # of HBM-sized. 0 = off (the PR-6 evict-means-die behavior).
    host_kv_pages: int = 0
    # fleet replica roles (runtime/router.py ServingRouter): ""
    # (default) = every replica "mixed", bit-identical to the pre-role
    # fleet. A comma-separated list, one per replica (e.g.
    # "prefill,decode,decode"), turns on the disaggregated role split:
    # prefill replicas absorb long-prompt admission and hand the
    # finished KV pages off to decode replicas as a serialized page
    # slab, keeping decode slot occupancy high under bursty long-prompt
    # traffic. Roles are placement preferences, never constraints — a
    # dead tier degrades to the mixed-fleet path.
    serve_replica_roles: str = ""
    # ---- long-context serving (ISSUE 18) ----
    # chunk-interleaved admission (runtime/serving.py): > 0 turns an
    # admitted cold prompt's prefill chunks into schedulable quanta —
    # the scheduler runs at most this many prefill chunks per step()
    # between decode ticks, so a 100k-token prompt admits without
    # head-of-line-blocking the replica's decode streams. Partial
    # prefill state is slot-resident (the slot is held, inactive, until
    # the last chunk lands); greedy/sampled streams are token-identical
    # to run-to-completion admission. 0 = off (prefill completes at
    # admission, the pre-18 behavior).
    prefill_interleave_chunks: int = 0
    # sequence-parallel prefill (runtime/router.py): >= 2 splits a
    # long prompt's page-aligned prefix into that many contiguous
    # sequence shards fanned out across the prefill tier; each shard
    # exports its KV pages as a partial-prefix slab
    # (export_prefix_slab(start_page=...)) and the decode replica
    # merges them in order through import_prefix_slab. Bitwise the
    # single-replica prefill (tests/test_seq_parallel.py pins page and
    # pool equality). Requires a handoff-capable fleet
    # (serve_replica_roles); 0/1 = off.
    seq_parallel_shards: int = 0
    # ---- multi-tenant serving (ISSUE 14) ----
    # per-request sampling DEFAULTS (submit() overrides per request;
    # the values ride the one fixed-shape slot program as per-slot
    # scalars — ops/sampling.py): temperature 0 = greedy argmax
    # (bitwise the pre-sampling path), top_p in (0, 1] (1 = off),
    # top_k >= 0 (0 = off). Sample streams are counter-based on the
    # request seed, so they reproduce across slot reassignment and
    # failover resubmission.
    serve_temperature: float = 0.0
    serve_top_p: float = 1.0
    serve_top_k: int = 0
    # paged LoRA adapter pool (runtime/lora.py + ops/lora.py): device
    # pages for concurrently-resident adapters (0 = no pool). Each page
    # holds one adapter's (a, b) weights for every LoRA-targeted Linear
    # op at rank serve_lora_rank; a host allocator/LRU faults
    # registered adapters in through ONE fixed-shape writer, so N
    # tenants share a replica with zero recompiles.
    serve_adapter_pool_pages: int = 0
    serve_lora_rank: int = 8
    # ---- unified telemetry plane (runtime/telemetry.py, ISSUE 13) ----
    # "on" (default): the metrics registry records counters/histograms
    # and the trace ring records per-request / per-step spans — the
    # substrate stats()/health() export through. "off": span creation
    # returns a shared no-op and every observe/inc short-circuits at one
    # predicate.
    telemetry: str = "on"
    # serve a Prometheus text endpoint (/metrics), a JSON snapshot
    # (/metrics.json) and the Chrome trace ring (/trace.json) on
    # 127.0.0.1:<port> from a stdlib http.server daemon thread. 0 = no
    # server (the default; the registry still records — export is pull).
    # Engines/routers/fit start it lazily on first use; one per process.
    metrics_port: int = 0
    # ---- flight recorder + SLO health plane (runtime/flightrec.py,
    # ISSUE 15) ----
    # post-mortem bundle directory: every trigger (watchdog fire,
    # replica fence, nonfinite rewind, uncaught engine/driver
    # exception, SIGTERM preempt, any fired FF_FAULT, an SLO breach
    # with slo_trip_recorder, or a manual dump_flight_record()) writes
    # an atomic manifest-hashed bundle here (trace window + metrics
    # snapshot + recent logs + trigger cause/stack + config/env
    # fingerprint + per-engine stats + the HBM ledger). "" = auto
    # triggers disabled (the in-memory window still records;
    # FF_FLIGHT_DIR is the env fallback). telemetry="off" disables the
    # recorder at the same single predicate as every other emit.
    flight_recorder_dir: str = ""
    flight_keep: int = 4          # retention: newest K bundles survive
    # one bundle per cooldown window — a crash storm writes one bundle,
    # the rest count as suppressed in the next bundle's trigger.json
    flight_cooldown_s: float = 30.0
    # triggers arriving within this of the first merge into ONE pending
    # bundle (the storm's causes are all listed); flush() forces the
    # pending write immediately
    flight_debounce_s: float = 1.0
    flight_window_s: float = 120.0  # trace-ring window a bundle captures
    # declarative SLOs, evaluated over sliding windows of the telemetry
    # histograms / engine counters (runtime/flightrec.py SLOMonitor).
    # 0 = that SLO is off. A breach fires only after a full window,
    # emits ff_slo_breach_total{slo,replica} + a margin gauge + an
    # alert log + a trace annotation, flips /healthz to "breach", and
    # clears after slo_clear_windows consecutive healthy windows.
    slo_ttft_p99_s: float = 0.0          # ceiling: p99 TTFT per replica
    slo_queue_wait_p99_s: float = 0.0    # ceiling: engine queue wait p99
    slo_prefix_hit_rate_min: float = 0.0  # floor: prefix-cache hit rate
    slo_spec_accept_min: float = 0.0     # floor: speculative accept rate
    slo_step_time_p99_s: float = 0.0     # ceiling: train step p99
    slo_checkpoint_stall_s: float = 0.0  # ceiling: checkpoint stall p99
    slo_window_s: float = 10.0           # sliding evaluation window
    slo_clear_windows: int = 2           # hysteresis: healthy windows
    #                                      required to clear a breach
    # ---- ffsan runtime sanitizer (runtime/locks.py, ISSUE 16) ----
    # "" (default) leaves the env-derived FF_SANITIZE mode alone
    # (off unless the env sets it). "on": runtime locks created
    # from here on become order-asserting proxies checking every
    # acquisition against the declared hierarchy, and the engines'
    # retrace sentinel reports any post-warmup jit cache miss —
    # both routed to the flight recorder as incidents. "strict":
    # same checks, but violations raise. "off": force-disable.
    # Module-level locks (telemetry, native loader) are created at
    # import, before any FFConfig exists — set FF_SANITIZE for
    # process-wide coverage (what the CI sanitize tier does).
    sanitize: str = ""
    slo_trip_recorder: bool = False      # breach also trips the recorder
    # ---- rolling deployment (runtime/deploy.py, ISSUE 17) ----
    # watch path the weight-version registry scans: async checkpointing
    # publishes manifest-verified artifacts here (save_checkpoint
    # step_<N> layout; version "v<N>"), and RollingDeployer.deploy()
    # rolls the fleet onto the newest intact one. "" = no watch path
    # (pass one to WeightArtifactRegistry directly).
    deploy_watch_dir: str = ""
    # canary soak: the first swapped replica serves under its own
    # rebaselined SLO windows for this many full slo_window_s windows;
    # any breach attributed to it inside the soak rolls the whole
    # deploy back. 0 = no soak (swap and move on — the drill-less path).
    deploy_canary_windows: int = 2
    # hard ceiling on one replica's drain-quiesce wait during a roll
    # (seconds): a replica that cannot quiesce aborts the deploy
    # (state "failed") instead of wedging the roll forever
    deploy_drain_timeout_s: float = 120.0
    # ---- elastic fleet (runtime/autoscale.py, ISSUE 20) ----
    # AutoscalePolicy bounds + hysteresis: scale OUT only after
    # slo_queue_wait/slo_ttft breaches persist across this many
    # consecutive policy windows, scale IN only after this many idle
    # windows, and never act twice within the cooldown — a breach
    # storm cannot thrash the fleet. One policy window = one
    # slo_window_s evaluation.
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 8
    autoscale_breach_windows: int = 2    # breach windows before scale-out
    autoscale_idle_windows: int = 6      # idle windows before scale-in
    autoscale_cooldown_s: float = 30.0   # min seconds between actions
    # preemption evacuation: a SIGTERM'd (or FF_FAULT `preempt`) replica
    # races this deadline to hand queued/in-flight requests and hot
    # prefix slabs to survivors; on expiry it degrades to a plain fence
    # (remaining work resubmits cold, exactly-once either way)
    preempt_deadline_s: float = 5.0

    # populated at FFModel construction
    strategies: Dict[str, "ParallelConfig"] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.grad_accum_steps < 1:
            raise ValueError(
                f"grad_accum_steps={self.grad_accum_steps}: must be >= 1")
        if self.batch_size % max(1, self.grad_accum_steps):
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by "
                f"grad_accum_steps {self.grad_accum_steps}")
        if self.strategy_lint not in ("off", "warn", "strict"):
            raise ValueError(
                f"strategy_lint={self.strategy_lint!r}: must be 'off', "
                f"'warn' or 'strict'")
        if self.on_nonfinite not in ("none", "skip", "backoff"):
            raise ValueError(
                f"on_nonfinite={self.on_nonfinite!r}: must be 'none', "
                f"'skip' or 'backoff'")
        if self.nonfinite_rewind_after < 0 or self.checkpoint_every < 0:
            raise ValueError(
                "nonfinite_rewind_after and checkpoint_every must be >= 0")
        if self.prefetch_depth < 0 or self.dispatch_ahead < 0:
            raise ValueError(
                f"prefetch_depth={self.prefetch_depth} and dispatch_ahead="
                f"{self.dispatch_ahead} must be >= 0")
        if self.loss_scale <= 0:
            # 0 would make the guard divide by zero and classify EVERY
            # step non-finite — the run would "complete" training nothing
            raise ValueError(
                f"loss_scale={self.loss_scale}: must be > 0")
        if self.loss_scale_growth_interval < 1:
            raise ValueError(
                f"loss_scale_growth_interval="
                f"{self.loss_scale_growth_interval}: must be >= 1")
        if self.on_topology_change not in ("resume_resharded", "research",
                                           "abort"):
            raise ValueError(
                f"on_topology_change={self.on_topology_change!r}: must be "
                f"'resume_resharded', 'research' or 'abort'")
        if self.elastic_min_devices < 1:
            raise ValueError(
                f"elastic_min_devices={self.elastic_min_devices}: "
                f"must be >= 1")
        if self.serve_slots < 1 or self.kv_page_size < 1 \
                or self.kv_pages < 0:
            raise ValueError(
                f"serve_slots={self.serve_slots} (>= 1), "
                f"kv_page_size={self.kv_page_size} (>= 1), "
                f"kv_pages={self.kv_pages} (>= 0, 0 = derive)")
        if self.kv_page_size & (self.kv_page_size - 1):
            # pow2 keeps position->page arithmetic exact under the pow2
            # prompt buckets AND keeps the radix chunk boundary aligned
            # with every bucket boundary (a non-pow2 page would let a
            # bucket end mid-page, splitting prefix chunks across
            # programs)
            raise ValueError(
                f"kv_page_size={self.kv_page_size}: must be a power of "
                f"two")
        if self.serve_speculate_k < 0:
            raise ValueError(
                f"serve_speculate_k={self.serve_speculate_k}: must be "
                f">= 0 (0 = speculative decoding off)")
        if self.prefill_interleave_chunks < 0:
            raise ValueError(
                f"prefill_interleave_chunks="
                f"{self.prefill_interleave_chunks}: must be >= 0 "
                f"(0 = run-to-completion prefill at admission)")
        if self.seq_parallel_shards < 0 or self.seq_parallel_shards == 1:
            raise ValueError(
                f"seq_parallel_shards={self.seq_parallel_shards}: must "
                f"be 0 (off) or >= 2 (shard count)")
        if self.serve_max_queue < 0:
            raise ValueError(
                f"serve_max_queue={self.serve_max_queue}: must be >= 0 "
                f"(0 = unbounded router queue)")
        if self.host_kv_pages < 0:
            raise ValueError(
                f"host_kv_pages={self.host_kv_pages}: must be >= 0 "
                f"(0 = no host tier)")
        if self.serve_replica_roles:
            roles = [t.strip()
                     for t in self.serve_replica_roles.split(",")]
            bad = [t for t in roles
                   if t not in ("prefill", "decode", "mixed")]
            if bad or not all(roles):
                raise ValueError(
                    f"serve_replica_roles={self.serve_replica_roles!r}: "
                    f"comma-separated 'prefill'|'decode'|'mixed', one "
                    f"per replica (bad: {bad or 'empty entry'})")
        # ONE validation rule for sampling params, shared with
        # engine/router submit paths (ops/sampling.py) — config-time and
        # submit-time acceptance can never diverge
        from flexflow_tpu.ops.sampling import validate_sampling

        validate_sampling(
            self.serve_temperature, self.serve_top_p, self.serve_top_k,
            "FFConfig (serve_temperature/serve_top_p/serve_top_k)")
        if self.serve_adapter_pool_pages < 0:
            raise ValueError(
                f"serve_adapter_pool_pages={self.serve_adapter_pool_pages}"
                f": must be >= 0 (0 = no adapter pool)")
        if self.serve_lora_rank < 1:
            raise ValueError(
                f"serve_lora_rank={self.serve_lora_rank}: must be >= 1")
        if self.sanitize not in ("", "off", "on", "strict"):
            raise ValueError(
                f"sanitize={self.sanitize!r}: must be '', 'off', "
                f"'on' or 'strict'")
        if self.telemetry not in ("on", "off"):
            raise ValueError(
                f"telemetry={self.telemetry!r}: must be 'on' or 'off'")
        if self.metrics_port < 0 or self.metrics_port > 65535:
            raise ValueError(
                f"metrics_port={self.metrics_port}: must be 0 (no "
                f"server) or a valid TCP port")
        if self.flight_keep < 1:
            raise ValueError(
                f"flight_keep={self.flight_keep}: must be >= 1 (the "
                f"bundle that just fired must survive its own retention)")
        if self.flight_cooldown_s < 0 or self.flight_debounce_s < 0:
            raise ValueError(
                f"flight_cooldown_s={self.flight_cooldown_s} and "
                f"flight_debounce_s={self.flight_debounce_s} must be "
                f">= 0")
        if self.flight_window_s <= 0:
            raise ValueError(
                f"flight_window_s={self.flight_window_s}: must be > 0")
        for knob in ("slo_ttft_p99_s", "slo_queue_wait_p99_s",
                     "slo_step_time_p99_s", "slo_checkpoint_stall_s"):
            if getattr(self, knob) < 0:
                raise ValueError(
                    f"{knob}={getattr(self, knob)}: must be >= 0 "
                    f"(0 = SLO off)")
        for knob in ("slo_prefix_hit_rate_min", "slo_spec_accept_min"):
            v = getattr(self, knob)
            if v < 0 or v > 1:
                raise ValueError(
                    f"{knob}={v}: must be in [0, 1] (0 = SLO off; it "
                    f"is a rate floor)")
        if self.slo_window_s <= 0:
            raise ValueError(
                f"slo_window_s={self.slo_window_s}: must be > 0")
        if self.slo_clear_windows < 1:
            raise ValueError(
                f"slo_clear_windows={self.slo_clear_windows}: must be "
                f">= 1 (a breach must be clearable)")
        if self.deploy_canary_windows < 0:
            raise ValueError(
                f"deploy_canary_windows={self.deploy_canary_windows}: "
                f"must be >= 0 (0 = no canary soak)")
        if self.deploy_drain_timeout_s <= 0:
            raise ValueError(
                f"deploy_drain_timeout_s={self.deploy_drain_timeout_s}: "
                f"must be > 0")
        if self.autoscale_min_replicas < 1:
            raise ValueError(
                f"autoscale_min_replicas={self.autoscale_min_replicas}: "
                f"must be >= 1 (the fleet must keep a survivor)")
        if self.autoscale_max_replicas < self.autoscale_min_replicas:
            raise ValueError(
                f"autoscale_max_replicas={self.autoscale_max_replicas}: "
                f"must be >= autoscale_min_replicas "
                f"({self.autoscale_min_replicas})")
        if self.autoscale_breach_windows < 1:
            raise ValueError(
                f"autoscale_breach_windows={self.autoscale_breach_windows}"
                f": must be >= 1")
        if self.autoscale_idle_windows < 1:
            raise ValueError(
                f"autoscale_idle_windows={self.autoscale_idle_windows}: "
                f"must be >= 1")
        if self.autoscale_cooldown_s < 0:
            raise ValueError(
                f"autoscale_cooldown_s={self.autoscale_cooldown_s}: "
                f"must be >= 0")
        if self.preempt_deadline_s <= 0:
            raise ValueError(
                f"preempt_deadline_s={self.preempt_deadline_s}: must be "
                f"> 0 (the evacuation race needs a budget)")
        if self.kv_cache_dtype not in ("native", "bf16", "int8", "fp8"):
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r}: must be "
                f"'native', 'bf16', 'int8' or 'fp8' (exact spelling — a "
                f"typo here would silently serve the wrong KV precision)")
        if self.serve_weight_dtype not in ("native", "int8", "fp8"):
            raise ValueError(
                f"serve_weight_dtype={self.serve_weight_dtype!r}: must "
                f"be 'native', 'int8' or 'fp8'")
        if self.decode_buckets is not None:
            bs = list(self.decode_buckets)
            if not bs or any(int(b) < 1 for b in bs) \
                    or sorted(set(int(b) for b in bs)) != [int(b) for b in bs]:
                raise ValueError(
                    f"decode_buckets={self.decode_buckets!r}: must be a "
                    f"strictly ascending list of positive ints")
        for field in ("compute_dtype", "master_dtype"):
            v = getattr(self, field)
            if v not in ("float32", "bfloat16"):
                raise ValueError(
                    f"{field}={v!r}: must be 'float32' or 'bfloat16' "
                    f"(exact spelling — a typo here would silently run the "
                    f"wrong precision)")
        if self.num_devices is None:
            if self.mesh_shape is not None:
                # derive from the mesh without touching the backend (keeps
                # graph-build/search-only flows from initializing devices)
                n = 1
                for s in self.mesh_shape.values():
                    n *= s
                self.num_devices = n
            else:
                import jax

                self.num_devices = len(jax.devices())
        if self.mesh_shape is None:
            self.mesh_shape = {"data": self.num_devices}

    @property
    def workers_per_node(self) -> int:
        return self.num_devices

    @property
    def num_nodes(self) -> int:
        return 1

    @staticmethod
    def parse_args(argv: Optional[List[str]] = None) -> "FFConfig":
        """CLI parity with reference flags (model.cc:1970-2071)."""
        p = argparse.ArgumentParser(add_help=False)
        p.add_argument("-e", "--epochs", type=int, default=1)
        p.add_argument("-b", "--batch-size", type=int, default=64)
        p.add_argument("--lr", "--learning-rate", dest="lr", type=float, default=0.01)
        p.add_argument("--wd", "--weight-decay", dest="wd", type=float, default=1e-4)
        p.add_argument("--budget", "--search-budget", dest="budget", type=int, default=0)
        p.add_argument("--alpha", "--search-alpha", dest="alpha", type=float, default=0.05)
        p.add_argument("--import", dest="import_file", type=str, default="")
        p.add_argument("--export", dest="export_file", type=str, default="")
        p.add_argument("--enable-parameter-parallel", action="store_true")
        p.add_argument("--enable-attribute-parallel", action="store_true")
        p.add_argument("--measure-costs", action="store_true")
        p.add_argument("--analyze-costs", action="store_true")
        p.add_argument("--cost-db", dest="cost_db", type=str, default="",
                       help="path to the persistent op-cost database "
                            "(JSON); measured/analyzed search costs are "
                            "read and written there so later searches "
                            "warm-start (also: FF_COST_DB env var)")
        p.add_argument("--taskgraph", dest="taskgraph", type=str, default="")
        p.add_argument("--profiling", action="store_true")
        p.add_argument("--fusion", action="store_true")
        p.add_argument("--num-devices", type=int, default=None)
        p.add_argument("--overlap-grad-sync", action="store_true",
                       help="bucketed grad reduce-scatter inside the "
                            "accumulation scan + ZeRO-1 sharded optimizer "
                            "update (opt-state HBM / data degree)")
        p.add_argument("--async-checkpointing", action="store_true",
                       help="publish checkpoints from a background thread "
                            "(snapshot in-step, fsync/manifest/rename off "
                            "the critical path)")
        p.add_argument("--fsdp", dest="fsdp_axis", nargs="?", const="data",
                       default="", metavar="AXIS",
                       help="shard params+optimizer state over AXIS "
                            "(default 'data') — ZeRO-3 analog")
        p.add_argument("--checkpoint-dir", type=str, default="",
                       help="enable the train supervisor: atomic periodic "
                            "checkpoints + auto-resume + SIGTERM handling")
        p.add_argument("--checkpoint-every", type=int, default=0)
        p.add_argument("--on-topology-change", type=str,
                       default="resume_resharded",
                       choices=("resume_resharded", "research", "abort"),
                       help="elastic resume policy when the visible "
                            "topology differs from the checkpoint's")
        p.add_argument("--no-verify-checkpoints", action="store_true",
                       help="skip content-hash manifest verification at "
                            "restore (on by default)")
        p.add_argument("--elastic-min-devices", type=int, default=1)
        p.add_argument("--serve-slots", type=int, default=4,
                       help="decode slots in the one compiled "
                            "slot-decode serving program")
        p.add_argument("--kv-page-size", type=int, default=128,
                       help="positions per paged-KV pool page "
                            "(power of two)")
        p.add_argument("--kv-pages", type=int, default=0,
                       help="KV pool pages (0 = derive the "
                            "no-pressure size)")
        p.add_argument("--no-prefix-cache", action="store_true",
                       help="disable the radix prefix cache "
                            "(on by default)")
        p.add_argument("--serve-speculate-k", type=int, default=0,
                       help="draft tokens proposed per speculative "
                            "decode iteration (0 = off; needs a "
                            "draft model)")
        p.add_argument("--serve-max-queue", type=int, default=0,
                       help="fleet-router queue bound: submissions past "
                            "it are rejected fast (0 = unbounded)")
        p.add_argument("--host-kv-pages", type=int, default=0,
                       help="pinned host-memory tier under the radix "
                            "prefix cache, in kv_page_size pages: "
                            "evicted ref-0 pages demote to host RAM "
                            "and promote back on a hit (0 = off)")
        p.add_argument("--serve-temperature", type=float, default=0.0,
                       help="default sampling temperature for serving "
                            "requests (0 = greedy argmax; per-request "
                            "submit() overrides)")
        p.add_argument("--serve-top-p", type=float, default=1.0,
                       help="default nucleus (top-p) filter in (0, 1] "
                            "(1 = off)")
        p.add_argument("--serve-top-k", type=int, default=0,
                       help="default top-k filter (0 = off)")
        p.add_argument("--serve-adapter-pool-pages", type=int, default=0,
                       help="paged LoRA adapter pool: device pages for "
                            "concurrently-resident adapters (0 = no "
                            "pool); tenants share one program, zero "
                            "recompiles")
        p.add_argument("--serve-lora-rank", type=int, default=8,
                       help="LoRA rank of the adapter pool's fixed page "
                            "geometry")
        p.add_argument("--serve-replica-roles", type=str, default="",
                       help="fleet replica roles, comma-separated "
                            "prefill|decode|mixed, one per replica "
                            "('' = all mixed); prefill replicas hand "
                            "finished KV pages off to decode replicas")
        p.add_argument("--prefill-interleave-chunks", type=int, default=0,
                       help="chunk-interleaved admission: max prefill "
                            "chunks the scheduler runs per step between "
                            "decode ticks (0 = run-to-completion "
                            "prefill at admission)")
        p.add_argument("--seq-parallel-shards", type=int, default=0,
                       help="sequence-parallel prefill: split a long "
                            "prompt's prefix into this many contiguous "
                            "shards across the prefill tier (0 = off, "
                            ">= 2 = shard count)")
        p.add_argument("--kv-cache-dtype", type=str, default="native",
                       choices=("native", "bf16", "int8", "fp8"),
                       help="paged KV pool storage dtype (int8/fp8: "
                            "per-page-per-head scales, in-kernel "
                            "dequant; 2-4x tokens per pool byte)")
        p.add_argument("--serve-weight-dtype", type=str, default="native",
                       choices=("native", "int8", "fp8"),
                       help="serving weight storage (weight-only "
                            "quantization with per-output-channel "
                            "scales, quantized once at engine init)")
        p.add_argument("--telemetry", type=str, default="on",
                       choices=("on", "off"),
                       help="unified telemetry plane: metrics registry "
                            "+ per-request trace ring (off = every "
                            "emit short-circuits)")
        p.add_argument("--sanitize", type=str, default="",
                       choices=("", "off", "on", "strict"),
                       help="ffsan runtime sanitizer: lock-order "
                            "asserting proxies + post-warmup "
                            "retrace sentinel ('' = follow "
                            "FF_SANITIZE; strict raises)")
        p.add_argument("--metrics-port", type=int, default=0,
                       help="serve Prometheus /metrics (+ /metrics.json"
                            ", /trace.json, /healthz, /slo.json) on "
                            "127.0.0.1:<port> (0 = no server)")
        p.add_argument("--flight-recorder-dir", type=str, default="",
                       help="post-mortem bundle directory: triggers "
                            "(watchdog/fence/rewind/fault/preempt/SLO "
                            "breach/manual) snapshot the recent trace "
                            "window + metrics + logs + HBM ledger into "
                            "atomic manifest-hashed bundles ('' = auto "
                            "triggers off)")
        p.add_argument("--flight-keep", type=int, default=4,
                       help="bundle retention: newest K survive")
        p.add_argument("--flight-cooldown-s", type=float, default=30.0,
                       help="one bundle per cooldown — a crash storm "
                            "writes one bundle, not N")
        p.add_argument("--flight-debounce-s", type=float, default=1.0,
                       help="triggers within this of the first merge "
                            "into ONE pending bundle (the storm's "
                            "causes all listed)")
        p.add_argument("--flight-window-s", type=float, default=120.0,
                       help="trace-ring window a bundle captures, in "
                            "seconds")
        p.add_argument("--slo-ttft-p99-s", type=float, default=0.0,
                       help="SLO ceiling: windowed p99 TTFT per replica "
                            "(0 = off)")
        p.add_argument("--slo-queue-wait-p99-s", type=float, default=0.0,
                       help="SLO ceiling: windowed p99 engine queue "
                            "wait (0 = off)")
        p.add_argument("--slo-prefix-hit-rate-min", type=float,
                       default=0.0,
                       help="SLO floor: windowed prefix-cache hit rate "
                            "(0 = off)")
        p.add_argument("--slo-spec-accept-min", type=float, default=0.0,
                       help="SLO floor: windowed speculative accept "
                            "rate (0 = off)")
        p.add_argument("--slo-step-time-p99-s", type=float, default=0.0,
                       help="SLO ceiling: windowed p99 train step time "
                            "(0 = off)")
        p.add_argument("--slo-checkpoint-stall-s", type=float,
                       default=0.0,
                       help="SLO ceiling: windowed p99 checkpoint "
                            "stall (0 = off)")
        p.add_argument("--slo-window-s", type=float, default=10.0,
                       help="SLO sliding-window length in seconds")
        p.add_argument("--slo-clear-windows", type=int, default=2,
                       help="hysteresis: consecutive healthy windows "
                            "required to clear a breach")
        p.add_argument("--slo-trip-recorder", action="store_true",
                       help="an SLO breach also trips the flight "
                            "recorder (needs --flight-recorder-dir)")
        p.add_argument("--autoscale-min-replicas", type=int, default=1,
                       help="elastic fleet: scale-in floor")
        p.add_argument("--autoscale-max-replicas", type=int, default=8,
                       help="elastic fleet: scale-out ceiling")
        p.add_argument("--autoscale-breach-windows", type=int, default=2,
                       help="consecutive SLO-breach windows before the "
                            "autoscaler adds a replica")
        p.add_argument("--autoscale-idle-windows", type=int, default=6,
                       help="consecutive idle windows before the "
                            "autoscaler retires a replica")
        p.add_argument("--autoscale-cooldown-s", type=float,
                       default=30.0,
                       help="refractory period between autoscaler "
                            "actions")
        p.add_argument("--preempt-deadline-s", type=float, default=5.0,
                       help="default evacuation budget when a replica "
                            "is preempted (SIGTERM/request_preempt)")
        # e.g. --mesh data=4,model=2 (replaces -ll:gpu device-count knobs)
        p.add_argument("--mesh", type=str, default="")
        args, _ = p.parse_known_args(argv)
        mesh_shape = None
        if args.mesh:
            mesh_shape = {}
            for part in args.mesh.split(","):
                ax, eq, size = part.partition("=")
                if not eq or not ax.strip() or not size.strip().isdigit() \
                        or int(size) < 1:
                    p.error(f"--mesh: bad entry {part!r}; expected "
                            f"'axis=size[,axis=size]', e.g. 'data=4,model=2'")
                mesh_shape[ax.strip()] = int(size)
        return FFConfig(
            batch_size=args.batch_size,
            epochs=args.epochs,
            learning_rate=args.lr,
            weight_decay=args.wd,
            search_budget=args.budget,
            search_alpha=args.alpha,
            import_strategy_file=args.import_file,
            export_strategy_file=args.export_file,
            enable_parameter_parallel=args.enable_parameter_parallel,
            enable_attribute_parallel=args.enable_attribute_parallel,
            measure_search_costs=("measure" if args.measure_costs else
                                  "analyze" if args.analyze_costs else False),
            cost_db_path=args.cost_db,
            taskgraph_file=args.taskgraph,
            profiling=args.profiling,
            perform_fusion=args.fusion,
            num_devices=args.num_devices,
            mesh_shape=mesh_shape,
            overlap_grad_sync=args.overlap_grad_sync,
            async_checkpointing=args.async_checkpointing,
            fsdp_axis=args.fsdp_axis,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
            on_topology_change=args.on_topology_change,
            verify_checkpoints=not args.no_verify_checkpoints,
            elastic_min_devices=args.elastic_min_devices,
            serve_slots=args.serve_slots,
            kv_page_size=args.kv_page_size,
            kv_pages=args.kv_pages,
            serve_prefix_cache=not args.no_prefix_cache,
            serve_speculate_k=args.serve_speculate_k,
            serve_max_queue=args.serve_max_queue,
            host_kv_pages=args.host_kv_pages,
            serve_temperature=args.serve_temperature,
            serve_top_p=args.serve_top_p,
            serve_top_k=args.serve_top_k,
            serve_adapter_pool_pages=args.serve_adapter_pool_pages,
            serve_lora_rank=args.serve_lora_rank,
            serve_replica_roles=args.serve_replica_roles,
            prefill_interleave_chunks=args.prefill_interleave_chunks,
            seq_parallel_shards=args.seq_parallel_shards,
            kv_cache_dtype=args.kv_cache_dtype,
            serve_weight_dtype=args.serve_weight_dtype,
            telemetry=args.telemetry,
            sanitize=args.sanitize,
            metrics_port=args.metrics_port,
            flight_recorder_dir=args.flight_recorder_dir,
            flight_keep=args.flight_keep,
            flight_cooldown_s=args.flight_cooldown_s,
            flight_debounce_s=args.flight_debounce_s,
            flight_window_s=args.flight_window_s,
            slo_ttft_p99_s=args.slo_ttft_p99_s,
            slo_queue_wait_p99_s=args.slo_queue_wait_p99_s,
            slo_prefix_hit_rate_min=args.slo_prefix_hit_rate_min,
            slo_spec_accept_min=args.slo_spec_accept_min,
            slo_step_time_p99_s=args.slo_step_time_p99_s,
            slo_checkpoint_stall_s=args.slo_checkpoint_stall_s,
            slo_window_s=args.slo_window_s,
            slo_clear_windows=args.slo_clear_windows,
            slo_trip_recorder=args.slo_trip_recorder,
            autoscale_min_replicas=args.autoscale_min_replicas,
            autoscale_max_replicas=args.autoscale_max_replicas,
            autoscale_breach_windows=args.autoscale_breach_windows,
            autoscale_idle_windows=args.autoscale_idle_windows,
            autoscale_cooldown_s=args.autoscale_cooldown_s,
            preempt_deadline_s=args.preempt_deadline_s,
        )
