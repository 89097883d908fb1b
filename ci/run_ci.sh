#!/bin/bash
# CI matrix (analog of the reference's .circleci/config.yml: build matrix
# {parameter-server, NCCL} x {build, 4-GPU tests} + nightly accuracy runs).
#
# Our matrix replaces gradient-sync backends (one XLA path here) with
# execution tiers:
#   unit      — pytest on the 8-device virtual CPU mesh (tests/conftest.py)
#   sweep     — every example end-to-end on the virtual mesh
#   accuracy  — accuracy-gated training runs (nightly tier)
#   native    — C shim + C++ apps build & run
#
#   resilience — fault-injection tests (FF_FAULT: kill-and-resume, NaN
#               skip/rewind, IO retry) + a 2-process multihost resume
#               smoke when the jax build has gloo CPU collectives
#   serving   — continuous-batching engine tests (incl. radix prefix
#               cache + speculative decoding) + a 200-request CPU smoke
#               with FF_FAULT=nan_loss injection and a skewed
#               shared-prefix phase (hits, 0 recompiles, no page leaks)
#   overlap   — host-overlap step engine tests (prefetch pipeline +
#               dispatch-ahead fit) + a slow-loader smoke asserting
#               throughput improves and host_wait drops; plus the
#               IN-GRAPH overlap drill (ISSUE 10): bucketed grad sync +
#               ZeRO-1 update pinned vs the serial epilogue, an
#               async-written manifest-verified checkpoint resuming
#               bitwise, and — gloo-gated — the same overlapped-sync
#               training preempted and resumed bitwise across TWO
#               controller processes
#   elastic   — elastic-recovery tests (topology-change resume, integrity
#               manifests, serving drain) + the corruption-injection
#               resume smoke + a 2-process run killed mid-epoch and
#               resumed SINGLE-process with on_topology_change=
#               resume_resharded (gloo-gated)
#   kernels   — Pallas kernel tier: paged-attention kernel parity vs the
#               einsum oracle + serving token-identity with the kernel
#               path forced (interpret mode on CPU = the REAL kernel
#               code), the block autotuner suite, and a tune-then-
#               consume smoke that writes and re-reads a real on-disk
#               autotune table
#   quant     — quantized serving tier: int8/fp8 KV pages (per-page-per-
#               head scales, in-kernel dequant) + weight-only int8/fp8
#               suites (scale round-trip, per-channel regression vs
#               per-tensor, pallas/einsum parity + token identity on
#               quantized pools, COW with quantized pages, divergence
#               budget vs full-width) + a serve-smoke leg running the
#               skewed shared-prefix workload on a bf16/int8 engine
#               pair — hit rate and zero warm-window recompiles must
#               match across dtypes
#   disagg    — disaggregated-fleet tier (ISSUE 12): the tiered-prefix-
#               cache state machine suite (pure host: demote/promote
#               ordering under the ordered publisher, cross-tier
#               refcounts, host LRU, abandoned-migration generation
#               check) + the fleet/engine integration suite (slab
#               handoff bitwise + token identity, role split, tier
#               faults, warmup variant sweep) + a 1-prefill/2-decode
#               smoke on skewed shared-prefix traffic with FF_FAULT
#               crashing the PREFILL replica mid-handoff — every
#               request completes exactly once via cold-path fallback,
#               token-identical, zero survivor recompiles — and a
#               working-set-3x-pool tiered-cache leg
#   obs       — unified-telemetry tier (ISSUE 13): the registry/tracing
#               suite (labeled series, histogram bucket math + quantile
#               estimates, concurrent-increment stress, Prometheus
#               exposition golden, trace-ring bounds, handoff/failover
#               span continuity, stats()/health() key superset pins) +
#               an obs smoke: a 1-prefill/2-decode fleet on skewed
#               shared-prefix traffic with a decode-replica crash
#               drill, /metrics scraped MID-RUN (TTFT/ITL histograms +
#               failover counters as labeled series over all replicas)
#               and the trace ring exported as perfetto-loadable
#               Chrome JSON in which every request has a complete span
#               tree and the failover/handoff requests each cross
#               replicas under ONE trace id; plus the flight-recorder /
#               SLO health plane (ISSUE 15): the recorder state-machine
#               suite (ring bounds, trigger debounce/cooldown, bundle
#               atomicity + torn-write drill, keep-K retention, SLO
#               window math with hysteresis, HBM ledger, /healthz
#               rollup), a post-mortem leg (the crash drill yields
#               exactly ONE manifest-intact bundle with complete
#               failed-over span trees) and an SLO leg (deterministic
#               slow()-fault TTFT breach: /healthz flips to breach
#               within one window and recovers)
#   router    — fleet-router tier: the multi-replica ServingRouter suite
#               (failover exactly-once + token identity incl. prefix
#               cache + speculation, deadline/shedding/affinity
#               semantics, hang detection, engine thread-safety) + a
#               2-replica 200-request smoke with FF_FAULT crashing
#               replica 0 mid-flight — all non-expired requests complete
#               exactly once, zero lost/duplicated, zero warm recompiles
#               on the survivor
#   tenancy   — multi-tenant serving tier (ISSUE 14): per-slot sampling
#               (counter-based seeded RNG, greedy bitwise at
#               temperature 0) + the paged LoRA adapter pool (host
#               allocator/LRU state machine, merged-weights stream
#               oracle, 8-tenant mixed-config zero-recompile pin,
#               per-adapter prefix-cache isolation) + rejection-sampled
#               speculation property tests (spec vs non-spec token
#               frequencies at K=1/3/8, small-draft and self-draft) +
#               seeded-reproducibility drills (slot reassignment,
#               engine instances, fleet failover) + an 8-adapter
#               mixed-sampling 2-replica fleet smoke under a mid-flight
#               crash: every seeded stream token-identical through
#               failover, adapter evicted + re-faulted under pool
#               pressure, zero warm-window recompiles, per-adapter
#               telemetry series present
#   deploy    — rolling-deployment tier (ISSUE 17): the weight-version
#               registry + RollingDeployer suite (drain->reopen, version-
#               salted prefix isolation, refused corrupt artifacts, torn-
#               swap rollback), then the 2-replica rolling-swap smoke: a
#               version published mid-flood rolls through the fleet with
#               every request served exactly once and zero warm-window
#               recompiles, and a second leg forces a canary SLO breach
#               (slow@canary) that must end in an automatic rollback —
#               fleet back on v1, exactly one manifest-intact post-mortem
#               bundle naming the breached SLO
#   longctx   — long-context serving tier (ISSUE 18): chunk-interleaved
#               admission + sequence-parallel prefill suites (token
#               identity interleaved vs run-to-completion, 2/3-shard
#               partial-slab merges bitwise, mid-prefill fault/deadline/
#               drain legs), then the smoke twice — plain and under
#               FF_SANITIZE=1: a maximal prompt admitted mid-decode-
#               flood must shrink the flood's worst inter-token gap
#               under interleave with zero timed-window recompiles, and
#               the 2-shard fleet merge stays bitwise + token-identical
#   search    — search v2 (ISSUE 19): persistent op-cost DB + multi-
#               objective (time x HBM) strategy search. The cost-DB /
#               warm-start / mem-mode / expert-axis suite, then the
#               smoke: a cold search persists one entry per op signature,
#               a warm re-run across a simulated process boundary
#               re-measures ZERO keyed ops (100% hit rate), a tight HBM
#               cap makes the multi-objective search choose remat/ZeRO/
#               offload relief that lints UNDER cap where the time-only
#               strategy lints over (escalated to error), and
#               calibration gauges (ff_csim_error_ratio et al.) land in
#               a telemetry scrape + a calib entry in the DB
#   elastic_serve — elastic fleet (ISSUE 20): SLO-driven autoscaling +
#               preemption-tolerant serving. The policy/membership/
#               evacuation suite (hysteresis + bounds, live add/remove
#               token identity, the drain-contract requeue regression,
#               bitwise survivor inheritance of prefix pages and
#               adapters, preempt exactly-once, deadline-starved fence
#               fallback), then the 2-leg smoke: a ~2x-capacity flood
#               breaches queue_wait and the autoscaler grows the fleet
#               to 3 (/healthz ok, zero survivor recompiles); a
#               preempt(800)@replica drill mid-flood evacuates the home
#               replica's requests + hot prefixes to survivors exactly
#               once (warm round-2 hits, one manifest-intact bundle
#               naming the preemption) — repeated under FF_SANITIZE=1
#   sanitize  — ffsan plane (ISSUE 16): static concurrency/
#               tracestability passes clean over runtime/ (tiered exit:
#               warnings fail too) + the seeded-violation harness, then
#               the router and disagg crash-drill smokes re-run under
#               FF_SANITIZE=1 (order-asserting lock proxies + armed
#               retrace sentinels) asserting zero violations and zero
#               post-warmup retraces
#
# Usage: ci/run_ci.sh [unit|sweep|accuracy|native|docs|lint|resilience|serving|overlap|elastic|kernels|quant|disagg|obs|router|tenancy|deploy|longctx|search|elastic_serve|sanitize|all]
set -e

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
cd "$ROOT"
TIER="${1:-all}"

# All CI tiers are CPU-only: pin the CPU platform for every child process
# in this script, and ask for the Pallas kernels in interpret mode (the
# smokes force the kernel paths; nothing interprets unasked). The chip
# check is `python chip_smoke.py` through the chip tool, not a CI tier.
export JAX_PLATFORMS=cpu
export FF_PALLAS_INTERPRET=1

run_unit()     { python -m pytest tests/ -x -q; }
run_sweep()    { bash tests/multi_device_tests.sh "${NDEV:-8}"; }
# accuracy tier defaults to 2 virtual devices: XLA CPU collectives need all
# participants at a rendezvous within 40 s, and 8 devices on a small host
# can starve one (see tests/accuracy_tests.sh)
run_accuracy() { bash tests/accuracy_tests.sh "${ACC_NDEV:-2}"; }
run_native()   {
  make -C flexflow_tpu/capi
  make -C examples/cpp
  FFT_JAX_PLATFORMS=cpu FFT_NUM_CPU_DEVICES=4 FFT_REPO_ROOT="$ROOT" \
    ./examples/cpp/alexnet 16 1 32
}
run_docs()     { make -C docs html; }
# lint tier: (1) fflint --strict over every shipped example strategy (the
# MANIFEST pairs each file with its model graph + mesh), (2) ruff over the
# Python package when the tool is available (config in pyproject.toml; the
# minimal CI image has no ruff — gate, don't fail, per the no-new-deps rule)
run_lint()     {
  local manifest="examples/strategies/MANIFEST"
  [ -f "$manifest" ] || { echo "lint: $manifest missing"; return 1; }
  while IFS='|' read -r f m mesh margs; do
    f=$(echo "$f" | xargs); m=$(echo "$m" | xargs)
    mesh=$(echo "$mesh" | xargs); margs=$(echo "$margs" | xargs)
    [ -z "$f" ] && continue
    case "$f" in \#*) continue ;; esac
    local extra=""
    for a in $margs; do extra="$extra --model-arg $a"; done
    echo "lint: fflint $m examples/strategies/$f (mesh $mesh)"
    # shellcheck disable=SC2086
    python -m flexflow_tpu.analysis "$m" "examples/strategies/$f" \
      --mesh "$mesh" --strict --quiet $extra
  done < <(grep -v '^#' "$manifest")
  if command -v ruff >/dev/null 2>&1; then
    ruff check flexflow_tpu
  elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check flexflow_tpu
  else
    echo "lint: ruff not installed in this image — skipping style gate"
  fi
}

# resilience tier: the fault-injection suite (every FF_FAULT path:
# kill-and-resume bitwise, NaN skip-step + rewind, injected orbax IO
# failure + retry, SIGTERM checkpoint-then-stop, watchdog), then the
# 2-process multihost training test as a resume smoke — it round-trips a
# sharded orbax checkpoint across controllers ("ckpt=ok") through the
# same atomic save/restore path the supervisor drives. The multihost leg
# needs gloo CPU collectives; probe and skip (loudly) where this jax
# build lacks them.
# gloo probe shared by every multihost smoke: the 2-process legs need
# CPU collectives, which some jax builds lack.
has_gloo() {
  JAX_PLATFORMS="" python -c "
import jax
jax.config.update('jax_cpu_collectives_implementation', 'gloo')" \
      >/dev/null 2>&1
}

run_resilience() {
  python -m pytest tests/test_resilience.py -q
  if has_gloo; then
    python -m pytest tests/test_multihost.py -q -k two_process_training
  else
    echo "resilience: no gloo CPU collectives in this jax build —" \
         "skipping the 2-process resume smoke"
  fi
}

# serving tier: the continuous-batching test file (token-identity vs
# sequential decode, bitwise paged-vs-dense attention, early-exit parity,
# recompile-counter flatness, prefix-cache COW/eviction/refcounts,
# speculative greedy identity), then the 200-request smoke with an
# injected nan_loss fault — request 37 is poisoned in-graph and must be
# retired as failed while the other 199 complete (no batch stall) —
# followed by its skewed shared-prefix phase (80% of requests share a
# 64-token system prompt: hits fire, warm window compiles nothing, and
# drain + flush leave zero leaked pages).
run_serving() {
  python -m pytest tests/test_serving.py -q
  FF_FAULT="nan_loss@serve:37" python scripts/serve_smoke.py 200
}

# overlap tier: the host-overlap step engine suite (bitwise identity vs
# the sync loop, checkpoint-cursor exactness under prefetch, io_fail
# retry inside the worker, retrace flatness), then the slow-loader smoke
# asserting throughput improves and the host_wait fraction drops.
# In-graph leg (ISSUE 10): the collective-overlap suite (bucketed grad
# sync + ZeRO-1 pinned numerics, async checkpointing, machine-model
# hierarchical pricing) and its smoke — local always; the 2-process
# overlapped-sync preempt/resume-bitwise drill where gloo exists.
run_overlap() {
  python -m pytest tests/test_overlap.py tests/test_pipeline_loader.py -q
  python -m pytest tests/test_collective_overlap.py \
    tests/test_machine_model.py -q
  python scripts/overlap_smoke.py
  python scripts/collective_overlap_smoke.py
  if has_gloo; then
    python scripts/collective_overlap_smoke.py two_process
  else
    echo "overlap: no gloo CPU collectives in this jax build —" \
         "skipping the 2-process overlapped-sync resume drill"
  fi
}

# elastic tier: the recovery suite (resume onto fewer devices /
# differently-shaped meshes, manifest verification + corrupted-latest
# fallback, retention sparing the last intact step, drain/health), the
# single-process corruption-injection resume smoke, and — where this jax
# build has gloo CPU collectives — the full changed-topology drill: a
# 2-process multihost run preempted mid-epoch, then relaunched as ONE
# surviving process that reshards onto 4 devices with the global batch
# preserved via grad-accum.
run_elastic() {
  python -m pytest tests/test_elastic.py -q
  python scripts/elastic_smoke.py corrupt
  if has_gloo; then
    python scripts/elastic_smoke.py shrink
  else
    echo "elastic: no gloo CPU collectives in this jax build —" \
         "skipping the 2-process shrink smoke"
  fi
}

# kernels tier: the paged-attention kernel + autotuner suites (slow-marked
# serving token-identity variants included — pytest -q runs the whole
# files), then the tune->persist->consume smoke against a real table file.
run_kernels() {
  python -m pytest tests/test_pallas_paged.py tests/test_kernel_tune.py -q
  python scripts/kernel_tune_smoke.py
  # every kernel x shape class of the chip_smoke.py sweep through Mosaic,
  # against the compile-only v5e topology (77 = no libtpu here: skipped)
  python scripts/aot_kernel_check.py || [ $? -eq 77 ]
}

# quant tier: the quantized-serving suite (slow-marked engine pairs
# included — pytest -q runs the whole file), then the bf16/int8
# serve-smoke pair on the skewed shared-prefix workload: identical hit
# counts, zero warm-window recompiles on both, ~2x tokens-per-pool-GB.
run_quant() {
  python -m pytest tests/test_quantized_serving.py -q
  python scripts/serve_smoke.py 120 quant
}

# disagg tier: the tier state machine + fleet integration suites, then
# the role-split smoke under a deterministic mid-handoff crash of the
# prefill replica (identity-indexed, so warmup consumes nothing; tick
# 12 lands while background handoffs stream through replica 0).
run_disagg() {
  python -m pytest tests/test_tiered_prefix.py tests/test_disagg.py -q
  FF_FAULT="crash(6)@replica:0" python scripts/disagg_smoke.py 160
}

# obs tier: the telemetry suite (slow-marked span-continuity variants
# included — pytest -q runs the whole file) + the flight-recorder /
# SLO / HBM-ledger suite (ISSUE 15), then the observability smoke:
# mid-run /metrics scrape + perfetto-loadable trace export with
# complete per-request span trees through a crash drill and a handoff,
# a post-mortem bundle leg and a /healthz SLO breach-and-recover leg.
run_obs() {
  python -m pytest tests/test_telemetry.py tests/test_flightrec.py -q
  python scripts/obs_smoke.py 120
}

# router tier: the fleet suite (failover/deadline/shedding/affinity +
# the concurrent-submit engine stress in test_serving), then the
# 2-replica smoke under a deterministic mid-flight crash of replica 0
# (crash@replica is identity-indexed, so the smoke's warmup consumes
# nothing from the plan; tick 10 guarantees work is genuinely
# mid-stream when the replica dies).
run_router() {
  python -m pytest tests/test_router.py -q
  python -m pytest tests/test_serving.py -q \
    -k "thread_safe or deadline_expires"
  FF_FAULT="crash(10)@replica:0" python scripts/router_smoke.py 200
}

# sanitize tier (ISSUE 16): the ffsan plane, both halves. Static: the
# concurrency + tracestability source passes must be CLEAN over
# flexflow_tpu/runtime (severity-tiered exit codes: any error OR
# warning fails the tier) and the seeded-violation harness in
# tests/test_ffsan.py must still catch every planted bug class.
# Dynamic: the router and disagg smokes re-run with their crash drills
# under FF_SANITIZE=1 — every runtime lock is an order-asserting proxy
# and every engine sentinel is armed after warmup; the smokes assert
# zero lock-order violations and zero post-warmup retraces before
# printing PASSED.
run_sanitize() {
  python -m flexflow_tpu.analysis \
    --passes concurrency,tracestability --tiered-exit
  python -m pytest tests/test_ffsan.py -q
  FF_SANITIZE=1 FF_FAULT="crash(10)@replica:0" \
    python scripts/router_smoke.py 200
  FF_SANITIZE=1 FF_FAULT="crash(6)@replica:0" \
    python scripts/disagg_smoke.py 160
}

# tenancy tier (ISSUE 14): the multi-tenant suites — per-slot sampling
# + paged LoRA adapter pool (test_tenancy) and rejection-sampled
# speculation property/reproducibility tests (test_sampled_spec, slow
# variants included: the K=1/3/8 distribution sweep and the sampled
# failover drill) — then the 8-adapter mixed-sampling fleet smoke under
# a deterministic mid-flight crash of replica 0 (tick 6: the drill must
# catch seeded sampled streams genuinely mid-decode; identity-indexed,
# so the smoke's warmup consumes nothing from the plan).
run_tenancy() {
  python -m pytest tests/test_tenancy.py tests/test_sampled_spec.py -q
  FF_FAULT="crash(6)@replica:0" python scripts/tenancy_smoke.py 48
}

# deploy tier (ISSUE 17): SLO-gated rolling deployment. The full suite
# (slow tests included: drain->reopen token identity, version-salted
# prefix isolation, the A/B mid-roll fleet, live rolling deploy), then
# the 2-leg smoke: a rolling swap under a skewed flood (exactly-once,
# capacity >= N-1, zero warm-window recompiles) and a forced canary
# breach that must roll the fleet back to v1 with exactly one
# manifest-intact bundle naming the breached SLO (the smoke arms its
# own slow@canary plan internally).
run_deploy() {
  python -m pytest tests/test_deploy.py -q
  python scripts/deploy_smoke.py 80
}

# longctx tier (ISSUE 18): long-context serving. The interleave/
# seq-parallel suites (slow tests included: interleaved-vs-run-to-
# completion token identity, the router's sharded handoff, the warmup
# variant sweep), then the smoke — once plain and once sanitized (the
# FF_SANITIZE leg also proves the new admission paths take the engine
# lock in order and never retrace warm programs).
run_longctx() {
  python -m pytest tests/test_longctx_serving.py tests/test_seq_parallel.py -q
  python scripts/longctx_smoke.py
  FF_SANITIZE=1 python scripts/longctx_smoke.py 24
}

# search tier (ISSUE 19): the persistent cost-DB / warm-start /
# multi-objective suite, then the cold->warm->drill->calibration smoke
# against a real DB file across a simulated process boundary.
run_search() {
  python -m pytest tests/test_cost_db.py -q
  python scripts/search_smoke.py
}

# elastic_serve tier (ISSUE 20): SLO-driven autoscaling + preemption-
# tolerant serving. The suite (policy hysteresis/bounds, live
# add/remove_replica token identity, the drain-contract requeue
# regression, bitwise survivor inheritance, preempt exactly-once, the
# deadline-starved fence fallback), then the 2-leg smoke — a flood at
# ~2x capacity must breach queue_wait and autoscale to 3 replicas
# (/healthz back to ok, zero survivor recompiles), and a preempt(800)
# drill mid-flood must complete every request exactly once with the
# evacuated prefix serving warm survivor hits and one manifest-intact
# bundle naming the preemption — re-run under FF_SANITIZE=1 to prove
# the membership/evacuation paths lock in order and never retrace.
run_elastic_serve() {
  python -m pytest tests/test_elastic_serve.py -q
  python scripts/elastic_serve_smoke.py 60
  FF_SANITIZE=1 python scripts/elastic_serve_smoke.py 40
}

case "$TIER" in
  unit)     run_unit ;;
  sweep)    run_sweep ;;
  accuracy) run_accuracy ;;
  native)   run_native ;;
  docs)     run_docs ;;
  lint)     run_lint ;;
  resilience) run_resilience ;;
  serving)  run_serving ;;
  overlap)  run_overlap ;;
  elastic)  run_elastic ;;
  kernels)  run_kernels ;;
  quant)    run_quant ;;
  disagg)   run_disagg ;;
  obs)      run_obs ;;
  router)   run_router ;;
  tenancy)  run_tenancy ;;
  deploy)   run_deploy ;;
  longctx)  run_longctx ;;
  search)   run_search ;;
  elastic_serve) run_elastic_serve ;;
  sanitize) run_sanitize ;;
  all)      run_lint; run_unit; run_resilience; run_serving; run_overlap; run_elastic; run_kernels; run_quant; run_disagg; run_obs; run_router; run_tenancy; run_deploy; run_longctx; run_search; run_elastic_serve; run_sanitize; run_native; run_docs; run_sweep ;;
  *) echo "unknown tier $TIER"; exit 2 ;;
esac
echo "ci($TIER): PASSED"
