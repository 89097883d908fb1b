#!/usr/bin/env python
"""Headline benchmark: Transformer training throughput + MFU.

Prints one JSON line per completed tier: {"metric", "value", "unit",
"vs_baseline", ...}. `vs_baseline` is MFU vs the hardware roofline (model
FLOPs / step-time / peak bf16 FLOPs of the attached chips) — the
reference's only published metric is its own `THROUGHPUT = %.2f samples/s`
print (python/flexflow/keras/models/base_model.py:434), so the roofline
fraction is the honest absolute yardstick.

One process, one command: the process that runs the tiers is the one that
holds the chip. It fails when jax's platform is not `tpu`, fails on a
`device_kind` outside the peak table, and a failing tier fails the run.
The compile cache is wherever flexflow_tpu._env.resolve_compilation_cache
places it. FF_BENCH_SKIP_TIERS=a,b skips the named tiers.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# peak dense bf16 FLOP/s per chip, keyed by the prefix of jax's
# `device_kind`. Source: Google Cloud TPU documentation, the "System
# architecture" page of each generation (v4, v5e, v5p, v6e "Trillium",
# TPU7x "Ironwood"), row "Peak compute per chip (bf16)". A device that is
# not here is an error (_peak_flops_per_chip), never a measured stand-in.
TPU_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU v7": 4614e12,
}

# (name, batch_per_dev, seq, hidden, layers, heads, iters, levers)
# Lever tiers run AFTER their base so a lever-induced failure can never
# cost the base number — each tier's JSON is already flushed when the
# next starts. FF_BENCH_MASTER_DTYPE / FF_BENCH_FUSED_LN override LEVER
# TIERS only; no-lever tiers always measure the unmodified configuration.
#   *_scan tiers run the iters through ONE lax.scan device program
#   (FFModel.train_scanned) instead of one dispatch per step — the
#   production multi-step path (config.scan_steps), and the measurement
#   free of per-dispatch latency.
#   full_scan_opt = the bf16-master-weights lever; xl_scan = the
#   head_dim-128 headline. Which levers win on the chip is not measured
#   in this round (PERF.md).
TPU_TIERS = [
    ("tiny", 8, 256, 512, 2, 8, 5, None),
    ("mid", 16, 512, 1024, 4, 16, 10, None),
    ("full", 16, 512, 1024, 8, 16, 20, None),
    ("full_scan", 16, 512, 1024, 8, 16, 20, {"scan": True}),
    # the opt tiers carry bf16 master weights only; the fused
    # add+layernorm lever stays off (FF_BENCH_FUSED_LN=1 turns it on)
    ("full_scan_opt", 16, 512, 1024, 8, 16, 20,
     {"scan": True, "master_dtype": "bfloat16"}),
    # headline: same depth at hidden 2048 / head_dim 128 — QK^T/AV
    # contract over head_dim, so d=64 leaves half the 128-wide MXU
    # contraction empty and d=128 fills it
    ("xl_scan", 16, 512, 2048, 8, 16, 15,
     {"scan": True, "master_dtype": "bfloat16"}),
    # hidden 4096 pushes matmul arithmetic intensity further up the
    # roofline
    ("xxl_scan", 8, 512, 4096, 6, 32, 8,
     {"scan": True, "master_dtype": "bfloat16"}),
    # depth extension of xxl (same width/head_dim, L6->L8): deeper
    # amortizes the embed/classifier overhead across more blocks
    ("x3l_scan", 8, 512, 4096, 8, 32, 6,
     {"scan": True, "master_dtype": "bfloat16"}),
]
# serving tier (runtime/serving.py): 32 mixed-length requests through the
# continuous-batching engine vs the same requests decoded sequentially
# one-at-a-time — the ISSUE-3 acceptance bar is >= 2x aggregate tokens/s
# on the CPU smoke shape with serve_slots=4
SERVE_REQUESTS = 32
SERVE_MAX_NEW = 32
# cycled over the requests; all bucket to <= 32, so max_seq_len stays 64
# (the static-shape decode attends the full gathered length — slack there
# is wasted FLOPs on every step of every slot)
SERVE_PROMPT_LENS = (6, 10, 14, 20, 24, 28)

# router_serving tier (ISSUE 8): the multi-replica ServingRouter. Two
# questions, answered in one row: (1) aggregate tokens/s at 2 replicas
# vs 1 (the fleet-scaling number — on the CPU smoke box both replicas
# share two cores, so the honest expectation is ~1x; on real hardware
# each replica owns its chips); (2) accepted-request p99 TTFT during a
# mid-flight replica kill under sustained overload, with shedding
# (serve_max_queue bounded) vs without — shedding must keep the
# accepted p99 bounded (no worse than ~2x the no-overload run) while
# the unshedded queue's p99 degrades with the backlog. Router counters
# (fenced, resubmitted, timeouts, rejected) ride the config block.
ROUTER_REQUESTS = 64
ROUTER_MAX_NEW = 16
# kill-drill shape: longer generations + more requests make the overload
# SUSTAINED (a burst that drains in one service interval measures
# nothing), and the shed window runs with dispatch_backlog=0 so accepted
# work waits in no deep engine queue — the bound shedding promises
ROUTER_KILL_MAX_NEW = 32
ROUTER_OVERLOAD_REQUESTS = 240
ROUTER_SHED_QUEUE = 1
# early kill: failover victims have accrued little pre-crash wait, so
# the shed window's p99 measures the SHEDDING bound, not the (separately
# counted) failover cost
ROUTER_KILL_TICK = 12

# prefix_serving tier (ISSUE 6): skewed shared-prefix traffic — 80% of
# requests share a long system prompt (the millions-of-users shape from
# ROADMAP item 1) — through the radix-prefix-cache engine vs the SAME
# engine with the cache off (the PR-3 continuous-batching path). The
# acceptance bar is >= 1.5x aggregate tokens/s with 0 recompiles in the
# timed window; the row also records p99 TTFT for both paths, the prefix
# hit rate, and the speculative accept rate (measured in a side window —
# speculation is a latency lever, not part of the throughput headline).
PREFIX_REQUESTS = 200
PREFIX_MAX_NEW = 8
PREFIX_SYSTEM_LEN = 120  # 7 full 16-token pages shared via the trie


def _peak_flops_per_chip(dev):
    """(peak bf16 FLOP/s, "spec") of one chip from TPU_PEAK_BF16; raises on
    a `device_kind` the table does not carry."""
    kind = getattr(dev, "device_kind", "")
    # longest key first: 'TPU v5 lite' must hit the v5e entry, not 'TPU v5'
    for k in sorted(TPU_PEAK_BF16, key=len, reverse=True):
        if kind.lower().startswith(k.lower()):
            return TPU_PEAK_BF16[k], "spec"
    raise ValueError(
        f"device_kind {kind!r} is not in bench.py's TPU_PEAK_BF16 table; "
        f"add it with its published peak and source before benchmarking")


def _phase(name):
    print(f"[bench] PHASE {name} t={time.time():.0f}", file=sys.stderr,
          flush=True)


def _run_tier(tier, n_dev, compute, peak, peak_src, backend, dev_kind):
    import numpy as np

    import jax

    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer, SingleDataLoader)
    from flexflow_tpu.models.transformer import build_encoder_classifier
    from flexflow_tpu.ops.base import InputOp

    name, bpd, seq, hidden, layers, heads, iters, levers = tier
    batch = bpd * n_dev
    _phase(f"build_{name}")

    # MFU levers (VERDICT r2 #4): bf16 master weights halve optimizer HBM
    # traffic; fused add+layernorm saves an HBM pass per residual hop.
    # Carried by the tier tuple; env knobs re-scope the LEVER tier only so
    # ablations never mutate the protected base tiers
    # env knobs re-scope tiers that HAVE MFU levers on; scan-only and
    # no-lever tiers always measure the unmodified configuration (they are
    # the ablation baselines)
    if levers and ("master_dtype" in levers or "use_fused_ln" in levers):
        levers = dict(levers)
        if os.environ.get("FF_BENCH_MASTER_DTYPE"):
            levers["master_dtype"] = os.environ["FF_BENCH_MASTER_DTYPE"]
        if os.environ.get("FF_BENCH_FUSED_LN"):
            levers["use_fused_ln"] = \
                os.environ["FF_BENCH_FUSED_LN"] == "1"
        if os.environ.get("FF_BENCH_FUSED_OPT"):
            levers["fused_optimizer"] = \
                os.environ["FF_BENCH_FUSED_OPT"] == "1"
    master = (levers or {}).get("master_dtype", "float32")
    fused_ln = (levers or {}).get("use_fused_ln", False)
    fused_opt = bool((levers or {}).get("fused_optimizer", False))
    scan_mode = bool((levers or {}).get("scan", False))
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": n_dev},
                   compute_dtype=compute, master_dtype=master,
                   use_fused_ln=fused_ln, fused_optimizer=fused_opt)
    ff = FFModel(cfg)
    x, out = build_encoder_classifier(ff, batch, seq, hidden, layers, heads)
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=out)

    rs = np.random.RandomState(0)
    n_samples = batch * 4
    xdat = rs.randn(n_samples, seq, hidden).astype(np.float32)
    y = rs.randint(0, 16, (n_samples, 1)).astype(np.int32)
    # dataset attached once, device-resident; next_batch is an on-device
    # slice (the reference's ZC-resident dataloader design) — the timed
    # loop measures training, not host->device re-uploads
    SingleDataLoader(ff, x, xdat)
    SingleDataLoader(ff, ff.label_tensor, y)

    _phase(f"compile_{name}")
    if scan_mode:
        losses, _ = ff.train_scanned(iters)  # compile + warmup, one program
        float(losses[-1])
    else:
        ff._run_train_step(ff._stage_batch())  # compile + warmup
        jax.block_until_ready(ff.params)
        ff._run_train_step(ff._stage_batch())
        jax.block_until_ready(ff.params)

    _phase(f"time_{name}")
    # the device link in this environment has high run-to-run variance;
    # take the best of 3 rounds (each fetch-synced end to end). Host-side
    # staging time is measured per round so every row reports its
    # host_wait fraction — a later throughput delta is then attributable
    # to overlap-engine changes vs kernel changes.
    dts, hosts = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        host_s = 0.0
        loss = None
        if scan_mode:
            losses, _ = ff.train_scanned(iters)
            loss = losses[-1]
        else:
            for _ in range(iters):
                h0 = time.perf_counter()
                b = ff._stage_batch()
                host_s += time.perf_counter() - h0
                loss, _ = ff._run_train_step(b)
        # fetch the last loss: forces the whole timed chain to completion
        float(loss)
        dts.append((time.perf_counter() - t0) / iters)
        hosts.append(host_s / iters)
    i_best = dts.index(min(dts))
    dt = dts[i_best]
    host_wait_fraction = (hosts[i_best] / dt) if dt > 0 else 0.0
    throughput = batch / dt

    # MFU: train step ~= fwd + 2x fwd for bwd; flops() methods count forward
    fwd_flops = sum(op.flops() for op in ff.ops
                    if not isinstance(op, InputOp))
    step_flops = 3.0 * fwd_flops
    mfu = step_flops / dt / (peak * n_dev)

    return {
        "metric": "transformer_train_throughput",
        "value": round(throughput, 2),
        "unit": "samples/s",
        "vs_baseline": round(mfu, 4),
        "mfu": round(mfu, 4),
        "step_time_ms": round(dt * 1e3, 3),
        "step_tflops": round(step_flops / 1e12, 3),
        "peak_tflops_per_chip": round(peak / 1e12, 1),
        "peak_source": peak_src,
        "backend": backend,
        "device_kind": dev_kind,
        "n_devices": n_dev,
        "tier": name,
        "config": {"batch": batch, "seq": seq, "hidden": hidden,
                   "layers": layers, "heads": heads, "dtype": compute,
                   "master_dtype": master, "fused_ln": fused_ln,
                   "fused_opt": fused_opt, "scan": scan_mode,
                   # attribution keys (every bench config block carries
                   # them): these tiers drive steps directly, so the
                   # dispatch-ahead engine is not in play
                   "dispatch_ahead": 0,
                   "host_wait_fraction": round(host_wait_fraction, 4)},
    }


def _run_serving_tier(n_dev, backend, dev_kind):
    """decode_throughput + serve_latency rows: continuous batching
    (ONE fixed-shape slot-decode program, paged KV cache, bucketed
    admission) vs the sequential one-request-at-a-time baseline, both
    fully warm — this measures the scheduler, not compile time."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm

    _phase("build_serving")
    vocab = 256
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=4,
                   kv_page_size=16)
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    rs = np.random.RandomState(0)
    lens = [SERVE_PROMPT_LENS[i % len(SERVE_PROMPT_LENS)]
            for i in range(SERVE_REQUESTS)]
    prompts = [rs.randint(1, vocab, (n,)).astype(np.int32) for n in lens]

    _phase("warm_serving")
    # ServingEngine.warmup drives every (bucket, matched_pages) variant
    # the WORKLOAD prompt set can reach (two passes: publish, then the
    # saturated repeats best-of-3 rounds hit) — the PR 7/8/10 gotcha
    # promoted to an API; the timed window then holds zero compiles
    # (asserted by the counter below). Same max_new as the measurement
    # so page-budget/eviction dynamics match exactly.
    # max_seq_len snug to the workload (bucket(28)=32 + 32 new = 64);
    # decode_chunk=32 amortizes dispatch overhead over one in-graph scan
    # per request generation (retirement stays per-slot — a freed slot
    # refills while the others keep decoding)
    eng = ff.make_serving_engine(max_seq_len=64, decode_chunk=32)
    eng.warmup(prompts, max_new_tokens=SERVE_MAX_NEW)
    for n in SERVE_PROMPT_LENS:
        ff.generate(rs.randint(1, vocab, (1, n)).astype(np.int32),
                    SERVE_MAX_NEW)

    # best-of-3 rounds per path: this host's load is bursty, and the
    # scheduler path (more dispatches than sequential's one fused scan)
    # suffers disproportionately under contention
    _phase("time_serving_sequential")
    t_seq, seq_tokens = None, 0
    for _ in range(3):
        t0 = time.perf_counter()
        seq_tokens = 0
        for p in prompts:
            out = ff.generate(p[None, :], SERVE_MAX_NEW)
            seq_tokens += out.shape[1] - p.size
        dt = time.perf_counter() - t0
        t_seq = dt if t_seq is None else min(t_seq, dt)

    _phase("time_serving_continuous")
    warm_recompiles = eng.recompile_count
    st0 = eng.stats()  # pre-window snapshot: warmup must not pollute
    t_serve, tokens, timed_reqs = None, 0, []
    for _ in range(3):
        before = eng.stats()["tokens_generated"]
        t0 = time.perf_counter()
        reqs = eng.run(prompts, max_new_tokens=SERVE_MAX_NEW)
        dt = time.perf_counter() - t0
        tokens = eng.stats()["tokens_generated"] - before
        t_serve = dt if t_serve is None else min(t_serve, dt)
        timed_reqs.extend(reqs)
    st = eng.stats()
    extra_recompiles = eng.recompile_count - warm_recompiles
    ok = all(r.state == "done" for r in timed_reqs)

    # telemetry honesty (ISSUE 13): re-run the same workload with the
    # telemetry plane on vs hard-off, INTERLEAVED (on, off, on, off, …)
    # so slow host drift hits both arms equally — the best-of tokens/s
    # delta is the measurement's own perturbation, stamped as
    # telemetry_overhead_pct instead of silently riding every serving
    # number; the registry's shape rides the config block so a series
    # explosion is visible in the trajectory too. Off-window recompiles
    # must stay zero (telemetry never touches compiled programs).
    _phase("time_serving_telemetry_off")
    from flexflow_tpu.runtime import telemetry as _tm

    _tm_prev = _tm.enabled()
    t_on2 = t_off = 0.0
    on2_tokens = off_tokens = 0
    off_recompiles = 0
    try:
        # 5 interleaved pairs, TOTAL time per arm (not best-of): the
        # windows are ~100ms, so a min over so few rounds just picks
        # the luckiest burst — the interleaved mean is the unbiased
        # estimate of the delta
        for _ in range(5):
            for arm_on in (True, False):
                _tm.set_enabled(arm_on)
                before_arm = eng.stats()["tokens_generated"]
                rc0 = eng.recompile_count
                t0 = time.perf_counter()
                eng.run(prompts, max_new_tokens=SERVE_MAX_NEW)
                dt = time.perf_counter() - t0
                toks = eng.stats()["tokens_generated"] - before_arm
                if arm_on:
                    on2_tokens += toks
                    t_on2 += dt
                else:
                    off_tokens += toks
                    t_off += dt
                    # off-ARM recompiles only: a compile in an on arm
                    # must not be stamped under the off-window key
                    off_recompiles += eng.recompile_count - rc0
    finally:
        _tm.set_enabled(_tm_prev)
    telemetry_registry = _tm.registry().describe()

    # flight-recorder honesty (ISSUE 15): the same interleaved
    # discipline for the recorder + SLO evaluator — ON (bundle dir
    # configured, generous non-breaching SLO ceilings evaluated at a
    # deliberately sub-window cadence so the evaluator genuinely runs
    # in the timed arms) vs the module gate OFF. The delta is stamped
    # as flightrec_overhead_pct (budget <= 2%), and off-arm recompiles
    # must stay zero — the health plane never touches compiled
    # programs.
    _phase("time_serving_flightrec_off")
    import shutil as _shutil
    import tempfile as _tempfile

    from flexflow_tpu.runtime import flightrec as _fr

    fr_dir = _tempfile.mkdtemp(prefix="ff_bench_flightrec_")
    _fr.configure(FFConfig(
        batch_size=2, mesh_shape={"data": 1},
        flight_recorder_dir=fr_dir,
        flight_cooldown_s=3600.0, flight_debounce_s=3600.0,
        slo_ttft_p99_s=60.0, slo_queue_wait_p99_s=60.0,
        # 0.25 s: ~40x the production default cadence, so the evaluator
        # judges several full windows inside every timed arm while the
        # stamp still reflects a recognizable deployment shape
        slo_window_s=0.25))
    t_fr_on = t_fr_off = 0.0
    fr_on_tokens = fr_off_tokens = 0
    fr_off_recompiles = 0
    try:
        for _ in range(5):
            for arm_on in (True, False):
                _fr.set_enabled(arm_on)
                before_arm = eng.stats()["tokens_generated"]
                rc0 = eng.recompile_count
                t0 = time.perf_counter()
                eng.run(prompts, max_new_tokens=SERVE_MAX_NEW)
                dt = time.perf_counter() - t0
                toks = eng.stats()["tokens_generated"] - before_arm
                if arm_on:
                    fr_on_tokens += toks
                    t_fr_on += dt
                else:
                    fr_off_tokens += toks
                    t_fr_off += dt
                    fr_off_recompiles += eng.recompile_count - rc0
    finally:
        _fr.set_enabled(True)
        _fr.reset()   # drop the bench dir/specs: later tiers' FF_FAULT
        #               drills must not write bundles
        _shutil.rmtree(fr_dir, ignore_errors=True)
    fr_off_tps = fr_off_tokens / t_fr_off
    fr_on_tps = fr_on_tokens / t_fr_on
    flightrec_overhead_pct = round(
        100.0 * (fr_off_tps - fr_on_tps) / max(fr_off_tps, 1e-9), 2)

    # ffsan honesty (ISSUE 16): the sanitizer's marginal cost on the
    # decode path, same interleaved discipline. The engine was built
    # with the sanitizer off, so its locks are raw threading primitives
    # in BOTH arms (proxying is decided at lock creation); the mode
    # toggle here switches the armed retrace sentinel, which brackets
    # every jit dispatch with a cache-size probe — the per-token
    # dynamic cost. The off arm's residual is one module-global read
    # per dispatch, a strict subset of the on arm, so this stamp
    # upper-bounds the production sanitizer-off overhead (budget
    # <= 0.5%).
    _phase("time_serving_sanitize_off")
    from flexflow_tpu.runtime import locks as _san

    t_sz_on = t_sz_off = 0.0
    sz_on_tokens = sz_off_tokens = 0
    sz_off_recompiles = sz_retraces = 0
    _san_prev = _san.mode()
    try:
        for _ in range(5):
            for arm_on in (True, False):
                _san.set_mode("on" if arm_on else "off")
                before_arm = eng.stats()["tokens_generated"]
                rc0 = eng.recompile_count
                t0 = time.perf_counter()
                eng.run(prompts, max_new_tokens=SERVE_MAX_NEW)
                dt = time.perf_counter() - t0
                toks = eng.stats()["tokens_generated"] - before_arm
                if arm_on:
                    sz_on_tokens += toks
                    t_sz_on += dt
                else:
                    sz_off_tokens += toks
                    t_sz_off += dt
                    sz_off_recompiles += eng.recompile_count - rc0
    finally:
        sz_retraces = len(_san.retrace_log())
        _san.set_mode(_san_prev)
        _san.reset()   # the warm bench engine must not retrace; any
        #                hit is reported below, not left in the ring
    sz_off_tps = sz_off_tokens / t_sz_off
    sz_on_tps = sz_on_tokens / t_sz_on
    sanitize_overhead_pct = round(
        100.0 * (sz_off_tps - sz_on_tps) / max(sz_off_tps, 1e-9), 2)
    # timed-window metrics only: TTFT percentiles from this window's
    # requests (the engine's lifetime stats would smuggle the warmup's
    # compile-inflated TTFTs into p99), occupancy from snapshot deltas
    ttfts = sorted(r.ttft for r in timed_reqs if r.ttft)

    def _pct(p):
        return round(
            ttfts[min(len(ttfts) - 1, int(p * len(ttfts)))] * 1e3, 3) \
            if ttfts else 0.0

    d_steps = st["decode_steps"] - st0["decode_steps"]
    occupancy = ((st["occupied_slot_steps"] - st0["occupied_slot_steps"])
                 / max(1, d_steps) / st["serve_slots"])

    serve_tps = tokens / t_serve
    seq_tps = seq_tokens / t_seq
    off_tps = off_tokens / t_off
    on2_tps = on2_tokens / t_on2
    # positive = telemetry costs throughput; small negatives are host
    # noise. Computed from the INTERLEAVED arms (not the headline
    # window) so run-order drift cancels. The ISSUE-13 budget is <= 2%.
    telemetry_overhead_pct = round(
        100.0 * (off_tps - on2_tps) / max(off_tps, 1e-9), 2)
    common = {"backend": backend, "device_kind": dev_kind,
              "n_devices": n_dev,
              "config": {"requests": SERVE_REQUESTS,
                         "max_new_tokens": SERVE_MAX_NEW,
                         "serve_slots": st["serve_slots"],
                         "kv_page_size": st["kv_page_size"],
                         "kv_pages": st["kv_pages"],
                         "decode_chunk": 32, "max_seq_len": 64,
                         "hidden": 128, "layers": 2,
                         # attribution keys: which decode-attention impl
                         # the engine's programs traced (+ autotune-table
                         # consultations), so a throughput delta is
                         # attributable to the kernel tier vs scheduling
                         "paged_attention_impl":
                             st["paged_attention_impl"],
                         "kernel_tune_hits": st["kernel_tune_hits"],
                         "kernel_tune_misses": st["kernel_tune_misses"],
                         # serving decodes, it never runs the training
                         # dispatch-ahead engine
                         "dispatch_ahead": 0,
                         "host_wait_fraction": 0.0,
                         # measurement honesty (ISSUE 13): what the
                         # telemetry plane itself cost this window, and
                         # the registry's series/histogram counts
                         "telemetry_overhead_pct":
                             telemetry_overhead_pct,
                         "telemetry_off_tokens_per_s":
                             round(off_tps, 2),
                         "telemetry_registry": telemetry_registry,
                         # ISSUE 15: the flight-recorder + SLO plane's
                         # own marginal cost (interleaved arms, same
                         # discipline; budget <= 2%)
                         "flightrec_overhead_pct":
                             flightrec_overhead_pct,
                         "flightrec_off_tokens_per_s":
                             round(fr_off_tps, 2),
                         # ISSUE 16: the runtime sanitizer's marginal
                         # cost (armed retrace sentinel; budget <= 0.5%)
                         "sanitize_overhead_pct":
                             sanitize_overhead_pct,
                         "sanitize_off_tokens_per_s":
                             round(sz_off_tps, 2)}}
    yield {
        "metric": "decode_throughput", "tier": "decode_throughput",
        "value": round(serve_tps, 2), "unit": "tokens/s",
        "vs_baseline": round(serve_tps / seq_tps, 3),
        "speedup_vs_sequential": round(serve_tps / seq_tps, 3),
        "sequential_tokens_per_s": round(seq_tps, 2),
        "tokens": tokens, "all_done": ok,
        "recompiles_after_warmup": extra_recompiles,
        "recompiles_in_telemetry_off_window": off_recompiles,
        "recompiles_in_flightrec_off_window": fr_off_recompiles,
        "recompiles_in_sanitize_off_window": sz_off_recompiles,
        "sanitizer_retraces_in_on_window": sz_retraces,
        "occupancy": round(occupancy, 4), **common,
    }
    yield {
        "metric": "serve_latency", "tier": "serve_latency",
        "value": _pct(0.50), "unit": "ms_ttft_p50",
        "p50_ttft_ms": _pct(0.50), "p99_ttft_ms": _pct(0.99),
        "occupancy": round(occupancy, 4),
        "decode_steps": d_steps, **common,
    }


def _run_prefix_serving_tier(n_dev, backend, dev_kind):
    """prefix_serving row: the radix prefix cache under skewed
    shared-prefix traffic vs the cache-off engine — identical model,
    slots, pool and buckets, so the delta is exactly the prefill compute
    and pages the cache avoids duplicating. Both engines are fully warm
    before their timed windows (the cold/hit prefill programs, the decode
    scan) and the row asserts-by-recording zero timed-window compiles."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm

    _phase("build_prefix_serving")
    vocab = 256
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=4,
                   kv_page_size=16)
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    rs = np.random.RandomState(0)
    system = rs.randint(1, vocab, (PREFIX_SYSTEM_LEN,)).astype(np.int32)
    prompts = []
    for i in range(PREFIX_REQUESTS):
        if i % 5 < 4:  # 80% shared-prefix, interleaved with background
            tail = rs.randint(1, vocab, (int(rs.randint(1, 8)),))
            prompts.append(np.concatenate([system, tail.astype(np.int32)]))
        else:
            n = int(rs.randint(3, 25))
            prompts.append(rs.randint(1, vocab, (n,)).astype(np.int32))

    def mk_engine(prefix_cache):
        # kv_pages sized so the steady-state cache never churns the
        # evictor mid-measurement; bucket 128 + max_new 8 fits 160
        return ff.make_serving_engine(max_seq_len=160, decode_chunk=8,
                                      kv_pages=128,
                                      prefix_cache=prefix_cache)

    _phase("warm_prefix_serving")
    engines = {}
    for name, on in (("prefix", True), ("baseline", False)):
        eng = engines[name] = mk_engine(on)
        # ServingEngine.warmup replaces the hand-curated variant list
        # this tier used to maintain (the PR 6/7/8/10 gotcha as an
        # API): two passes over the WORKLOAD prompts drive every cold
        # bucket and every (bucket, matched_pages) hit variant the
        # best-of-3 repetition can reach, at the measurement's own
        # max_new so pool dynamics match
        eng.warmup(prompts, max_new_tokens=PREFIX_MAX_NEW)

    results = {}
    for name, eng in engines.items():
        _phase(f"time_prefix_serving_{name}")
        warm_compiles = eng.recompile_count
        best_dt, tokens, timed_reqs = None, 0, []
        for _ in range(3):
            before = eng.stats()["tokens_generated"]
            t0 = time.perf_counter()
            reqs = eng.run(prompts, max_new_tokens=PREFIX_MAX_NEW)
            dt = time.perf_counter() - t0
            tokens = eng.stats()["tokens_generated"] - before
            best_dt = dt if best_dt is None else min(best_dt, dt)
            timed_reqs.extend(reqs)
        ttfts = sorted(r.ttft for r in timed_reqs if r.ttft)

        def _pct(p, tt=ttfts):
            return round(tt[min(len(tt) - 1, int(p * len(tt)))] * 1e3, 3) \
                if tt else 0.0

        results[name] = {
            "tps": tokens / best_dt,
            "p50": _pct(0.50), "p99": _pct(0.99),
            "all_done": all(r.state == "done" for r in timed_reqs),
            "recompiles": eng.recompile_count - warm_compiles,
            "stats": eng.stats(),
        }

    # speculative side window: the accept-rate instrumentation measured
    # end to end (self-draft => the accept path genuinely exercises; a
    # production draft would be a distilled small model). Compiles its
    # own programs, hence OUTSIDE both timed windows above.
    _phase("spec_accept_window")
    spec = ff.make_serving_engine(max_seq_len=160, decode_chunk=8,
                                  kv_pages=128, draft_model=ff,
                                  speculate_k=3)
    spec.run(prompts[:24], max_new_tokens=PREFIX_MAX_NEW)
    spec_st = spec.stats()

    pst = results["prefix"]["stats"]
    yield {
        "metric": "prefix_serving_throughput", "tier": "prefix_serving",
        "value": round(results["prefix"]["tps"], 2), "unit": "tokens/s",
        "vs_baseline": round(results["prefix"]["tps"]
                             / results["baseline"]["tps"], 3),
        "baseline_tokens_per_s": round(results["baseline"]["tps"], 2),
        "p50_ttft_ms": results["prefix"]["p50"],
        "p99_ttft_ms": results["prefix"]["p99"],
        "baseline_p50_ttft_ms": results["baseline"]["p50"],
        "baseline_p99_ttft_ms": results["baseline"]["p99"],
        "all_done": results["prefix"]["all_done"]
        and results["baseline"]["all_done"],
        "recompiles_after_warmup": results["prefix"]["recompiles"]
        + results["baseline"]["recompiles"],
        "prefix_hit_rate": pst["prefix_hit_rate"],
        "prefill_tokens_saved": pst["prefill_tokens_saved"],
        "kv_pages_cached": pst["kv_pages_cached"],
        "spec_accept_rate": spec_st["spec_accept_rate"],
        "spec_proposed": spec_st["spec_proposed"],
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": PREFIX_REQUESTS,
                   "shared_prefix_fraction": 0.8,
                   "system_prompt_len": PREFIX_SYSTEM_LEN,
                   "max_new_tokens": PREFIX_MAX_NEW,
                   "serve_slots": 4, "kv_page_size": 16, "kv_pages": 128,
                   "decode_chunk": 8, "max_seq_len": 160,
                   "speculate_k_side_window": 3,
                   "hidden": 128, "layers": 2,
                   "paged_attention_impl": pst["paged_attention_impl"],
                   "kernel_tune_hits": pst["kernel_tune_hits"],
                   "kernel_tune_misses": pst["kernel_tune_misses"],
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_router_serving_tier(n_dev, backend, dev_kind):
    """router_serving row: the fleet router (runtime/router.py) measured
    three ways — replica-scaling throughput (2 vs 1 replicas, same total
    load), a no-overload paced baseline, and a kill-under-overload drill
    (FF_FAULT crashes replica 0 mid-run while paced submission exceeds
    the measured service rate) run twice: shedding on (bounded router
    queue) vs off. Every router uses prefix_cache=False so warm rounds
    stay warm (repeated prompts would otherwise reach hit-prefill
    variants the timed window never warmed)."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm
    from flexflow_tpu.runtime import faultinject

    _phase("build_router_serving")
    vocab = 256
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=4,
                   kv_page_size=16)
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    rs = np.random.RandomState(0)
    lens = [SERVE_PROMPT_LENS[i % len(SERVE_PROMPT_LENS)]
            for i in range(ROUTER_REQUESTS)]
    prompts = [rs.randint(1, vocab, (n,)).astype(np.int32) for n in lens]
    warm = [rs.randint(1, vocab, (n,)).astype(np.int32)
            for n in SERVE_PROMPT_LENS]

    def mk_router(replicas, max_queue=0, backlog=None):
        # 8 slots x chunk 2: a queued request is admitted at a driver
        # TICK boundary, so the tick is the latency quantum of every
        # shed-queue wait — keep it small (chunk 8 ticks are ~4x longer
        # and the shed-p99 bound drowns in a single tick's wait). The
        # fleet-throughput cost of the shorter scan is the same for
        # every window, so the comparisons stay apples-to-apples.
        r = ff.make_serving_router(
            replicas=replicas, max_queue=max_queue,
            dispatch_backlog=backlog, max_seq_len=96, serve_slots=8,
            decode_chunk=2, prefix_cache=False, start=False)
        r.warmup(warm, max_new_tokens=4)
        return r

    # ---- replica scaling: the same 64 requests through 1 then 2 replicas
    tps = {}
    for n_rep in (1, 2):
        _phase(f"time_router_{n_rep}_replicas")
        router = mk_router(n_rep)
        try:
            warm_compiles = [e.recompile_count for e in router.engines]
            best = None
            for _ in range(2):      # best-of-2: bursty-host guard
                t0 = time.perf_counter()
                reqs = router.run(prompts, max_new_tokens=ROUTER_MAX_NEW,
                                  timeout=1200)
                dt = time.perf_counter() - t0
                assert all(r.state == "done" for r in reqs)
                best = dt if best is None else min(best, dt)
            tps[n_rep] = ROUTER_REQUESTS * ROUTER_MAX_NEW / best
            recompiled = any(
                e.recompile_count != c
                for e, c in zip(router.engines, warm_compiles))
        finally:
            router.close()

    # the drill windows are CLOSED-LOOP floods, not paced arrivals: an
    # instantaneous flood is genuine overload whatever this epoch's
    # service rate is, so the drill needs no rate calibration that a
    # co-tenant load swing between windows would invalidate
    def flood_run(router, n, max_new):
        router.start()
        time.sleep(0.05)    # drivers up before the first arrival — the
        #                     first TTFT must not measure thread spin-up
        reqs = [router.submit(prompts[i % len(prompts)], max_new)
                for i in range(n)]
        router.wait([r for r in reqs if r.state != "rejected"],
                    timeout=1200)
        done = sorted(r.ttft for r in reqs if r.state == "done")

        def pct(p):
            return round(done[min(len(done) - 1,
                                  int(p * len(done)))] * 1e3, 3) \
                if done else 0.0

        return reqs, pct

    # ---- no-overload baseline: paced WELL under the service rate, same
    # shallow-dispatch config as the shed window (isolate the queue
    # bound, not the backlog depth). 0.4x, not 0.7x: the estimate comes
    # from a fully SATURATED window, and per-request service at light
    # occupancy is slower (the fixed-shape dispatch amortizes over fewer
    # busy slots), so "well under" needs real headroom
    # every percentile window runs best-of-2 with a FRESH router per
    # round (the file-wide bursty-host guard: a co-tenant burst inflates
    # one round, the min survives; both sides of every ratio get the
    # same treatment)
    def best_of(fn, rounds=2):
        best = None
        for _ in range(rounds):
            w = fn()
            if best is None or w["p99_ttft_ms"] < best["p99_ttft_ms"]:
                best = w
        return best

    def light_window():
        # "no overload" = a momentarily FULL fleet, not an idle one: one
        # request per fleet slot plus one — the load level shedding
        # promises to preserve for accepted work
        _phase("time_router_light")
        router = mk_router(2, backlog=0)
        try:
            _, pct = flood_run(router, 2 * 8 + 1, ROUTER_KILL_MAX_NEW)
            return {"p99_ttft_ms": pct(0.99),
                    "p50_ttft_ms": pct(0.50)}
        finally:
            router.close()

    p99_light = best_of(light_window)["p99_ttft_ms"]

    def drill_window(name, max_queue, fault=None):
        _phase(f"time_router_{name}")
        if fault:
            os.environ["FF_FAULT"] = fault
            faultinject.reset()
        router = mk_router(2, max_queue=max_queue, backlog=0)
        try:
            reqs, pct = flood_run(router, ROUTER_OVERLOAD_REQUESTS,
                                  ROUTER_KILL_MAX_NEW)
            st = router.stats()
            return {
                "p99_ttft_ms": pct(0.99), "p50_ttft_ms": pct(0.50),
                "accepted": sum(1 for r in reqs
                                if r.state != "rejected"),
                "rejected": st["rejected"], "fenced": st["fenced"],
                "resubmitted": st["resubmitted"],
                "timeouts": st["timeouts"],
                "completed": st["completed"],
            }
        finally:
            router.close()

    # ---- sustained overload WITHOUT a kill, shedding on vs off: the
    # pure shedding bound (no failover victims in the percentile), then
    # the same pair DURING a replica kill (FF_FAULT crashes replica 0
    # mid-run; fresh plan per window — the crash is one-shot per parse)
    old_fault = os.environ.get("FF_FAULT")
    kill_fault = f"crash({ROUTER_KILL_TICK})@replica:0"
    try:
        overload = {
            "shed": best_of(lambda: drill_window(
                "overload_shed", ROUTER_SHED_QUEUE)),
            "noshed": best_of(lambda: drill_window(
                "overload_noshed", 0)),
        }
        kill = {
            "shed": best_of(lambda: drill_window(
                "kill_shed", ROUTER_SHED_QUEUE, fault=kill_fault)),
            "noshed": best_of(lambda: drill_window(
                "kill_noshed", 0, fault=kill_fault)),
        }
    finally:
        if old_fault is None:
            os.environ.pop("FF_FAULT", None)
        else:
            os.environ["FF_FAULT"] = old_fault
        faultinject.reset()

    p99_shed = overload["shed"]["p99_ttft_ms"]
    p99_noshed = overload["noshed"]["p99_ttft_ms"]
    return {
        "metric": "router_serving_throughput", "tier": "router_serving",
        "value": round(tps[2], 2), "unit": "tokens/s",
        "vs_baseline": round(tps[2] / tps[1], 3),
        "replicas_2_tokens_per_s": round(tps[2], 2),
        "replicas_1_tokens_per_s": round(tps[1], 2),
        "p99_ttft_ms_light": p99_light,
        "p99_ttft_ms_overload_shed": p99_shed,
        "p99_ttft_ms_overload_noshed": p99_noshed,
        "p99_ttft_ms_kill_shed": kill["shed"]["p99_ttft_ms"],
        "p99_ttft_ms_kill_noshed": kill["noshed"]["p99_ttft_ms"],
        # the ISSUE-8 acceptance shape: under sustained overload,
        # shedding keeps accepted p99 bounded vs the no-overload run
        # while the unshedded queue's p99 degrades with the backlog
        "shed_p99_bounded_2x_light": bool(p99_shed <= 2 * p99_light),
        "noshed_p99_vs_shed": round(p99_noshed / max(p99_shed, 1e-9), 2),
        "overload_shed": overload["shed"],
        "overload_noshed": overload["noshed"],
        "kill_shed": kill["shed"], "kill_noshed": kill["noshed"],
        "recompiles_after_warmup": bool(recompiled),
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": ROUTER_REQUESTS,
                   "max_new_tokens": ROUTER_MAX_NEW,
                   "kill_max_new_tokens": ROUTER_KILL_MAX_NEW,
                   "overload_requests": ROUTER_OVERLOAD_REQUESTS,
                   "load_shape": "closed_loop_flood",
                   "kill_busy_tick": ROUTER_KILL_TICK,
                   "serve_max_queue_shed": ROUTER_SHED_QUEUE,
                   "serve_slots": 8, "kv_page_size": 16,
                   "decode_chunk": 2, "max_seq_len": 96,
                   "hidden": 128, "layers": 2,
                   "prefix_cache": False,
                   # the router-counter stamp (ISSUE 8 satellite):
                   # failure-drill ledger of the shedded kill window
                   "router_fenced": kill["shed"]["fenced"],
                   "router_resubmitted": kill["shed"]["resubmitted"],
                   "router_timeouts": kill["shed"]["timeouts"],
                   "router_rejected": kill["shed"]["rejected"],
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_paged_attention_tier(n_dev, backend, dev_kind):
    """paged_attention microbench (ISSUE 7): the Pallas paged-decode
    kernel vs the einsum page-gather oracle on the SAME pool, timed
    through the dispatch-floor harness at decode (S=1) and verify
    (S=K+1) shapes across several pool occupancies — the einsum path's
    cost tracks the TABLE width (it re-materializes the whole logical
    cache), the kernel's tracks the live frontier, which is exactly the
    ratio this row records. Also runs the flash block-size autotuner on
    one shape and records whether the measured pick CHANGED the static
    default (the h4096-regression story made re-tunable). Off-TPU the
    kernel runs in interpret mode, so the CPU ratio is a code-path
    smoke, not a perf claim — the row says which."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm
    from flexflow_tpu.search import kernel_tune, measure

    _phase("build_paged_attention")
    vocab = 256
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=1, heads=8,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)
    op = next(o for o in ff.ops
              if type(o).__name__ == "MultiHeadAttention")
    params = {k: jnp.asarray(v) for k, v in ff.params[op.name].items()}

    slots, page_size, pages_per_slot = 4, 16, 16   # max_len 256/slot
    pool_pages = 1 + slots * pages_per_slot
    kvh, dqk, dv = op.num_kv_heads, op.qk_head_dim, op.v_head_dim
    rs = np.random.RandomState(0)
    pool = {"k": jnp.asarray(rs.randn(pool_pages, page_size, kvh, dqk),
                             jnp.float32),
            "v": jnp.asarray(rs.randn(pool_pages, page_size, kvh, dv),
                             jnp.float32)}
    table = jnp.asarray(
        1 + np.arange(slots * pages_per_slot).reshape(slots,
                                                      pages_per_slot),
        jnp.int32)
    row_len = jnp.full((slots,), 24, jnp.int32)
    prompt_pad = jnp.full((slots,), 32, jnp.int32)

    shapes = []
    max_len = pages_per_slot * page_size
    for occ_name, frontier in (("25%", max_len // 4 - 1),
                               ("100%", max_len - 1)):
        for s_name, s in (("decode", 1), ("verify", 4)):
            shapes.append((f"{s_name}@{occ_name}", s, frontier))

    _phase("time_paged_attention")
    rows, ratios = {}, []
    for name, s, frontier in shapes:
        x = jnp.asarray(rs.randn(slots, s, op.q_in), jnp.float32)
        wp = jnp.minimum(
            jnp.full((slots,), frontier - s + 1, jnp.int32)[:, None]
            + jnp.arange(s, dtype=jnp.int32)[None, :], max_len - 1)
        timed = {}
        for impl in ("einsum", "pallas"):
            def step(x_, pool_k, pool_v, impl=impl, s=s, wp=wp):
                out, _ = (op.paged_verify_forward if s > 1
                          else op.paged_decode_forward)(
                    params, [x_, x_, x_], {"k": pool_k, "v": pool_v},
                    table, wp if s > 1 else wp[:, 0],
                    jnp.full((slots,), 24, jnp.int32), row_len,
                    prompt_pad, impl=impl)
                return jnp.sum(out.astype(jnp.float32))

            # best-of-3 rounds with warm programs via the dispatch-floor
            # harness (the same primitive the autotuner trusts)
            timed[impl] = measure.time_scalar_program(
                jax.jit(step), x, pool["k"], pool["v"], warmup=1, iters=3)
        ratio = timed["einsum"] / max(timed["pallas"], 1e-12)
        ratios.append(ratio)
        rows[name] = {"einsum_ms": round(timed["einsum"] * 1e3, 4),
                      "pallas_ms": round(timed["pallas"] * 1e3, 4),
                      "pallas_speedup": round(ratio, 3)}

    # flash block autotune demonstration: at seq 512 the static
    # heuristic takes the whole-sequence 512 tile; the measured sweep
    # reliably prefers a smaller tile on this backend (3/3 repeat runs
    # during bring-up) — a CHANGED pick recorded from a real
    # measurement, the ISSUE-7 acceptance row
    _phase("tune_paged_attention")
    try:
        import tempfile

        # a bench-local table: a 2-iteration demonstration sweep must
        # NEVER overwrite an operator's carefully tuned entry in the
        # persistent default table
        tune_path = os.path.join(
            tempfile.mkdtemp(prefix="ff_bench_ktune_"),
            "kernel_tune.json")
        tune = kernel_tune.tune_flash_attention(
            512, head_dim=16, heads=2, batch=1,
            candidates=((128, 128), (256, 256), (512, 512)), iters=2,
            path=tune_path)
        tune = {k: tune[k] for k in ("sig", "blocks", "static", "changed",
                                     "seconds")}
    except Exception as e:  # noqa: BLE001 — the ratio rows still land
        tune = {"error": f"{type(e).__name__}: {e}"}

    headline = rows["decode@100%"]["pallas_speedup"]
    return {
        "metric": "paged_attention_microbench", "tier": "paged_attention",
        "value": headline, "unit": "x_vs_einsum",
        "vs_baseline": headline,
        "shapes": rows,
        "pallas_native": backend == "tpu",  # CPU = interpret-mode smoke
        "autotune": tune,
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"serve_slots": slots, "kv_page_size": page_size,
                   "pages_per_slot": pages_per_slot,
                   "kv_pages": pool_pages, "heads": 8, "kv_heads": kvh,
                   "head_dim": dqk, "hidden": 128,
                   "paged_attention_impl": "swept",
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_quantized_serving_tier(n_dev, backend, dev_kind):
    """quantized_serving tier (ISSUE 11): the int8 KV pool + int8
    weights vs a bf16 pool at EQUAL pool bytes, on the same warmed
    engine-pair protocol as prefix_serving. Two questions, one row:

    (1) CAPACITY — tokens-per-pool-GB at a fixed byte budget. Both
        engines get the largest page count fitting the SAME budget; the
        int8 pages are ~half the bytes (payload halves; the per-page-
        per-head scale sliver rides the budget), so the usable page
        count — and with it prefix-cache capacity and the max
        concurrent max-length requests the pool can hold — doubles.
        The acceptance bar is capacity_ratio >= 2.0 (the shared scratch
        page amortizes across 2x the usable pages, which is what makes
        the ratio land ON 2.0 rather than epsilon under it).
    (2) THROUGHPUT-PER-GB — tokens/s divided by pool GB on a skewed
        shared-prefix workload, both engines fully warmed, zero
        timed-window recompiles (stamped). On this CPU box the
        quantized engine pays interpret/dequant overhead compute-side;
        the per-GB number is the capacity story, the on-chip win needs
        native Mosaic (pallas_native says which).

    Token agreement int8-vs-bf16 rides the row as the measured
    divergence (budgeted per docs/serving.md, identity not claimed)."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm
    from flexflow_tpu.search import kernel_tune

    _phase("build_quantized_serving")
    vocab = 128
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=64, layers=1, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)
    op = next(o for o in ff.ops
              if type(o).__name__ == "MultiHeadAttention")

    page_size, slots, max_seq_len = 16, 4, 80
    pages_per_slot = max_seq_len // page_size           # 5
    # equal pool bytes: price a page per dtype analytically, give each
    # engine the LARGEST page count fitting one shared byte budget
    kvh, dsum = op.num_kv_heads, op.qk_head_dim + op.v_head_dim
    page_bf16 = page_size * kvh * dsum * 2
    page_int8 = page_size * kvh * dsum + 2 * kvh * 4    # + k/v scales
    pages_bf16 = 25                                     # the byte budget
    budget = pages_bf16 * page_bf16
    pages_int8 = budget // page_int8

    def build(kv_dtype, wd, pages):
        return ff.make_serving_engine(
            serve_slots=slots, kv_page_size=page_size,
            kv_pages=int(pages), max_seq_len=max_seq_len,
            decode_buckets=[32, 48], decode_chunk=8,
            kv_cache_dtype=kv_dtype, weight_dtype=wd)

    eng = {"bf16": build("bf16", "native", pages_bf16),
           "int8": build("int8", "int8", pages_int8)}
    for name, e in eng.items():
        assert e.stats()["kv_pool_bytes"] <= budget, (
            name, e.stats()["kv_pool_bytes"], budget)

    # skewed shared-prefix workload: 80% share a 32-token system prompt
    # (2 full pages), interleaved with short background prompts
    rs = np.random.RandomState(0)
    system = rs.randint(1, vocab, (32,)).astype(np.int32)
    n_req, max_new = 48, 8
    prompts = []
    for i in range(n_req):
        if i % 5 < 4:
            tail = rs.randint(1, vocab, (1 + int(rs.randint(0, 8)),))
            prompts.append(np.concatenate([system, tail.astype(np.int32)]))
        else:
            prompts.append(rs.randint(
                1, vocab, (3 + int(rs.randint(0, 20)),)).astype(np.int32))

    _phase("warm_quantized_serving")
    # ServingEngine.warmup over the WORKLOAD prompts (two passes, the
    # measurement's own max_new) replaces the hand-curated variant list
    # this tier used to maintain: under pool pressure the reachable
    # (bucket, matched_pages) set depends on the eviction orbit, and
    # running the real workload twice IS that orbit — the PR 7 gotcha
    # ("warm ALL hit-prefill variants or the timed window compiles")
    # promoted to an API
    warm = {}
    for name, e in eng.items():
        e.warmup([p.copy() for p in prompts], max_new_tokens=max_new)
        warm[name] = e.recompile_count

    rows = {}
    streams = {}
    for name, e in eng.items():
        _phase(f"time_quantized_serving_{name}")
        best_tps, toks = None, 0
        for _ in range(2):                              # best-of-2
            t0 = time.perf_counter()
            reqs = e.run([p.copy() for p in prompts],
                         max_new_tokens=max_new)
            dt = time.perf_counter() - t0
            toks = sum(len(r.tokens) for r in reqs)
            assert all(r.state == "done" for r in reqs)
            tps = toks / dt
            if best_tps is None or tps > best_tps:
                best_tps = tps
            streams[name] = [np.asarray(r.tokens, np.int32)
                             for r in reqs]
        st = e.stats()
        pool_gb = st["kv_pool_bytes"] / (1 << 30)
        cap_tokens = (st["kv_pages"] - 1) * page_size
        rows[name] = {
            "tokens_per_s": round(best_tps, 2),
            "tokens_per_s_per_pool_gb": round(best_tps / pool_gb, 1),
            "pool_bytes": st["kv_pool_bytes"],
            "kv_pages": st["kv_pages"],
            "capacity_tokens": cap_tokens,
            "tokens_per_pool_gb": round(cap_tokens / pool_gb, 1),
            "max_concurrent_max_len_requests":
                (st["kv_pages"] - 1) // pages_per_slot,
            "kv_bytes_per_token": st["kv_bytes_per_token"],
            "prefix_hit_rate": st["prefix_hit_rate"],
            "recompiles_after_warmup":
                e.recompile_count - warm[name],
            "kv_cache_dtype": st["kv_cache_dtype"],
            "weight_dtype": st["weight_dtype"],
        }
    agree = float(np.mean([np.mean(a == b) if a.shape == b.shape
                           else 0.0
                           for a, b in zip(streams["bf16"],
                                           streams["int8"])]))
    capacity_ratio = (rows["int8"]["tokens_per_pool_gb"]
                      / rows["bf16"]["tokens_per_pool_gb"])
    slots_ratio = (rows["int8"]["max_concurrent_max_len_requests"]
                   / rows["bf16"]["max_concurrent_max_len_requests"])

    # autotune demonstration: measure the paged kernel on the QUANTIZED
    # pool shape into a bench-local table (never the operator's
    # persistent one) — the dtype-keyed entry an 'auto' engine consults
    _phase("tune_quantized_paged")
    try:
        import tempfile

        tpath = os.path.join(
            tempfile.mkdtemp(prefix="ff_bench_qtune_"), "ktune.json")
        tune = kernel_tune.tune_paged_attention(
            page_size=page_size, pages_per_slot=pages_per_slot,
            head_dim=op.qk_head_dim, kv_heads=kvh, heads=op.num_heads,
            slots=slots, kv_dtype="int8", iters=2, path=tpath)
        tune = {k: tune[k] for k in ("sig", "impl", "kv_dtype",
                                     "seconds")}
    except Exception as e:  # noqa: BLE001 — the capacity row still lands
        tune = {"error": f"{type(e).__name__}: {e}"}

    st8 = eng["int8"].stats()
    return {
        "metric": "quantized_serving_capacity", "tier": "quantized_serving",
        "value": round(capacity_ratio, 3), "unit": "x_tokens_per_pool_gb",
        "vs_baseline": round(capacity_ratio, 3),
        "capacity_ratio_int8_vs_bf16": round(capacity_ratio, 3),
        "capacity_2x": bool(capacity_ratio >= 2.0),
        "max_concurrent_slots_ratio": round(slots_ratio, 3),
        "tokens_per_s_per_gb_int8":
            rows["int8"]["tokens_per_s_per_pool_gb"],
        "tokens_per_s_per_gb_bf16":
            rows["bf16"]["tokens_per_s_per_pool_gb"],
        "greedy_agreement_int8_vs_bf16": round(agree, 4),
        "zero_warm_recompiles": bool(
            rows["int8"]["recompiles_after_warmup"] == 0
            and rows["bf16"]["recompiles_after_warmup"] == 0),
        "engines": rows,
        "autotune": tune,
        "pallas_native": backend == "tpu",
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": n_req, "max_new_tokens": max_new,
                   "serve_slots": slots, "kv_page_size": page_size,
                   "max_seq_len": max_seq_len,
                   "pool_byte_budget": budget,
                   "hidden": 64, "layers": 1, "kv_heads": kvh,
                   "kv_cache_dtype": "int8_vs_bf16",
                   "weight_dtype_int8_engine":
                       rows["int8"]["weight_dtype"],
                   "paged_attention_impl":
                       st8["paged_attention_impl"],
                   "kernel_tune_hits": st8["kernel_tune_hits"],
                   "kernel_tune_misses": st8["kernel_tune_misses"],
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_tiered_prefix_tier(n_dev, backend, dev_kind):
    """tiered_prefix tier (ISSUE 12): the HBM->host prefix-cache tier
    under a working set deliberately sized ~3x the HBM pool, plus the
    disaggregation identity contracts.

    (1) TIER VALUE — 12 distinct 7-page (112-token) prefixes rotate
        through a pool whose cache space holds only a few: the untiered
        engine's evictions DIE (every recurrence re-prefills cold)
        while the tiered engine demotes to host RAM and promotes on
        re-match.
        Both engines identical geometry, both warmed by
        ServingEngine.warmup over the workload itself; the row stamps
        timed-window hit rate and p99 TTFT for both (acceptance: tiered
        hit rate HIGHER, tiered p99 LOWER, zero timed-window recompiles
        on either engine) and the demotion/promotion counters.
    (2) IDENTITY — the handoff + tier paths move pages bitwise, pinned
        two ways with speculation live: a full-width 1-prefill/1-decode
        fleet vs a genuinely COLD single-replica engine (hit==cold is
        bitwise on full-width pools), and an int8-KV fleet / pressured
        tiered int8 engine vs a prefill_into_cache-seeded (resp.
        pressure-free) single engine — under lossy KV, hit-vs-cold is
        not bitwise by design (docs/serving.md), so the int8 contract
        compares equal published state, which is exactly what the
        handoff and the tier migrations replay."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm

    _phase("build_tiered_prefix")
    vocab = 128
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    # the tier-value engines run a PREFILL-DOMINATED shape (hidden 512,
    # 7-page prefixes): the tier trades one D2H + one H2D per page
    # against re-running prefill over page_size positions, so it pays
    # exactly when prefill compute dominates page-copy time — the
    # production serving regime (docs/serving.md "when a host tier pays
    # for itself"). On a toy 1-layer model the migration dispatches
    # cost more than the prefill they save and the tier honestly loses.
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=512, layers=2,
                         heads=8, kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    ps, slots, max_seq_len, max_new = 16, 2, 144, 4
    prefix_pages = 7        # 112-token shared prefixes, bucket 128
    kv_pages = 28           # 19 live (2 slots x 9 + scratch) + ~9 cache
    n_prefix, rounds = 12, 2
    rs = np.random.RandomState(0)
    prefixes = [rs.randint(1, vocab, (prefix_pages * ps,)).astype(
        np.int32) for _ in range(n_prefix)]
    working_set_pages = prefix_pages * n_prefix         # 84 = 3 x pool
    # round-robin over the prefixes: each prefix recurs only after all
    # the others ran, so the untiered LRU has ALWAYS evicted it again
    prompts = [np.concatenate(
        [prefixes[i], rs.randint(1, vocab, (1 + (r + i) % 6,)).astype(
            np.int32)])
        for r in range(rounds) for i in range(n_prefix)]

    def mk_engine(host_pages, **kw):
        return ff.make_serving_engine(
            serve_slots=slots, kv_page_size=ps, kv_pages=kv_pages,
            max_seq_len=max_seq_len, decode_chunk=8,
            host_kv_pages=host_pages, **kw)

    _phase("warm_tiered_prefix")
    engines = {"tiered": mk_engine(96), "untiered": mk_engine(0)}
    for eng in engines.values():
        eng.warmup(prompts, max_new_tokens=max_new)

    results = {}
    for name, eng in engines.items():
        _phase(f"time_tiered_prefix_{name}")
        warm_compiles = eng.recompile_count
        best_dt, timed_reqs = None, []
        st0 = eng.stats()
        for _ in range(2):
            t0 = time.perf_counter()
            reqs = eng.run([p.copy() for p in prompts],
                           max_new_tokens=max_new)
            dt = time.perf_counter() - t0
            best_dt = dt if best_dt is None else min(best_dt, dt)
            timed_reqs.extend(reqs)
        st = eng.stats()
        ttfts = sorted(r.ttft for r in timed_reqs if r.ttft)

        def _pct(p, tt=ttfts):
            return round(tt[min(len(tt) - 1, int(p * len(tt)))] * 1e3, 3) \
                if tt else 0.0

        # hit rate over the TIMED WINDOW only (stats deltas): lifetime
        # rates would smuggle the warmup's publishes into the number
        lk = st["prefix_lookups"] - st0["prefix_lookups"]
        results[name] = {
            "tokens_per_s": round(
                sum(len(r.tokens) for r in timed_reqs) / 2 / best_dt, 2),
            "hit_rate": round(
                (st["prefix_hits"] - st0["prefix_hits"]) / max(1, lk), 4),
            "p50_ttft_ms": _pct(0.50), "p99_ttft_ms": _pct(0.99),
            "all_done": all(r.state == "done" for r in timed_reqs),
            "recompiles": eng.recompile_count - warm_compiles,
            # migration counters over the TIMED WINDOW (same delta
            # discipline as the hit rate — lifetime values would fold
            # warmup churn into the measured window); kv_pages_host is
            # a point-in-time gauge
            "tier_demotions": st["tier_demotions"]
            - st0["tier_demotions"],
            "tier_promotions": st["tier_promotions"]
            - st0["tier_promotions"],
            "tier_host_evictions": st["tier_host_evictions"]
            - st0["tier_host_evictions"],
            "kv_pages_host": st["kv_pages_host"],
        }

    # ---- identity legs (handoff + tier, speculation live) ----
    # A separate TINY model keeps the ~10 engines these legs build (a
    # fleet + references, each with draft/verify programs) cheap —
    # identity does not care about model size, only page plumbing.
    _phase("tiered_prefix_identity")
    ff2 = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}))
    _, logits2 = llama_lm(ff2, 2, seq_len=16, hidden=64, layers=1,
                          heads=4, kv_heads=2, vocab_size=vocab)
    ff2.compile(final_tensor=logits2)
    i_ps, i_msl = 16, 80
    ident_prompts = [np.concatenate(
        [rs.randint(1, vocab, (3 * i_ps,)).astype(np.int32),
         rs.randint(1, vocab, (3,)).astype(np.int32)]) for _ in range(6)]

    def streams(reqs):
        return [list(r.tokens) for r in reqs]

    def ident_engine(**ekw):
        return ff2.make_serving_engine(
            serve_slots=slots, kv_page_size=i_ps, max_seq_len=i_msl,
            decode_chunk=8, kv_pages=64, **ekw)

    def fleet_vs(ref_engine, seed_ref, **ekw):
        """Run ident_prompts through a 1-prefill/1-decode fleet and a
        single-replica reference; True when token-identical."""
        if seed_ref:
            for p in ident_prompts:
                ref_engine.prefill_into_cache(p)
        want = streams(ref_engine.run(ident_prompts,
                                      max_new_tokens=max_new))
        router = ff2.make_serving_router(
            replicas=2, roles=["prefill", "decode"], serve_slots=slots,
            kv_page_size=i_ps, max_seq_len=i_msl, kv_pages=64,
            decode_chunk=8, start=False, **ekw)
        try:
            reqs = router.run(ident_prompts, max_new_tokens=max_new,
                              timeout=900)
            ok = all(r.state == "done" for r in reqs)
            got = streams(reqs)
            return bool(ok and got == want), router.stats()["handoffs"]
        finally:
            router.close()

    spec = dict(draft_model=ff2, speculate_k=2)
    # (a) full width: fleet vs a genuinely COLD single replica
    ident_fullwidth, handoffs_fw = fleet_vs(
        ident_engine(**spec), seed_ref=False, **spec)
    # (b) int8 KV + speculation: fleet vs a seeded single replica
    # (hit-vs-cold is not bitwise under lossy KV — docs/serving.md —
    # so the int8 contract compares equal published state, which is
    # exactly what the handoff replays)
    ident_int8, handoffs_i8 = fleet_vs(
        ident_engine(kv_cache_dtype="int8", **spec), seed_ref=True,
        kv_cache_dtype="int8", **spec)
    # (c) tier path under int8 + speculation: a pressured tiered engine
    # (pool sized to 11 pages: 1 slot's worth of cache slack) vs a
    # genuinely roomy engine — promotions are bitwise, so pressure must
    # not change a stream
    roomy = ident_engine(kv_cache_dtype="int8", **spec)
    tier8 = ff2.make_serving_engine(
        serve_slots=slots, kv_page_size=i_ps, max_seq_len=i_msl,
        decode_chunk=8, kv_pages=14, host_kv_pages=64,
        kv_cache_dtype="int8", **spec)
    want8 = [streams(roomy.run(ident_prompts, max_new_tokens=max_new))
             for _ in range(2)]
    got8 = [streams(tier8.run(ident_prompts, max_new_tokens=max_new))
            for _ in range(2)]
    t8 = tier8.stats()
    ident_tier_int8 = bool(got8 == want8 and t8["tier_promotions"] > 0)

    tiered, untiered = results["tiered"], results["untiered"]
    return {
        "metric": "tiered_prefix_serving", "tier": "tiered_prefix",
        "value": tiered["hit_rate"], "unit": "timed_window_hit_rate",
        "vs_baseline": round(
            tiered["hit_rate"] / max(1e-4, untiered["hit_rate"]), 3),
        "untiered_hit_rate": untiered["hit_rate"],
        "p99_ttft_ms": tiered["p99_ttft_ms"],
        "untiered_p99_ttft_ms": untiered["p99_ttft_ms"],
        "hit_rate_higher": bool(
            tiered["hit_rate"] > untiered["hit_rate"]),
        "p99_ttft_lower": bool(
            tiered["p99_ttft_ms"] < untiered["p99_ttft_ms"]),
        "recompiles_after_warmup": tiered["recompiles"]
        + untiered["recompiles"],
        "all_done": tiered["all_done"] and untiered["all_done"],
        "token_identity_fleet_vs_cold_fullwidth_spec": ident_fullwidth,
        "token_identity_fleet_int8_spec_seeded_ref": ident_int8,
        "token_identity_tier_int8_spec": ident_tier_int8,
        "identity_handoffs": {"fullwidth": handoffs_fw,
                              "int8": handoffs_i8},
        "engines": results,
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": len(prompts), "max_new_tokens": max_new,
                   "serve_slots": slots, "kv_page_size": ps,
                   "kv_pages": kv_pages, "host_kv_pages": 96,
                   "prefix_working_set_pages": working_set_pages,
                   "working_set_vs_pool": round(
                       working_set_pages / kv_pages, 2),
                   "distinct_prefixes": n_prefix,
                   "prefix_pages": prefix_pages,
                   "max_seq_len": max_seq_len, "decode_chunk": 8,
                   "hidden": 512, "layers": 2,
                   "identity_model_hidden": 64,
                   "speculate_k_identity_legs": 2,
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_multi_tenant_tier(n_dev, backend, dev_kind):
    """multi_tenant row (ISSUE 14): 8 LoRA tenants with mixed sampling
    configs on ONE engine vs the same engine single-tenant greedy —
    aggregate tokens/s both ways and the recompile counts that prove
    tenant churn is data, not programs. The multi-tenant number honestly
    carries the gathered-LoRA matmuls and the adapter fault-in writes
    (8 tenants through a 6-page pool: the LRU churns); what it must NOT
    carry is a single compile."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm

    _phase("build_multi_tenant")
    vocab, rank, n_adapters = 128, 8, 8
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=64, layers=1, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    rs = np.random.RandomState(0)
    n_requests, max_new = 32, 24
    prompts = [rs.randint(1, vocab, (int(rs.randint(4, 14)),)
                          ).astype(np.int32) for _ in range(n_requests)]

    def build(pool_pages):
        return ff.make_serving_engine(
            serve_slots=4, kv_page_size=8, max_seq_len=64,
            decode_chunk=8, adapter_pool_pages=pool_pages,
            lora_rank=rank)

    def timed(eng, submit_plan, rounds=3):
        warm = eng.recompile_count
        best, tokens = None, 0
        for _ in range(rounds):
            before = eng.stats()["tokens_generated"]
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new, **kw) for p, kw in submit_plan]
            while eng.step():
                pass
            dt = time.perf_counter() - t0
            assert all(r.state == "done" for r in reqs)
            tokens = eng.stats()["tokens_generated"] - before
            best = dt if best is None else min(best, dt)
        return tokens / best, eng.recompile_count - warm

    _phase("warm_multi_tenant")
    single = build(0)
    single.warmup(prompts, max_new_tokens=max_new)
    multi = build(6)
    names = [f"tenant{i}" for i in range(n_adapters)]
    geo = multi.lora.geometry
    for i, name in enumerate(names):
        ra = np.random.RandomState(100 + i)
        multi.register_adapter(name, {
            n: {"a": (ra.randn(g[0], rank) * 0.2).astype(np.float32),
                "b": (ra.randn(rank, g[1]) * 0.2).astype(np.float32)}
            for n, g in geo.items()})
    multi.warmup(prompts, max_new_tokens=max_new)

    def tenant_kw(i):
        if i % 2 == 0:
            return {"adapter": names[i % n_adapters], "temperature": 0.0,
                    "seed": i}
        return {"adapter": names[i % n_adapters],
                "temperature": 0.7 + 0.1 * (i % 3),
                "top_p": 0.9 if i % 3 else 1.0, "seed": i}

    multi_plan = [(p, tenant_kw(i)) for i, p in enumerate(prompts)]
    # warm pass outside the window: every tenant namespace publishes its
    # prefixes and faults in once, so the timed rounds measure steady
    # state (the LRU still churns — 8 tenants, 6 pages)
    for p, kw in multi_plan:
        multi.submit(p, 4, **kw)
    while multi.step():
        pass
    multi_warm_faults = multi.stats()["adapter_faults"]

    _phase("time_multi_tenant")
    single_tps, single_rc = timed(single, [(p, {}) for p in prompts])
    multi_tps, multi_rc = timed(multi, multi_plan)
    st = multi.stats()
    return {
        "metric": "multi_tenant_serving", "tier": "multi_tenant",
        "value": round(multi_tps, 2), "unit": "tokens/s",
        "single_tenant_tokens_per_s": round(single_tps, 2),
        "vs_single_tenant": round(multi_tps / max(single_tps, 1e-9), 3),
        "recompiles_after_warmup_multi": multi_rc,
        "recompiles_after_warmup_single": single_rc,
        "adapters": n_adapters,
        "adapter_pool_pages": st["adapter_pool_pages"],
        "adapter_faults_timed": st["adapter_faults"] - multi_warm_faults,
        "adapter_evictions": st["adapter_evictions"],
        "sampled_requests": st["sampled_requests"],
        "lora_rank": rank,
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": n_requests, "max_new_tokens": max_new,
                   "serve_slots": 4, "kv_page_size": 8,
                   "decode_chunk": 8, "hidden": 64, "layers": 1,
                   "vocab": vocab,
                   "paged_attention_impl": st["paged_attention_impl"]},
    }


def _run_rolling_deploy_tier(n_dev, backend, dev_kind):
    """rolling_deploy row (ISSUE 17): the SLO-gated rolling deployment's
    cost, measured honestly — the SAME closed-loop flood through a
    2-replica fleet twice, once steady-state and once with a weight
    version published mid-flood and rolled through the fleet (suspend ->
    drain -> hot-swap -> re-warmup -> readmit, one replica at a time).
    The claim is that a roll costs capacity (one replica out at a time),
    never correctness or compiles: every request completes, p99 TTFT
    degrades boundedly, zero warm-window recompiles anywhere. A third
    window forces a canary SLO breach (FF_FAULT slow@canary under a
    tight TTFT ceiling) and stamps the rollback-drill latency — breach
    detected to fleet-back-on-v1 — in the config block."""
    import shutil
    import tempfile

    import numpy as np

    import jax

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm
    from flexflow_tpu.runtime import faultinject, flightrec
    from flexflow_tpu.runtime.deploy import (RollingDeployer,
                                             WeightArtifactRegistry)

    _phase("build_rolling_deploy")
    vocab = 256
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=4,
                   kv_page_size=16, slo_window_s=1.0)
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    work = tempfile.mkdtemp(prefix="ff_bench_deploy_")
    registry = WeightArtifactRegistry(os.path.join(work, "watch"))
    rs = np.random.RandomState(0)
    lens = [SERVE_PROMPT_LENS[i % len(SERVE_PROMPT_LENS)]
            for i in range(ROUTER_REQUESTS)]
    prompts = [rs.randint(1, vocab, (n,)).astype(np.int32) for n in lens]
    warm = [rs.randint(1, vocab, (n,)).astype(np.int32)
            for n in SERVE_PROMPT_LENS]

    def publish(step, scale):
        keep = ff.params
        ff.params = ff.executor.reshard_params(jax.tree_util.tree_map(
            lambda x: (np.asarray(x) * scale).astype(
                np.asarray(x).dtype), keep))
        try:
            return registry.publish(ff, step=step)
        finally:
            ff.params = keep

    def mk_router():
        r = ff.make_serving_router(
            replicas=2, max_seq_len=96, serve_slots=8, decode_chunk=2,
            prefix_cache=False, start=False)
        r.warmup(warm, max_new_tokens=4)
        return r

    def flood_window(name, deploy_to=None, canary_windows=1,
                     fault=None, slo_cfg=None):
        """Flood the fleet; optionally run a deploy mid-flood. Returns
        (p99/p50 TTFT, tokens/s, deploy report, recompile leak)."""
        _phase(f"time_deploy_{name}")
        old_fault = os.environ.get("FF_FAULT")
        if fault:
            os.environ["FF_FAULT"] = fault
            faultinject.reset()
        router = mk_router()
        # AFTER mk_router: engine/router creation re-runs
        # flightrec.configure with the model cfg (last configure wins),
        # so the drill's tight SLO ceiling must land on top of it
        if slo_cfg is not None:
            flightrec.configure(slo_cfg)
        try:
            warm_compiles = [e.recompile_count for e in router.engines]
            router.start()
            time.sleep(0.05)
            t0 = time.perf_counter()
            reqs = [router.submit(prompts[i % len(prompts)],
                                  ROUTER_MAX_NEW)
                    for i in range(ROUTER_REQUESTS)]
            report = None
            if deploy_to is not None:
                dep = RollingDeployer(router, registry,
                                      canary_windows=canary_windows)
                report = dep.deploy(deploy_to, warmup_prompts=warm,
                                    max_new_tokens=4)
            router.wait(reqs, timeout=1200)
            dt = time.perf_counter() - t0
            assert all(r.state == "done" for r in reqs), \
                f"{name}: a request was dropped through the roll"
            done = sorted(r.ttft for r in reqs)

            def pct(p):
                return round(done[min(len(done) - 1,
                                      int(p * len(done)))] * 1e3, 3)

            leaked = any(e.recompile_count != c for e, c
                         in zip(router.engines, warm_compiles))
            tps = ROUTER_REQUESTS * ROUTER_MAX_NEW / dt
            return {"p99_ttft_ms": pct(0.99), "p50_ttft_ms": pct(0.50),
                    "tokens_per_s": round(tps, 2)}, report, leaked
        finally:
            router.close()
            if fault:
                if old_fault is None:
                    os.environ.pop("FF_FAULT", None)
                else:
                    os.environ["FF_FAULT"] = old_fault
                faultinject.reset()

    try:
        v1 = publish(1, 1.25)
        steady, _, leak_steady = flood_window("steady")
        rolling, roll_report, leak_roll = flood_window(
            "rolling", deploy_to=v1)
        assert roll_report["state"] == "completed", roll_report

        # rollback drill: tight TTFT ceiling + slow@canary stalls ->
        # breach in the canary's first rebaselined window -> automatic
        # rollback; the drill latency is breach -> fleet-on-prior
        v2 = publish(2, 1.5)
        _, back_report, _ = flood_window(
            "rollback_drill", deploy_to=v2, canary_windows=2,
            fault="slow(600)@canary:1-400",
            slo_cfg=FFConfig(
                batch_size=2, mesh_shape={"data": 1},
                slo_ttft_p99_s=0.25, slo_window_s=1.0,
                flight_recorder_dir=os.path.join(work, "flight"),
                flight_debounce_s=600.0))
        assert back_report["state"] == "rolled_back", back_report
    finally:
        shutil.rmtree(work, ignore_errors=True)

    return {
        "metric": "rolling_deploy_serving", "tier": "rolling_deploy",
        # headline: aggregate tokens/s THROUGH the roll (the honest
        # cost number), with steady state as the baseline ratio
        "value": rolling["tokens_per_s"], "unit": "tokens/s",
        "vs_baseline": round(rolling["tokens_per_s"]
                             / steady["tokens_per_s"], 3),
        "steady_tokens_per_s": steady["tokens_per_s"],
        "rolling_tokens_per_s": rolling["tokens_per_s"],
        "p99_ttft_ms_steady": steady["p99_ttft_ms"],
        "p99_ttft_ms_rolling": rolling["p99_ttft_ms"],
        "p50_ttft_ms_steady": steady["p50_ttft_ms"],
        "p50_ttft_ms_rolling": rolling["p50_ttft_ms"],
        "roll_duration_s": roll_report["duration_s"],
        "recompiles_after_warmup": bool(leak_steady or leak_roll),
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": ROUTER_REQUESTS,
                   "max_new_tokens": ROUTER_MAX_NEW,
                   "load_shape": "closed_loop_flood",
                   "replicas": 2, "serve_slots": 8, "kv_page_size": 16,
                   "decode_chunk": 2, "max_seq_len": 96,
                   "hidden": 128, "layers": 2, "prefix_cache": False,
                   "canary_windows": 1, "slo_window_s": 1.0,
                   # the rollback-drill stamp (ISSUE 17 acceptance):
                   # canary breach -> every replica back on the prior
                   # version
                   "rollback_breach_slo":
                       (back_report["breach"] or {}).get("slo"),
                   "rollback_latency_s": back_report["rollback_s"],
                   "rollback_replicas": len(back_report["swapped"])},
    }



def _run_elastic_fleet_tier(n_dev, backend, dev_kind):
    """elastic_fleet row (ISSUE 20): one fleet walked through its whole
    elastic lifecycle, each transition priced.

    (1) CONGESTED — a 2x closed-loop flood (64 requests, 2 replicas)
        after a seed round: the overloaded baseline p99 TTFT.
    (2) SCALE-OUT — the same flood with add_replica() fired after the
        submits land: add_replica latency, recovery seconds (newcomer
        admitted -> fleet queue drained), p99 TTFT vs the congested
        window, and a zero-survivor-recompile check (the newcomer warms
        off-lock; the incumbents' programs must not be touched).
    (3) SCALE-IN — the shared prefix's affinity home is retired via
        remove_replica(): tokens/s capacity step-down (3 -> 2 replicas)
        with the fleet prefix hit rate re-measured after the evacuation
        — the home's hot pages must serve from survivors.
    (4) PREEMPT DRILL — request_preempt() mid-flood on a live replica:
        every request completes exactly once (no fence, no loss), and
        the drill's evacuation bytes + deadline margin are stamped in
        the config block."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm

    _phase("build_elastic_fleet")
    vocab = 256
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=4,
                   kv_page_size=16, slo_window_s=1.0)
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2, heads=4,
                         kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)

    rs = np.random.RandomState(7)
    # every prompt shares a 2-page system prefix (kv_page_size=16) so
    # affinity concentrates its pages on one home replica — the replica
    # the scale-in and preempt windows then take away
    system = rs.randint(1, vocab, (32,)).astype(np.int32)
    tails = [rs.randint(1, vocab, (n,)).astype(np.int32)
             for n in SERVE_PROMPT_LENS]
    warm = [np.concatenate([system, t]) for t in tails]
    prompts = [warm[i % len(warm)] for i in range(ROUTER_REQUESTS)]
    # the flood must OUTLAST the transition it measures (the newcomer's
    # off-lock warmup takes ~10s of compile on a shared CPU host): a
    # deep backlog of long decodes, not the quick ROUTER_REQUESTS burst
    # the steady-state tiers use
    flood_n, max_new = 896, 40

    router = ff.make_serving_router(
        replicas=2, max_seq_len=112, serve_slots=4, decode_chunk=2,
        prefix_cache=True, start=False)
    router.warmup(warm, max_new_tokens=4)

    def run_round(n, tag):
        _phase(f"time_elastic_{tag}")
        t0 = time.perf_counter()
        reqs = [router.submit(prompts[i % len(prompts)], max_new)
                for i in range(n)]
        return t0, reqs

    def settle(t0, reqs, tag):
        router.wait(reqs, timeout=1200)
        dt = time.perf_counter() - t0
        assert all(r.state == "done" for r in reqs), \
            f"{tag}: a request was dropped through the transition"
        ttfts = sorted(r.ttft for r in reqs)

        def pct(p):
            return round(ttfts[min(len(ttfts) - 1,
                                   int(p * len(ttfts)))] * 1e3, 3)

        return {"p99_ttft_ms": pct(0.99),
                "tokens_per_s": round(len(reqs) * max_new / dt, 2)}

    def hit_counters():
        hits = lookups = 0
        for eng in router.engines:
            pc = eng.prefix_cache
            if pc is not None:
                hits += pc.hits
                lookups += pc.lookups
        return hits, lookups

    try:
        router.start()
        time.sleep(0.05)
        # seed round: both incumbents page the shared prefix and the
        # affinity map homes it, so every timed window is equally warm
        settle(*run_round(len(warm) * 2, "seed"), tag="seed")

        # (1) congested baseline
        t0, reqs = run_round(flood_n, "congested")
        congested = settle(t0, reqs, "congested")

        # (2) scale-out mid-flood: recovery is clocked from the SCALING
        # DECISION (the add_replica call) to the backlog draining — the
        # newcomer's off-lock build/warmup is part of the honest number
        incumbent_compiles = [e.recompile_count for e in router.engines]
        t0, reqs = run_round(flood_n, "scale_out")
        t_add = time.perf_counter()
        router.add_replica(warmup_prompts=warm, max_new_tokens=4)
        add_s = time.perf_counter() - t_add
        while router.health()["queued"] > 0:
            time.sleep(0.005)
        recovery_s = time.perf_counter() - t_add
        scaled = settle(t0, reqs, "scale_out")
        leaked = any(e.recompile_count != c for e, c
                     in zip(router.engines, incumbent_compiles))

        # (3) scale-in: retire the shared prefix's home, keep its pages
        _phase("time_elastic_scale_in")
        probe = router.submit(warm[0], 4)
        router.wait([probe], timeout=600)
        home = probe.replica
        h0, l0 = hit_counters()
        pre = settle(*run_round(96, "pre_scale_in"), tag="pre_scale_in")
        h1, l1 = hit_counters()
        snap = router.remove_replica(home)
        assert not snap["fenced"], snap
        post = settle(*run_round(96, "post_scale_in"),
                      tag="post_scale_in")
        h2, l2 = hit_counters()
        hit_before = (h1 - h0) / max(1, l1 - l0)
        hit_after = (h2 - h1) / max(1, l2 - l1)

        # (4) preempt drill on one of the two remaining live replicas,
        # mid-flood so it carries queued + in-flight work and hot pages
        pre_drill = router.stats()
        alive = [row["replica"] for row in pre_drill["per_replica"]
                 if not row["fenced"] and not row["retired"]]
        t0, reqs = run_round(128, "preempt")
        time.sleep(0.5)
        router.request_preempt(alive[0], 0.8)
        settle(t0, reqs, "preempt")
        st = router.stats()
        assert st["preempts"] - pre_drill["preempts"] == 1, \
            "preempt drill never fired (flood drained too early?)"
        assert router.health()["fenced"] == 0, \
            "preempt drill fenced a replica (evacuation should be clean)"
        assert all(r.losses == 0 for r in reqs), \
            "preempt drill counted a loss (evacuation is not a loss)"
    finally:
        router.close()

    return {
        "metric": "elastic_fleet_serving", "tier": "elastic_fleet",
        # headline: seconds from newcomer-admitted to backlog-drained
        # under the 2x flood, with the p99 TTFT ratio (scaled vs
        # congested) as the baseline comparison
        "value": round(recovery_s, 3), "unit": "s",
        "vs_baseline": round(scaled["p99_ttft_ms"]
                             / max(1e-9, congested["p99_ttft_ms"]), 3),
        "p99_ttft_ms_congested": congested["p99_ttft_ms"],
        "p99_ttft_ms_scaled": scaled["p99_ttft_ms"],
        "add_replica_s": round(add_s, 3),
        "recovery_s": round(recovery_s, 3),
        "recompiles_after_warmup": bool(leaked),
        "scale_in_tokens_per_s_before": pre["tokens_per_s"],
        "scale_in_tokens_per_s_after": post["tokens_per_s"],
        "scale_in_hit_rate_before": round(hit_before, 3),
        "scale_in_hit_rate_after": round(hit_after, 3),
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"requests": flood_n,
                   "max_new_tokens": max_new,
                   "load_shape": "closed_loop_flood_2x",
                   "replicas_start": 2, "replicas_peak": 3,
                   "serve_slots": 4, "kv_page_size": 16,
                   "shared_prefix_tokens": int(system.size),
                   "max_seq_len": 112, "decode_chunk": 2,
                   "hidden": 128, "layers": 2, "prefix_cache": True,
                   # the preempt-drill stamps (ISSUE 20 acceptance):
                   # deltas over the drill window, except the margin
                   # (the drill is the fleet's only preemption)
                   "preempt_deadline_s": 0.8,
                   "preempt_margin_s": st["preempt_margin_s"],
                   "evacuation_bytes": st["evacuation_bytes"]
                       - pre_drill["evacuation_bytes"],
                   "evacuated_requests": st["evacuated_requests"]
                       - pre_drill["evacuated_requests"],
                   "evacuated_slabs": st["evacuated_slabs"]
                       - pre_drill["evacuated_slabs"],
                   "evac_deadline_misses": st["evac_deadline_misses"]},
    }


def _run_long_context_tier(n_dev, backend, dev_kind):
    """long_context tier (ISSUE 18): the two long-context serving
    claims, measured.

    (1) INTERLEAVE — a live decode stream's inter-token gaps while a
        MAXIMAL (500-token, 32-chunk) prompt admits mid-stream,
        interleave off (run-to-completion admission: the stream eats
        the whole prefill as ONE gap) vs on (one chunk quantum per
        tick). Both engines warmed by an identical cold round (prefix
        cache off so timed rounds replay the warm round's programs);
        acceptance: interleaved p99 gap measurably LOWER, identical
        tokens both arms, zero timed-window recompiles.
    (2) SEQ-PARALLEL — TTFT vs prompt length at 3 lengths, a
        single-replica engine vs a 2-prefill/1-decode fleet with
        ``seq_parallel_shards=2``. On the CPU smoke box the shards run
        serially on shared cores (the router executes them from one
        driver thread), so ~1x is the honest expectation — the curve is
        about hardware that gives each prefill replica its own chips;
        the row also pins the sharded streams token-identical to the
        single engine and the seq_parallel/partial-import counters."""
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.models.llama import llama_lm

    _phase("build_long_context")
    vocab = 128
    ps, chunk, monster_len = 8, 16, 500     # monster buckets to 512
    flood_new, monster_new = 40, 4
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    # heavy enough that the 32-chunk admission stall dwarfs one decode
    # tick (the head-of-line effect the interleave arm measures)
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2,
                         heads=4, kv_heads=2, vocab_size=vocab)
    ff.compile(final_tensor=logits)
    rs = np.random.RandomState(0)
    flood = rs.randint(1, vocab, (12,)).astype(np.int32)
    monster = rs.randint(1, vocab, (monster_len,)).astype(np.int32)

    def flood_round(eng):
        """One cold round: flood stream decoding, monster dropped on it
        mid-stream; returns (inter-token gaps, flood toks, monster
        toks)."""
        fr = eng.submit(flood, max_new_tokens=flood_new)
        while len(fr.tokens) < 4:
            eng.step()
        mr = eng.submit(monster, max_new_tokens=monster_new)
        gaps, last, prev = [], len(fr.tokens), time.perf_counter()
        while fr.state not in ("done", "failed") \
                or mr.state not in ("done", "failed"):
            eng.step()
            now = time.perf_counter()
            if len(fr.tokens) > last:
                gaps.append((now - prev) / (len(fr.tokens) - last))
                last, prev = len(fr.tokens), now
        assert fr.state == "done" and mr.state == "done"
        return gaps, list(fr.tokens), list(mr.tokens)

    arms = {}
    for budget in (0, 1):
        _phase(f"time_long_context_interleave_{budget}")
        eng = ff.make_serving_engine(
            serve_slots=2, kv_page_size=ps, max_seq_len=520,
            decode_buckets=[16, 512], prefill_chunk=chunk,
            prefill_interleave_chunks=budget, prefix_cache=False)
        flood_round(eng)                        # warm
        rc = eng.recompile_count
        gaps, ftoks, mtoks = [], None, None
        for _ in range(3):
            g, ftoks, mtoks = flood_round(eng)
            gaps.extend(g)
        gaps.sort()

        def _pct(q, g=gaps):
            return round(g[min(len(g) - 1, int(q * len(g)))] * 1e3, 3)

        arms[budget] = {
            "intertoken_p50_ms": _pct(0.50),
            "intertoken_p99_ms": _pct(0.99),
            "intertoken_max_ms": round(gaps[-1] * 1e3, 3),
            "recompiles": eng.recompile_count - rc,
            "chunks_interleaved":
                eng.stats()["prefill_chunks_interleaved"],
            "preempted_ticks": eng.stats()["prefill_preempted_ticks"],
            "streams": (ftoks, mtoks),
        }
    off, on = arms[0], arms[1]
    interleave_identity = off.pop("streams") == on.pop("streams")

    # ---- TTFT vs prompt length, single vs 2-shard fleet ----
    _phase("time_long_context_seq_parallel")
    lengths = [120, 248, 500]                   # 15 / 31 / 62 pages
    sp_kw = dict(serve_slots=2, kv_page_size=ps, max_seq_len=520,
                 decode_buckets=[16, 128, 256, 512])
    single = ff.make_serving_engine(**sp_kw)
    router = ff.make_serving_router(
        replicas=3, roles=["prefill", "prefill", "decode"],
        seq_parallel_shards=2, handoff_min_pages=2, **sp_kw)
    curve, identity_sharded = [], True
    try:
        # warm pass: fresh prompts per length drive every cold program
        # both paths reach (timed prompts are fresh too, so they replay
        # exactly these)
        for L in lengths:
            warm = rs.randint(1, vocab, (L,)).astype(np.int32)
            single.run([warm], max_new_tokens=2)
            router.run([warm], max_new_tokens=2, timeout=600)
        rc_single = single.recompile_count
        rc_fleet = [e.recompile_count for e in router.engines]
        for L in lengths:
            prompt = rs.randint(1, vocab, (L,)).astype(np.int32)
            t0 = time.perf_counter()
            sreq = single.run([prompt], max_new_tokens=2)[0]
            dt_single = time.perf_counter() - t0
            t0 = time.perf_counter()
            freq = router.run([prompt], max_new_tokens=2,
                              timeout=600)[0]
            dt_fleet = time.perf_counter() - t0
            identity_sharded &= (freq.state == "done"
                                 and list(freq.tokens)
                                 == list(sreq.tokens))
            curve.append({
                "prompt_tokens": L,
                "prompt_pages": L // ps,
                "single_ttft_ms": round(dt_single * 1e3, 1),
                "sharded_ttft_ms": round(dt_fleet * 1e3, 1),
            })
        fleet = router.stats()["fleet"]
        seq_parallel_prefills = fleet["seq_parallel_prefills"]
        partial_slab_imports = fleet["partial_slab_imports"]
        recompiles_sp = (single.recompile_count - rc_single) + sum(
            e.recompile_count - c
            for e, c in zip(router.engines, rc_fleet))
    finally:
        router.close()

    return {
        "metric": "long_context_serving", "tier": "long_context",
        # headline: how much interleaving flattens the decode stream's
        # worst-case stall while the maximal prompt admits
        "value": on["intertoken_p99_ms"], "unit": "intertoken_p99_ms",
        "vs_baseline": round(
            on["intertoken_p99_ms"]
            / max(1e-3, off["intertoken_p99_ms"]), 3),
        "intertoken_p99_ms_interleave_off": off["intertoken_p99_ms"],
        "intertoken_p99_lower": bool(
            on["intertoken_p99_ms"] < off["intertoken_p99_ms"]),
        "token_identity_interleave": bool(interleave_identity),
        "ttft_vs_length": curve,
        "token_identity_sharded_vs_single": bool(identity_sharded),
        "seq_parallel_prefills": seq_parallel_prefills,
        "partial_slab_imports": partial_slab_imports,
        "recompiles_after_warmup": off["recompiles"] + on["recompiles"]
        + recompiles_sp,
        "arms": {"interleave_off": off, "interleave_on": on},
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"monster_tokens": monster_len,
                   "prefill_chunk": chunk,
                   "monster_chunks": 512 // chunk,
                   "flood_max_new_tokens": flood_new,
                   "interleave_rounds_timed": 3,
                   "curve_lengths": lengths,
                   "seq_parallel_shards": 2,
                   "fleet_roles": ["prefill", "prefill", "decode"],
                   "serve_slots": 2, "kv_page_size": ps,
                   "max_seq_len": 520, "hidden": 128, "layers": 2,
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_overlap_tier(n_dev, backend, dev_kind):
    """input_overlap tier: the synchronous fit() loop vs the host-overlap
    step engine (runtime/pipeline_loader.py prefetch + dispatch-ahead)
    under a deliberately SLOW host loader — a sleep injected into
    next_batch models an input pipeline that cannot keep up (remote
    storage, heavy augmentation). The engine's claim is that loader time
    overlaps device compute, so samples/s approaches
    1/max(loader, step) instead of 1/(loader + step); the row reports the
    measured host_wait fraction for both loops."""
    import numpy as np

    from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType,
                              MetricsType, SGDOptimizer, SingleDataLoader)

    _phase("build_input_overlap")

    class SlowLoader(SingleDataLoader):
        delay_s = 0.0

        def next_batch(self):
            time.sleep(SlowLoader.delay_s)
            return super().next_batch()

    batch = 32 * n_dev
    n_batches, timed_epochs = 8, 2
    delay_s, depth, ahead = 0.040, 3, 4
    # host-resident data is the scenario (device-resident datasets have
    # no host loader to overlap); native off so the sleep actually lands
    # on the pull path the pipeline wraps
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": n_dev},
                   device_resident_data=False, native_dataloader=False,
                   prefetch_depth=0, dispatch_ahead=ahead)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 256], name="x")
    t = ff.dense(x, 2048, ActiMode.AC_MODE_RELU)
    t = ff.dense(t, 2048, ActiMode.AC_MODE_RELU)
    ff.dense(t, 16, name="out")
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY])
    rs = np.random.RandomState(0)
    n = batch * n_batches
    SlowLoader(ff, x, rs.randn(n, 256).astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 16, (n, 1)).astype(np.int32))

    _phase("warm_input_overlap")
    ff.fit(epochs=1, verbose=False)  # compile + warm, fast loader
    SlowLoader.delay_s = delay_s

    def timed_fit():
        # best-of-3 like every other tier: this host's load is bursty and
        # the 2-thread handoff suffers disproportionately under contention
        best_dt, bd = None, {}
        for _ in range(3):
            t0 = time.perf_counter()
            ff.fit(epochs=timed_epochs, verbose=False)
            dt = time.perf_counter() - t0
            if best_dt is None or dt < best_dt:
                best_dt, bd = dt, (ff.last_step_breakdown or {})
        return batch * n_batches * timed_epochs / best_dt, bd

    _phase("time_input_overlap_sync")
    ff.config.prefetch_depth = 0
    sync_sps, bd_sync = timed_fit()
    _phase("time_input_overlap_overlap")
    ff.config.prefetch_depth = depth
    overlap_sps, bd_overlap = timed_fit()

    hw_sync = round(bd_sync.get("host_wait_fraction", 0.0), 4)
    hw_overlap = round(bd_overlap.get("host_wait_fraction", 0.0), 4)
    return {
        "metric": "input_overlap_throughput", "tier": "input_overlap",
        "value": round(overlap_sps, 2), "unit": "samples/s",
        "vs_baseline": round(overlap_sps / sync_sps, 3),
        "speedup_vs_sync": round(overlap_sps / sync_sps, 3),
        "sync_samples_per_s": round(sync_sps, 2),
        "host_wait_fraction": hw_overlap,
        "host_wait_fraction_sync": hw_sync,
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"batch": batch, "features": 256, "hidden": 2048,
                   "num_batches": n_batches, "epochs": timed_epochs,
                   "loader_delay_ms": round(delay_s * 1e3, 2),
                   "prefetch_depth": depth, "dispatch_ahead": ahead,
                   "host_wait_fraction": hw_overlap},
    }


def _run_collective_overlap_tier(n_dev, backend, dev_kind):
    """collective_overlap tier (ISSUE 10): (a) step time + epilogue
    fraction with overlap_grad_sync (bucketed in-scan grad reduce-scatter
    + ZeRO-1 sharded update) ON vs OFF, and (b) per-step checkpoint stall
    at checkpoint_every=1 with async vs sync publishing. On this CPU box
    the collective numbers are smoke-grade (virtual devices share cores —
    the overlap win needs real ICI); the checkpoint stall is a genuine
    host-side measurement either way (the async save moves orbax
    serialization + manifest hashing + fsync off the step path)."""
    import shutil
    import tempfile

    import numpy as np

    from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType,
                              MetricsType, SGDOptimizer)
    from flexflow_tpu.runtime.checkpoint import (save_checkpoint,
                                                 wait_pending_saves)

    _phase("build_collective_overlap")
    batch, accum, steps = 16 * n_dev, 2, 6

    def build(overlap):
        cfg = FFConfig(batch_size=batch, mesh_shape={"data": n_dev},
                       grad_accum_steps=accum, overlap_grad_sync=overlap)
        ff = FFModel(cfg)
        x = ff.create_tensor([batch, 256], name="x")
        t = ff.dense(x, 1024, ActiMode.AC_MODE_RELU)
        t = ff.dense(t, 1024, ActiMode.AC_MODE_RELU)
        ff.dense(t, 16, name="out")
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        return ff

    rs = np.random.RandomState(0)
    bt = {"x": rs.randn(batch, 256).astype(np.float32),
          "label": rs.randint(0, 16, (batch, 1)).astype(np.int32)}

    def time_steps(ff):
        ff._run_train_step(bt)  # compile + warm
        import jax

        jax.block_until_ready(ff._last_loss)
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                ff._run_train_step(bt)
            jax.block_until_ready(ff._last_loss)
            dt = (time.perf_counter() - t0) / steps
            best = dt if best is None or dt < best else best
        return best

    _phase("time_collective_overlap_off")
    ff_off = build(False)
    t_off = time_steps(ff_off)
    bd_off = ff_off.step_breakdown(batch=bt, iters=2)
    _phase("time_collective_overlap_on")
    ff_on = build(True)
    t_on = time_steps(ff_on)
    bd_on = ff_on.step_breakdown(batch=bt, iters=2)

    # checkpoint stall: per-step saves at checkpoint_every=1 cadence
    _phase("time_ckpt_stall")

    def ckpt_wall(async_save):
        d = tempfile.mkdtemp(prefix="ff_bench_ckpt_")
        try:
            t0 = time.perf_counter()
            for i in range(steps):
                ff_on._run_train_step(bt)
                save_checkpoint(ff_on, d, step=i, keep=2,
                                async_save=async_save)
            import jax

            jax.block_until_ready(ff_on._last_loss)
            stepped = time.perf_counter() - t0  # saves still pending OK:
            # the stall the TRAINING LOOP sees is the quantity measured
            wait_pending_saves(d)
            return stepped
        finally:
            shutil.rmtree(d, ignore_errors=True)

    wall_sync = ckpt_wall(False)
    wall_async = ckpt_wall(True)
    stall_sync_ms = max(wall_sync / steps - t_on, 0.0) * 1e3
    stall_async_ms = max(wall_async / steps - t_on, 0.0) * 1e3
    return {
        "metric": "collective_overlap_step", "tier": "collective_overlap",
        "value": round(t_on * 1e3, 3), "unit": "ms/step",
        "vs_baseline": round(t_off / max(t_on, 1e-12), 3),
        "step_ms_sync_epilogue": round(t_off * 1e3, 3),
        "epilogue_fraction_on": bd_on.get("epilogue_fraction"),
        "epilogue_fraction_off": bd_off.get("epilogue_fraction"),
        "collective_instructions_on": bd_on.get("collective_instructions"),
        "collective_instructions_off": bd_off.get(
            "collective_instructions"),
        "ckpt_stall_ms_sync": round(stall_sync_ms, 3),
        "ckpt_stall_ms_async": round(stall_async_ms, 3),
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"batch": batch, "hidden": 1024,
                   "grad_accum_steps": accum, "steps": steps,
                   "overlap_grad_sync": True, "async_checkpointing": True,
                   "checkpoint_every": 1,
                   "dispatch_ahead": 0, "host_wait_fraction": 0.0},
    }


def _run_search_warmstart_tier(n_dev, backend, dev_kind):
    """search_warmstart tier (ISSUE 19): cold vs warm strategy search
    against a REAL persistent cost DB. The cold leg analyzes every op
    signature and persists one DB entry each; the warm leg drops every
    in-process cache (simulating a fresh session) and re-runs the same
    search, which must re-measure zero keyed ops — the stamped speedup
    is the whole point of the DB. Then the csim calibration loop: the
    multi-objective search's predicted step time vs the observed wall
    time of real jitted steps (smoke-grade on CPU — the csim prices TPU
    collectives, so the ratio only means something on real hardware;
    the stamp proves the gauge + DB plumbing end to end)."""
    import shutil
    import tempfile

    import numpy as np

    from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType,
                              MetricsType, SGDOptimizer)
    from flexflow_tpu.runtime import telemetry
    from flexflow_tpu.search import cost_db, measure, table_store
    from flexflow_tpu.search.driver import (optimize_strategies,
                                            optimize_strategies_multi)

    _phase("build_search_warmstart")
    tmp = tempfile.mkdtemp(prefix="ff_bench_costdb_")
    db = os.path.join(tmp, "cost_db.json")
    mesh = ({"data": n_dev // 2, "model": 2} if n_dev >= 4
            else {"data": n_dev})
    batch, budget, steps = 16 * n_dev, 120, 6

    cfg = FFConfig(batch_size=batch, mesh_shape=mesh, cost_db_path=db)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 256], name="x")
    t = ff.dense(x, 512, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 512, ActiMode.AC_MODE_RELU, name="fc2")
    ff.dense(t, 16, name="out")

    try:
        # cold: empty DB — every signature is analyzed and persisted
        measure._SIGNATURE_CACHE.clear()
        table_store.clear_cache()
        cost_db.reset_stats()
        _phase("search_cold")
        t0 = time.perf_counter()
        measured = measure.analyze_op_costs(ff, mesh, db_path=db)
        optimize_strategies(ff, budget=budget, mesh_shape=mesh, seed=0,
                            measured=measured, use_native=False)
        t_cold = time.perf_counter() - t0
        db_entries = cost_db.entry_count(db)

        # warm: drop every in-process cache (fresh-session sim), rerun —
        # zero re-measures, all signatures served from the DB file
        measure._SIGNATURE_CACHE.clear()
        table_store.clear_cache()
        cost_db.reset_stats()
        _phase("search_warm")
        t0 = time.perf_counter()
        measured = measure.analyze_op_costs(ff, mesh, db_path=db)
        optimize_strategies_multi(ff, budget=budget, mesh_shape=mesh,
                                  seed=0, measured=measured,
                                  use_native=False)
        t_warm = time.perf_counter() - t0
        s = cost_db.stats()
        hit_rate = s["hits"] / max(s["hits"] + s["misses"], 1)

        # calibration: real jitted steps observed into the step-time
        # histogram, then predicted-vs-observed exported as gauges + a
        # calib DB entry (ratio = predicted / observed p50)
        _phase("search_calibration")
        ff.compile(SGDOptimizer(lr=0.01),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        rs = np.random.RandomState(0)
        bt = {"x": rs.randn(batch, 256).astype(np.float32),
              "label": rs.randint(0, 16, (batch, 1)).astype(np.int32)}
        import jax

        ff._run_train_step(bt)  # compile + warm
        jax.block_until_ready(ff._last_loss)
        telemetry.reset()
        hist = telemetry.registry().histogram(
            "ff_train_step_seconds", "fit() per-step wall time")
        for _ in range(steps):
            t0 = time.perf_counter()
            ff._run_train_step(bt)
            jax.block_until_ready(ff._last_loss)
            hist.observe(time.perf_counter() - t0)
        rec = cost_db.export_calibration(ff, path=db)
        ratio = rec["ratio"] if rec else None
        telemetry.reset()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    return {
        "metric": "search_warm_wall", "tier": "search_warmstart",
        "value": round(t_warm * 1e3, 3), "unit": "ms",
        # cold/warm: >1 means the warm search was strictly faster
        "vs_baseline": round(t_cold / max(t_warm, 1e-9), 3),
        "cold_wall_ms": round(t_cold * 1e3, 3),
        "warm_strictly_faster": bool(t_warm < t_cold),
        "db_entries": db_entries,
        "warm_remeasures": s["misses"],
        "backend": backend, "device_kind": dev_kind, "n_devices": n_dev,
        "config": {"mesh": mesh, "batch": batch, "budget": budget,
                   "steps": steps, "db_hit_rate": round(hit_rate, 4),
                   "csim_error_ratio": (round(ratio, 6)
                                        if ratio is not None else None)},
    }


# (name, runner) — after the training tiers so a serving failure can never
# cost a training number. Each runner takes (n_dev, backend, dev_kind) and
# returns one row or an iterable of rows.
EXTRA_TIERS = (
    # decode_throughput + serve_latency (continuous batching vs sequential)
    ("decode_throughput", _run_serving_tier),
    # radix prefix cache + speculative accept rate under skewed
    # shared-prefix traffic, vs the cache-off engine
    ("prefix_serving", _run_prefix_serving_tier),
    # fleet throughput at 2 replicas vs 1 + the kill-under-overload p99
    # drill with shedding on vs off
    ("router_serving", _run_router_serving_tier),
    # Pallas paged-decode kernel vs the einsum page-gather oracle + the
    # flash block autotune record
    ("paged_attention", _run_paged_attention_tier),
    # ISSUE 11: int8 KV pool + int8 weights vs bf16 at equal pool bytes
    ("quantized_serving", _run_quantized_serving_tier),
    # ISSUE 12: host-tier prefix cache under a working set ~3x the pool +
    # the disaggregated-fleet identity stamps
    ("tiered_prefix", _run_tiered_prefix_tier),
    # ISSUE 14: 8 mixed-sampling LoRA tenants on one engine vs
    # single-tenant greedy
    ("multi_tenant", _run_multi_tenant_tier),
    # ISSUE 17: p99 TTFT + tokens/s through a live weight roll vs steady
    # state, plus the canary-breach rollback drill
    ("rolling_deploy", _run_rolling_deploy_tier),
    # ISSUE 20: scale-out recovery, scale-in step-down, preempt drill
    ("elastic_fleet", _run_elastic_fleet_tier),
    # ISSUE 18: decode inter-token p99 while a maximal prompt admits +
    # the TTFT-vs-length curve
    ("long_context", _run_long_context_tier),
    # host-overlap step engine vs the synchronous loop under a slow loader
    ("input_overlap", _run_overlap_tier),
    # in-graph grad-sync overlap + ZeRO-1 update vs the serial epilogue,
    # and the checkpoint-stall pair
    ("collective_overlap", _run_collective_overlap_tier),
    # ISSUE 19: cold vs warm strategy search against the persistent cost
    # DB + the csim calibration stamp
    ("search_warmstart", _run_search_warmstart_tier),
)


def main():
    sys.path.insert(0, REPO)
    import jax

    from flexflow_tpu._env import resolve_compilation_cache

    _phase("backend_init")
    devs = jax.devices()
    backend = devs[0].platform
    n_dev = len(devs)
    dev_kind = getattr(devs[0], "device_kind", "?")
    if backend != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and jax found platform {backend!r}: "
            f"a number from another backend is not a device metric")
    peak, peak_src = _peak_flops_per_chip(devs[0])
    print(f"[bench] platform={backend} devices={n_dev} kind={dev_kind} "
          f"compile_cache={resolve_compilation_cache()}", file=sys.stderr,
          flush=True)

    skip = {t for t in os.environ.get("FF_BENCH_SKIP_TIERS", "").split(",")
            if t}
    for tier in TPU_TIERS:
        if tier[0] in skip:
            continue
        print(json.dumps(_run_tier(tier, n_dev, "bfloat16", peak, peak_src,
                                   backend, dev_kind)), flush=True)
    for name, run in EXTRA_TIERS:
        if name in skip:
            continue
        rows = run(n_dev, backend, dev_kind)
        for row in ([rows] if isinstance(rows, dict) else rows):
            print(json.dumps(row), flush=True)
    _phase("done")
    return 0


if __name__ == "__main__":
    sys.exit(main())
