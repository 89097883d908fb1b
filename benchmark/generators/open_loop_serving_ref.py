"""Open-loop serving traffic for a configuration that names its own reference
(`"reference": "<module>"` in the configuration file, kind
"open_loop_serving_ref" in the traffic file).

The schedule, the engine, the warm-up, the loop and the statistics are
`open_loop_serving`'s, imported and used as they are (and re-exported:
`knee_sweep.py` loads a generator by the traffic file's `kind` and calls
`generate`, `build_engine`, `warm`, `drive`, `latency_metrics` and
`attainment` on it). `run` differs from that file's in two things:

  * `correct` comes from `reference/serve_check_ref.py`, which runs the same
    two checks with the forward function of the module the configuration
    names (benchmark/reference/<module>.py, `forward(params, tokens, sizes)`);
  * `ctx["stats_delta"]` also carries the engine's routing counters of an
    expert model (`moe_assignments`, `moe_experts_hit`; a
    program without them reports none, and their readers then return nothing).

`generate` differs in one: the ARRANGEMENT of the window (which request is
due when, with which prompt and output length) is drawn from the traffic
file's `arrangement_seed` and is the same in every run, and `--seed` draws the
prompts' tokens only. That is a trace replayed: `open_loop_serving.generate`
already fixes the multiset of lengths and the count, so a seed only ever chose
their order, and where a decode step's time follows the live rows (an expert
model streams the experts they hit) the order alone moves `tpot_p50_s` by
more than its bound (PERF.md section 2).

The next architecture brings a reference file and no generator. The next
`benchmark` PR folds this file and `serve_check_ref.py` into their twins
(PERF.md section 7).
"""

import numpy as np

from benchmark.generators import open_loop_serving as base
from benchmark.generators.open_loop_serving import (  # noqa: F401
    attainment, build_engine, drive, latency_metrics, warm)

COUNTERS = ("requests", "completed", "failed", "timeouts", "tokens_generated",
            "decode_steps", "occupied_slot_steps", "recompiles",
            "prefix_hits", "prefix_lookups")
ROUTING = ("moe_assignments", "moe_experts_hit")


def generate(traffic, seed, seconds, vocab, scale=1):
    """`open_loop_serving.generate`'s schedule for the traffic file's
    `arrangement_seed`, with the prompts' tokens drawn from `seed` (same
    lengths, same order, same due times in every run)."""
    sched = base.generate(traffic, traffic["arrangement_seed"], seconds,
                          vocab, scale)
    rng = np.random.default_rng([int(seed), 0x70CE])
    sched.prompts = [rng.integers(1, vocab, size=p.size, dtype=np.int32)
                     for p in sched.prompts]
    return sched


def run(h):
    from benchmark.reference import serve_check_ref

    traffic = h.traffic
    seconds = h.seconds
    sched = generate(traffic, h.args.seed, seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = build_engine(h)
    warm(h, eng, traffic)

    stats0 = eng.stats()
    h.setup_done()
    records, lateness, t_end = drive(
        eng, sched, seconds, float(traffic["drain_grace_s"]), h.annotate,
        h.trace_poll)
    h.window_done()
    stats1 = eng.stats()
    h.log(f"generator lateness: median {lateness['median_s'] * 1e3:.3f} ms, "
          f"max {lateness['max_s'] * 1e3:.3f} ms; loop ended at "
          f"{t_end:.2f} s of a {seconds} s window")

    e2e = latency_metrics(records, seconds)
    delta = {k: stats1[k] - stats0[k] for k in COUNTERS + ROUTING
             if k in stats1}
    h.log(f"engine stats delta: {delta}")
    h.log(f"window: {e2e}")
    limits = traffic.get("limits")
    if limits:
        h.log(f"share meeting TTFT <= {limits['ttft_s']} s and TPOT <= "
              f"{limits['tpot_s']} s: "
              f"{attainment(records, limits['ttft_s'], limits['tpot_s']):.3f}")

    checks = serve_check_ref.run(h, ff, records)
    compiles = max(delta["recompiles"], h.compiles_in_window())
    correct = (checks["ok"] and compiles == 0 and e2e["failed"] == 0
               and delta["failed"] == 0)
    return {
        "correct": bool(correct), "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        "end_to_end": {name: e2e[name] for name in traffic["end_to_end"]
                       if name in e2e},
        "ctx": {"mode": "serve", "stats_delta": delta, "slots": eng.slots,
                "records": records, "window": e2e,
                "compiles_in_window": compiles, "lateness": lateness},
    }
