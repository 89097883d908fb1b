"""Open-loop serving traffic for a configuration whose attention layers keep
different things in the page pool: window layers a ring of pages a slot,
global layers everything (kind "open_loop_serving_window" in the traffic
file).

Everything is `open_loop_serving_ref`'s, imported and used as it is (the
arrangement replayed from the traffic file's `arrangement_seed`, the engine,
the warm-up, the loop, the statistics, the routing counters), and re-exported
for `knee_sweep.py`. `run` differs in where `correct` comes from:
`reference/serve_check_window.py`, which holds a request's MEAN margin to a
limit (this router's eight gates of about 0.31 flip on bf16 rounding, as
`deepseek-v3.2-serve`'s do) and rescores, beside the three shortest completed
requests, the shortest one whose prompt is at least LONG_PROMPT tokens: the
long contexts are this cell's point. `ctx["stats_delta"]` also carries the
engine's page counters of a model with window layers (WINDOW_COUNTERS; a
program without them reports none, and their reader then returns nothing).
`open_loop_serving_ref.run` may not be edited by the PR that brought this
file, so the function is written out again here; the next `benchmark` PR
folds the four generators into one whose check is named by the traffic file
(PERF.md section 7).
"""

from benchmark.generators.open_loop_serving_ref import (  # noqa: F401
    COUNTERS, ROUTING, attainment, build_engine, drive, generate,
    latency_metrics, warm)

WINDOW_COUNTERS = ("kv_page_steps_global", "kv_page_steps_window",
                   "kv_window_pages_recycled")


def run(h):
    from benchmark.reference import serve_check_window

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
    delta = {k: stats1[k] - stats0[k]
             for k in COUNTERS + ROUTING + WINDOW_COUNTERS if k in stats1}
    h.log(f"engine stats delta: {delta}")
    h.log(f"window: {e2e}")
    limits = traffic.get("limits")
    if limits:
        h.log(f"share meeting TTFT <= {limits['ttft_s']} s and TPOT <= "
              f"{limits['tpot_s']} s: "
              f"{attainment(records, limits['ttft_s'], limits['tpot_s']):.3f}")

    checks = serve_check_window.run(h, ff, records)
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
