"""`shared_doc_serving` with THE DOCUMENTS' TOKENS IN THE ARRANGEMENT (kind
"shared_doc_serving_arranged" in the traffic file): the resident set is drawn
from `arrangement_seed` and is the same in every run, as the weights are, and
`--seed` draws the questions' tokens only.

Why (PERF.md section 6, PR 46): a cell that holds a SHARE of the experts and
keeps documents resident routes with its documents. Greedy decoding under
seeded weights settles on a few tokens a request, so a request routes to
nearly the same experts all its life, and which experts goes with its
document, not with its question: the documents a seed drew set how many held
experts a step streams, and `tpot_p50_s` went after them. `generate` and
`warm` are `shared_doc_serving_window`'s (that kind's schedule and warm-up,
which hold nothing of a window), imported.

Everything else is `shared_doc_serving`'s, imported, used as it is and
re-exported (`knee_sweep.py` and the controls load a generator by the traffic
file's `kind`): the pool arithmetic, the seating of the documents, the loop,
the occupancy lines, the statistics, BOTH checks. `run` is that file's `run`
written out again for two differences (it names its own `generate` and `warm`,
and may not be edited here): this module's `generate` and `warm`, and the
counters of a model with zero-computation experts added to the list it hands
the readers (`moe_zero_picks`, `moe_real_picks`, `moe_held_picks`; a program
without them reports none).
"""

import gc

from benchmark.generators.shared_doc_serving import (  # noqa: F401
    COUNTERS, FULL_FROM_S, _Occupancy, attainment, build_engine, check,
    check_emitted, check_predict, drive, latency_metrics, pool_arithmetic,
    seat_documents)
from benchmark.generators.shared_doc_serving import EXTRA as _EXTRA
from benchmark.generators.shared_doc_serving_window import (  # noqa: F401
    generate, warm)

EXTRA = _EXTRA + ("moe_zero_picks", "moe_real_picks", "moe_held_picks")


def run(h):
    traffic = h.traffic
    seconds = h.seconds
    sched = generate(traffic, h.args.seed, seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = build_engine(h)
    h.log(f"pool: {pool_arithmetic(traffic, eng.page_size, eng.slots, h.scale)}"
          f" of {eng.num_pages} pages")
    warm(h, eng, traffic)

    stats0 = eng.stats()
    occ = _Occupancy(eng, seconds, h.trace_poll)
    h.setup_done()
    records, lateness, t_end = drive(
        eng, sched, seconds, float(traffic["drain_grace_s"]), h.annotate,
        occ)
    h.window_done()
    stats1 = eng.stats()
    h.log(f"generator lateness: median {lateness['median_s'] * 1e3:.3f} ms, "
          f"max {lateness['max_s'] * 1e3:.3f} ms; loop ended at "
          f"{t_end:.2f} s of a {seconds} s window (grace "
          f"{traffic['drain_grace_s']} s)")
    h.log(occ.line())
    for k, r in enumerate(records):
        r["index"] = k

    e2e = latency_metrics(records, seconds)
    delta = {k: stats1[k] - stats0[k] for k in COUNTERS + EXTRA
             if k in stats1}
    h.log(f"engine stats delta: {delta}")
    h.log(f"window: {e2e}")
    limits = traffic.get("limits")
    if limits:
        h.log(f"share meeting TTFT <= {limits['ttft_s']} s and TPOT <= "
              f"{limits['tpot_s']} s (logged, not judged: above the knee "
              f"TTFT grows by design): "
              f"{attainment(records, limits['ttft_s'], limits['tpot_s']):.3f}")
    hit, asked = (delta.get("prefix_hit_tokens"),
                  delta.get("prefix_prompt_tokens"))
    if asked:
        h.log(f"prefix hits: {hit} of {asked} prompt tokens "
              f"({100.0 * hit / asked:.2f} %)")
        if hit < 0.99 * asked:
            cold = sorted({int(sched.docs[sched.doc_of[k]].size)
                           for k, r in enumerate(records)
                           if r.get("request") is not None
                           and r["request"].prefix_tokens
                           < sched.docs[sched.doc_of[k]].size})
            h.log(f"a document was evicted: requests of documents of "
                  f"{cold} tokens prefilled cold")

    # the reference's float32 pass over 33 k tokens needs the pool's room
    slots = eng.slots
    eng.kv.pool = eng.kv.draft_pool = None
    del eng
    gc.collect()
    checks = check(h, ff, records, sched)
    compiles = max(delta["recompiles"], h.compiles_in_window())
    correct = (checks["ok"] and compiles == 0 and e2e["failed"] == 0
               and delta["failed"] == 0)
    return {
        "correct": bool(correct), "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        "end_to_end": {name: e2e[name] for name in traffic["end_to_end"]
                       if name in e2e},
        "ctx": {"mode": "serve", "stats_delta": delta, "slots": slots,
                "records": records, "window": e2e,
                "compiles_in_window": compiles, "lateness": lateness},
    }
