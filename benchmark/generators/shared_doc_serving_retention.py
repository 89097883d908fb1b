"""`shared_doc_serving_state` for a configuration whose cached ops are ALL
recurrent states and whose state is a power-retention layer's (kind
"shared_doc_serving_retention" in the traffic file): resident documents whose
ONE snapshot a layer stays published in the engine's prefix cache (a page
holds nothing there: it is only the key under which the trie files the
snapshot), and fresh short questions that resume from them.

Everything is `shared_doc_serving_state`'s (and through it
`shared_doc_serving`'s), imported and used as it is, and re-exported
(`knee_sweep.py` loads a generator by the traffic file's `kind`): the
schedule, the seating of the documents, the warm-up, the loop, the occupancy
lines, the statistics, the snapshot counters. `run` is written out again for
ONE difference (that file's `run` names its check module and may not be
edited here): `correct` comes from `reference/serve_check_retention.py`, whose
check (c) reads this op's state ({"s", "z"}) on the first and the last layer,
where `serve_check_snapshot.py` reads a Mamba layer's {"h", "conv"} on the
first.
"""

import gc

from benchmark.generators.shared_doc_serving_state import (  # noqa: F401
    COUNTERS, EXTRA, FULL_FROM_S, SNAPSHOTS, _Occupancy, attainment,
    build_engine, drive, generate, latency_metrics, pool_arithmetic,
    seat_documents, warm)


def run(h):
    from benchmark.reference import serve_check_retention

    traffic = h.traffic
    seconds = h.seconds
    sched = generate(traffic, h.args.seed, seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = build_engine(h)
    st = eng.stats()
    h.log(f"pool: {pool_arithmetic(traffic, eng.page_size, eng.slots, h.scale)}"
          f" of {eng.num_pages} pages; state pool "
          f"{st['state_pool_bytes'] / 1e9:.2f} GB "
          f"({st['state_bytes_per_slot'] / 1e6:.1f} MB a slot), snapshots "
          f"{st['state_snapshot_pool_bytes'] / 1e9:.2f} GB "
          f"({eng.state_snapshots} + the scratch row)")
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
    delta = {k: stats1[k] - stats0[k] for k in COUNTERS + EXTRA + SNAPSHOTS
             if k in stats1}
    h.log(f"engine stats delta: {delta}")
    h.log(f"window: {e2e}")
    limits = traffic.get("limits")
    if limits:
        h.log(f"share meeting TTFT <= {limits['ttft_s']} s and TPOT <= "
              f"{limits['tpot_s']} s (logged, not judged: above the knee "
              f"TTFT grows by design): "
              f"{attainment(records, limits['ttft_s'], limits['tpot_s']):.3f}")
    h.log(f"prefix hits: {delta['prefix_hit_tokens']} of "
          f"{delta['prefix_prompt_tokens']} prompt tokens; snapshots: "
          f"{delta['state_snapshot_hits']} of {delta['prefix_lookups']} "
          f"admissions resumed from one, {stats1['state_snapshots_held']} "
          f"held, {delta['state_snapshots_taken']} taken and "
          f"{delta['state_snapshots_evicted']} evicted in the window")

    # check (c)'s probe needs the warm engine; the reference's float32 pass
    # over 32 k tokens needs the room of its pools
    probed = serve_check_retention.probe(h, eng, sched.docs[0])
    slots = eng.slots
    eng.kv.pool = eng.kv.snapshots = None
    del eng
    gc.collect()
    checks = serve_check_retention.run(h, ff, records, sched, probed)
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
