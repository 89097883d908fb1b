"""`shared_doc_serving` for a configuration whose model keeps a recurrent
state beside its pages (kind "shared_doc_serving_state" in the traffic file):
resident documents whose pages AND whose snapshot of the recurrent state stay
published in the engine's prefix cache, and fresh short questions that resume
from them.

The schedule (one arrangement replayed), the pool arithmetic, the seating of
the documents (`ServingEngine.prefill_into_cache`, which for such a model
publishes a document's pages and ONE snapshot on the last of them), the
warm-up, the loop, the occupancy lines and the statistics are
`shared_doc_serving`'s, imported and used as they are (and re-exported:
`knee_sweep.py` loads a generator by the traffic file's `kind`). `run` differs
in two things, which is why it is written out again (that file's `run` drops
the engine before its checks and may not be edited here):

  * `correct` comes from `reference/serve_check_snapshot.py`, whose check (c)
    needs the engine after the window: one probe question on a resident
    document through the warm hit program, the slot's state read back. The
    engine's pools are dropped after the probe and before the reference's
    17 k-token passes;
  * `ctx["stats_delta"]` also carries the snapshot counters
    (`state_snapshot_hits`, `state_snapshots_taken`,
    `state_snapshots_evicted`).
"""

import gc

from benchmark.generators.shared_doc_serving import (  # noqa: F401
    COUNTERS, EXTRA, FULL_FROM_S, _Occupancy, attainment, build_engine,
    drive, generate, latency_metrics, pool_arithmetic, seat_documents, warm)

SNAPSHOTS = ("state_snapshot_hits", "state_snapshots_taken",
             "state_snapshots_evicted")


def run(h):
    from benchmark.reference import serve_check_snapshot

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
    # over 17 k tokens needs the room of its pools
    probed = serve_check_snapshot.probe(h, eng, sched.docs[0])
    slots = eng.slots
    eng.kv.pool = eng.kv.snapshots = None
    del eng
    gc.collect()
    checks = serve_check_snapshot.run(h, ff, records, sched, probed)
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
