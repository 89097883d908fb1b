"""`shared_doc_serving` for a configuration whose model has WINDOW layers
(kind "shared_doc_serving_window" in the traffic file): resident documents
whose global pages AND, of each window layer, the page of the window before
the document's end stay published in the engine's prefix cache, and fresh
short questions that resume from them.

The pool arithmetic, the seating of the documents
(`ServingEngine.prefill_into_cache`, which for such a model publishes a
document's global pages and ONE snapshot on the last of them: a page a window
layer), the loop, the occupancy lines and the statistics are
`shared_doc_serving`'s, imported and used as they are (and re-exported:
`knee_sweep.py` loads a generator by the traffic file's `kind`).

The schedule is `shared_doc_serving`'s arrangement with THE DOCUMENTS' TOKENS
IN IT: `generate` here draws them from `arrangement_seed` too, so the resident
set is the same in every run, as the weights are, and `--seed` draws the
questions' tokens. Why (PERF.md section 6, PR 46): greedy decoding under
seeded weights settles on a few tokens a request (a median request of 256
tokens emits 9-10 distinct ones), so a request routes to nearly the same
experts all its life, and which experts goes with its document, not with its
question. The 16 documents of a seed decided how many of the 16 held experts
a step streams: 160-181 k hits a window over twelve seeds, `tpot_p50_s` after
them at correlation 0.91, and the driver refused the cell for that spread.
With the documents fixed, twelve seeds read 167.6-171.4 k hits. `warm` is
written out again for that alone (`shared_doc_serving.warm` seats the
documents its own `generate` draws).

`run` differs from `shared_doc_serving_state`'s in its checks alone, which is
why it is written out again (that file's `run` reads a recurrent state out of
the slot, which this model has none of, and may not be edited here):

  * `correct` comes from `reference/serve_check_ring.py`, whose check (c)
    needs the engine after the window: one probe question on a resident
    document through the warm hit program, then cold, the rings' rows read
    back both times. The engine's pools are dropped after the probe and
    before the reference's 33 k-token passes;
  * `ctx["stats_delta"]` carries the snapshot counters
    (`state_snapshot_hits`, `state_snapshots_taken`,
    `state_snapshots_evicted`), so `snapshot_hit_share` reads them as it does
    for a recurrent state.
"""

import dataclasses
import gc
import time

import numpy as np

from benchmark.generators import shared_doc_serving as base
from benchmark.generators.shared_doc_serving import (  # noqa: F401
    COUNTERS, EXTRA, FULL_FROM_S, _Occupancy, attainment, build_engine,
    drive, latency_metrics, pool_arithmetic, seat_documents)
from benchmark.generators.shared_doc_serving_state import SNAPSHOTS


def generate(traffic, seed, seconds, vocab, scale=1):
    """`shared_doc_serving.generate`'s schedule, its documents replaced by
    the ones `arrangement_seed` draws: `--seed` keeps the questions."""
    sched = base.generate(traffic, seed, seconds, vocab, scale)
    rng = np.random.default_rng([int(traffic["arrangement_seed"]), 0x70CE])
    docs = [rng.integers(1, vocab, size=d.size, dtype=np.int32)
            for d in sched.docs]
    prompts = [np.concatenate([docs[d], p[docs[d].size:]])
               for d, p in zip(sched.doc_of, sched.prompts)]
    return dataclasses.replace(sched, docs=docs, prompts=prompts)


def warm(h, eng, traffic):
    """`shared_doc_serving.warm` over this module's documents: the resident
    set and, per document length, one request of the longest question (the
    hit program and the decode program). Nothing is flushed."""
    t0 = time.perf_counter()
    before = eng.recompile_count
    docs = generate(traffic, h.args.seed, 1.0, h.vocab, h.scale).docs
    seat_documents(h.log, eng, docs)
    rng = np.random.default_rng([int(h.args.seed), 0xC01D])
    qmax = max(1, traffic["question_tokens"]["max"] // h.scale)
    firsts = {}
    for d in docs:
        firsts.setdefault(d.size, d)
    eng.run([np.concatenate([d, rng.integers(1, h.vocab, size=qmax,
                                             dtype=np.int32)])
             for d in firsts.values()],
            max_new_tokens=max(2, eng.decode_chunk + 1))
    st = eng.stats()
    h.log(f"warm-up: {len(docs)} documents resident "
          f"({st['kv_pages_cached']} pages cached, {st['free_pages']} free), "
          f"{eng.recompile_count - before} programs in "
          f"{time.perf_counter() - t0:.1f} s")


def run(h):
    from benchmark.reference import serve_check_ring

    traffic = h.traffic
    seconds = h.seconds
    sched = generate(traffic, h.args.seed, seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = build_engine(h)
    st = eng.stats()
    h.log(f"pool: {pool_arithmetic(traffic, eng.page_size, eng.slots, h.scale)}"
          f" of {eng.num_pages} pages; window rings "
          f"{st['kv_window_pool_bytes'] / 1e9:.2f} GB, snapshots "
          f"{st['state_snapshot_pool_bytes'] / 1e9:.3f} GB "
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
    # over 33 k tokens needs the room of its pools
    probed = serve_check_ring.probe(h, eng, sched.docs[0])
    slots = eng.slots
    eng.kv.pool = eng.kv.snapshots = None
    del eng
    gc.collect()
    checks = serve_check_ring.run(h, ff, records, sched, probed)
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
