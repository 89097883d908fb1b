"""`correct` of a serving cell over RESIDENT DOCUMENTS whose model has WINDOW
layers, so that every admission resumes each of them from ONE page on a trie
node (runtime/kv_pool.py: a window layer is a citizen of the snapshot
protocol). Three checks; (a) and (b) against the configuration's own plain
reference (float32, the window as a dense mask, no cache), compared on
logits, never on tokens:

(a) `ff.predict` logits on `graph_seq_len` seeded tokens (32 windows at 4096),
    relative RMS error (`predict_rel_rms`): the dense path (the flash forward
    with the window's lower edge and the sink, bf16 matmuls, the grouped
    experts) at the published widths.
(b) the LONGEST completed request of EACH document length (one of them on the
    longest document; 500-1024 emitted tokens each, the ring wrapped two to
    four times) rescored in ONE reference pass over document + question +
    answer: the MEAN, over the request's emitted tokens, of how far the
    token's reference logit lies below that position's maximum
    (`emitted_margin_mean`; `shared_doc_serving.check_emitted`, used as it
    is on those two records). The longest, not the shortest that function
    would pick of all records: a reference pass costs by the document, not
    by the answer, and over 64 tokens one near-tie of the router that flips
    on bf16 rounding is a third of a sound request's mean (six sound windows
    read 0.0 to 0.00096), over 700 it is a thirtieth. This holds the document's global pages, each window layer's page on
    the node, the tail prefill that resumed from them, the seat of the rings
    and every decode step through both kinds of page to the reference's full
    forward.
(c) the rings themselves, THROUGH A HIT: after the window one probe question
    on a resident document goes through the warm engine's own hit program;
    while it is seated every window layer's keys and values of the prompt's
    last `window` positions are read out of the slot's ring. Then the prefix
    cache is flushed and the SAME prompt is prefilled cold through the cold
    program the documents were seated with, and the same rows are read.
    JUDGED: the largest relative RMS difference, over the window layers, of
    the rows BEFORE the match point (`ring_rel_rms`): they are a copy of the
    node's page, which the document's own cold prefill wrote with the same
    chunks as the probe's cold prefill, so they agree to the last bit or
    nearly; a hit that seats another page, or another layer's, or a page
    held in fewer bits than the ring, reads of order 0.03 to 1. LOGGED: the
    same of the tail's rows, which differ by how a 128-row tail (the expert
    stream kernel, the flash forward against 640 keys) and a 2048-row chunk
    (grouped experts) round and route: check (b) holds those.

`probe` runs on the engine (the timed path) and must run before the engine's
pools are dropped; `run` judges after they are (a 33 k-token float32 pass does
not fit beside them). A control plants its fault around `probe` / the window
and calls `run` after (benchmark/mimo_controls.py). The tolerances live in the
configuration file with their reasons.
"""

import numpy as np

from benchmark import spec
from benchmark.reference.serve_check_snapshot import (PROBE_QUESTION, _rel,
                                                      check_predict)


def ring_rows(eng, slot, length):
    """{op name: {"k", "v"}}: every window layer's rows of sequence positions
    [length - window, length) as the slot's ring holds them (host copies)."""
    from flexflow_tpu.runtime.kv_pool import op_keeps

    out = {}
    for op in eng.gen.attn_ops:
        w = op_keeps(op)
        if w is None:
            continue
        group = eng.kv.window_groups[w]
        pos = np.arange(max(0, length - w), length)
        pages = group.tables[slot][(pos // eng.page_size) % group.ring]
        offs = pos % eng.page_size
        pool = eng.kv.pool[op.name]
        out[op.name] = {n: np.asarray(pool[n][pages, offs], np.float32)
                        for n in ("k", "v")}
    return out


def _seated_rows(eng, prompt):
    """Submit `prompt`, step until it has emitted a token (one tick: the
    prefill and at most a dispatch of decode steps, which write behind the
    prompt and overwrite none of its last window's rows), read its rings'
    rows of the prompt's last window, run it to its end. (request, rows)."""
    assert eng.decode_chunk <= eng.page_size
    req = eng.submit(prompt, 3 * eng.decode_chunk)
    while not req.tokens and eng.pending():
        eng.step()
    if req.slot < 0:
        raise RuntimeError(f"the probe request ended early: {req.state} "
                           f"{req.error}")
    rows = ring_rows(eng, req.slot, prompt.size)
    while eng.pending():
        eng.step()
    return req, rows


def probe(h, eng, doc):
    """{"warm": the rings' rows after a hit, "cold": after a cold prefill of
    the same prompt, "prefix_tokens": what each admission found cached,
    "document": the document's tokens}. A program compiled here was not the
    window's: that is an error."""
    before = eng.recompile_count
    rng = np.random.default_rng([int(h.args.seed), 0x57A7E])
    question = rng.integers(1, h.vocab, dtype=np.int32,
                            size=max(2, PROBE_QUESTION // h.scale))
    prompt = np.concatenate([doc, question])
    hit, warm = _seated_rows(eng, prompt)
    compiled = eng.recompile_count - before
    eng.flush_prefix_cache()
    miss, cold = _seated_rows(eng, prompt)
    if compiled or eng.recompile_count != before:
        raise RuntimeError("the probe request compiled a program: it did "
                           "not run the window's warm ones")
    return {"warm": warm, "cold": cold, "document": int(doc.size),
            "prompt": int(prompt.size), "prefix_tokens": (int(hit.prefix_tokens),
                              int(miss.prefix_tokens)),
            "same_tokens": hit.tokens == miss.tokens}


def _split_errors(probed):
    """{op: {"copied": .., "tail": ..}}: relative RMS difference of the
    rows before the match point (the document's) and behind it (the
    question's), keys and values together."""
    out = {}
    for op, cold in probed["cold"].items():
        n = cold["k"].shape[0] - (probed["prompt"] - probed["document"])
        parts = {}
        for what, rows in (("copied", slice(0, n)), ("tail", slice(n, None))):
            got, want = (np.concatenate(
                [side[op][x][rows].reshape(-1) for x in ("k", "v")])
                for side in (probed["warm"], probed["cold"]))
            parts[what] = _rel(got, want) if got.size else 0.0
        out[op] = parts
    return out


def longest_of_each_length(records, sched):
    """The completed request with the most emitted tokens of each document
    length: what check (b) rescores."""
    keep = {}
    for r in records:
        if r["state"] != "done":
            continue
        size = int(sched.docs[sched.doc_of[r["index"]]].size)
        if size not in keep or r["tokens"] > keep[size]["tokens"]:
            keep[size] = r
    return list(keep.values())


def check_rings(h, probed):
    tol = h.config["tolerances"]["ring_rel_rms"]
    errs = _split_errors(probed)
    worst = max(e["copied"] for e in errs.values())
    hit = probed["prefix_tokens"] == (probed["document"], 0)
    h.log(f"check (c) the rings' rows of the prompt's last window after a "
          f"hit (found cached: {probed['prefix_tokens'][0]} of a document "
          f"of {probed['document']}) against a cold prefill of the same "
          f"prompt (found cached: {probed['prefix_tokens'][1]}): largest "
          f"relative RMS difference of a layer's rows before the match "
          f"point {worst:.6f} (tolerance {tol}); before / behind it "
          f"(logged) by layer: "
          + ", ".join(f"{op} {e['copied']:.5f} / {e['tail']:.5f}"
                      for op, e in errs.items())
          + f"; the two emitted the same tokens: {probed['same_tokens']} "
            f"(logged)")
    if not hit:
        h.log("check (c): the probe did NOT resume from its document's "
              "pages and snapshot, or the cold prefill found them")
    return bool(hit and worst <= tol), worst, errs


def run(h, ff, records, sched, probed, reference_params=None):
    """The three checks; `probed` is `probe`'s result, taken while the engine
    lived. `reference_params` where the program under test was given other
    weights than the reference should read (a control)."""
    from benchmark.generators import shared_doc_serving

    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    params = ff.params if reference_params is None else reference_params
    ok_a, rel = check_predict(h, ff, reference, z, params)
    b = shared_doc_serving.check_emitted(
        h, reference, z, params, longest_of_each_length(records, sched),
        sched)
    ok_c, ring_rel, errs = check_rings(h, probed)
    return {**b, "ok": bool(ok_a and b["ok"] and ok_c),
            "predict_rel_rms": rel, "ring_rel_rms": ring_rel,
            "ring_errors": errs}
