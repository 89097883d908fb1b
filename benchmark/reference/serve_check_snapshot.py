"""`correct` of a serving cell over RESIDENT DOCUMENTS whose model keeps a
recurrent state beside its pages, so that every admission resumes from a
SNAPSHOT of that state on a trie node (runtime/kv_pool.py). Three checks, all
against the configuration's own plain reference (float32, the recurrence token
by token), compared on logits and states, never on tokens:

(a) `ff.predict` logits on `graph_seq_len` seeded tokens, relative RMS error
    (`predict_rel_rms`): the dense path (the chunked scan, flash forward,
    bf16 matmuls, the tied head) at the published widths.
(b) the shortest completed request of EACH document length rescored in ONE
    reference pass over document + question + answer (8-17 k tokens): every
    emitted token's reference logit must lie within `emitted_margin` of that
    position's maximum. This holds the document's pages, its snapshot, the
    128-row tail prefill that resumed from it and every in-place decode step
    of 36 state-space layers to the reference's recurrence from token 0.
(c) the state itself, THROUGH A HIT (PR 37's check (c), which went through a
    cold prefill): after the window one probe question on a resident document
    goes through the warm engine's own programs (the hit prefill from the
    snapshot, the seat, PROBE_STEPS decode steps in place); while it is still
    seated the engine hands out the slot's state and the reference computes
    what a cache holds after the same document + question + emitted tokens.
    JUDGED: the recurrent state H of the FIRST Mamba layer, relative RMS error
    (`state_rel_rms`): its input is the scaled embedding row, nothing upstream
    has rounded, so what is left is the layer's own bf16 projections, which
    average out over the tokens a state sums, and the precision the state AND
    the snapshot are held in, which does not. Logged, not judged: the same of
    every deeper layer and of each conv tail.

`probe` runs on the engine (the timed path) and must run before the engine's
pools are dropped; `run` judges after they are (a 17 k-token float32 pass does
not fit beside them). A control plants its fault around `probe` / the window
and calls `run` after (benchmark/granite_controls.py). The tolerances live in
the configuration file with their reasons.
"""

import numpy as np

from benchmark import spec

PROBE_QUESTION = 80     # tokens: inside the question range, ends inside a page
PROBE_STEPS = 304       # decode steps before the state is read
PAD_TO = 1024           # reference sequence lengths round up to this


def probe(h, eng, doc):
    """{"tokens": what the slot's state has read (document, question and
    every emitted token but the last), "state": `eng.slot_state` of the
    probe's slot at that moment, "prefix_tokens": what the admission found
    cached}; the request then runs to its end. A program compiled here was
    not the window's: that is an error."""
    before = eng.recompile_count
    rng = np.random.default_rng([int(h.args.seed), 0x57A7E])
    question = rng.integers(1, h.vocab, dtype=np.int32,
                            size=max(2, PROBE_QUESTION // h.scale))
    prompt = np.concatenate([doc, question])
    steps = max(eng.decode_chunk, PROBE_STEPS // h.scale)
    # two chunks more than it is read at: still seated when it is read
    req = eng.submit(prompt, steps + 2 * eng.decode_chunk)
    while len(req.tokens) <= steps and eng.pending():
        eng.step()
    if req.slot < 0:
        raise RuntimeError(f"the probe request ended early: {req.state} "
                           f"{req.error}")
    state = eng.slot_state(req.slot)
    tokens = np.concatenate([prompt, req.tokens[:-1]]).astype(np.int32)
    while eng.pending():
        eng.step()
    if eng.recompile_count != before:
        raise RuntimeError("the probe request compiled a program: it did "
                           "not run the window's warm ones")
    return {"tokens": tokens, "state": state,
            "prefix_tokens": int(req.prefix_tokens), "document": int(doc.size)}


def _rel(got, want):
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(np.asarray(got, np.float32) - want)
                 / np.linalg.norm(want))


def _padded(seq):
    out = np.zeros((-(-seq.size // PAD_TO) * PAD_TO,), np.int32)
    out[:seq.size] = seq        # causal: the rows behind change nothing
    return out


def check_predict(h, ff, reference, z, params):
    import jax

    tol = h.config["tolerances"]["predict_rel_rms"]
    seq = h.cut["graph_seq_len"] // h.scale
    rng = np.random.default_rng([int(h.args.seed), 0xD15E])
    toks = rng.integers(1, z["vocab_size"], size=(1, seq), dtype=np.int32)
    got = np.asarray(jax.block_until_ready(
        ff.predict({"input": toks})), np.float32)[0]
    want = np.asarray(reference.forward(params, toks[0], z))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    h.log(f"check (a) predict vs reference on {seq} tokens: relative RMS "
          f"error {rel:.5f} (tolerance {tol}), logit std {want.std():.4f}")
    return rel <= tol, rel


def check_emitted(h, reference, z, params, records, sched):
    import jax.numpy as jnp

    tol = h.config["tolerances"]["emitted_margin"]
    done = sorted((r for r in records if r["state"] == "done"),
                  key=lambda r: r["prompt_tokens"] + r["tokens"])
    ok, worst, scored = True, 0.0, []
    for size in sorted({int(d.size) for d in sched.docs}):
        mine = [r for r in done
                if sched.docs[sched.doc_of[r["index"]]].size == size]
        if not mine:
            h.log(f"check (b): no completed request of the {size}-token "
                  f"documents to rescore")
            ok = False
            continue
        req = mine[0]["request"]
        full = np.asarray(req.output, np.int32)
        p = req.prompt.size
        rows = reference.forward(params, _padded(full), z,
                                 logit_rows=(p - 1, full.size - 1))
        emitted = jnp.asarray(full[p:])
        margins = np.asarray(rows.max(axis=-1) - jnp.take_along_axis(
            rows, emitted[:, None], axis=-1)[:, 0])
        worst = max(worst, float(margins.max()))
        scored.append(size)
        h.log(f"check (b) document {size} tokens, request prompt={p} "
              f"(found cached: {req.prefix_tokens}) emitted={emitted.size}: "
              f"reference margin of the emitted tokens max "
              f"{margins.max():.5f} mean {margins.mean():.5f} (the first, "
              f"which the hit prefill emits, {margins[0]:.5f}), "
              f"{int((margins == 0).sum())}/{emitted.size} are the "
              f"reference's own argmax; reference logit std "
              f"{float(rows.std()):.4f}")
    h.log(f"check (b) worst margin {worst:.5f} (tolerance {tol}); document "
          f"lengths rescored {scored}")
    return bool(ok and worst <= tol), worst, scored


def check_state(h, reference, z, params, probed):
    tol = h.config["tolerances"]["state_rel_rms"]
    seq = probed["tokens"]
    want = {}
    reference.forward(params, _padded(seq), z, states=want, rows=seq.size,
                      logit_rows=(0, 1))
    errs = {op: {k: _rel(probed["state"][op][k], st[k]) for k in st}
            for op, st in want.items()}
    first = next(iter(want))            # the layers' order
    rel = errs[first]["h"]
    hit = probed["prefix_tokens"] == probed["document"]
    h.log(f"check (c) state after {seq.size} tokens (a document of "
          f"{probed['document']}, of which {probed['prefix_tokens']} came "
          f"from its pages and its snapshot; the rest prefilled and decoded "
          f"in place): {first} H relative RMS error {rel:.6f} (tolerance "
          f"{tol}); logged, H / conv tail by layer: "
          + ", ".join(f"{op} {e['h']:.5f} / {e['conv']:.5f}"
                      for op, e in errs.items()))
    if not hit:
        h.log("check (c): the probe did NOT resume from its document's "
              "snapshot")
    return bool(hit and rel <= tol), rel, errs


def run(h, ff, records, sched, probed, reference_params=None):
    """The three checks; `probed` is `probe`'s result, taken while the engine
    lived. `reference_params` where the program under test was given other
    weights than the reference should read (a control)."""
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    params = ff.params if reference_params is None else reference_params
    ok_a, rel = check_predict(h, ff, reference, z, params)
    ok_b, worst, scored = check_emitted(h, reference, z, params, records,
                                        sched)
    ok_c, state_rel, errs = check_state(h, reference, z, params, probed)
    return {"ok": bool(ok_a and ok_b and ok_c), "predict_rel_rms": rel,
            "worst_margin": worst, "rescored_document_tokens": scored,
            "state_rel_rms": state_rel, "state_errors": errs}
