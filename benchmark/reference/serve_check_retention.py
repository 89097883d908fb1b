"""`serve_check_snapshot` for a model whose cached ops are ALL recurrent
states and whose state is a power-retention layer's (ops/retention.py: {"s",
"z"}, no conv tail): the same three checks against the configuration's plain
reference (float32, the quadratic form), compared on logits and states, never
on tokens. (a) `ff.predict`, (b) the emitted tokens' reference margins over
whole document + question + answer passes and the probe (`probe`) are that
file's, imported and used as they are; check (c) is written out here because
that file's reads the keys "h" and "conv" of ONE layer:

(c) the state itself, THROUGH A HIT: after the window one probe question on a
    resident document goes through the warm engine's own programs (the hit
    prefill from the snapshot, the seat, PROBE_STEPS decode steps in place);
    while it is still seated the engine hands out the slot's state and the
    reference computes what a cache holds after the same document + question
    + emitted tokens: ONE weighted sum over all of them from token 0, no
    recurrence and no chunk. JUDGED: S of the FIRST retention layer, relative
    RMS error (`state_rel_rms`): its input is the embedding row, nothing
    upstream has rounded, so what is left is the layer's own bf16 projections
    (the keys enter SQUARED, and a state's entries are sums of zero-mean
    terms, so that error does not average out as a Mamba state's does) and
    the precision the state AND the snapshot are held in; and S of the LAST
    layer (`state_rel_rms_last`), which sees the bf16 stream of the layers
    before it as well and so has a limit of its own (PR 43 (g): layer 0 alone
    sees little of a fault upstream). Logged, not judged: z of both.

(b) and (c) pad their sequences to ONE length a document size (the longest a
request of that document can reach, `_padded_to_document`), so that the 16 k
document's rescored request and the probe share the reference's compiled
shapes: a compile of its layer functions costs about 10 s a new length.

`run` judges after the engine's pools are dropped (a 32 k-token float32 pass
does not fit beside them). A control plants its fault around `probe` / the
window and calls `run` after (benchmark/brumby_controls.py). The tolerances
live in the configuration file with their reasons.
"""

import numpy as np

from benchmark import spec
from benchmark.reference import serve_check_snapshot
from benchmark.reference.serve_check_snapshot import (  # noqa: F401
    PAD_TO, PROBE_QUESTION, PROBE_STEPS, _rel, check_predict, probe)


def _padded_to_document(h, seq, document):
    """`seq` behind zeros up to the longest a request of a `document`-token
    document reaches (document + the longest question + the longest answer),
    rounded up to PAD_TO: causal, the rows behind change nothing."""
    t = h.traffic
    most = document + max(1, t["question_tokens"]["max"] // h.scale) \
        + max(1, t["output_tokens"]["max"] // h.scale)
    out = np.zeros((-(-max(most, seq.size) // PAD_TO) * PAD_TO,), np.int32)
    out[:seq.size] = seq
    return out


def check_emitted(h, reference, z, params, records, sched):
    """`serve_check_snapshot.check_emitted` with each request padded to its
    document's one length."""
    sizes = sorted({int(d.size) for d in sched.docs}, reverse=True)

    def padded(seq):
        return _padded_to_document(
            h, seq, next(k for k in sizes if k <= seq.size))

    keep = serve_check_snapshot._padded
    serve_check_snapshot._padded = padded
    try:
        return serve_check_snapshot.check_emitted(h, reference, z, params,
                                                  records, sched)
    finally:
        serve_check_snapshot._padded = keep


def check_state(h, reference, z, params, probed):
    tol = h.config["tolerances"]
    seq = probed["tokens"]
    last = int(z["num_hidden_layers"]) - 1
    want = {}
    reference.forward(
        params, _padded_to_document(h, seq, probed["document"]), z,
        states=want, rows=seq.size, logit_rows=(0, 1),
        state_layers=(0, last))
    errs = {op: {k: _rel(probed["state"][op][k], st[k]) for k in st}
            for op, st in want.items()}
    rel = errs["retention_0"]["s"]
    rel_last = errs[f"retention_{last}"]["s"]
    hit = probed["prefix_tokens"] == probed["document"]
    h.log(f"check (c) state after {seq.size} tokens (a document of "
          f"{probed['document']}, of which {probed['prefix_tokens']} came "
          f"from its snapshot; the rest prefilled and decoded in place): S "
          f"relative RMS error, layer 0 {rel:.6f} (tolerance "
          f"{tol['state_rel_rms']}), layer {last} {rel_last:.6f} (tolerance "
          f"{tol['state_rel_rms_last']}); S / z by layer: "
          + ", ".join(f"{op} {e['s']:.6f} / {e['z']:.6f}"
                      for op, e in errs.items()))
    if not hit:
        h.log("check (c): the probe did NOT resume from its document's "
              "snapshot")
    ok = (hit and rel <= tol["state_rel_rms"]
          and rel_last <= tol["state_rel_rms_last"])
    return bool(ok), rel, rel_last, errs


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
    ok_c, state_rel, state_rel_last, errs = check_state(
        h, reference, z, params, probed)
    return {"ok": bool(ok_a and ok_b and ok_c), "predict_rel_rms": rel,
            "worst_margin": worst, "rescored_document_tokens": scored,
            "state_rel_rms": state_rel, "state_rel_rms_last": state_rel_last,
            "state_errors": errs}
