"""`correct` of a serving cell whose model keeps a recurrent state beside its
pages: `serve_check_ref.py`'s checks (a) and (b) as they are, and

(c) the state itself. After the window ONE probe request goes through the
    warm engine's own programs: its bucket's prefill with the padding behind
    it, the seat, then PROBE_STEPS decode steps in place. While it is still
    seated the engine hands out the slot's state (`ServingEngine.slot_state`)
    and the reference computes what a cache holds after the same tokens
    (`forward(states=, rows=)`: the recurrence token by token in float32, one pass).
    JUDGED: the recurrent state H of the pattern's FIRST state-space layer,
    relative RMS error. The first, because where it is layer 0 its input is
    the embedding row and nothing upstream has rounded: what is left is the
    layer's own bf16 projections, which average out over the tokens a state
    sums, and whatever precision the state is HELD in, which does not: every
    step rounds the whole state again. (a) and (b) cannot see that: through
    22 layers a state held in bf16 reads like the bf16 residual stream's own
    error (PERF.md section 6, PR 37). Logged, not judged: the same error of
    every deeper state-space layer (it carries the stream's error upstream
    of it) and of each conv tail.

`probe` runs on the engine (the timed path); `run` judges. A control plants
its fault around `probe` and calls `run` after (benchmark/nemotron_controls.py).
The tolerance `state_rel_rms` lives in the configuration file with its reason.
"""

import numpy as np

from benchmark import spec
from benchmark.reference import serve_check_ref

PROBE_PROMPT = 200  # tokens: inside a bucket, so padding rows lie behind it
PROBE_STEPS = 304   # decode steps before the state is read: 504 tokens in all


def probe(h, eng):
    """{"tokens": what the slot's state has read (the prompt and every
    emitted token but the last), "state": `eng.slot_state` of the probe's
    slot at that moment, "prompt_rows"}; the request then runs to its end.
    A program compiled here was not the window's: that is an error."""
    before = eng.recompile_count
    rng = np.random.default_rng([int(h.args.seed), 0x57A7E])
    prompt = rng.integers(1, h.vocab, size=max(4, PROBE_PROMPT // h.scale),
                          dtype=np.int32)
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
    return {"tokens": tokens, "state": state, "prompt_rows": prompt.size}


def _rel(got, want):
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(np.asarray(got, np.float32) - want)
                 / np.linalg.norm(want))


def run(h, ff, eng, records, probed=None):
    checks = serve_check_ref.run(h, ff, records)
    probed = probed or probe(h, eng)
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    tol = h.config["tolerances"]["state_rel_rms"]
    seq = probed["tokens"]
    pad = serve_check_ref.PAD_TO
    padded = np.zeros((-(-seq.size // pad) * pad,), np.int32)
    padded[:seq.size] = seq             # causal: the rows behind change nothing
    want = {}
    reference.forward(ff.params, padded, z, states=want, rows=seq.size)
    errs = {op: {k: _rel(probed["state"][op][k], st[k]) for k in st}
            for op, st in want.items()}
    first = next(iter(want))            # the pattern's order
    rel = errs[first]["h"]
    h.log(f"check (c) state after {seq.size} tokens "
          f"({probed['prompt_rows']} prefilled, the rest decoded in "
          f"place): {first} H relative RMS error "
          f"{rel:.6f} (tolerance {tol}); logged, H / conv tail by layer: "
          + ", ".join(f"{op} {e['h']:.5f} / {e['conv']:.5f}"
                      for op, e in errs.items()))
    return {**checks, "ok": bool(checks["ok"] and rel <= tol),
            "state_rel_rms": rel,
            "state_errors": errs}
