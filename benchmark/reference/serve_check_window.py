"""`correct` of a serving cell whose attention layers keep different things
in the page pool (window layers a ring of pages a slot, global layers every
token), outside the timed window. The forward function is that of the module
the configuration names (`"reference": "exaone_moe"` ->
benchmark/reference/exaone_moe.py, `forward(params, tokens, sizes,
routing=None, rows=None)`, the window as a dense mask, no cache).

(a) `ff.predict` logits on one seeded sequence of `graph_seq_len` tokens (32
    windows at 4096) agree with the reference: the dense path (the flash
    forward with the window's lower edge, bf16 matmuls, the grouped experts)
    at the published widths. Judged: relative RMS error, `predict_rel_rms`.
(b) the SHORT_REQUESTS shortest completed requests, and the shortest
    completed request whose prompt is at least LONG_PROMPT tokens (it crosses
    four prefill chunks and its global layer reads 8 k keys a step), each
    rescored in ONE pass of the reference over prompt + emitted tokens.
    Judged: the MEAN, over a request's emitted tokens, of how far the token's
    reference logit lies below the reference's maximum at its position
    (`emitted_margin_mean`), and the same mean over ALL the rescored tokens
    of the four requests together (`emitted_margin_pooled`: about 680 tokens,
    so a few near-tie flips weigh a fifth of what they weigh in the shortest
    request's 128; it is the limit an 8-bit cache fails): through chunked
    prefill, the seat of the window
    layers' rings, the paged decode of both kinds of layer and the pool. The
    largest single margin is logged and not judged: with eight gates of about
    0.31 a near-tie of the 8th and 9th expert flips on bf16 rounding and
    moves one token's logits by more than any fault this check is for (the
    reason `deepseek-v3.2-serve` gives for the same choice). A window without
    a completed long request is not correct.

Where the reference reports its expert choices (a) also logs the share of
(token, layer) pairs whose top-k expert SET differs between the program and
the reference; logged, never judged.

`run(h, ff, records, long_prompt=...)`: a control rescores a longer request
through the same function (benchmark/exaone_controls.py). The tolerances live
in the configuration file with their reasons.
"""

import numpy as np

from benchmark import spec

SHORT_REQUESTS = 3
LONG_PROMPT = 8192
PAD_SHORT = 512     # the short requests share one padded length
PAD_LONG = 2048


def expert_flip_share(ff, toks, sizes, ref_routing):
    """Share of (token, expert layer) pairs where the program's top-k expert
    set is not the reference's; None where either side reports none. The
    program's side is its own `ln2_{i}` output through its own router the way
    the op computes it: a matmul in the compute dtype, sigmoid in float32,
    the k largest of s + b."""
    import jax
    import jax.numpy as jnp

    k = int(sizes["num_experts_per_tok"])
    layers = [i for i, kind in enumerate(sizes["mlp_layer_types"])
              if kind != "dense"]
    ops = [ff.get_op_by_name(f"ln2_{i}") for i in layers]
    if not ref_routing or any(op is None for op in ops):
        return None
    fwd = jax.jit(ff.executor.make_forward([op.outputs[0] for op in ops]))
    normed = fwd(ff.params, ff.bn_state, ff.executor.shard_batch(
        {"input": toks}))
    flips = 0
    for i, m, theirs in zip(layers, normed, ref_routing):
        p = ff.params[f"moe_{i}"]
        s = jax.nn.sigmoid((m[0] @ p["router"].astype(m.dtype))
                           .astype(jnp.float32))
        mine = jax.lax.top_k(s + p["score_bias"].astype(jnp.float32), k)[1]
        flips += int((np.sort(np.asarray(mine), -1)
                      != np.sort(np.asarray(theirs), -1)).any(-1).sum())
    return flips / (len(layers) * toks.shape[1])


def margins_of(reference, params, z, req, pad_to):
    """How far below the reference's maximum each emitted token's reference
    logit lies: one pass over prompt + emitted tokens, padded to `pad_to`
    (causal: the rows behind change nothing), only the emitted tokens' rows
    through the last layer and the head."""
    import jax.numpy as jnp

    full = np.asarray(req.output, np.int32)
    padded = np.zeros((-(-full.size // pad_to) * pad_to,), np.int32)
    padded[:full.size] = full
    p = req.prompt.size
    rows = reference.forward(params, padded, z, rows=(p - 1, full.size - 1))
    emitted = jnp.asarray(full[p:])
    return np.asarray(rows.max(axis=-1) - jnp.take_along_axis(
        rows, emitted[:, None], axis=-1)[:, 0])


def check_predict(h, ff, reference, z):
    import jax

    tol = h.config["tolerances"]["predict_rel_rms"]
    seq = h.cut["graph_seq_len"] // h.scale
    rng = np.random.default_rng([int(h.args.seed), 0xD15E])
    toks = rng.integers(1, z["vocab_size"], size=(1, seq), dtype=np.int32)
    got = np.asarray(jax.block_until_ready(
        ff.predict({"input": toks})), np.float32)[0]
    routing = []
    want = np.asarray(reference.forward(ff.params, toks[0], z,
                                        routing=routing))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    h.log(f"check (a) predict vs reference on {seq} tokens: relative RMS "
          f"error {rel:.5f} (tolerance {tol}), logit std {want.std():.4f}")
    flips = expert_flip_share(ff, toks, z, routing)
    if flips is not None:
        h.log(f"check (a) expert choice: {100 * flips:.3f} % of the (token, "
              f"expert layer) pairs route to another top-"
              f"{z['num_experts_per_tok']} set than the float32 reference "
              f"(near-ties; logged, not judged)")
    return {"ok": bool(rel <= tol), "predict_rel_rms": rel,
            "expert_flip_share": flips}


def sample(records, long_prompt):
    """[(what, record)]: the SHORT_REQUESTS shortest completed requests and
    the shortest completed one whose prompt has `long_prompt` tokens at
    least (None where the window completed none)."""
    done = sorted((r for r in records if r["state"] == "done"),
                  key=lambda r: r["prompt_tokens"] + r["tokens"])
    long = next((r for r in done if r["prompt_tokens"] >= long_prompt), None)
    return ([("short", r) for r in done[:SHORT_REQUESTS]]
            + [("long", long)])


def check_emitted(h, ff, reference, z, records, long_prompt):
    tol = h.config["tolerances"]["emitted_margin_mean"]
    picked = sample(records, long_prompt // h.scale)
    shorts = [r for what, r in picked if what == "short"]
    pad_short = max([PAD_SHORT] + [
        -(-(r["prompt_tokens"] + r["tokens"]) // PAD_SHORT) * PAD_SHORT
        for r in shorts])
    tol_pooled = h.config["tolerances"]["emitted_margin_pooled"]
    ok, worst, worst_mean, every = len(shorts) == SHORT_REQUESTS, 0.0, 0.0, []
    if not ok:
        h.log(f"check (b): only {len(shorts)} completed requests to sample")
    for what, r in picked:
        if r is None:
            h.log(f"check (b): no completed request of at least "
                  f"{long_prompt // h.scale} prompt tokens to rescore")
            ok = False
            continue
        req = r["request"]
        m = margins_of(reference, ff.params, z, req,
                       pad_short if what == "short" else PAD_LONG)
        worst, worst_mean = max(worst, float(m.max())), \
            max(worst_mean, float(m.mean()))
        every.append(m)
        h.log(f"check (b) {what} request prompt={req.prompt.size} "
              f"emitted={m.size}: reference margin of the emitted tokens "
              f"mean {m.mean():.5f} max {m.max():.5f} (the first, which the "
              f"prefill emits, {m[0]:.5f}), {int((m == 0).sum())}/{m.size} "
              f"are the reference's own argmax")
    pooled = float(np.concatenate(every).mean()) if every else 0.0
    h.log(f"check (b) worst mean margin of a request {worst_mean:.5f} "
          f"(tolerance {tol}); mean margin of all {sum(map(len, every))} "
          f"rescored tokens {pooled:.5f} (tolerance {tol_pooled}); largest "
          f"single margin {worst:.5f} (logged, not judged)")
    return {"ok": bool(ok and worst_mean <= tol and pooled <= tol_pooled),
            "emitted_margin_mean": worst_mean,
            "emitted_margin_pooled": pooled, "worst_margin": worst}


def run(h, ff, records, long_prompt=LONG_PROMPT):
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    a = check_predict(h, ff, reference, z)
    b = check_emitted(h, ff, reference, z, records, long_prompt)
    return {**a, **b, "ok": bool(a["ok"] and b["ok"])}
