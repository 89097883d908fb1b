"""`correct` of a serving cell whose configuration names its reference
(`"reference": "olmoe"` -> benchmark/reference/olmoe.py, which gives
`forward(params, tokens, sizes, routing=None)`), outside the timed window.
Checks (a) and (b) are `serve_check.py`'s, with that forward function:

(a) `ff.predict` logits on one seeded sequence agree with the reference: the
    dense path (flash forward, bf16 matmuls, the grouped expert matmuls) at
    the published widths.
(b) For the SAMPLE_REQUESTS shortest completed requests the reference scores
    prompt + emitted tokens in ONE pass; each emitted token's reference logit
    must lie within a margin of that position's maximum: a logit-level check
    through prefill, the paged cache and decode that reads no engine
    internals.

Where the reference reports its expert choices (a `routing` list) and the
model has `moe_{i}` ops, (a) also logs the share of (token, layer) pairs whose
top-k expert SET differs between the program and the reference. The program's
side is its own `ln2_{i}` output (fetched with the executor's forward for
those tensors) through its own router weights the way the op computes it: a
matmul in the compute dtype, softmax in float32, top-k. It is logged and
returned, never judged: at a near-tie of the k-th and (k+1)-th gate bf16
rounding picks the other expert, whose gate is nearly the same, and what that
does to the logits is inside (a)'s error.

The tolerances live in the configuration file with their reasons.
"""

import numpy as np

from benchmark import spec

SAMPLE_REQUESTS = 4
PAD_TO = 256        # reference sequence lengths round up to this: few shapes


def expert_flip_share(ff, toks, sizes, ref_routing):
    """Share of (token, layer) pairs where the program's top-k expert set is
    not the reference's; None for a model without `moe_{i}` ops."""
    import jax
    import jax.numpy as jnp

    layers, k = int(sizes["num_hidden_layers"]), int(
        sizes["num_experts_per_tok"])
    ops = [ff.get_op_by_name(f"ln2_{i}") for i in range(layers)]
    if not ref_routing or any(op is None for op in ops):
        return None
    fwd = jax.jit(ff.executor.make_forward([op.outputs[0] for op in ops]))
    normed = fwd(ff.params, ff.bn_state, ff.executor.shard_batch(
        {"input": toks}))
    flips = 0
    for i, m in enumerate(normed):
        router = ff.params[f"moe_{i}"]["router"].astype(m.dtype)
        gates = jax.nn.softmax((m[0] @ router).astype(jnp.float32), axis=-1)
        mine = np.sort(np.asarray(jax.lax.top_k(gates, k)[1]), axis=-1)
        theirs = np.sort(np.asarray(ref_routing[i]), axis=-1)
        flips += int((mine != theirs).any(axis=-1).sum())
    return flips / (layers * toks.shape[1])


def run(h, ff, records):
    import jax
    import jax.numpy as jnp

    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    tol = h.config["tolerances"]
    params = ff.params
    ok = True

    # (a) the dense path
    seq = h.cut["graph_seq_len"] // h.scale
    rng = np.random.default_rng([int(h.args.seed), 0xD15E])
    toks = rng.integers(1, z["vocab_size"], size=(1, seq), dtype=np.int32)
    got = np.asarray(jax.block_until_ready(
        ff.predict({"input": toks})), np.float32)[0]
    routing = []
    want = np.asarray(reference.forward(params, toks[0], z, routing=routing))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    h.log(f"check (a) predict vs reference on {seq} tokens: relative RMS "
          f"error {rel:.5f} (tolerance {tol['predict_rel_rms']}), logit std "
          f"{want.std():.4f}")
    ok &= rel <= tol["predict_rel_rms"]
    flips = expert_flip_share(ff, toks, z, routing)
    if flips is not None:
        h.log(f"check (a) expert choice: {100 * flips:.3f} % of the "
              f"{seq * int(z['num_hidden_layers'])} (token, layer) pairs "
              f"route to another top-{z['num_experts_per_tok']} set than "
              f"the float32 reference (near-ties; logged, not judged)")

    # (b) through prefill, the paged cache and decode
    done = sorted((r for r in records if r["state"] == "done"),
                  key=lambda r: r["prompt_tokens"] + r["tokens"])
    worst, below = 0.0, 0
    for r in done[:SAMPLE_REQUESTS]:
        req = r["request"]
        full = np.asarray(req.output, np.int32)
        padded = np.zeros((-(-full.size // PAD_TO) * PAD_TO,), np.int32)
        padded[:full.size] = full       # causal: trailing pads change nothing
        logits = reference.forward(params, padded, z)
        p = req.prompt.size
        rows = logits[p - 1:full.size - 1]          # predict each emitted token
        emitted = jnp.asarray(full[p:])
        margins = np.asarray(rows.max(axis=-1)
                             - jnp.take_along_axis(rows, emitted[:, None],
                                                   axis=-1)[:, 0])
        worst = max(worst, float(margins.max()))
        below += int((margins > tol["emitted_margin"]).sum())
        h.log(f"check (b) request prompt={p} emitted={emitted.size}: "
              f"reference margin of the emitted tokens max "
              f"{margins.max():.5f} mean {margins.mean():.5f}, "
              f"{int((margins == 0).sum())}/{emitted.size} are the "
              f"reference's own argmax")
    if len(done) < SAMPLE_REQUESTS:
        h.log(f"check (b): only {len(done)} completed requests to sample")
        ok = False
    h.log(f"check (b) worst margin {worst:.5f} (tolerance "
          f"{tol['emitted_margin']}), {below} tokens beyond it")
    ok &= below == 0
    return {"ok": bool(ok), "predict_rel_rms": rel, "worst_margin": worst,
            "expert_flip_share": flips}
