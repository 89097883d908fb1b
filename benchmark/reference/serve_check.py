"""`correct` of a serving cell, outside the timed window.

(a) `ff.predict` logits on one seeded sequence agree with the reference: the
    dense path (flash forward, bf16 matmuls) at the published widths.
(b) For the SAMPLE_REQUESTS shortest completed requests the reference scores
    prompt + emitted tokens in ONE pass; each emitted token's reference logit
    must lie within a margin of that position's maximum. That is a
    logit-level check through prefill, the paged cache and decode which
    reads no engine internals: a wrong page, position or mask moves the
    emitted token far from the reference's argmax, while bf16 rounding
    moves it only among near-ties. (Tokens are not compared: with random
    weights the largest logit changes on rounding.)

The tolerances live in the configuration file with their reasons.
"""

import numpy as np

SAMPLE_REQUESTS = 4
PAD_TO = 256        # reference sequence lengths round up to this: few shapes


def run(h, ff, records):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import decoder

    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    tol = h.config["tolerances"]
    kw = dict(layers=z["num_hidden_layers"], theta=z["rope_theta"],
              eps=h.config["program_rms_norm_eps"])
    params = ff.params
    ok = True

    # (a) the dense path
    seq = h.cut["graph_seq_len"] // h.scale
    rng = np.random.default_rng([int(h.args.seed), 0xD15E])
    toks = rng.integers(1, z["vocab_size"], size=(1, seq), dtype=np.int32)
    got = np.asarray(jax.block_until_ready(
        ff.predict({"input": toks})), np.float32)[0]
    want = np.asarray(decoder.forward(params, toks[0], **kw))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    h.log(f"check (a) predict vs reference on {seq} tokens: relative RMS "
          f"error {rel:.5f} (tolerance {tol['predict_rel_rms']}), logit std "
          f"{want.std():.4f}")
    ok &= rel <= tol["predict_rel_rms"]

    # (b) through prefill, the paged cache and decode
    done = sorted((r for r in records if r["state"] == "done"),
                  key=lambda r: r["prompt_tokens"] + r["tokens"])
    worst, below = 0.0, 0
    for r in done[:SAMPLE_REQUESTS]:
        req = r["request"]
        full = np.asarray(req.output, np.int32)
        padded = np.zeros((-(-full.size // PAD_TO) * PAD_TO,), np.int32)
        padded[:full.size] = full       # causal: trailing pads change nothing
        logits = decoder.forward(params, padded, **kw)
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
    return {"ok": bool(ok), "predict_rel_rms": rel, "worst_margin": worst}
