"""The plain reference of the Granite 4.0-H family (IBM, HF
`modeling_granitemoehybrid.py`, `model_type` `granitemoehybrid`, with
`num_local_experts` 0): the forward pass in float32 `jax.numpy`.

    h = embedding_multiplier * E[tokens]
    per layer i:
        m = Mamba2(RMSNorm(h; w1_i))  if layer_types[i] == "mamba"
            Attention(RMSNorm(h; w1_i))  otherwise
        h = h + residual_multiplier * m
        [g | u] = RMSNorm(h; w2_i) W_in;  h = h + residual_multiplier * (silu(g) * u) W_out
    logits = RMSNorm(h; w_f) E^T / logits_scaling          # the head is the embedding

  Mamba2: [z | xBC | dt] = u W_in;  xBC = silu(conv1d(xBC)) (causal,
      depthwise, width 4, with bias, written as a sum over 4 shifted rows);
      x (H heads, P), B, C (G groups, N), head j reading group j // (H / G);
      dt = softplus(dt + dt_bias), not clamped;  A = -exp(A_log);
      H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T (H_0 = 0);
      y_t = H_t C_t + D x_t;  y = RMSNorm_groups(y * silu(z); w);  out = y W_out.
      The recurrence runs TOKEN BY TOKEN (a `lax.scan` over positions whose
      carry is the (H, P, N) state): no chunked form, no cache. `forward`
      hands out the state a cache would hold after the first `rows` tokens: H
      and the last width - 1 pre-convolution rows.
  Attention: causal softmax attention, grouped KV heads, no bias, NO rotary
      and no other position signal (`position_embedding_type` "nope"),
      softmax(attention_multiplier * q k^T) v: the multiplier stands where
      1 / sqrt(d) stands elsewhere.

No kernel, no cache, no chunked scan. Matmuls run under
`jax.default_matmul_precision("highest")`. It takes the PROGRAM's weights by
name (`granite_hybrid_lm`'s: `tok_embed`, `norm1_{i}`, `mamba_{i}` or
`attn_{i}`, `norm2_{i}`, `mlp_{i}`, `norm_f`) and casts them to float32 one
layer at a time; nothing is imported from the program.

Departures from the source: the source's expert branch (absent at
`num_local_experts` 0) is not written; attention queries are processed in
blocks of QUERY_BLOCK rows under a dense causal mask over ALL keys (one
compiled shape whatever the block, so that 17 k tokens fit and compile once):
the result does not depend on the block. `logit_rows` = (first, last) computes
the head for those rows alone (17 k x 100352 float32 logits would be 7 GB).
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "state", "eps", "residual"))
def mamba(h, norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
          w_out, rows, *, heads, head_dim, groups, state, eps, residual):
    """h + residual * mamba2(RMSNorm(h)) on h (S, D), the recurrence token by
    token; beside it the state after row `rows` - 1: H (heads, head_dim,
    state) and the last width - 1 rows that entered the convolution."""
    with jax.default_matmul_precision("highest"):
        (norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
         w_out) = map(_f32, (norm, w_in, conv_w, conv_b, dt_bias, a_log,
                             d_skip, norm_w, w_out))
        s = h.shape[0]
        d_inner, gn = heads * head_dim, groups * state
        width = conv_w.shape[1]
        zxd = rms_norm(h, norm, eps) @ w_in
        z = zxd[:, :d_inner]
        xbc = zxd[:, d_inner:2 * d_inner + 2 * gn]
        dt = jax.nn.softplus(zxd[:, 2 * d_inner + 2 * gn:] + dt_bias)  # (S, H)
        # causal depthwise conv: row t reads rows t - width + 1 .. t
        xp = jnp.concatenate([jnp.zeros((width - 1, xbc.shape[1])), xbc])
        xbc = silu(conv_b + sum(xp[k:k + s] * conv_w[:, k]
                                for k in range(width)))
        x = xbc[:, :d_inner].reshape(s, heads, head_dim)
        bm = jnp.repeat(xbc[:, d_inner:d_inner + gn].reshape(
            s, groups, state), heads // groups, axis=1)            # (S, H, N)
        cm = jnp.repeat(xbc[:, d_inner + gn:].reshape(
            s, groups, state), heads // groups, axis=1)
        a = -jnp.exp(a_log)                                          # (H,)

        tail = jax.lax.dynamic_slice_in_dim(xp, rows, width - 1)

        def step(carry, row):
            hs, kept = carry
            x_t, b_t, c_t, dt_t, t = row
            hs = (jnp.exp(dt_t * a)[:, None, None] * hs
                  + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
            return ((hs, jnp.where(t == rows - 1, hs, kept)),
                    jnp.einsum("hpn,hn->hp", hs, c_t))

        zero = jnp.zeros((heads, head_dim, state))
        (_, kept), y = jax.lax.scan(step, (zero, zero),
                                    (x, bm, cm, dt, jnp.arange(s)))
        y = (y + d_skip[:, None] * x).reshape(s, d_inner) * silu(z)
        yg = y.reshape(s, groups, d_inner // groups)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + eps)
        out = (yg.reshape(s, d_inner) * norm_w) @ w_out
        return h + residual * out, kept, tail


@functools.partial(jax.jit, static_argnames=("scale", "eps", "residual"))
def attention(h, norm, wq, wk, wv, wo, *, scale, eps, residual):
    """h + residual * attention(RMSNorm(h)) on h (S, D); weights in the
    program's layout: wq (D, H, d), wk / wv (D, KVH, d), wo (H, d, D). S is a
    multiple of QUERY_BLOCK (the callers pad)."""
    with jax.default_matmul_precision("highest"):
        norm, wq, wk, wv, wo = map(_f32, (norm, wq, wk, wv, wo))
        s = h.shape[0]
        heads, kv_heads = wq.shape[1], wk.shape[1]
        a = rms_norm(h, norm, eps)
        q = jnp.einsum("sd,dhk->shk", a, wq)
        k = jnp.repeat(jnp.einsum("sd,dhk->shk", a, wk), heads // kv_heads,
                       axis=1)
        v = jnp.repeat(jnp.einsum("sd,dhk->shk", a, wv), heads // kv_heads,
                       axis=1)
        cols = jnp.arange(s)[None, :]

        def block(args):
            qb, q0 = args
            scores = scale * jnp.einsum("qhk,shk->hqs", qb, k)
            rows = q0 + jnp.arange(QUERY_BLOCK)[:, None]
            scores = jnp.where(cols <= rows, scores, -jnp.inf)
            return jnp.einsum("hqs,shk->qhk",
                              jax.nn.softmax(scores, axis=-1), v)

        nb = s // QUERY_BLOCK
        ctx = jax.lax.map(block, (
            q.reshape(nb, QUERY_BLOCK, heads, -1),
            jnp.arange(nb) * QUERY_BLOCK)).reshape(s, heads, -1)
        return h + residual * jnp.einsum("qhk,hkd->qd", ctx, wo)


@functools.partial(jax.jit, static_argnames=("eps", "residual"))
def mlp(h, norm, w_in, w_out, *, eps, residual):
    with jax.default_matmul_precision("highest"):
        gu = rms_norm(h, _f32(norm), eps) @ _f32(w_in)
        f = gu.shape[1] // 2
        return h + residual * ((silu(gu[:, :f]) * gu[:, f:]) @ _f32(w_out))


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def head(h, norm_f, embed, *, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(norm_f), eps) @ _f32(embed).T / scaling


def forward(params, tokens, sizes, states=None, rows=None, logit_rows=None):
    """Logits of one sequence `tokens` (S,) under the program's weights
    `params` ({op name: {weight name: array}}, granite_hybrid_lm's names): all
    (S, V) of them, or rows `logit_rows` = (first, last) alone. `sizes` holds
    the configuration's keys (`layer_types`, the multipliers, `rms_norm_eps`,
    the Mamba sizes). `states`, if a dict, receives each Mamba layer's {"h",
    "conv"} after the first `rows` tokens (all of them by default) under the
    layer's op name: what a cache holds when the sequence stops there. The
    rows behind are computed and change nothing (causal), so a caller can pad
    to a length it has compiled; the length is rounded up to QUERY_BLOCK
    here."""
    eps = float(sizes["rms_norm_eps"])
    res = float(sizes["residual_multiplier"])
    tokens = jnp.asarray(tokens)
    n = tokens.shape[0]
    rows = jnp.int32(n if rows is None else rows)
    tokens = jnp.pad(tokens, (0, -n % QUERY_BLOCK))
    embed = params["tok_embed"]["kernel"]
    h = float(sizes["embedding_multiplier"]) * _f32(embed[tokens])
    for i, kind in enumerate(sizes["layer_types"]):
        norm = params[f"norm1_{i}"]["scale"]
        if kind == "mamba":
            m = params[f"mamba_{i}"]
            h, hs, tail = mamba(
                h, norm, m["w_in"], m["conv_w"], m["conv_b"], m["dt_bias"],
                m["A_log"], m["D"], m["norm_w"], m["w_out"], rows,
                heads=int(sizes["mamba_n_heads"]),
                head_dim=int(sizes["mamba_d_head"]),
                groups=int(sizes["mamba_n_groups"]),
                state=int(sizes["mamba_d_state"]), eps=eps, residual=res)
            if states is not None:
                states[f"mamba_{i}"] = {"h": hs, "conv": tail}
        elif kind == "attention":
            at = params[f"attn_{i}"]
            h = attention(h, norm, at["wq"], at["wk"], at["wv"], at["wo"],
                          scale=float(sizes["attention_multiplier"]),
                          eps=eps, residual=res)
        else:
            raise ValueError(f"layer type {kind!r} at layer {i}")
        f = params[f"mlp_{i}"]
        h = mlp(h, params[f"norm2_{i}"]["scale"], f["w_in"], f["w_out"],
                eps=eps, residual=res)
    lo, hi = (0, n) if logit_rows is None else logit_rows
    return head(h[lo:hi], params["norm_f"]["scale"], embed, eps=eps,
                scaling=float(sizes["logits_scaling"]))
