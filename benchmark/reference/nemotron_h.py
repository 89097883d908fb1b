"""The plain reference of the Nemotron-H family (NVIDIA Nemotron-H, Nemotron 3;
HF `modeling_nemotron_h.py`, `model_type` `nemotron_h`): the forward pass in
float32 `jax.numpy`. Every layer is ONE mixer under one pre-norm, chosen by
the pattern string:

    h = embed[tokens]
    per layer i, c = pattern[i]:   h += mixer_c(RMSNorm(h; w_i, eps))
    logits = RMSNorm(h) Whead

  M (Mamba-2): [z | xBC | dt] = u W_in;  xBC = silu(conv1d(xBC)) (causal,
      depthwise, width 4, with bias);  x (H heads, P), B, C (G groups, N),
      head j reading group j // (H / G);  dt = softplus(dt + dt_bias);
      A = -exp(A_log);  H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T (H_0 = 0);
      y_t = H_t C_t + D x_t;  y = RMSNorm_groups(y * silu(z); w) (the mean of
      squares over each of the G groups of d_inner / G);  out = y W_out.
      The recurrence runs TOKEN BY TOKEN (a `lax.scan` over positions whose
      carry is the (H, P, N) state): no chunked form, no cache. `forward`
      hands out the state a cache would hold after the first `rows` tokens:
      H and the last width - 1 pre-convolution rows.
  * (attention): causal softmax attention, grouped KV heads, no bias, scale
      d^-0.5, NO rotary and no other position signal (`sizes["attention_rope"]`
      true would apply rotary: the family's code never does).
  E (LatentMoE): s = sigmoid(u W_r) in float32;  the k largest of s + b chosen;
      gates g = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
      l = u W_dn;  r = (sum_e g_e relu(l W1_e)^2 W2_e) W_up;
      sh = relu(u V1)^2 V2;  out = r + sh.
      With `experts_held` = [first, count] the sum runs over ITS experts only
      (one chip's share of an expert-parallel layer; the router keeps its full
      width and the shared expert is whole).

No kernel, no cache, no sort and no grouped matmul: the experts are a loop
over the held ones, each applied to every row and weighted by its column of a
dense (S, E) gate matrix that is zero off the top-k. Matmuls run under
`jax.default_matmul_precision("highest")`. It takes the PROGRAM's weights by
name (`nemotron_h_lm`'s: `norm_{i}`, `mamba_{i}`, `attn_{i}`, `moe_{i}`) and
casts them to float32 one layer, and inside a layer one expert, at a time.

Departures from the source: the source holds each expert's matrices as
separate (out, in) Linear weights, the program stacks them as (E, in, out);
the multi-token-prediction module is not part of the next token's forward
pass and is left out.

Attention queries are processed in blocks of QUERY_BLOCK rows so that the
score matrix of a long sequence stays small; the result does not depend on
it.
"""

import functools
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rotary(x, theta):
    """x (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


@functools.partial(jax.jit, static_argnames=("heads", "head_dim", "groups",
                                             "state", "eps"))
def mamba(h, norm, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
          w_out, rows, *, heads, head_dim, groups, state, eps):
    """h + mamba2(RMSNorm(h)) on h (S, D), the recurrence token by token;
    beside it the state after row `rows` - 1: H (heads, head_dim, state) and
    the last width - 1 rows that entered the convolution."""
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
        return h + (yg.reshape(s, d_inner) * norm_w) @ w_out, kept, tail


@functools.partial(jax.jit, static_argnames=("rope", "theta", "eps"))
def attention(h, norm, wq, wk, wv, wo, *, rope, theta, eps):
    """h + attention(RMSNorm(h)) on h (S, D); weights in the program's
    layout: wq (D, H, d), wk/wv (D, KVH, d), wo (H, d, D)."""
    with jax.default_matmul_precision("highest"):
        norm, wq, wk, wv, wo = map(_f32, (norm, wq, wk, wv, wo))
        s = h.shape[0]
        heads, kv_heads, d = wq.shape[1], wk.shape[1], wq.shape[2]
        a = rms_norm(h, norm, eps)
        q = jnp.einsum("sd,dhk->shk", a, wq)
        k = jnp.einsum("sd,dhk->shk", a, wk)
        v = jnp.einsum("sd,dhk->shk", a, wv)
        if rope:
            q, k = rotary(q, theta), rotary(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        blocks = []
        for q0 in range(0, s, QUERY_BLOCK):
            q1 = min(s, q0 + QUERY_BLOCK)
            scores = jnp.einsum("qhk,shk->hqs", q[q0:q1], k[:q1]) \
                / math.sqrt(d)
            rows = jnp.arange(q0, q1)[:, None]
            cols = jnp.arange(q1)[None, :]
            scores = jnp.where(cols <= rows, scores, -jnp.inf)
            blocks.append(jnp.einsum("hqs,shk->qhk",
                                     jax.nn.softmax(scores, axis=-1), v[:q1]))
        ctx = jnp.concatenate(blocks, axis=0)
        return h + jnp.einsum("qhk,hkd->qd", ctx, wo)


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize", "scaling",
                                             "eps"))
def route(h, norm, router, bias, w_dn, *, top_k, renormalize, scaling, eps):
    """(u, l = u W_dn, dense gates (S, E) that are zero off each row's top-k,
    the top-k expert ids (S, k))."""
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, _f32(norm), eps)
        s = jax.nn.sigmoid(u @ _f32(router))
        top_e = jax.lax.top_k(s + _f32(bias), top_k)[1]
        top_s = jnp.take_along_axis(s, top_e, axis=-1)
        if renormalize:
            top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
        rows = jnp.arange(s.shape[0])[:, None]
        gates = jnp.zeros_like(s).at[rows, top_e].set(top_s * scaling)
        return u, u @ _f32(w_dn), gates, top_e


@jax.jit
def expert(l, gate_col, w1, w2):
    """One expert on EVERY row of the latent, weighted by its column of the
    dense gates (zero for a row that did not choose it)."""
    with jax.default_matmul_precision("highest"):
        return gate_col[:, None] * (
            jnp.square(jax.nn.relu(l @ _f32(w1))) @ _f32(w2))


@jax.jit
def moe_out(h, u, r, w_up, v1, v2):
    """h + (the routed sum back out of the latent) + the shared expert."""
    with jax.default_matmul_precision("highest"):
        return (h + r @ _f32(w_up)
                + jnp.square(jax.nn.relu(u @ _f32(v1))) @ _f32(v2))


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, norm_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(norm_f), eps) @ _f32(w_head)


def forward(params, tokens, sizes, routing=None, states=None, rows=None):
    """Logits (S, V) of one sequence `tokens` (S,) under the program's
    weights `params` ({op name: {weight name: array}}, nemotron_h_lm's
    names). `sizes` holds the configuration's keys
    (`hybrid_override_pattern`, `norm_eps`, the Mamba and router sizes,
    `experts_held`). `routing`, if a list, receives each expert layer's
    chosen expert ids (S, k); `states`, if a dict, each Mamba layer's
    {"h", "conv"} after the first `rows` tokens (all of them by default)
    under the layer's op name: what a cache holds when the sequence stops
    there. The rows behind are computed and change nothing (causal), so a
    caller can pad to a length it has compiled."""
    eps = float(sizes["norm_eps"])
    tokens = jnp.asarray(tokens)
    rows = jnp.int32(tokens.shape[0] if rows is None else rows)
    h = _f32(params["tok_embed"]["kernel"][tokens])
    for i, c in enumerate(sizes["hybrid_override_pattern"]):
        norm = params[f"norm_{i}"]["scale"]
        if c == "M":
            m = params[f"mamba_{i}"]
            h, hs, tail = mamba(
                h, norm, m["w_in"], m["conv_w"], m["conv_b"], m["dt_bias"],
                m["A_log"], m["D"], m["norm_w"], m["w_out"], rows,
                heads=int(sizes["mamba_num_heads"]),
                head_dim=int(sizes["mamba_head_dim"]),
                groups=int(sizes["n_groups"]),
                state=int(sizes["ssm_state_size"]), eps=eps)
            if states is not None:
                states[f"mamba_{i}"] = {"h": hs, "conv": tail}
        elif c == "*":
            at = params[f"attn_{i}"]
            h = attention(h, norm, at["wq"], at["wk"], at["wv"], at["wo"],
                          rope=bool(sizes.get("attention_rope", False)),
                          theta=float(sizes["rope_theta"]), eps=eps)
        elif c == "E":
            moe = params[f"moe_{i}"]
            u, lat, gates, top_e = route(
                h, norm, moe["router"], moe["score_bias"],
                moe["w_latent_in"], top_k=int(sizes["num_experts_per_tok"]),
                renormalize=bool(sizes["norm_topk_prob"]),
                scaling=float(sizes["routed_scaling_factor"]), eps=eps)
            if routing is not None:
                routing.append(top_e)
            first, count = sizes.get("experts_held") or (
                0, moe["router"].shape[1])
            r = jnp.zeros_like(lat)
            for e in range(int(count)):
                r = r + expert(lat, gates[:, int(first) + e],
                               moe["w_up"][e], moe["w_down"][e])
            h = moe_out(h, u, r, moe["w_latent_out"], moe["shared_up"],
                        moe["shared_down"])
        else:
            raise ValueError(f"pattern character {c!r} at layer {i}")
    return head(h, params["norm_f"]["scale"], params["lm_head"]["kernel"],
                eps=eps)

