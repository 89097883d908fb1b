"""The plain reference of the K-EXAONE family (LG AI Research, HF
`LGAI-EXAONE/K-EXAONE-236B-A23B` config.json, `model_type` `exaone_moe`): the
forward pass in float32 `jax.numpy`.

    h = embed[tokens]
    per layer i:
      a = RMSNorm(h; ln1_i, eps)
      q = a Wq, k = a Wk, v = a Wv        (H heads of d; KVH key-value heads)
      q, k = RMSNorm over each head's d entries (one learned d-vector each)
      layer_types[i] == "sliding_attention":  q, k = rotary(q), rotary(k);
          position t sees keys t - window < s <= t   (window = sliding_windows[i])
      layer_types[i] == "full_attention":     no rotary; t sees keys s <= t
      h += softmax(q k^T / sqrt(d)) v Wo
      m = RMSNorm(h; ln2_i, eps)
      mlp_layer_types[i] == "dense":  h += (silu(m Wgate) * m Wup) Wdown
      else: s = sigmoid(m Wr) in float32;  T = the k largest of s + b;
            g = routed_scaling_factor * s[T] / sum(s[T])  (gates from s alone)
            h += SwiGLU_shared(m) + sum_{e in T, e held} g_e SwiGLU_e(m)
    logits = RMSNorm(h; ln_f) Whead

With `experts_held` = [first, count] the routed sum runs over ITS experts only
(one chip's share of an expert-parallel layer; the router keeps its full width
and the shared expert is whole). The vocabulary is whatever slice the
program's `tok_embed` / `lm_head` hold.

No kernel, no cache, no sort and no grouped matmul. The window is a dense mask
over ALL keys (a window layer computes the same S x S scores a global one
does and masks more of them). The experts are a loop over the held ones, each
applied to every row and weighted by its column of a dense (S, E) gate matrix
that is zero off the top-k. Matmuls run under
`jax.default_matmul_precision("highest")`. It takes the PROGRAM's weights by
name (`exaone_moe_lm`'s: `ln1_{i}`, `attn_window_{i}` / `attn_global_{i}`,
`ln2_{i}`, `ffn_*_{i}` / `moe_{i}`) and casts them to float32 one layer, and
inside a layer one expert, at a time.

Assumed where config.json is silent (the configuration file lists each):
pre-norm blocks, the per-head QK norm before rotary, no rotary on the global
layers, the router's selection bias. Departures from the source: rotate-half
rotary as everywhere in this package; the source holds each expert's matrices
as separate (out, in) Linear weights, the program stacks them (E, in, out); the
multi-token-prediction layer is not part of the next token's forward pass and
is left out.

Queries are processed in blocks of `query_block(S)` rows and feed-forward rows
in blocks of ROW_BLOCK, so that a sequence of 33 k tokens fits beside a
serving engine; the result does not depend on either.
"""

import functools
import math

import jax
import jax.numpy as jnp

ROW_BLOCK = 2048


def query_block(rows: int, keys: int) -> int:
    """Query rows a block, a divisor of `rows`: its float32 scores are heads
    x block x `keys`."""
    for qb in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if rows % qb == 0 and (qb <= 128 or keys <= 12288):
            return qb
    return 1


def _f32(a):
    return a.astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


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


@functools.partial(jax.jit, static_argnames=("window", "rope", "theta", "eps",
                                             "rows"))
def attention(h, ln1, wq, wk, wv, wo, q_norm, k_norm, *, window, rope, theta,
              eps, rows):
    """Rows `rows` = (lo, hi) of h + attention(RMSNorm(h)), h (S, D); weights
    in the program's layout: wq (D, H, d), wk / wv (D, KVH, d), wo (H, d, D),
    q_norm / k_norm (d,). `window` 0: a global layer."""
    with jax.default_matmul_precision("highest"):
        ln1, wq, wk, wv, wo, q_norm, k_norm = map(
            _f32, (ln1, wq, wk, wv, wo, q_norm, k_norm))
        s = h.shape[0]
        heads, kv_heads, d = wq.shape[1], wk.shape[1], wq.shape[2]
        a = rms_norm(h, ln1, eps)
        q = rms_norm(jnp.einsum("sd,dhk->shk", a, wq), q_norm, eps)
        k = rms_norm(jnp.einsum("sd,dhk->shk", a, wk), k_norm, eps)
        v = jnp.einsum("sd,dhk->shk", a, wv)
        if rope:
            q, k = rotary(q, theta), rotary(k, theta)
        qg = q.reshape(s, kv_heads, heads // kv_heads, d)
        cols = jnp.arange(s)[None, :]
        lo, hi = rows
        qb = query_block(hi - lo, s)

        def block(q0):
            at = q0 + jnp.arange(qb)[:, None]
            seen = cols <= at
            if window:
                seen = seen & (cols > at - window)
            scores = jnp.einsum(
                "qkgd,skd->kgqs", jax.lax.dynamic_slice_in_dim(qg, q0, qb),
                k) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            ctx = jnp.einsum("kgqs,skd->qkgd", probs, v)
            return jnp.einsum("qhk,hkd->qd", ctx.reshape(qb, heads, d), wo)

        out = jax.lax.map(block, lo + qb * jnp.arange((hi - lo) // qb))
        return h[lo:hi] + out.reshape(hi - lo, -1)


def _row_blocks(n):
    rb = min(n, ROW_BLOCK)
    return [(r0, min(rb, n - r0)) for r0 in range(0, n, rb)]


@functools.partial(jax.jit, static_argnames=("eps",))
def normed(h, scale, *, eps):
    return rms_norm(h, _f32(scale), eps)


@functools.partial(jax.jit, donate_argnums=(0,))
def swiglu_into(acc, m, gate_col, w_gate, w_up, w_down, r0):
    """acc[r0 : r0 + rows] += gate_col * SwiGLU(m) for the block's rows."""
    with jax.default_matmul_precision("highest"):
        g = m @ _f32(w_gate)
        y = gate_col[:, None] * (((g * jax.nn.sigmoid(g))
                                  * (m @ _f32(w_up))) @ _f32(w_down))
        n = m.shape[0]
        return jax.lax.dynamic_update_slice_in_dim(
            acc, jax.lax.dynamic_slice_in_dim(acc, r0, n) + y, r0, 0)


@functools.partial(jax.jit, static_argnames=("top_k", "scaling",
                                             "renormalize"))
def route(m, router, bias, *, top_k, scaling, renormalize):
    """(dense gates (S, E), zero off each row's chosen experts; the chosen
    expert ids (S, k)). Chosen by s + b; weighted by s alone."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(m @ _f32(router))
        top_e = jax.lax.top_k(s + _f32(bias), top_k)[1]
        g = jnp.take_along_axis(s, top_e, axis=-1)
        if renormalize:
            g = g / jnp.sum(g, axis=-1, keepdims=True)
        g = g * scaling
        n = s.shape[0]
        return jnp.zeros_like(s).at[jnp.arange(n)[:, None], top_e].set(g), \
            top_e


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, ln_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(ln_f), eps) @ _f32(w_head)


def feed_forward(h, i, params, sizes, routing):
    """h + layer i's feed-forward of h (n, D), in blocks of ROW_BLOCK rows."""
    eps = float(sizes["rms_norm_eps"])
    n_rows = h.shape[0]
    ln2 = params[f"ln2_{i}"]["scale"]
    ones = jnp.ones((min(n_rows, ROW_BLOCK),), jnp.float32)
    acc = jnp.copy(h)   # `swiglu_into` donates acc, h is still read
    if sizes["mlp_layer_types"][i] == "dense":
        for r0, n in _row_blocks(n_rows):
            acc = swiglu_into(
                acc, normed(h[r0:r0 + n], ln2, eps=eps), ones[:n],
                params[f"ffn_gate_{i}"]["kernel"],
                params[f"ffn_up_{i}"]["kernel"],
                params[f"ffn_down_{i}"]["kernel"], r0)
        return acc
    moe = params[f"moe_{i}"]
    first = int((sizes.get("experts_held") or (0, 0))[0])
    chosen = []
    for r0, n in _row_blocks(n_rows):
        m = normed(h[r0:r0 + n], ln2, eps=eps)
        gates, top_e = route(
            m, moe["router"], moe["score_bias"],
            top_k=int(sizes["num_experts_per_tok"]),
            scaling=float(sizes["routed_scaling_factor"]),
            renormalize=bool(sizes["norm_topk_prob"]))
        chosen.append(top_e)
        acc = swiglu_into(acc, m, ones[:n], moe["shared_gate"],
                          moe["shared_up"], moe["shared_down"], r0)
        for e in range(moe["w_gate"].shape[0]):
            acc = swiglu_into(acc, m, gates[:, first + e], moe["w_gate"][e],
                              moe["w_up"][e], moe["w_down"][e], r0)
    if routing is not None:
        routing.append(jnp.concatenate(chosen))
    return acc


def attn_name(sizes, i):
    """Layer i's attention op in the program: its name says its kind."""
    return (f"attn_window_{i}" if sizes["sliding_windows"][i]
            else f"attn_global_{i}")


def forward(params, tokens, sizes, routing=None, rows=None):
    """Logits (S, V), or of rows lo .. hi - 1 with `rows=(lo, hi)`, of one
    sequence `tokens` (S,) under the program's weights `params` ({op name:
    {weight name: array}}, exaone_moe_lm's names). `sizes` holds the
    configuration's keys (`layer_types`, `sliding_windows`,
    `mlp_layer_types`, the router's, `experts_held`). `routing`, if a list,
    receives each expert layer's chosen expert ids (rows, k). The last
    layer computes only the whole query blocks that hold the asked rows."""
    eps = float(sizes["rms_norm_eps"])
    theta = float(sizes["rope_parameters"]["rope_theta"])
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    h = _f32(params["tok_embed"]["kernel"][tokens])
    lo, hi = rows if rows is not None else (0, s)
    qb = query_block(s, s)
    a0, a1 = lo // qb * qb, -(-hi // qb) * qb
    layers = int(sizes["num_hidden_layers"])
    for i in range(layers):
        window = int(sizes["sliding_windows"][i])
        if (sizes["layer_types"][i] == "sliding_attention") != bool(window):
            raise ValueError(f"layer {i}: {sizes['layer_types'][i]} with "
                             f"window {window}")
        at = params[attn_name(sizes, i)]
        h = attention(h, params[f"ln1_{i}"]["scale"], at["wq"], at["wk"],
                      at["wv"], at["wo"], at["q_norm"], at["k_norm"],
                      window=window, rope=bool(window), theta=theta, eps=eps,
                      rows=(a0, a1) if i == layers - 1 else (0, s))
        h = feed_forward(h, i, params, sizes, routing)
    return head(h[lo - a0:hi - a0], params["ln_f"]["scale"],
                params["lm_head"]["kernel"], eps=eps)
