"""The plain reference of MiMo-V2-Flash (Xiaomi, HF
`XiaomiMiMo/MiMo-V2-Flash` config.json, `model_type` `mimo_v2_flash`): the
forward pass in float32 `jax.numpy`.

    h = embed[tokens]
    per layer i, kind t = hybrid_layer_pattern[i] (0 global, 1 window):
      a = RMSNorm(h; ln1_i, eps)
      q = a Wq (H heads of d), k = a Wk (KVH_t heads of d),
      v = attention_value_scale * a Wv (KVH_t heads of dv)
      q, k = rotary over the first `rope_dim` entries of each head
             (rotate-half within them), base rope_theta (t = 0) or
             swa_rope_theta (t = 1); the other entries as they are
      s = q k^T / sqrt(d); position p sees keys j <= p (t = 0) or
          p - sliding_window < j <= p (t = 1)
      sink b (H,), where the kind has one (add_swa_attention_sink_bias /
          add_full_attention_sink_bias): prob = exp(s) / (exp(b) + sum exp(s))
      h += (prob v) Wo
      m = RMSNorm(h; ln2_i, eps)
      moe_layer_freq[i] == 0:  h += (silu(m Wgate) * m Wup) Wdown
      else: s = sigmoid(m Wr) in float32;  T = the k largest of s + b;
            g = s[T] / sum(s[T])  (gates from s alone; no scaling factor)
            h += sum_{e in T, e held} g_e SwiGLU_e(m)        (no shared expert)
    logits = RMSNorm(h; ln_f) Whead

With `experts_held` = [first, count] the routed sum runs over ITS experts only
(one chip's share of an expert-parallel layer; the router keeps its full
width). The vocabulary is whatever slice the program's `tok_embed` /
`lm_head` hold.

No kernel, no cache, no sort and no grouped matmul. The window is a dense mask
over ALL keys, the sink one more column of the softmax whose probability is
dropped. The experts are a loop over the held ones, each applied to every row
and weighted by its column of a dense (S, E) gate matrix that is zero off the
top-k. Matmuls run under `jax.default_matmul_precision("highest")`. It takes
the PROGRAM's weights by name (`mimo_v2_lm`'s: `ln1_{i}`, `attn_window_{i}` /
`attn_global_{i}` with `wq`, `wk`, `wv`, `wo` and, where the kind has one,
`sink`; `ln2_{i}`, `ffn_*_{i}` / `moe_{i}`) and casts them to float32 one
layer, and inside a layer one expert, at a time.

Assumed where config.json is silent (the configuration file lists each):
pre-norm blocks, no QK norm, the rotary entries (the leading `rope_dim`,
rotate-half within them), the router's selection bias, the window as what
masks (`attention_chunk_size` is not read). The multi-token-prediction layers
are not part of the next token's forward pass and are left out.

Queries are processed in blocks of `query_block(S)` rows and feed-forward rows
in blocks of ROW_BLOCK, so that a sequence of 33 k tokens fits beside the
weights; the result does not depend on either. `window_rows`, if a dict,
receives each window layer's keys and values of the sequence's last
`sliding_window` positions before `window_rows_end` ({op name: {"k", "v"}}):
what a serving engine's ring holds of that layer.
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
        if rows % qb == 0 and (qb <= 64 or keys <= 8192):
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


def rotary(x, theta, rope_dim):
    """x (S, H, D) at positions 0..S-1: the first `rope_dim` entries of each
    head turn, the rest pass."""
    s = x.shape[0]
    r, rest = x[..., :rope_dim], x[..., rope_dim:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, rope_dim, 2, dtype=jnp.float32)
                               / rope_dim)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return jnp.concatenate(
        [r * jnp.cos(emb) + rotate_half(r) * jnp.sin(emb), rest], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "window", "theta", "rope_dim", "value_scale", "eps", "rows"))
def attention(h, ln1, wq, wk, wv, wo, sink, *, window, theta, rope_dim,
              value_scale, eps, rows):
    """(rows `rows` = (lo, hi) of h + attention(RMSNorm(h)), k, v), h (S, D);
    weights in the program's layout: wq (D, H, d), wk (D, KVH, d), wv (D,
    KVH, dv), wo (H, dv, D), sink (H,) or None. `window` 0: a global
    layer."""
    with jax.default_matmul_precision("highest"):
        ln1, wq, wk, wv, wo = map(_f32, (ln1, wq, wk, wv, wo))
        s = h.shape[0]
        heads, kv_heads, d = wq.shape[1], wk.shape[1], wq.shape[2]
        dv = wv.shape[2]
        a = rms_norm(h, ln1, eps)
        q = rotary(jnp.einsum("sd,dhk->shk", a, wq), theta, rope_dim)
        k = rotary(jnp.einsum("sd,dhk->shk", a, wk), theta, rope_dim)
        v = value_scale * jnp.einsum("sd,dhk->shk", a, wv)
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
            scores = jnp.where(seen, scores, -jnp.inf)
            if sink is not None:
                b = _f32(sink).reshape(kv_heads, heads // kv_heads, 1, 1)
                scores = jnp.concatenate(
                    [scores, jnp.broadcast_to(b, scores.shape[:-1] + (1,))],
                    axis=-1)
            probs = jax.nn.softmax(scores, axis=-1)[..., :s]
            ctx = jnp.einsum("kgqs,skd->qkgd", probs, v)
            return jnp.einsum("qhk,hkd->qd", ctx.reshape(qb, heads, dv), wo)

        out = jax.lax.map(block, lo + qb * jnp.arange((hi - lo) // qb))
        return h[lo:hi] + out.reshape(hi - lo, -1), k, v


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


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize"))
def route(m, router, bias, *, top_k, renormalize):
    """(dense gates (S, E), zero off each row's chosen experts; the chosen
    expert ids (S, k)). Chosen by s + b; weighted by s alone."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(m @ _f32(router))
        top_e = jax.lax.top_k(s + _f32(bias), top_k)[1]
        g = jnp.take_along_axis(s, top_e, axis=-1)
        if renormalize:
            g = g / jnp.sum(g, axis=-1, keepdims=True)
        n = s.shape[0]
        return jnp.zeros_like(s).at[jnp.arange(n)[:, None], top_e].set(g), \
            top_e


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, ln_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(ln_f), eps) @ _f32(w_head)


def feed_forward(h, i, params, sizes, routing):
    """h + layer i's feed-forward of h (n, D), in blocks of ROW_BLOCK rows."""
    eps = float(sizes["layernorm_epsilon"])
    n_rows = h.shape[0]
    ln2 = params[f"ln2_{i}"]["scale"]
    ones = jnp.ones((min(n_rows, ROW_BLOCK),), jnp.float32)
    acc = jnp.copy(h)   # `swiglu_into` donates acc, h is still read
    if not sizes["moe_layer_freq"][i]:
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
            renormalize=bool(sizes["norm_topk_prob"]))
        chosen.append(top_e)
        for e in range(moe["w_gate"].shape[0]):
            acc = swiglu_into(acc, m, gates[:, first + e], moe["w_gate"][e],
                              moe["w_up"][e], moe["w_down"][e], r0)
    if routing is not None:
        routing.append(jnp.concatenate(chosen))
    return acc


def attn_name(sizes, i):
    """Layer i's attention op in the program: its name says its kind."""
    return (f"attn_window_{i}" if sizes["hybrid_layer_pattern"][i]
            else f"attn_global_{i}")


def rope_dim_of(sizes) -> int:
    """partial_rotary_factor x head_dim, down to an even number (0.334 x 192
    = 64.1 -> 64)."""
    return int(float(sizes["partial_rotary_factor"])
               * int(sizes["head_dim"])) // 2 * 2


def forward(params, tokens, sizes, routing=None, rows=None,
            window_rows=None, window_rows_end=None):
    """Logits (S, V), or of rows lo .. hi - 1 with `rows=(lo, hi)`, of one
    sequence `tokens` (S,) under the program's weights `params` ({op name:
    {weight name: array}}, mimo_v2_lm's names). `sizes` holds the
    configuration's keys. `routing`, if a list, receives each expert layer's
    chosen expert ids (rows, k). The last layer computes only the whole
    query blocks that hold the asked rows."""
    eps = float(sizes["layernorm_epsilon"])
    rope_dim = rope_dim_of(sizes)
    window = int(sizes["sliding_window"])
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    h = _f32(params["tok_embed"]["kernel"][tokens])
    lo, hi = rows if rows is not None else (0, s)
    qb = query_block(s, s)
    a0, a1 = lo // qb * qb, -(-hi // qb) * qb
    layers = int(sizes["num_hidden_layers"])
    for i in range(layers):
        swa = bool(sizes["hybrid_layer_pattern"][i])
        at = params[attn_name(sizes, i)]
        has_sink = bool(sizes["add_swa_attention_sink_bias"] if swa
                        else sizes["add_full_attention_sink_bias"])
        h, k, v = attention(
            h, params[f"ln1_{i}"]["scale"], at["wq"], at["wk"], at["wv"],
            at["wo"], at["sink"] if has_sink else None,
            window=window if swa else 0,
            theta=float(sizes["swa_rope_theta"] if swa
                        else sizes["rope_theta"]),
            rope_dim=rope_dim,
            value_scale=float(sizes["attention_value_scale"]), eps=eps,
            rows=(a0, a1) if i == layers - 1 else (0, s))
        if window_rows is not None and swa:
            end = s if window_rows_end is None else int(window_rows_end)
            window_rows[attn_name(sizes, i)] = {
                "k": k[max(0, end - window):end],
                "v": v[max(0, end - window):end]}
        del k, v
        h = feed_forward(h, i, params, sizes, routing)
    return head(h[lo - a0:hi - a0], params["ln_f"]["scale"],
                params["lm_head"]["kernel"], eps=eps)
