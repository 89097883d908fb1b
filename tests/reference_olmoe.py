"""The plain reference of OLMoE (Muennighoff et al., arXiv:2409.02060; HF
`modeling_olmoe.py`): the forward pass in float32 `jax.numpy`.

    h = embed[tokens]
    per layer:
      a = RMSNorm(h);  q = RMSNorm_q(a Wq),  k = RMSNorm_k(a Wk),  v = a Wv
          (QK-norm: one learned scale each over the WHOLE projection, all
           heads together, before the split into heads and before rotary;
           rotary as the source applies it: rotate_half, inv_freq =
           theta^(-2i/d))
      h += causal_softmax(q k^T / sqrt(d)) v Wo
      m = RMSNorm(h);  p = softmax_f32(m Wr) over all experts
      S = the num_experts_per_tok largest of p
      h += sum_{e in S} p_e * ((silu(m Wgate_e) * (m Wup_e)) Wdown_e)
          (p is NOT renormalised over S unless norm_topk_prob; no capacity,
           no dropped token)
    logits = RMSNorm(h) Whead

No kernel, no cache, no sort and no grouped matmul: the experts are a loop
over all of them, each applied to every row and weighted by a dense (S, E)
matrix of gates that is zero off the top-k. Matmuls run under
`jax.default_matmul_precision("highest")` (on a TPU a float32 matmul
otherwise runs in bf16 passes). It takes the PROGRAM's weights by name
(`olmoe_lm`'s: `attn_{i}` with `q_norm`/`k_norm`, `moe_{i}` with `router`,
`w_gate`, `w_up`, `w_down`) and casts them to float32 one layer, and inside a
layer one expert, at a time.

Departures from the source: none in the mathematics. The source holds each
expert's matrices as separate (out, in) Linear weights; the program stacks
them as (E, in, out). `clip_qkv` is null in the published configuration and
is not implemented.

Queries are processed in blocks of QUERY_BLOCK rows so that the score matrix
of a long sequence stays small; the result does not depend on it.
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


@functools.partial(jax.jit, static_argnames=("theta", "eps"))
def attention(h, ln1, wq, wk, wv, wo, q_norm, k_norm, *, theta, eps):
    """h + attention(RMSNorm(h)) on h (S, D); weights in the program's
    layout: wq (D, H, d), wk/wv (D, KVH, d), wo (H, d, D), q_norm (H*d,),
    k_norm (KVH*d,)."""
    with jax.default_matmul_precision("highest"):
        ln1, wq, wk, wv, wo, q_norm, k_norm = map(
            _f32, (ln1, wq, wk, wv, wo, q_norm, k_norm))
        s, dm = h.shape
        heads, kv_heads, d = wq.shape[1], wk.shape[1], wq.shape[2]
        a = rms_norm(h, ln1, eps)
        q = rms_norm(a @ wq.reshape(dm, heads * d), q_norm, eps)
        k = rms_norm(a @ wk.reshape(dm, kv_heads * d), k_norm, eps)
        q = rotary(q.reshape(s, heads, d), theta)
        k = rotary(k.reshape(s, kv_heads, d), theta)
        v = jnp.einsum("sd,dhk->shk", a, wv)
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


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize", "eps"))
def route(h, ln2, router, *, top_k, renormalize, eps):
    """(m, dense gates (S, E) that are zero off each row's top-k, the top-k
    expert ids (S, k))."""
    with jax.default_matmul_precision("highest"):
        m = rms_norm(h, _f32(ln2), eps)
        p = jax.nn.softmax(m @ _f32(router), axis=-1)
        top_p, top_e = jax.lax.top_k(p, top_k)
        if renormalize:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        rows = jnp.arange(p.shape[0])[:, None]
        return m, jnp.zeros_like(p).at[rows, top_e].set(top_p), top_e


@jax.jit
def expert(m, gate_col, w_gate, w_up, w_down):
    """One expert on EVERY row, weighted by its column of the dense gates
    (zero for a row that did not choose it)."""
    with jax.default_matmul_precision("highest"):
        g = m @ _f32(w_gate)
        return gate_col[:, None] * (((g * jax.nn.sigmoid(g))
                                     * (m @ _f32(w_up))) @ _f32(w_down))


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, ln_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(ln_f), eps) @ _f32(w_head)


def forward(params, tokens, sizes, routing=None):
    """Logits (S, V) of one sequence `tokens` (S,) under the program's
    weights `params` ({op name: {weight name: array}}, olmoe_lm's names).
    `sizes` holds the configuration's keys (`num_hidden_layers`,
    `rope_theta`, `rms_norm_eps`, `num_experts_per_tok`, `norm_topk_prob`).
    `routing`, if a list, receives each layer's chosen expert ids (S, k)."""
    eps, theta = float(sizes["rms_norm_eps"]), float(sizes["rope_theta"])
    h = _f32(params["tok_embed"]["kernel"][jnp.asarray(tokens)])
    for i in range(int(sizes["num_hidden_layers"])):
        at, moe = params[f"attn_{i}"], params[f"moe_{i}"]
        h = attention(h, params[f"ln1_{i}"]["scale"], at["wq"], at["wk"],
                      at["wv"], at["wo"], at["q_norm"], at["k_norm"],
                      theta=theta, eps=eps)
        m, gates, top_e = route(
            h, params[f"ln2_{i}"]["scale"], moe["router"],
            top_k=int(sizes["num_experts_per_tok"]),
            renormalize=bool(sizes["norm_topk_prob"]), eps=eps)
        if routing is not None:
            routing.append(top_e)
        for e in range(moe["router"].shape[1]):
            h = h + expert(m, gates[:, e], moe["w_gate"][e], moe["w_up"][e],
                           moe["w_down"][e])
    return head(h, params["ln_f"]["scale"], params["lm_head"]["kernel"],
                eps=eps)
