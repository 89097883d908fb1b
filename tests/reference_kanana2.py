"""The plain reference of Kanana-2-30B-A3B's decoder (HF
`kakaocorp/kanana-2-30b-a3b-instruct-2601`, `model_type` deepseek_v3;
DeepSeek-V2 / -V3 reports: multi-head latent attention with no query
compression, the sigmoid router with a selection-only bias): the forward
pass, the mean next-token cross-entropy and its gradient in float32
`jax.numpy`, the EXPANDED form of attention only.

    h = embed[tokens]
    per layer, a = RMSNorm(h):
      q_i = [ (a W_Q)_i^nope ; RoPE((a W_Q)_i^rope) ]                  i = 1..H
      [cKV ; kR] = a W_DKV;  cKV = RMSNorm(cKV);  kR = RoPE(kR)  (one for all heads)
      k_{s,i} = [ cKV_s W_UK,i ; kR_s ]      v_{s,i} = cKV_s W_UV,i
      o_{t,i} = sum_{s <= t} softmax_{s <= t}(q_{t,i} . k_{s,i} * (d_n + d_R)^-0.5) v_{s,i}
      h += [o_1 .. o_H] W_O
      layer < first_k_dense_replace:  h += SwiGLU(RMSNorm(h))
      else, m = RMSNorm(h):
        s = sigmoid(m W_r);  s' = s + b   (b selects only);  T = top-k of s'
        g_e = scaling * s_e / sum_{T} s
        h += SwiGLU_shared(m) + sum_{e in T, e held} g_e SwiGLU_e(m)
    logits = RMSNorm(h) W_head
    loss = mean over positions of -log softmax(logits)[label]

RoPE is plain (inv_freq = theta^(-2i/d)), pairs rotate-half. The loss is the
cross-entropy alone: the configuration gives no coefficient for a balancing
loss, and `b` enters only a top-k's indices, so its gradient is zero.

No kernel, no cache, no absorbed form, no sorting of tokens by expert: K and V
are built per head from the latents, the causal softmax is over the whole key
row under a mask, the held experts are a loop under a dense gate matrix
(zero off each row's chosen experts). Matmuls run under
`jax.default_matmul_precision("highest")`. It draws nothing: it takes the
PROGRAM's weights by name (`kanana2_lm`'s: `attn_{i}` with `w_q`, `w_dkv`,
`kv_norm`, `w_uk`, `w_uv`, `wo`; `ffn_gate_{i}` ..; `moe_{i}` with the experts
the program holds, `sizes["experts_held"]` = (first, count)) and casts them to
float32. One sequence is ONE jitted call (forward, or loss and gradient): the
blocks of query rows, the experts and the expert layers themselves (stacked)
are `lax.map` / `lax.scan` steps inside it, each rematerialised for the
backward pass, so that a 4096-token sequence at the published widths fits
beside a training program's state, a batch costs two dispatches, not
thousands, and the compiler sees one expert layer, not four.
"""

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512       # rows of one block of attention logits


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, theta):
    """x (S, ..., d) at positions 0..S-1."""
    s, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    emb = emb.reshape((s,) + (1,) * (x.ndim - 2) + (d,))
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def swiglu(m, w_gate, w_up, w_down):
    g = m @ w_gate
    return ((g * jax.nn.sigmoid(g)) * (m @ w_up)) @ w_down


def attention(h, ln1, at, z):
    """The attention's output for h (S, D) (not yet added to h)."""
    c, dn = z["kv_lora_rank"], z["qk_nope_head_dim"]
    dr = z["qk_rope_head_dim"]
    s = h.shape[0]
    a = rms_norm(h, _f32(ln1), z["rms_norm_eps"])
    q = jnp.einsum("sd,dhk->shk", a, _f32(at["w_q"]))
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], z["rope_theta"])],
                        axis=-1)
    kv = a @ _f32(at["w_dkv"])
    ckv = rms_norm(kv[:, :c], _f32(at["kv_norm"]), z["rms_norm_eps"])
    kr = rope(kv[:, c:], z["rope_theta"])
    k = jnp.einsum("sc,chk->shk", ckv, _f32(at["w_uk"]))
    k = jnp.concatenate(
        [k, jnp.broadcast_to(kr[:, None, :], k.shape[:2] + (dr,))], axis=-1)
    v = jnp.einsum("sc,chv->shv", ckv, _f32(at["w_uv"]))
    scale = (dn + dr) ** -0.5
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    cols = jnp.arange(s)[None, None, :]

    @jax.checkpoint
    def block(args):
        qblk, q0 = args                                     # (qb, H, d)
        scores = jnp.einsum("qhk,shk->hqs", qblk, k) * scale
        rows = q0 + jnp.arange(qb)[None, :, None]
        scores = jnp.where(cols <= rows, scores, -jnp.inf)
        return jnp.einsum("hqs,shv->qhv", jax.nn.softmax(scores, axis=-1),
                          v)

    ctx = jax.lax.map(block, (q.reshape((s // qb, qb) + q.shape[1:]),
                              jnp.arange(0, s, qb)))
    return jnp.einsum("qhv,hvd->qd", ctx.reshape((s,) + ctx.shape[2:]),
                      _f32(at["wo"]))


def route(m, router, bias, z):
    """(dense gates (S, E), zero off each row's chosen experts; the chosen
    expert ids (S, k))."""
    s = jax.nn.sigmoid(m @ _f32(router))
    top_e = jax.lax.top_k(s + _f32(bias), z["num_experts_per_tok"])[1]
    g = jnp.take_along_axis(s, top_e, axis=-1)
    if z["norm_topk_prob"]:
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = g * z["routed_scaling_factor"]
    n = s.shape[0]
    return jnp.zeros_like(s).at[jnp.arange(n)[:, None], top_e].set(g), top_e


def expert_layer(m, moe, z):
    """(shared experts + this share's part of the routed sum, chosen ids)."""
    gates, top_e = route(m, moe["router"], moe["score_bias"], z)
    first, count = z["experts_held"]
    y = swiglu(m, _f32(moe["shared_gate"]), _f32(moe["shared_up"]),
               _f32(moe["shared_down"]))

    @jax.checkpoint
    def one(acc, e):
        w_gate, w_up, w_down, gate = e
        return acc + gate[:, None] * swiglu(m, w_gate, w_up, w_down), None

    held = gates[:, first:first + count].T                  # (count, S)
    y, _ = jax.lax.scan(one, y, (_f32(moe["w_gate"]), _f32(moe["w_up"]),
                                 _f32(moe["w_down"]), held))
    return y, top_e


def _sizes(sizes):
    """The keys this file reads, as a hashable tuple for jit."""
    first, count = sizes["experts_held"]
    return (("num_hidden_layers", int(sizes["num_hidden_layers"])),
            ("first_k_dense_replace", int(sizes["first_k_dense_replace"])),
            ("kv_lora_rank", int(sizes["kv_lora_rank"])),
            ("qk_nope_head_dim", int(sizes["qk_nope_head_dim"])),
            ("qk_rope_head_dim", int(sizes["qk_rope_head_dim"])),
            ("rope_theta", float(sizes["rope_theta"])),
            ("rms_norm_eps", float(sizes["rms_norm_eps"])),
            ("num_experts_per_tok", int(sizes["num_experts_per_tok"])),
            ("norm_topk_prob", bool(sizes["norm_topk_prob"])),
            ("routed_scaling_factor",
             float(sizes["routed_scaling_factor"])),
            ("experts_held", (int(first), int(count))))


def _logits(params, tokens, z):
    """(logits (S, V), chosen experts (expert layers, S, k))."""
    h = _f32(params["tok_embed"]["kernel"])[tokens]
    eps, dense = z["rms_norm_eps"], z["first_k_dense_replace"]

    def block(h, p):
        """One layer: its attention, then `p["ff"]` (a dense layer's three
        matrices, or an expert layer's weights)."""
        h = h + attention(h, p["ln1"], p["attn"], z)
        m = rms_norm(h, _f32(p["ln2"]), eps)
        if isinstance(p["ff"], tuple):
            return h + swiglu(m, *(_f32(w) for w in p["ff"])), None
        y, top_e = expert_layer(m, p["ff"], z)
        return h + y, top_e

    def weights(i):
        ff = tuple(params[f"ffn_{n}_{i}"]["kernel"]
                   for n in ("gate", "up", "down")) if i < dense \
            else params[f"moe_{i}"]
        return {"ln1": params[f"ln1_{i}"]["scale"],
                "attn": params[f"attn_{i}"],
                "ln2": params[f"ln2_{i}"]["scale"], "ff": ff}

    for i in range(dense):
        h, _ = jax.checkpoint(block)(h, weights(i))
    # the expert layers are one body over their stacked weights: the same
    # arithmetic layer by layer, compiled once
    stacked = jax.tree.map(lambda *a: jnp.stack([_f32(x) for x in a]),
                           *[weights(i)
                             for i in range(dense, z["num_hidden_layers"])])
    h, chosen = jax.lax.scan(jax.checkpoint(block), h, stacked)
    logits = rms_norm(h, _f32(params["ln_f"]["scale"]), eps) \
        @ _f32(params["lm_head"]["kernel"])
    return logits, chosen


def token_losses(logits, labels):
    """Cross-entropy of each position, float32."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnames=("z",))
def _forward(params, tokens, *, z):
    with jax.default_matmul_precision("highest"):
        return _logits(params, tokens, dict(z))


@functools.partial(jax.jit, static_argnames=("z",))
def _sequence_loss(params, tokens, labels, *, z):
    with jax.default_matmul_precision("highest"):
        logits, chosen = _logits(params, tokens, dict(z))
        return jnp.sum(token_losses(logits, labels)), chosen


@functools.partial(jax.jit, static_argnames=("z",))
def _sequence_loss_and_grads(subset, params, tokens, labels, *, z):
    def loss(sub):
        merged = {op: {**ws, **sub.get(op, {})} for op, ws in params.items()}
        logits, chosen = _logits(merged, tokens, dict(z))
        return jnp.sum(token_losses(logits, labels)), chosen

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss, has_aux=True)(subset)


def forward(params, tokens, sizes, routing=None, trace=None):
    """Logits (S, V) of one sequence `tokens` (S,) under the program's
    weights `params` ({op name: {weight name: array}}, kanana2_lm's names).
    `sizes` holds the configuration's keys. `trace`, if a dict, receives the
    chosen experts (`experts`: (expert layers, S, k)); `routing` is accepted
    for the harness's call and left empty."""
    logits, chosen = _forward(params, jnp.asarray(tokens), z=_sizes(sizes))
    if trace is not None:
        trace["experts"] = chosen
    return logits


def mean_loss(params, x, y, sizes, trace=None):
    """Mean next-token cross-entropy over a batch x (B, S), y (B, S), one
    sequence at a time; `trace["experts"]` lists each sequence's choices."""
    total, count = 0.0, 0
    for tokens, labels in zip(x, y):
        loss, chosen = _sequence_loss(params, jnp.asarray(tokens),
                                      jnp.asarray(labels), z=_sizes(sizes))
        total += float(loss)
        count += int(labels.size)
        if trace is not None:
            trace.setdefault("experts", []).append(chosen)
    return total / count


def mean_loss_and_grads(params, x, y, wrt, sizes, trace=None):
    """Mean next-token cross-entropy over a batch x (B, S), y (B, S) and its
    gradient with respect to the weights named in `wrt` ([(op, weight)]),
    one sequence at a time, plain reverse mode."""
    subset = {}
    for op, w in wrt:
        subset.setdefault(op, {})[w] = _f32(params[op][w])
    total, grads, count = 0.0, None, 0
    for tokens, labels in zip(x, y):
        (loss, chosen), g = _sequence_loss_and_grads(
            subset, params, jnp.asarray(tokens), jnp.asarray(labels),
            z=_sizes(sizes))
        total += float(loss)
        count += int(labels.size)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        if trace is not None:
            trace.setdefault("experts", []).append(chosen)
    return total / count, jax.tree.map(lambda a: a / count, grads)
