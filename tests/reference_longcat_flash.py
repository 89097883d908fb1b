"""The plain reference of LongCat-Flash's decoder (HF
`meituan-longcat/LongCat-Flash-Chat` config.json; the LongCat-Flash technical
report and the released modelling code for the two LoRA scales and the router):
the forward pass in float32 `jax.numpy`, the EXPANDED form of attention only.

    h = embed[tokens]
    per layer l (a DOUBLE block; every norm an RMSNorm):
      a0 = N_in0(h);   h = h + MLA_0(a0)
      m0 = N_post0(h); s = MoE(m0);            h = h + FFN_0(m0)      # s is NOT added here
      a1 = N_in1(h);   h = h + MLA_1(a1)
      m1 = N_post1(h); h = h + FFN_1(m1) + s                          # the shortcut lands here
    logits = N_f(h) W_head

    FFN_j(x) = (silu(x W_g) * (x W_u)) W_d
    MLA(a):  cQ = RMSNorm(a W_DQ);  [q^nope_i ; q^rope_i] = sq * (cQ W_UQ)_i;  q^rope_i = RoPE(q^rope_i)
             [cKV ; kR] = a W_DKV;  cKV = skv * RMSNorm(cKV);  kR = RoPE(kR)   (not scaled; one for all heads)
             k_{s,i} = [cKV_s W_UK,i ; kR_s];  v_{s,i} = cKV_s W_UV,i
             o_{t,i} = sum_{s<=t} softmax_s(q_{t,i} . k_{s,i} * (d_n + d_R)^-0.5) v_{s,i};  MLA = [o_1..o_H] W_O
             sq = (hidden / q_lora_rank)^0.5, skv = (hidden / kv_lora_rank)^0.5 where the config's flags are set
    MoE(m):  p = softmax_{E+Z}(m W_r);  T = top-k of p + b   (b selects only)
             g_e = c * p_e for e in T     (never from p + b, never renormalised)
             MoE(m) = sum_{e in T, e < E, e held} g_e SwiGLU_e(m) + (sum_{e in T, e >= E} g_e) * m

RoPE is plain (inv_freq = theta^(-2i/d)), pairs rotate-half (a departure from
the checkpoint's interleaved pairs: a fixed permutation of columns, invisible
under seeded weights). `sizes["experts_held"]` = (first, count) is the share of
the E experts whose terms this reference computes, the share the program was
given; the identity term is whole (it is computed where the token lives,
once). What the absent experts would add is left out.

No kernel, no cache, no absorbed form, no batching, no sorting of tokens by
expert: K and V are built per head from the latents, the causal softmax is
over the key row under a mask, the held experts are a loop under a dense gate
matrix (zero off each row's chosen experts). Matmuls run under
`jax.default_matmul_precision("highest")`. It draws nothing: it takes the
PROGRAM's weights by name (`longcat_flash_lm`'s: `ln_in_{l}_{j}`,
`attn_{l}_{j}`, `ln_post_{l}_{j}`, `ffn_{l}_{j}` with `w_in` = [W_g | W_u] and
`w_out`, `moe_{l}` with the experts the program holds) and casts them to
float32 one matrix at a time. Work is cut into blocks (query rows, heads, rows
of the feed-forwards) so that a 33 k-token sequence at width 6144 fits beside
the weights, and a block of query rows is given the keys up to the next
multiple of KEY_BLOCK only (the rest are causally dead); no result depends on
the blocks.

`trace`, if a dict, receives per layer the chosen router columns (`experts`:
{layer: [(rows, k)]}); `program_disagreement` compares them with what the
program's own weights and arithmetic choose. `rows=(lo, hi)` returns the
logits of those rows only: the LAST layer then computes its expert layer, its
second attention's queries and its second feed-forward for the blocks of rows
that hold them only (everything earlier feeds later keys, so it runs whole).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64        # rows of one attention block
KEY_BLOCK = 16384       # a block of rows sees keys up to the next multiple
HEAD_BLOCK = 16         # heads whose K and V exist at a time
ROW_BLOCK = 4096        # rows of one feed-forward block


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rope_tables(n, dim, theta):
    """cos, sin (n, dim) of positions 0..n-1."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freqs = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    return jnp.asarray(np.cos(emb), jnp.float32), \
        jnp.asarray(np.sin(emb), jnp.float32)


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, cos, sin):
    """x (S, ..., d) with cos, sin (S, d)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return x * cos.reshape(shape) + rotate_half(x) * sin.reshape(shape)


def lora_scales(sizes):
    """(sq, skv): the factors on the projected query and the normed latent."""
    d = float(sizes["hidden_size"])
    sq = (d / sizes["q_lora_rank"]) ** 0.5 \
        if sizes.get("mla_scale_q_lora") else 1.0
    skv = (d / sizes["kv_lora_rank"]) ** 0.5 \
        if sizes.get("mla_scale_kv_lora") else 1.0
    return sq, skv


@functools.partial(jax.jit, static_argnames=("eps", "c", "skv"))
def latents(h, ln, w_dq, q_norm, w_dkv, kv_norm, cos, sin, *, eps, c, skv):
    """What every position contributes: cQ, the scaled cKV, kR."""
    with jax.default_matmul_precision("highest"):
        a = rms_norm(h, _f32(ln), eps)
        cq = rms_norm(a @ _f32(w_dq), _f32(q_norm), eps)
        kv = a @ _f32(w_dkv)
        ckv = skv * rms_norm(kv[:, :c], _f32(kv_norm), eps)
        return cq, ckv, rope(kv[:, c:], cos, sin)


@jax.jit
def expand_heads(ckv, kr, w_uk, w_uv):
    """K (S, Hb, d_n + d_R) and V (S, Hb, d_v) of one block of heads."""
    with jax.default_matmul_precision("highest"):
        k = jnp.einsum("sc,chk->shk", ckv, _f32(w_uk))
        k = jnp.concatenate(
            [k, jnp.broadcast_to(kr[:, None, :], k.shape[:2] + kr.shape[1:])],
            axis=-1)
        return k, jnp.einsum("sc,chv->shv", ckv, _f32(w_uv))


@functools.partial(jax.jit, static_argnames=(
    "scale", "sq", "block", "blocks", "keys", "d_nope"), donate_argnums=(0,))
def attend_rows(acc, cq, k, v, w_uq, wo, cos, sin, q_lo, *, scale, sq, block,
                blocks, keys, d_nope):
    """acc[q_lo : q_lo + blocks * block] += those rows of one head block's
    causal attention output through its rows of W_O, a block of rows at a
    time, against the first `keys` keys."""
    with jax.default_matmul_precision("highest"):
        k, v = k[:keys], v[:keys]
        w_uq, wo = _f32(w_uq), _f32(wo)
        rows = jnp.arange(block)[:, None]
        cols = jnp.arange(keys)[None, :]

        def one(j, acc):
            q0 = q_lo + j * block
            cqb = jax.lax.dynamic_slice_in_dim(cq, q0, block)
            cb = jax.lax.dynamic_slice_in_dim(cos, q0, block)
            sb = jax.lax.dynamic_slice_in_dim(sin, q0, block)
            q = sq * jnp.einsum("qr,rhk->qhk", cqb, w_uq)
            q = jnp.concatenate([q[..., :d_nope],
                                 rope(q[..., d_nope:], cb, sb)], axis=-1)
            logits = jnp.einsum("qhk,shk->hqs", q, k) * scale
            logits = jnp.where((cols <= q0 + rows)[None], logits, -jnp.inf)
            ctx = jnp.einsum("hqs,shv->qhv",
                             jax.nn.softmax(logits, axis=-1), v)
            out = jnp.einsum("qhv,hvd->qd", ctx, wo)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, jax.lax.dynamic_slice_in_dim(acc, q0, block) + out,
                q0, 0)

        return jax.lax.fori_loop(0, blocks, one, acc)


def attention(h, ln, at, sizes, cos, sin, need):
    """h + the attention output, for the rows need = (a0, a1) (whole blocks
    of QUERY_BLOCK); the other rows come back as they were. `h` is given up
    (its buffer becomes the result's)."""
    eps = float(sizes["rms_norm_eps"])
    s = h.shape[0]
    sq, skv = lora_scales(sizes)
    cq, ckv, kr = latents(
        h, ln, at["w_dq"], at["q_norm"], at["w_dkv"], at["kv_norm"], cos,
        sin, eps=eps, c=int(sizes["kv_lora_rank"]), skv=skv)
    qb = math.gcd(s, QUERY_BLOCK)
    # causality: the rows of one span see no key past its end, so they share
    # one call (and one compiled shape)
    span = KEY_BLOCK // qb * qb
    spans = []
    for lo in range(0, s, span):
        q_lo, q_hi = max(need[0], lo), min(need[1], lo + span)
        if q_lo < q_hi:
            spans.append((q_lo, q_hi, min(s, lo + span)))
    heads = at["w_uq"].shape[1]
    hb = math.gcd(heads, HEAD_BLOCK)
    d_nope = int(sizes["qk_nope_head_dim"])
    scale = (d_nope + int(sizes["qk_rope_head_dim"])) ** -0.5
    acc = h
    for h0 in range(0, heads, hb):
        k, v = expand_heads(ckv, kr, at["w_uk"][:, h0:h0 + hb],
                            at["w_uv"][:, h0:h0 + hb])
        w_uq, wo = at["w_uq"][:, h0:h0 + hb], at["wo"][h0:h0 + hb]
        for q_lo, q_hi, keys in spans:
            acc = attend_rows(acc, cq, k, v, w_uq, wo, cos, sin, q_lo,
                              scale=scale, sq=sq, block=qb,
                              blocks=(q_hi - q_lo) // qb, keys=keys,
                              d_nope=d_nope)
    return acc


def _row_blocks(s):
    rb = min(s, ROW_BLOCK)
    return [(r0, min(rb, s - r0)) for r0 in range(0, s, rb)]


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


@functools.partial(jax.jit, donate_argnums=(0,))
def scaled_into(acc, m, factor, r0):
    """acc[r0 : r0 + rows] += factor * m for the block's rows."""
    n = m.shape[0]
    return jax.lax.dynamic_update_slice_in_dim(
        acc, jax.lax.dynamic_slice_in_dim(acc, r0, n)
        + factor[:, None] * m, r0, 0)


@functools.partial(jax.jit, static_argnames=("top_k", "scaling"))
def route(m, router, bias, *, top_k, scaling):
    """(dense gates (S, E + Z), zero off each row's chosen columns; the
    chosen columns (S, k))."""
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(m @ _f32(router), axis=-1)
        top_e = jax.lax.top_k(p + _f32(bias), top_k)[1]
        g = scaling * jnp.take_along_axis(p, top_e, axis=-1)
        n = p.shape[0]
        return jnp.zeros_like(p).at[jnp.arange(n)[:, None], top_e].set(g), \
            top_e


def expert_layer(m, moe, sizes, trace=None, layer=None, identity=True):
    """MoE(m) (n, D) for normed rows m (n, D): the held experts' terms and,
    with `identity`, the identity term."""
    real = int(sizes["router_experts"])
    first = int(sizes["experts_held"][0])
    out = jnp.zeros(m.shape, jnp.float32)
    for r0, n in _row_blocks(m.shape[0]):
        mb = m[r0:r0 + n]
        gates, top_e = route(mb, moe["router"], moe["score_bias"],
                             top_k=int(sizes["moe_topk"]),
                             scaling=float(sizes["routed_scaling_factor"]))
        if trace is not None:
            trace.setdefault("experts", {}).setdefault(layer, []).append(
                top_e)
        for e in range(moe["w_gate"].shape[0]):
            out = swiglu_into(out, mb, gates[:, first + e], moe["w_gate"][e],
                              moe["w_up"][e], moe["w_down"][e], r0)
        if identity:
            out = scaled_into(out, mb, jnp.sum(gates[:, real:], axis=-1), r0)
    return out


def dense_ffn_into(acc, m, ffn, r0, width):
    w_in = ffn["w_in"]
    ones = jnp.ones((m.shape[0],), jnp.float32)
    return swiglu_into(acc, m, ones, w_in[:, :width], w_in[:, width:],
                       ffn["w_out"], r0)


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, ln_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(ln_f), eps) @ _f32(w_head)


def forward(params, tokens, sizes, routing=None, trace=None, rows=None):
    """Logits (S, V), or of rows lo .. hi - 1 with `rows=(lo, hi)`, of one
    sequence `tokens` (S,) under the program's weights `params` ({op name:
    {weight name: array}}, longcat_flash_lm's names). `sizes` holds the
    configuration's keys; `routing` is accepted for the harness's call and
    left empty."""
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    eps = float(sizes["rms_norm_eps"])
    width = int(sizes["ffn_hidden_size"])
    cos, sin = rope_tables(s, int(sizes["qk_rope_head_dim"]),
                           float(sizes["rope_theta"]))
    h = _f32(params["tok_embed"]["kernel"][tokens])
    lo, hi = rows if rows is not None else (0, s)
    # the whole blocks of query rows that hold rows lo .. hi - 1: all the
    # last layer's tail has to compute
    qb = math.gcd(s, QUERY_BLOCK)
    a0, a1 = lo // qb * qb, -(-hi // qb) * qb
    layers = int(sizes["num_layers"])
    for l in range(layers):
        last = l == layers - 1
        n0, n1 = (a0, a1) if last else (0, s)
        shortcut = None
        for j in range(2):
            tail = last and j == 1
            h = attention(h, params[f"ln_in_{l}_{j}"]["scale"],
                          params[f"attn_{l}_{j}"], sizes, cos, sin,
                          (n0, n1) if tail else (0, s))
            if tail:
                h = h[n0:n1]
            post = params[f"ln_post_{l}_{j}"]["scale"]
            if j == 0:
                shortcut = expert_layer(
                    normed(h[n0:n1], post, eps=eps), params[f"moe_{l}"],
                    sizes, trace, l)
            acc = jnp.copy(h)   # `swiglu_into` donates acc, h is still read
            for r0, n in _row_blocks(h.shape[0]):
                acc = dense_ffn_into(
                    acc, normed(h[r0:r0 + n], post, eps=eps),
                    params[f"ffn_{l}_{j}"], r0, width)
            h = acc
        h = h + shortcut
    return head(h[lo - a0:hi - a0], params["ln_f"]["scale"],
                params["lm_head"]["kernel"], eps=eps)


def program_disagreement(ff, tokens, sizes, trace):
    """Share of (token, layer) rows where the program's own arithmetic (its
    `ln_post_{l}_0` outputs through its own router, in its compute dtype)
    picks another set of router columns than this reference did in `trace`.
    Logged by the check, never judged: a near-tie at the cut flips on
    rounding, and what that does to the logits is inside the check's
    error."""
    layers = int(sizes["num_layers"])
    post = [ff.get_op_by_name(f"ln_post_{l}_0") for l in range(layers)]
    fwd = jax.jit(ff.executor.make_forward([op.outputs[0] for op in post]))
    toks = jnp.asarray(tokens)[None]
    outs = fwd(ff.params, ff.bn_state,
               ff.executor.shard_batch({"input": np.asarray(toks)}))
    flips = rows = 0
    for l in range(layers):
        theirs = np.sort(np.concatenate(
            [np.asarray(t) for t in trace["experts"][l]]), axis=-1)
        op = ff.get_op_by_name(f"moe_{l}")
        # the last layer's reference rows may be a slice; compare what both
        # hold (check (a) asks for every row)
        m = outs[l][0][:theirs.shape[0]]
        mine = np.sort(np.asarray(
            op._route(dict(ff.params[f"moe_{l}"]), m)[2]), axis=-1)
        flips += int((mine != theirs).any(axis=-1).sum())
        rows += theirs.shape[0]
    return {"expert_set_differs": flips / max(1, rows)}
