"""The plain reference of DeepSeek-V3.2's decoder (DeepSeek-V2 / -V3 reports:
multi-head latent attention and the sigmoid router; DeepSeek-V3.2-Exp report:
the lightning indexer; HF `deepseek-ai/DeepSeek-V3.2`): the forward pass in
float32 `jax.numpy`, the EXPANDED form of attention only.

    h = embed[tokens]
    per layer, a = RMSNorm(h):
      cQ = RMSNorm(a W_DQ)
      q_i = [ (cQ W_UQ)_i^nope ; RoPE((cQ W_UQ)_i^rope) ]
      [cKV ; kR] = a W_DKV;  cKV = RMSNorm(cKV);  kR = RoPE(kR)  (one for all heads)
      k_{s,i} = [ cKV_s W_UK,i ; kR_s ]      v_{s,i} = cKV_s W_UV,i
      qI_j = (cQ W_IQ)_j,  kI = LayerNorm(a W_IK)   (RoPE on their first d_R dims)
      w = a W_Iw * J^-0.5 * d_I^-0.5
      I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)                    s <= t
      S_t = `jax.lax.top_k` of row t of I over the whole row (index_topk; ties
            to the lower position; all s <= t while t < index_topk)
      o_{t,i} = sum_{s in S_t} softmax_{S_t}(q_{t,i} . k_{s,i} * (d_n + d_R)^-0.5 * m^2) v_{s,i}
      h += [o_1 .. o_H] W_O                      m = 0.1 mscale_all_dim ln(factor) + 1
      layer < first_k_dense_replace:  h += SwiGLU(RMSNorm(h))
      else, m = RMSNorm(h):
        s = sigmoid(m W_r);  s' = s + b   (b selects only)
        a group's score = its two largest s' summed; keep topk_group groups;
        T = top-k of s' inside them;  g_e = scaling * s_e / sum_{T} s
        h += SwiGLU_shared(m) + sum_{e in T, e held} g_e SwiGLU_e(m)
    logits = RMSNorm(h) W_head

RoPE is YaRN's as HF `DeepseekV3YarnRotaryEmbedding` computes it (frequencies
interpolated over the correction range; cos and sin times mscale /
mscale_all_dim's ratio), pairs rotate-half.

No kernel, no cache, no absorbed form, no batching: K and V are built per head
from the latents, the selection is a `top_k` over the full score row scattered
into a mask (once per block of rows), the held experts are a loop under a dense gate matrix. Matmuls
run under `jax.default_matmul_precision("highest")`. It draws nothing: it
takes the PROGRAM's weights by name (`deepseek_v32_lm`'s: `attn_{i}`,
`ffn_gate_{i}` .., `moe_{i}` with the experts the program holds, `sizes
["experts_held"]` = (first, count)) and casts them to float32 one matrix at a
time. Work is cut into blocks (query rows, heads, rows of the feed-forward)
so that a 33 k-token sequence fits beside the weights, and a block of query
rows is given the keys up to the next multiple of KEY_BLOCK only (the rest are
causally dead); no result depends on the blocks. The loop over the blocks of
query rows that share a key bound runs inside one jitted call (`lax.map`,
`lax.fori_loop`): with one dispatch a block the host, not the chip, set the
pace of a 33 k-token pass (20 000 dispatches of 3 ms).

`trace`, if a dict, receives per layer the selected positions
(`selected`: (S, index_topk) int32, valid where <= the row) and the chosen
experts (`experts`: (S, k)); `program_disagreement` compares them with what
the program's own weights and arithmetic choose. `routing` is accepted for
the harness's call and left empty (its reader assumes a softmax router).
`rows=(lo, hi)` returns the logits of those rows only, and the LAST layer then
computes its queries and its feed-forward for the blocks of rows that hold
them only (every earlier layer feeds the later layers' keys, so it runs
whole); `trace` then lists the last layer's choices for those blocks alone.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64        # rows of one index-score / attention block
KEY_BLOCK = 4096        # a block of rows sees keys up to the next multiple
HEAD_BLOCK = 16         # heads whose K and V exist at a time
ROW_BLOCK = 4096        # rows of one feed-forward block


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def yarn_tables(n, dim, theta, sc):
    """cos, sin (n, dim) of positions 0..n-1."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    amp = 1.0
    if sc:
        factor = float(sc["factor"])
        orig = float(sc["original_max_position_embeddings"])

        def corr(rot):
            return dim * math.log(orig / (rot * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(corr(float(sc["beta_fast"]))), 0)
        high = min(math.ceil(corr(float(sc["beta_slow"]))), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        mask = 1.0 - ramp
        inv = inv / factor * (1 - mask) + inv * mask

        def ms(m):
            return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

        amp = ms(float(sc.get("mscale", 1.0))) \
            / ms(float(sc.get("mscale_all_dim", 0.0)))
    freqs = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    return (jnp.asarray(np.cos(emb) * amp, jnp.float32),
            jnp.asarray(np.sin(emb) * amp, jnp.float32))


def softmax_scale(sizes):
    sc = sizes.get("rope_scaling")
    d = int(sizes["qk_nope_head_dim"]) + int(sizes["qk_rope_head_dim"])
    m = 1.0
    if sc and float(sc["factor"]) > 1:
        m = 0.1 * float(sc.get("mscale_all_dim", 0.0)) \
            * math.log(float(sc["factor"])) + 1.0
    return d ** -0.5 * m * m


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def rope(x, cos, sin):
    """x (S, ..., d) with cos, sin (S, d)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    return x * cos.reshape(shape) + rotate_half(x) * sin.reshape(shape)


@functools.partial(jax.jit, static_argnames=("eps", "c"))
def latents(h, ln1, w_dq, q_norm, w_dkv, kv_norm, w_ik, ik_s, ik_b, w_iw,
            cos, sin, *, eps, c):
    """What every position contributes: a, cQ, cKV, kR, kI, w."""
    with jax.default_matmul_precision("highest"):
        a = rms_norm(h, _f32(ln1), eps)
        cq = rms_norm(a @ _f32(w_dq), _f32(q_norm), eps)
        kv = a @ _f32(w_dkv)
        ckv = rms_norm(kv[:, :c], _f32(kv_norm), eps)
        kr = rope(kv[:, c:], cos, sin)
        ki = layer_norm(a @ _f32(w_ik), _f32(ik_s), _f32(ik_b), eps)
        r = cos.shape[-1]
        ki = jnp.concatenate([rope(ki[:, :r], cos, sin), ki[:, r:]], axis=-1)
        j, di = w_iw.shape[1], w_ik.shape[1]
        w = (a @ _f32(w_iw)) * (j ** -0.5 * di ** -0.5)
        return cq, ckv, kr, ki, w


@functools.partial(jax.jit,
                   static_argnames=("top_k", "block", "blocks", "keys"))
def select_rows(cq, w, ki, w_iq, cos, sin, q_lo, *, top_k, block, blocks,
                keys):
    """Query rows q_lo .. q_lo + blocks * block, a block at a time, against
    the first `keys` keys: the selected positions (rows, top_k) and the same
    as a mask (rows, keys)."""
    with jax.default_matmul_precision("highest"):
        ki = ki[:keys]
        w_iq = _f32(w_iq)
        r = cos.shape[-1]
        rows = jnp.arange(block)[:, None]

        def one(j):
            q0 = q_lo + j * block
            cqb = jax.lax.dynamic_slice_in_dim(cq, q0, block)
            cb = jax.lax.dynamic_slice_in_dim(cos, q0, block)
            sb = jax.lax.dynamic_slice_in_dim(sin, q0, block)
            qi = jnp.einsum("qr,rjk->qjk", cqb, w_iq)
            qi = jnp.concatenate([rope(qi[..., :r], cb, sb), qi[..., r:]],
                                 axis=-1)
            wb = jax.lax.dynamic_slice_in_dim(w, q0, block)
            score = jnp.einsum("qjs,qj->qs", jax.nn.relu(
                jnp.einsum("qjk,sk->qjs", qi, ki)), wb)
            live = jnp.arange(keys)[None, :] <= q0 + rows
            idx = jax.lax.top_k(jnp.where(live, score, -jnp.inf), top_k)[1]
            # a row with fewer live keys than top_k also lists dead positions
            return idx, jnp.zeros((block, keys), bool).at[rows, idx].set(
                True) & live

        idx, mask = jax.lax.map(one, jnp.arange(blocks))
        return (idx.reshape(blocks * block, top_k),
                mask.reshape(blocks * block, keys))


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
    "scale", "block", "blocks", "keys", "d_nope"), donate_argnums=(0,))
def attend_rows(acc, cq, chosen, k, v, w_uq, wo, cos, sin, q_lo, *, scale,
                block, blocks, keys, d_nope):
    """acc[q_lo : q_lo + blocks * block] += those rows of one head block's
    attention output through its rows of W_O, a block of rows at a time;
    `chosen` (blocks * block, keys) is the selection of those rows among
    the first `keys` keys."""
    with jax.default_matmul_precision("highest"):
        k, v = k[:keys], v[:keys]
        w_uq, wo = _f32(w_uq), _f32(wo)

        def one(j, acc):
            q0 = q_lo + j * block
            cqb = jax.lax.dynamic_slice_in_dim(cq, q0, block)
            cb = jax.lax.dynamic_slice_in_dim(cos, q0, block)
            sb = jax.lax.dynamic_slice_in_dim(sin, q0, block)
            q = jnp.einsum("qr,rhk->qhk", cqb, w_uq)
            q = jnp.concatenate([q[..., :d_nope],
                                 rope(q[..., d_nope:], cb, sb)], axis=-1)
            mask = jax.lax.dynamic_slice_in_dim(chosen, j * block, block)
            logits = jnp.einsum("qhk,shk->hqs", q, k) * scale
            logits = jnp.where(mask[None], logits, -jnp.inf)
            ctx = jnp.einsum("hqs,shv->qhv",
                             jax.nn.softmax(logits, axis=-1), v)
            out = jnp.einsum("qhv,hvd->qd", ctx, wo)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, jax.lax.dynamic_slice_in_dim(acc, q0, block) + out,
                q0, 0)

        return jax.lax.fori_loop(0, blocks, one, acc)


def attention(h, ln1, at, sizes, cos, sin, trace, need):
    """h + the attention output, for the rows need = (a0, a1) (whole blocks
    of QUERY_BLOCK); the other rows come back as they were."""
    eps = float(sizes["rms_norm_eps"])
    c = int(sizes["kv_lora_rank"])
    s = h.shape[0]
    cq, ckv, kr, ki, w = latents(
        h, ln1, at["w_dq"], at["q_norm"], at["w_dkv"], at["kv_norm"],
        at["w_ik"], at["ik_norm_scale"], at["ik_norm_bias"], at["w_iw"],
        cos, sin, eps=eps, c=c)
    qb = math.gcd(s, QUERY_BLOCK)
    top_k = min(int(sizes["index_topk"]), s)
    # causality: the rows of one span of `span` rows see no key past its
    # end, so they share one call (and one compiled shape)
    span = max(KEY_BLOCK, top_k) // qb * qb
    spans = []
    for lo in range(0, s, span):
        q_lo, q_hi = max(need[0], lo), min(need[1], lo + span)
        if q_lo < q_hi:
            spans.append((q_lo, q_hi, min(s, lo + span)))
    picked, chosen = zip(*[
        select_rows(cq, w, ki, at["w_iq"], cos, sin, q_lo, top_k=top_k,
                    block=qb, blocks=(q_hi - q_lo) // qb, keys=keys)
        for q_lo, q_hi, keys in spans])
    if trace is not None:
        trace.setdefault("selected", []).append(jnp.concatenate(picked))
    del picked
    heads = at["w_uq"].shape[1]
    hb = math.gcd(heads, HEAD_BLOCK)
    acc = h
    for h0 in range(0, heads, hb):
        k, v = expand_heads(ckv, kr, at["w_uk"][:, h0:h0 + hb],
                            at["w_uv"][:, h0:h0 + hb])
        w_uq, wo = at["w_uq"][:, h0:h0 + hb], at["wo"][h0:h0 + hb]
        for (q_lo, q_hi, keys), mask in zip(spans, chosen):
            acc = attend_rows(
                acc, cq, mask, k, v, w_uq, wo, cos, sin, q_lo,
                scale=softmax_scale(sizes), block=qb,
                blocks=(q_hi - q_lo) // qb, keys=keys,
                d_nope=int(sizes["qk_nope_head_dim"]))
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


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "scaling", "renormalize"))
def route(m, router, bias, *, top_k, n_group, topk_group, scaling,
          renormalize):
    """(dense gates (S, E), zero off each row's chosen experts; the chosen
    expert ids (S, k))."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(m @ _f32(router))
        sel = s + _f32(bias)
        n, e = s.shape
        if n_group > 1:
            grouped = sel.reshape(n, n_group, e // n_group)
            gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            kept = jax.lax.top_k(gscore, topk_group)[1]
            gmask = jnp.zeros((n, n_group), bool).at[
                jnp.arange(n)[:, None], kept].set(True)
            sel = jnp.where(jnp.repeat(gmask, e // n_group, axis=1), sel,
                            -jnp.inf)
        top_e = jax.lax.top_k(sel, top_k)[1]
        g = jnp.take_along_axis(s, top_e, axis=-1)
        if renormalize:
            g = g / jnp.sum(g, axis=-1, keepdims=True)
        g = g * scaling
        return jnp.zeros_like(s).at[jnp.arange(n)[:, None], top_e].set(g), \
            top_e


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h, ln_f, w_head, *, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h, _f32(ln_f), eps) @ _f32(w_head)


def feed_forward(h, i, params, sizes, trace):
    """h + layer i's feed-forward of h (n, D), in blocks of ROW_BLOCK rows."""
    eps = float(sizes["rms_norm_eps"])
    n_rows = h.shape[0]
    ln2 = params[f"ln2_{i}"]["scale"]
    ones = jnp.ones((min(n_rows, ROW_BLOCK),), jnp.float32)
    acc = jnp.copy(h)   # `swiglu_into` donates acc, h is still read
    if i < int(sizes["first_k_dense_replace"]):
        for r0, n in _row_blocks(n_rows):
            acc = swiglu_into(
                acc, normed(h[r0:r0 + n], ln2, eps=eps), ones[:n],
                params[f"ffn_gate_{i}"]["kernel"],
                params[f"ffn_up_{i}"]["kernel"],
                params[f"ffn_down_{i}"]["kernel"], r0)
        return acc
    moe = params[f"moe_{i}"]
    first = int(sizes["experts_held"][0])
    for r0, n in _row_blocks(n_rows):
        m = normed(h[r0:r0 + n], ln2, eps=eps)
        gates, top_e = route(
            m, moe["router"], moe["score_bias"],
            top_k=int(sizes["num_experts_per_tok"]),
            n_group=int(sizes["n_group"]),
            topk_group=int(sizes["topk_group"]),
            scaling=float(sizes["routed_scaling_factor"]),
            renormalize=bool(sizes["norm_topk_prob"]))
        if trace is not None:
            trace.setdefault("experts", {}).setdefault(i, []).append(top_e)
        acc = swiglu_into(acc, m, ones[:n], moe["shared_gate"],
                          moe["shared_up"], moe["shared_down"], r0)
        for e in range(moe["w_gate"].shape[0]):
            acc = swiglu_into(acc, m, gates[:, first + e], moe["w_gate"][e],
                              moe["w_up"][e], moe["w_down"][e], r0)
    return acc


def forward(params, tokens, sizes, routing=None, trace=None, rows=None):
    """Logits (S, V), or of rows lo .. hi - 1 with `rows=(lo, hi)`, of one
    sequence `tokens` (S,) under the program's weights `params` ({op name:
    {weight name: array}}, deepseek_v32_lm's names). `sizes` holds the
    configuration's keys."""
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    cos, sin = yarn_tables(s, int(sizes["qk_rope_head_dim"]),
                           float(sizes["rope_theta"]),
                           sizes.get("rope_scaling"))
    h = _f32(params["tok_embed"]["kernel"][tokens])
    lo, hi = rows if rows is not None else (0, s)
    # the whole blocks of query rows that hold rows lo .. hi - 1: all the
    # last layer has to compute
    qb = math.gcd(s, QUERY_BLOCK)
    a0, a1 = lo // qb * qb, -(-hi // qb) * qb
    layers = int(sizes["num_hidden_layers"])
    for i in range(layers):
        last = i == layers - 1
        h = attention(h, params[f"ln1_{i}"]["scale"], params[f"attn_{i}"],
                      sizes, cos, sin, trace, (a0, a1) if last else (0, s))
        h = feed_forward(h[a0:a1] if last else h, i, params, sizes, trace)
    return head(h[lo - a0:hi - a0], params["ln_f"]["scale"],
                params["lm_head"]["kernel"], eps=float(sizes["rms_norm_eps"]))


def program_disagreement(ff, tokens, sizes, trace):
    """Shares of (token, layer) rows where the program's own arithmetic (its
    `ln1_{i}` / `ln2_{i}` outputs through its own weights, in its compute
    dtype) selects another set S_t, and routes to another expert set, than
    this reference did in `trace`. Logged by the check, never judged: a
    near-tie at the cut flips on rounding, and what that does to the logits
    is inside the check's error."""
    layers = int(sizes["num_hidden_layers"])
    dense = int(sizes["first_k_dense_replace"])
    ln1 = [ff.get_op_by_name(f"ln1_{i}") for i in range(layers)]
    ln2 = [ff.get_op_by_name(f"ln2_{i}") for i in range(dense, layers)]
    fwd = jax.jit(ff.executor.make_forward(
        [op.outputs[0] for op in ln1 + ln2]))
    toks = jnp.asarray(tokens)[None]
    outs = fwd(ff.params, ff.bn_state,
               ff.executor.shard_batch({"input": np.asarray(toks)}))
    s = toks.shape[1]
    sel_flips = exp_flips = 0
    for i in range(layers):
        op = ff.get_op_by_name(f"attn_{i}")
        p = {k: v.astype(outs[i].dtype) if v.dtype != outs[i].dtype else v
             for k, v in ff.params[f"attn_{i}"].items()}
        mine = np.asarray(op.selection(p, outs[i]))[0]          # (S, S) bool
        idx = np.asarray(trace["selected"][i])
        theirs = np.zeros((s, s), bool)
        theirs[np.arange(s)[:, None], idx] = True
        theirs &= np.tri(s, dtype=bool)
        sel_flips += int((mine != theirs).any(axis=-1).sum())
    for n, i in enumerate(range(dense, layers)):
        op = ff.get_op_by_name(f"moe_{i}")
        m = outs[layers + n][0]
        p = {k: v for k, v in ff.params[f"moe_{i}"].items()}
        mine = np.sort(np.asarray(op._route(p, m)[2]), axis=-1)
        theirs = np.sort(np.concatenate(
            [np.asarray(t) for t in trace["experts"][i]]), axis=-1)
        exp_flips += int((mine != theirs).any(axis=-1).sum())
    return {"selected_set_differs": sel_flips / (layers * s),
            "expert_set_differs": exp_flips / max(1, (layers - dense) * s)}
