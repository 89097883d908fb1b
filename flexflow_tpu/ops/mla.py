"""Multi-head LATENT attention, plain (DeepSeek-V2/V3 MLA, Kanana-2,
LongCat-Flash) or with a learned sparse selection (DeepSeek-V3.2's lightning
indexer): `FFModel.latent_attention`.

What is cached per token is not K and V per head but ONE latent row shared
by all heads, and one index key:

    cQ = RMSNorm(a W_DQ);  q_i = [(cQ W_UQ)_i^nope ; RoPE((cQ W_UQ)_i^rope)]
    [cKV ; kR] = a W_DKV;  cKV = RMSNorm(cKV);  kR = RoPE(kR)   (one for all heads)
    k_{s,i} = [cKV_s W_UK,i ; kR_s]        v_{s,i} = cKV_s W_UV,i
    qI_j = (cQ W_IQ)_j, kI = LayerNorm(a W_IK)   (RoPE on their first d_R dims)
    w = a W_Iw * J^-0.5 * d_I^-0.5
    I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)
    S_t = the index_topk live positions of largest I_{t,s} (all, while fewer)
    o_{t,i} = sum_{s in S_t} softmax_{S_t}(q_{t,i} . k_{s,i} * scale) v_{s,i}

The architecture is described by two constructor arguments, neither a
performance selector. `q_lora_rank=None`: no query compression, q_i = a W_Q,i
(one `w_q` (D, H, d_nope + d_R) in place of `w_dq`, `q_norm`, `w_uq`).
`index_topk=None`: no indexer (no index weight, no `ki` rows, S_t = every
live position: plain causal MLA, DeepSeek-V2/V3's and Kanana-2's).
`q_lora_scale` / `kv_lora_scale` (LongCat-Flash's `mla_scale_q_lora` /
`mla_scale_kv_lora`: (hidden / rank)^0.5) multiply the projected query
`cQ W_UQ` (both its parts, before the rotary) and the normalised latent
`RMSNorm(cKV)`; the rotary key is not scaled, and the cached row holds the
SCALED latent, so nothing that reads a cache knows of the factor.

Two forms of the same numbers. EXPANDED (`forward`: predict, fit): K and V
are built per head from the latents. Without a selection that is a dense
causal attention with keys of d_nope + d_R and values of d_v, and it takes
the Pallas flash kernels and their FlashAttention-2 backward
(`pallas_kernels.flash_attention`, which takes the two widths apart and the
key in its two parts: the one rotary key a token is never broadcast to the
heads, `_forward_flash`) wherever
`attention.flash_eligible` says a dense attention does, on one device; with a
selection, or where the rule refuses (the CPU, a mesh), it is the blocked XLA
form below, which is differentiable too. ABSORBED (everything that reads a
cache): W_UK moves into the query and W_UV into the output,

    q_{t,i} . k_{s,i} = [q^nope_{t,i} W_UK,i^T ; q^rope_{t,i}] . [cKV_s ; kR_s]
    o_{t,i} = (sum_s p_{t,i,s} cKV_s) W_UV,i

so attention runs against the cached rows as they are: H heads x (c + d_R)
against one row a token.

Cache layout: `lat` rows of LAT = c + d_R rounded up to 128 lanes ([cKV ; kR
; zeros]: at c 512, d_R 64 that is 640, 64 lanes = 128 B a token of padding,
which a tiled HBM layout of a 576-wide minor dim would add anyway; it is
counted in `cache_bytes_per_token`), and `ki` rows of d_I. Both pools ride the
same page ids.

Selection is exact: `dsa_threshold` finds each row's index_topk-th largest
score digit by digit on the scores' bit patterns (8 passes of 15 counts) and
cuts ties by position (lowest first, 4 more passes); the same two numbers a
row drive the XLA mask here (`dsa_chosen`) and both Pallas cores.

What the paged decode core READS (`paged_decode_forward`, impl `pallas`)
WITHOUT an indexer: one pool, `lat`, on the page table, and
`mla_dense_core_pallas` (kernel `mla_paged_core_dense`) walks each slot's live
pages in place through the table, a block of pages a turn, one online softmax
over all heads: no gather, no list. 2 x H x (c + d_R + c) FLOPs a cached row
of LAT x 2 B: at 64 heads 109 FLOP/B, under the v5e's ridge of 240, so
bandwidth-bound while every slot streams its own pages. WITH an indexer:
only the rows the selection kept. `dsa_selected` turns the two numbers into
a list of pool rows per slot (index_topk of them, ascending, the first
n_sel real), XLA gathers those rows of the latent pool into (slots,
index_topk, LAT) and `mla_gathered_core_pallas` runs the online softmax
over index_topk / 128 blocks a slot, whatever the context; a context under
index_topk just has a short list. The gather is an XLA fusion, not part of
the named kernel: a device trace times it apart (PERF.md section 6, PR 31).

YaRN rotary (`rope_scaling`: factor, original_max_position_embeddings,
beta_fast, beta_slow, mscale, mscale_all_dim): frequencies interpolated
between theta^(-2i/d) and the same / factor over the correction range; cos
and sin carry mscale / mscale_all_dim's ratio (1 when they are equal), and
the softmax scale is (d_nope + d_R)^-0.5 * (0.1 mscale_all_dim ln factor + 1)^2.
Pairs are rotate-half, as everywhere in this package.
"""

from __future__ import annotations

import math
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.attention import (kv_storage_dtype,
                                        resolve_paged_attention_impl)
from flexflow_tpu.ops.base import Op, WeightSpec

LANES = 128
# f32 bytes one block of query rows may spend on its (rows, heads, keys)
# logits: the row block is the largest power of two under it
_BLOCK_LOGIT_BYTES = 256 << 20
# the fewest keys a prefill chunk is given (its cache allowing): on the v5e
# the softmax reduction XLA builds for a block of rows against 6144 or 8192
# keys runs 30 x slower than against 4096 or 10240 (two of the eight chunks
# of a 16 k cold prefill took 4.7 of its 6.95 s; the block's rows do not
# matter, 32 read like 64), so a chunk whose causal range ends earlier sees
# the cache's rows up to here, dead under the live rule (PERF.md section 6,
# PR 30)
_MIN_CHUNK_KEYS = 10240


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """(dim // 2,) f32 rotary frequencies, YaRN-interpolated when `scaling`
    is given (HF `DeepseekV3YarnRotaryEmbedding`)."""
    i = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** i
    if not scaling:
        return extra.astype(np.float32)
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1 = extrapolate (high frequency)
    return ((extra / factor) * (1 - keep) + extra * keep).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_rotate(x, pos, inv_freq, amp: float = 1.0):
    """Rotate-half rotary of x (B, S, ..., d) at positions pos (B, S), d =
    2 * len(inv_freq); f32 angles and arithmetic."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _f32_key(x):
    """uint32 keys ordered as the floats are (no NaN, no -0.0)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def dsa_threshold(scores, k: int):
    """Per row of `scores` (..., L) f32 (dead positions hold -inf): (thr
    f32, tie_cut int32) such that the k positions of largest score, ties
    going to the lowest position, are exactly those with score > thr, or
    score == thr at a position <= tie_cut. A row with at most k live
    positions gets (-inf, L): all of them."""
    L = scores.shape[-1]
    key = _f32_key(scores)
    live = scores > -jnp.inf
    n_live = jnp.sum(live, axis=-1)
    lead = scores.shape[:-1]

    digits = jnp.arange(1, 16, dtype=jnp.uint32)

    def key_digit(i, prefix):
        # four bits a pass: of the 15 candidates prefix | d << shift the
        # counts fall as d grows, so the digit is how many reach k
        shift = (28 - 4 * i).astype(jnp.uint32)
        cand = prefix[..., None] | (digits << shift)            # (..., 15)
        cnt = jnp.sum(live[..., None, :]
                      & (key[..., None, :] >= cand[..., None]), axis=-1)
        digit = jnp.sum(cnt >= k, axis=-1).astype(jnp.uint32)
        return prefix | (digit << shift)

    # the largest key that at least k live keys reach: the k-th largest
    kth = jax.lax.fori_loop(0, 8, key_digit, jnp.zeros(lead, jnp.uint32))
    above = jnp.sum(live & (key > kth[..., None]), axis=-1)
    need = k - above                    # ties at the threshold to take
    tied = live & (key == kth[..., None])
    pos = jnp.arange(L, dtype=jnp.int32)
    passes = -(-max(1, (L - 1).bit_length()) // 4)

    def pos_digit(i, p):
        shift = 4 * (passes - 1 - i)
        cand = p[..., None] | (digits.astype(jnp.int32) << shift)
        cnt = jnp.sum(tied[..., None, :] & (pos < cand[..., None]), axis=-1)
        digit = jnp.sum(cnt < need[..., None], axis=-1).astype(jnp.int32)
        return p | (digit << shift)

    # the largest position with fewer than `need` ties before it: that of
    # the need-th tie
    cut = jax.lax.fori_loop(0, passes, pos_digit,
                            jnp.zeros(lead, jnp.int32))
    kth_f = jax.lax.bitcast_convert_type(
        jnp.where(kth >> 31 == 1, kth & jnp.uint32(0x7FFFFFFF), ~kth),
        jnp.float32)
    few = n_live <= k
    return (jnp.where(few, -jnp.inf, kth_f),
            jnp.where(few, jnp.int32(L), cut))


def dsa_chosen(scores, thr, tie_cut):
    """(..., L) bool: the positions `dsa_threshold`'s pair selects."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    t = thr[..., None]
    return (scores > -jnp.inf) & (
        (scores > t) | ((scores == t) & (pos <= tie_cut[..., None])))


def dsa_selected(scores, thr, tie_cut, k: int, ps: int, page_table):
    """The selection as a list of pool rows: (rows (..., k) int32, n_sel
    (...) int32). `scores` (..., L) is read as L // ps pages of ps
    positions, `page_table` (..., L // ps) int32 (ids under 2^24) names each
    page's place in a pool seen as (pages * ps, .). rows[..., :n_sel] are
    page_table[..., pos // ps] * ps + pos % ps for the positions
    `dsa_chosen(scores, thr, tie_cut)` marks, ascending by position, and the
    rest position 0's row.

    Dense passes, no sort, no scatter and no gather: with `within` a page's
    running count of chosen positions and `incl` the running count of
    whole pages, the r-th chosen position lies in the page after those
    with incl <= r. That one comparison, `le` (..., k, pages), is a run of
    ones, so ONE product of it with per-page DIFFERENCES brings each r the
    count before its page (sum of the pages' counts), the page's `within`
    row (the first page's plus the summed differences) and its id in the
    table (the same, a byte at a time: every factor is an integer under
    256, exact in bf16, summed in f32); the offset in the page is how many
    of the row's running counts are <= r's rank in the page."""
    if ps > 256:
        raise ValueError(f"pages of {ps} positions: at most 256")
    lead, L = scores.shape[:-1], scores.shape[-1]
    p = L // ps
    bf, f32 = jnp.bfloat16, jnp.float32
    chosen = dsa_chosen(scores, thr, tie_cut).reshape(lead + (p, ps))
    within = jnp.einsum("...pi,ij->...pj", chosen.astype(bf),
                        jnp.triu(jnp.ones((ps, ps), bf)),      # [i <= j]
                        preferred_element_type=f32)
    cnt = within[..., -1:]                              # (..., p, 1)
    incl = jnp.cumsum(cnt[..., 0].astype(jnp.int32), axis=-1)
    # a pair that marks more than k (no `dsa_threshold` does) lists the
    # first k: a reader of the list never runs past it
    n_sel = jnp.minimum(incl[..., -1], k)

    def steps(x):       # x[q + 1] - x[q] along the pages; 0 for the last
        return jnp.concatenate([x[..., 1:, :] - x[..., :-1, :],
                                jnp.zeros_like(x[..., :1, :])], axis=-2)

    ids = jnp.stack([(page_table >> s) & 0xFF for s in (0, 8, 16)],
                    axis=-1).astype(f32)                # (..., p, 3)
    r = jnp.arange(k, dtype=jnp.int32)
    le = incl[..., None, :] <= r[:, None]               # (..., k, p)
    got = jnp.einsum("...kp,...pn->...kn", le.astype(bf),
                     jnp.concatenate([steps(within), cnt, steps(ids)],
                                     axis=-1).astype(bf),
                     preferred_element_type=f32)
    counts = within[..., :1, :] + got[..., :ps]         # the page's row
    off = jnp.sum(counts <= (r - got[..., ps])[..., None], axis=-1,
                  dtype=jnp.int32)
    mine = ids[..., :1, :] + got[..., ps + 1:]          # (..., k, 3) bytes
    page = jnp.sum(mine.astype(jnp.int32)
                   << jnp.arange(0, 24, 8, dtype=jnp.int32), axis=-1)
    return jnp.where(r < n_sel[..., None], page * ps + off,
                     page_table[..., :1] * ps), n_sel


class LatentAttention(Op):
    op_type = OperatorType.OP_MULTIHEAD_ATTENTION
    # the serving engine's and generate()'s cache protocol (init_cache ...
    # gather_paged_kv): runtime/generation.py dispatches on this
    kv_cache_protocol = True
    causal = True
    # a chunked prefill closes each chunk with an optimization barrier: the
    # next chunks' projections depend on the tokens alone, XLA hoists them
    # above this chunk's work, and at 16 chunks of a 32 k prompt 5 GB of
    # them were live at once (runtime/generation.py `_prefill`)
    prefill_chunk_barrier = True

    kernel_phase = "core"   # profiler.scope_table: an unnamed Mosaic call

    def __init__(self, model, name, inputs, embed_dim: int, num_heads: int,
                 q_lora_rank: Optional[int], kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int,
                 v_head_dim: int, index_n_heads: Optional[int] = None,
                 index_head_dim: Optional[int] = None,
                 index_topk: Optional[int] = None,
                 rope_theta: float = 10000.0,
                 rope_scaling: Optional[dict] = None, eps: float = 1e-6,
                 uq_init_gain: float = 1.0, q_lora_scale: float = 1.0,
                 kv_lora_scale: float = 1.0):
        super().__init__(model, name, inputs)
        self.embed_dim, self.num_heads = embed_dim, num_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.d_nope, self.d_rope = qk_nope_head_dim, qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.index_n_heads, self.index_head_dim = index_n_heads, index_head_dim
        # None: no indexer, every live position is attended
        self.index_topk = None if index_topk is None else int(index_topk)
        self.indexed = self.index_topk is not None
        assert qk_rope_head_dim % 2 == 0
        if self.indexed:
            assert index_n_heads and index_head_dim >= qk_rope_head_dim
            if q_lora_rank is None:
                raise ValueError(
                    f"{name}: the indexer's queries are projected from the "
                    f"compressed query (q_lora_rank), which this layer "
                    f"does not have")
        # the rows a token leaves in a cache
        self._cached = ("lat", "ki") if self.indexed else ("lat",)
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.eps = eps
        # the seeded draw of W_UQ is this much wider than glorot's (a
        # configuration that wants peaked attention from random weights)
        self.uq_init_gain = float(uq_init_gain)
        # factors on the projected query and on the normalised latent
        self.q_lora_scale = float(q_lora_scale)
        self.kv_lora_scale = float(kv_lora_scale)
        self.in_dim = inputs[0].dims[-1]
        self.lat_width = -(-(kv_lora_rank + qk_rope_head_dim) // LANES) * LANES
        self.inv_freq = yarn_inv_freq(qk_rope_head_dim, self.rope_theta,
                                      self.rope_scaling)
        sc = self.rope_scaling or {}
        factor = float(sc.get("factor", 1.0))
        all_dim = yarn_mscale(factor, float(sc.get("mscale_all_dim", 0.0)))
        self.rope_amp = yarn_mscale(factor, float(sc.get("mscale", 1.0))) \
            / all_dim if sc else 1.0
        self.scale = (self.d_nope + self.d_rope) ** -0.5 * all_dim * all_dim
        if self.indexed:
            self.index_scale = index_n_heads ** -0.5 * index_head_dim ** -0.5
        self.finalize()

    def output_shapes(self):
        x = self.inputs[0]
        return [tuple(x.dims[:-1]) + (self.embed_dim,)], [x.dtype]

    def weights(self) -> List[WeightSpec]:
        D, H, rq, c = self.in_dim, self.num_heads, self.q_lora_rank, \
            self.kv_lora_rank
        dq = self.d_nope + self.d_rope
        J, dI, dv = self.index_n_heads, self.index_head_dim, self.v_head_dim
        g2 = self.uq_init_gain ** 2
        # a weight's seeded draw is keyed by its place in this list: the
        # full form's order is fixed
        query = [WeightSpec("w_q", (D, H, dq), fan=(D / g2, H * dq / g2))] \
            if rq is None else [
            WeightSpec("w_dq", (D, rq)),
            WeightSpec("q_norm", (rq,), init="one"),
            WeightSpec("w_uq", (rq, H, dq), fan=(rq / g2, H * dq / g2))]
        index = [] if not self.indexed else [
            WeightSpec("w_iq", (rq, J, dI), fan=(rq, J * dI)),
            WeightSpec("w_ik", (D, dI)),
            WeightSpec("ik_norm_scale", (dI,), init="one"),
            WeightSpec("ik_norm_bias", (dI,), init="zero"),
            WeightSpec("w_iw", (D, J))]
        return query + [
            WeightSpec("w_dkv", (D, c + self.d_rope)),
            WeightSpec("kv_norm", (c,), init="one"),
            WeightSpec("w_uk", (c, H, self.d_nope),
                       fan=(c, H * self.d_nope)),
            WeightSpec("w_uv", (c, H, dv), fan=(c, H * dv)),
            WeightSpec("wo", (H, dv, self.embed_dim),
                       fan=(H * dv, self.embed_dim)),
        ] + index

    # ---- projections -------------------------------------------------------

    def _rms(self, x, scale):
        xf = x.astype(jnp.float32)
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + self.eps)
        return (xf * scale.astype(jnp.float32)).astype(x.dtype)

    def _layer_norm(self, x, scale, bias):
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        return (xf * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)

    def _rope_head(self, x, pos):
        """RoPE on the first d_rope dims of an index query/key."""
        r = self.d_rope
        return jnp.concatenate(
            [rope_rotate(x[..., :r], pos, self.inv_freq, self.rope_amp),
             x[..., r:]], axis=-1)

    def _latents(self, params, a, pos):
        """(cKV (B, S, c) normed, kR (B, S, d_R) rotated): what a token
        leaves for every head."""
        c = self.kv_lora_rank
        kv = a @ params["w_dkv"]
        ckv = self._rms(kv[..., :c], params["kv_norm"])
        if self.kv_lora_scale != 1.0:
            ckv = ckv * self.kv_lora_scale
        return ckv, rope_rotate(kv[..., c:], pos, self.inv_freq,
                                self.rope_amp)

    def _project(self, params, a, pos):
        """Everything one slab of tokens a (B, S, D) at positions pos (B, S)
        contributes: queries (`q_nope`, `q_rope` (B, S, H, .)) and what is
        cached (`lat` (B, S, LAT) = [cKV ; kR ; 0]); with an indexer also
        its queries (`qi` (B, S, J, dI), `w` (B, S, J) f32) and `ki`
        (B, S, dI), cached too."""
        c = self.kv_lora_rank
        with jax.named_scope("project"):
            if self.q_lora_rank is None:
                q = jnp.einsum("bsd,dhk->bshk", a, params["w_q"])
            else:
                cq = self._rms(a @ params["w_dq"], params["q_norm"])
                q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"])
            if self.q_lora_scale != 1.0:
                q = q * self.q_lora_scale
            ckv, kr = self._latents(params, a, pos)
            pad = self.lat_width - c - self.d_rope
            lat = jnp.concatenate(
                [ckv, kr] + ([jnp.zeros(kr.shape[:-1] + (pad,), kr.dtype)]
                             if pad else []), axis=-1)
            out = {"q_nope": q[..., :self.d_nope],
                   "q_rope": rope_rotate(q[..., self.d_nope:], pos,
                                         self.inv_freq, self.rope_amp),
                   "lat": lat}
        if self.indexed:
            with jax.named_scope("index"):
                out["qi"] = self._rope_head(
                    jnp.einsum("bsr,rjk->bsjk", cq, params["w_iq"]), pos)
                out["ki"] = self._rope_head(self._layer_norm(
                    a @ params["w_ik"], params["ik_norm_scale"],
                    params["ik_norm_bias"]), pos)
                out["w"] = (a @ params["w_iw"]).astype(jnp.float32) \
                    * self.index_scale
        return out

    def _absorb(self, params, q_nope, q_rope):
        """(..., H, LAT) queries against latent rows: [q_nope W_UK^T ;
        q_rope ; 0]."""
        with jax.named_scope("project"):
            qa = jnp.einsum("...hk,chk->...hc", q_nope, params["w_uk"])
            pad = self.lat_width - self.kv_lora_rank - self.d_rope
            parts = [qa, q_rope]
            if pad:
                parts.append(jnp.zeros(qa.shape[:-1] + (pad,), qa.dtype))
            return jnp.concatenate(parts, axis=-1)

    def _out(self, params, o):
        """(B, S, H, d_v) head outputs -> (B, S, D): one matmul over the
        (B, S, H * d_v) rows as they lie."""
        with jax.named_scope("out"):
            wo = params["wo"]
            return o.reshape(o.shape[:2] + (-1,)) @ wo.reshape(-1,
                                                               wo.shape[-1])

    # ---- the blocked attention both forms share ----------------------------

    def _row_block(self, s: int, n_keys: int) -> int:
        cap = max(1, _BLOCK_LOGIT_BYTES // (4 * self.num_heads * n_keys))
        return math.gcd(s, 1 << (cap.bit_length() - 1))

    def _blocked(self, pr, frontier, row_len, prompt_pad, L, ki, attend):
        """Selection and attention in blocks of query rows against L keys,
        so that neither an (S, L) score matrix nor an (S, H, L) logit
        tensor ever exists whole. Query row (b, s) may see key j iff
        j < row_len[b]  or  prompt_pad[b] <= j <= frontier[b, s]; of those
        its index scores against `ki` (B, L, dI) keep index_topk (all of
        them without an indexer). `attend(block, chosen)` -> (B, R, H, .)
        gets each block's slices of `pr` and the (B, R, L) mask."""
        b, s = frontier.shape
        r = self._row_block(s, L)
        j = jnp.arange(L, dtype=jnp.int32)
        rows = {n: pr[n] for n in ("q_nope", "q_rope", "qi", "w")
                if n in pr}
        rows["frontier"] = frontier

        def split(x):       # (B, S, ...) -> (S // r, B, r, ...)
            return jnp.moveaxis(
                x.reshape((b, s // r, r) + x.shape[2:]), 1, 0)

        def one(blk):
            fr = blk["frontier"]                            # (B, r)
            with jax.named_scope("select"):
                live = (j < row_len[:, None, None]) | (
                    (j >= prompt_pad[:, None, None]) & (j <= fr[..., None]))
            if not self.indexed:
                return attend(blk, live)
            with jax.named_scope("index"):
                sc = jnp.einsum("brjd,bld->brjl", blk["qi"], ki.astype(
                    blk["qi"].dtype), preferred_element_type=jnp.float32)
                sc = jnp.einsum("brjl,brj->brl", jnp.maximum(sc, 0.0),
                                blk["w"]) + 0.0
            with jax.named_scope("select"):
                sc = jnp.where(live, sc, -jnp.inf)
                thr, cut = dsa_threshold(sc, self.index_topk)
                chosen = dsa_chosen(sc, thr, cut)
            return attend(blk, chosen)

        out = jax.lax.map(one, {n: split(x) for n, x in rows.items()})
        return jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])

    def _attend_latent(self, params, pr, cache, frontier, row_len,
                       prompt_pad):
        """ABSORBED: pr's queries against cached rows `cache["lat"]` (B, L,
        LAT) and, with an indexer, `cache["ki"]` (B, L, dI) -> (B, S, D)."""
        lat, ki = cache["lat"], cache.get("ki")
        c = self.kv_lora_rank
        latc = lat.astype(pr["q_nope"].dtype)

        def attend(blk, chosen):
            # absorbed per block: a chunk's (S, H, LAT) queries never exist
            q_lat = self._absorb(params, blk["q_nope"], blk["q_rope"])
            with jax.named_scope("core"):
                logits = jnp.einsum("brhc,blc->brhl", q_lat, latc,
                                    preferred_element_type=jnp.float32)
                logits = jnp.where(chosen[:, :, None, :],
                                   logits * self.scale,
                                   jnp.finfo(jnp.float32).min)
                p = jax.nn.softmax(logits, axis=-1).astype(latc.dtype)
                ctx = jnp.einsum("brhl,blc->brhc", p, latc[..., :c])
            with jax.named_scope("out"):
                return jnp.einsum("brhc,chv->brhv", ctx, params["w_uv"])

        return self._out(params, self._blocked(
            pr, frontier, row_len, prompt_pad, lat.shape[1], ki, attend))

    def forward(self, params, xs, *, training=False, rng=None):
        """EXPANDED: per-head K and V from the slab's own latents, causal."""
        a = xs[0]
        b, s = a.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        if self._takes_flash(s):
            return [self._forward_flash(params, a, pos)]
        pr = self._project(params, a, pos)
        c = self.kv_lora_rank
        with jax.named_scope("project"):
            ckv = pr["lat"][..., :c]
            kr = pr["lat"][..., c:c + self.d_rope]
            k = jnp.concatenate(
                [jnp.einsum("blc,chk->blhk", ckv, params["w_uk"]),
                 jnp.broadcast_to(kr[:, :, None, :],
                                  (b, s, self.num_heads, self.d_rope))],
                axis=-1)
            v = jnp.einsum("blc,chv->blhv", ckv, params["w_uv"])

        def attend(blk, chosen):
            with jax.named_scope("core"):
                q = jnp.concatenate([blk["q_nope"], blk["q_rope"]], axis=-1)
                logits = jnp.einsum("brhk,blhk->brhl", q, k,
                                    preferred_element_type=jnp.float32)
                logits = jnp.where(chosen[:, :, None, :],
                                   logits * self.scale,
                                   jnp.finfo(jnp.float32).min)
                p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
                return jnp.einsum("brhl,blhv->brhv", p, v)

        zero = jnp.zeros((b,), jnp.int32)
        return [self._out(params, self._blocked(
            pr, pos, zero, zero, s, pr.get("ki"), attend))]

    def _forward_flash(self, params, a, pos):
        """`forward` on the flash kernels, no selection: every operand is
        made (B, S, H * d) wide by a matmul of its own against the weight's
        (., H * d) view and stays there (the kernels read a head's tiles
        through their index maps), and the key's two parts go apart:
        [cKV W_UK] a head and the ONE rotary key a token, which no
        (B, S, H, d_nope + d_R) array ever holds. The flash calls say their
        own phases (layout `project`, kernels `core`)."""
        from flexflow_tpu.ops.pallas_kernels import flash_attention

        b, s = a.shape[:2]
        h, dn = self.num_heads, self.d_nope

        def heads(x, w):            # x (B, S, r) @ w (r, H, d) -> (B, S, H, d)
            return (x @ w.reshape(w.shape[0], -1)).reshape(b, s, h, -1)

        with jax.named_scope("project"):
            if self.q_lora_rank is None:
                x, wq = a, params["w_q"]
            else:
                x = self._rms(a @ params["w_dq"], params["q_norm"])
                wq = params["w_uq"]
            q_nope, q_rope = heads(x, wq[..., :dn]), heads(x, wq[..., dn:])
            if self.q_lora_scale != 1.0:
                q_nope = q_nope * self.q_lora_scale
                q_rope = q_rope * self.q_lora_scale
            q_rope = rope_rotate(q_rope, pos, self.inv_freq, self.rope_amp)
            ckv, kr = self._latents(params, a, pos)
            k_nope = heads(ckv, params["w_uk"])
            v = heads(ckv, params["w_uv"])
        return self._out(params, flash_attention(
            (q_nope, q_rope), (k_nope, kr), v, True, self.scale))

    def _takes_flash(self, s: int) -> bool:
        """Whether `forward` over s tokens is the flash kernels: no
        selection to apply (they know the causal mask alone), the dense
        rule of ops/attention.py, and a program on one device (a Mosaic
        call inside a GSPMD-partitioned program would run replicated)."""
        from flexflow_tpu.ops.attention import flash_eligible

        mesh = getattr(self.model, "mesh", None)
        return (not self.indexed and (mesh is None or mesh.size == 1)
                and flash_eligible(getattr(self.model, "config", None),
                                   True, s, s))

    def selection(self, params, a):
        """(B, S, S) bool: the positions each row of a causal slab a
        (B, S, D) selects (what `forward` attends; for tests and checks)."""
        b, s = a.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        pr = self._project(params, a, pos)
        zero = jnp.zeros((b,), jnp.int32)
        return self._blocked(pr, pos, zero, zero, s, pr.get("ki"),
                             lambda blk, chosen: chosen[:, :, None])[:, :, 0]

    # ---- contiguous cache (generate(), and a request's prefill) ------------

    def init_cache(self, batch: int, max_len: int, dtype):
        cache = {"lat": jnp.zeros((batch, max_len, self.lat_width), dtype)}
        if self.indexed:
            cache["ki"] = jnp.zeros((batch, max_len, self.index_head_dim),
                                    dtype)
        return cache

    def _write(self, cache, pr, start):
        with jax.named_scope("project"):
            return {n: jax.lax.dynamic_update_slice(
                cache[n], pr[n].astype(cache[n].dtype), (0, start, 0))
                for n in self._cached}

    def chunk_forward(self, params, xs, cache, start):
        """Positions [start, start + C) of a prompt: write their rows,
        attend the static prefix [0, start + C) causally (given at least
        _MIN_CHUNK_KEYS of the cache's rows: what lies past a row's own
        position is dead under the live rule)."""
        a = xs[0]
        b, c = a.shape[:2]
        pos = jnp.broadcast_to(start + jnp.arange(c, dtype=jnp.int32),
                               (b, c))
        pr = self._project(params, a, pos)
        cache = self._write(cache, pr, start)
        keys = min(cache["lat"].shape[1], max(start + c, _MIN_CHUNK_KEYS))
        zero = jnp.zeros((b,), jnp.int32)
        out = self._attend_latent(
            params, pr, {n: cache[n][:, :keys] for n in self._cached}, pos,
            zero, zero)
        return out, cache

    def prefill_forward(self, params, xs, cache):
        return self.chunk_forward(params, xs, cache, 0)

    def query_forward(self, params, xs, cache, rope_pos, row_lengths):
        """Read-only: each row's last prompt token (already written) at its
        own position against its live prefix j < row_lengths."""
        pr = self._project(params, xs[0], rope_pos[:, None])
        zero = jnp.zeros_like(row_lengths)
        out = self._attend_latent(
            params, pr, cache, (row_lengths - 1)[:, None], zero, zero)
        return out, cache

    def decode_forward(self, params, xs, cache, pos, rope_pos=None,
                       row_lengths=None, prompt_len=None):
        """generate()'s one-token step at cache slot `pos`; ragged rows as
        MultiHeadAttention.decode_forward."""
        a = xs[0]
        b = a.shape[0]
        rp = jnp.broadcast_to(pos if rope_pos is None else rope_pos, (b,))
        pr = self._project(params, a, rp[:, None])
        cache = self._write(cache, pr, pos)
        zero = jnp.zeros((b,), jnp.int32)
        out = self._attend_latent(
            params, pr, cache,
            jnp.broadcast_to(pos, (b, 1)).astype(jnp.int32),
            zero if row_lengths is None else row_lengths,
            zero if row_lengths is None else zero + prompt_len)
        return out, cache

    # ---- paged pool (runtime/serving.py, runtime/kv_pool.py) ---------------

    def cache_bytes_per_token(self) -> int:
        """bf16 bytes one cached token takes in this op's pools, the
        latent row's padding lanes included."""
        return (self.lat_width
                + (self.index_head_dim if self.indexed else 0)) * 2

    def decode_span_counts(self, context, page_size=None):
        """Host-side counts of one decode dispatch from the live rows'
        context lengths (an int array, one entry a row and step): bytes of
        index keys read, tokens the selection keeps, tokens it saw; and,
        given the pool's page size, the bytes the index kernel's block
        stream moves for them (each row's pages rounded up to whole
        blocks: over `index_read_bytes` it is the stream's over-fetch).
        Nothing without an indexer: the engine's own page counts say what
        the dense core reads."""
        if not self.indexed:
            return {}
        ctx = np.asarray(context, np.int64)
        key = self.index_head_dim * 2
        counts = {"index_read_bytes": int(ctx.sum()) * key,
                  "dsa_selected_tokens": int(np.minimum(
                      ctx, self.index_topk).sum()),
                  "dsa_context_tokens": int(ctx.sum())}
        if page_size is not None:
            from flexflow_tpu.ops.pallas_kernels import dsa_index_block_tokens

            block = dsa_index_block_tokens(page_size)
            counts["index_streamed_bytes"] = int(
                (-(-ctx // block)).sum()) * block * key
        return counts

    def paged_turn_pages(self, cache, width: int) -> int:
        """`MultiHeadAttention.paged_turn_pages`. With an indexer 1: the
        core reads gathered rows and no page stream (the index kernel's
        blocks are counted by `decode_span_counts`). Without one, the pages
        a turn of the dense core's stream takes."""
        if self.indexed:
            return 1
        from flexflow_tpu.ops.pallas_kernels import mla_dense_turn_pages

        return mla_dense_turn_pages(width)

    def init_paged_cache(self, num_pages: int, page_size: int, dtype,
                         kv_dtype=None):
        """The pools a token's cached rows live in, all on one page table:
        `lat`, and with an indexer `ki`."""
        sdtype, qmax = kv_storage_dtype(kv_dtype)
        if qmax is not None:
            raise NotImplementedError(
                f"{self.name}: a quantized latent cache ({kv_dtype}) is not "
                f"built; kv_cache_dtype native or bf16")
        store = sdtype if sdtype is not None else dtype
        widths = {"lat": self.lat_width, "ki": self.index_head_dim}
        return {n: jnp.zeros((num_pages, page_size, widths[n]), store)
                for n in self._cached}

    def scatter_cache_tail(self, pool, cache, p0: int, pages, impl="einsum"):
        """Write a request's contiguous cache past position p0 into its own
        fresh `pages` (whole pages: one update-slice a page)."""
        ps = pool["lat"].shape[1]
        out = {}
        with jax.named_scope("core"):
            for n in self._cached:
                x = cache[n][0, p0:]
                pad = pages.shape[0] * ps - x.shape[0]
                if pad:
                    x = jnp.pad(x, ((0, pad), (0, 0)))
                out[n] = pool[n].at[pages].set(
                    x.reshape(pages.shape[0], ps, -1).astype(pool[n].dtype))
        return out

    def export_page(self, cache, page):
        return {n: cache[n][page] for n in self._cached}

    def import_page(self, cache, page, payload):
        return {n: cache[n].at[page].set(
            jnp.asarray(payload[n]).astype(cache[n].dtype))
            for n in self._cached}

    def gather_paged_kv(self, cache, pages):
        with jax.named_scope("gather"):
            return {n: cache[n][pages].reshape(1, -1, cache[n].shape[-1])
                    for n in self._cached}

    def paged_decode_forward(self, params, xs, cache, page_table, write_pos,
                             rope_pos, row_len, prompt_pad, impl=None):
        """One decode step of every slot over the paged pools: append the
        token's latent row and index key at (page_table[b, write_pos //
        ps], write_pos % ps), then score, select and attend through the
        page tables. Without an indexer `pallas` is the dense core, every
        live page of a slot read in place through its table
        (`mla_dense_core_pallas`). With one: the index kernel reads its pool
        in place, a block of 8 pages a turn (so a slot's context is streamed
        rounded up to whole blocks: `decode_span_counts`'
        `index_streamed_bytes`), and
        the core reads the selected latent rows, gathered; `einsum`: the
        slots' pages gathered into contiguous rows and the blocked XLA
        attention, the parity oracle."""
        ps = cache["lat"].shape[1]
        pr = self._project(params, xs[0], rope_pos[:, None])
        with jax.named_scope("project"):
            page_ids = jnp.take_along_axis(
                page_table, (write_pos // ps)[:, None], axis=1)[:, 0]
            offs = write_pos % ps
            cache = {n: cache[n].at[page_ids, offs].set(
                pr[n][:, 0].astype(cache[n].dtype)) for n in self._cached}
        if resolve_paged_attention_impl(impl) != "pallas":
            b = page_table.shape[0]
            with jax.named_scope("gather"):
                rows = {n: cache[n][page_table].reshape(
                    b, -1, cache[n].shape[-1]) for n in self._cached}
            return self._attend_latent(params, pr, rows, write_pos[:, None],
                                       row_len, prompt_pad), cache
        from flexflow_tpu.ops.pallas_kernels import (
            dsa_index_scores_pallas, mla_dense_core_pallas,
            mla_gathered_core_pallas)

        if not self.indexed:
            q_lat = self._absorb(params, pr["q_nope"][:, 0],
                                 pr["q_rope"][:, 0])
            # the call says its own phase (`core`)
            ctx = mla_dense_core_pallas(
                q_lat, cache["lat"], page_table, write_pos, row_len,
                prompt_pad, scale=self.scale, c=self.kv_lora_rank)
            with jax.named_scope("out"):
                o = jnp.einsum("bhc,chv->bhv", ctx, params["w_uv"])
            return self._out(params, o[:, None]), cache

        with jax.named_scope("index"):
            scores = dsa_index_scores_pallas(
                pr["qi"][:, 0], pr["w"][:, 0], cache["ki"], page_table,
                write_pos, row_len, prompt_pad)
        q_lat = self._absorb(params, pr["q_nope"][:, 0], pr["q_rope"][:, 0])
        with jax.named_scope("select"):
            thr, cut = dsa_threshold(scores, self.index_topk)
            # a table smaller than index_topk has no more rows to list
            rows, n_sel = dsa_selected(
                scores, thr, cut,
                min(self.index_topk, page_table.shape[1] * ps), ps,
                page_table)
        # the call says its own phases: XLA's row `gather`, the kernel `core`
        ctx = mla_gathered_core_pallas(q_lat, rows, n_sel, cache["lat"],
                                       scale=self.scale, c=self.kv_lora_rank)
        with jax.named_scope("out"):
            o = jnp.einsum("bhc,chv->bhv", ctx, params["w_uv"])
        return self._out(params, o[:, None]), cache

    def paged_verify_forward(self, *args, **kw):
        raise NotImplementedError(
            f"{self.name}: speculative verify over a latent cache is not "
            f"built (the selection, or the dense core's frontier, would run "
            f"per slab position); serve this model without a draft")

    # ---- strategy search -----------------------------------------------------

    def partitionable_output_dims(self):
        return [0]

    def weight_partition(self, axis_map):
        return {w.name: P(*([None] * len(w.shape)))
                for w in self.weight_specs()}

    def flops(self):
        b, s = self.inputs[0].dims[0], self.inputs[0].dims[1]
        proj = 2 * b * s * sum(
            int(np.prod(w.shape)) for w in self.weight_specs()
            if len(w.shape) > 1)
        # a causal row sees half the sequence on average, the selection
        # at most index_topk of it
        keys = min(s, self.index_topk) if self.indexed else s / 2
        core = 2 * b * s * self.num_heads * keys * (
            self.d_nope + self.d_rope + self.v_head_dim)
        index = 2 * b * s * s * self.index_n_heads * self.index_head_dim \
            if self.indexed else 0
        return int(proj + core + index)
