"""Ring attention: sequence/context parallelism over the 'seq' mesh axis.

Net-new capability vs the reference, whose attention asserts batch-only
partitioning (reference: src/ops/attention.cu:118-120; SURVEY §5.7). Design:
K/V shards rotate around the ICI ring via `jax.lax.ppermute` while each
device's Q shard accumulates attention with online-softmax rescaling
(blockwise/flash-style running max/sum), so sequence length scales with the
number of devices at O(S/P) activation memory per chip and compute overlaps
the rotation.

Also provides the Ulysses lowering (all-to-all head<->seq swap) as the
alternative SP strategy, and a blockwise local attention step shared by both.

All functions here must be called INSIDE shard_map (they use axis_name
collectives); flexflow_tpu/ops/attention.py wires them into MultiHeadAttention
when the strategy shards the sequence dim.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


NEG_INF = -1e30


def pvary(x, axis_name):
    """Mark x as device-varying over axis_name (vma typing for scan carries).
    jax.lax.pvary was renamed to pcast(..., to='varying')."""
    if hasattr(lax, "pcast"):
        return lax.pcast(x, axis_name, to="varying")
    if hasattr(lax, "pvary"):
        return lax.pvary(x, axis_name)
    return x


def _block_attend(q, k, v, m, l, o, scale, mask=None, dropout_rng=None,
                  dropout_rate=0.0):
    """One online-softmax accumulation step.

    q: (B, Sq, H, D); k/v: (B, Sk, H, D); m,l: (B, H, Sq); o: (B, Sq, H, D).
    Returns updated (m, l, o). f32 accumulation regardless of input dtype.

    Attention dropout: the Bernoulli mask is applied to the unnormalized
    block probs feeding the value product, while `l` keeps accumulating the
    undropped sum — the final o/l division then equals dropout(softmax(s)) @ v
    of the dense formulation exactly (dropout commutes with the global
    normalization elementwise).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)                      # (B, H, Sq)
    p = jnp.exp(s - m_new[..., None])               # (B, H, Sq, Sk)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    pv_in = p
    if dropout_rng is not None and dropout_rate > 0.0:
        keep = 1.0 - dropout_rate
        drop_mask = jax.random.bernoulli(dropout_rng, keep, p.shape)
        pv_in = jnp.where(drop_mask, p / keep, 0.0)
    pv = jnp.einsum("bhqk,bkhd->bqhd", pv_in.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _flash_block(q, k, v, causal: bool, scale: float):
    """One ring step through the Pallas flash kernel: the block's normalized
    output (B,S,H,D) f32 and logsumexp (B,H,S)."""
    from flexflow_tpu.ops.pallas_kernels import flash_attention_fwd_pallas

    b, sq, h, _ = q.shape
    out, lse8 = flash_attention_fwd_pallas(q, k, v, causal, scale)
    return out.astype(jnp.float32), lse8[..., 0].reshape(b, h, sq)


def _merge_blocks(o, lse, o_s, lse_s):
    """Combine two normalized attention partials by their logsumexps.
    (An all-masked partial carries lse = NEG_INF = -1e30; its weight
    exp(NEG_INF - new_lse) underflows to exactly 0.)"""
    new_lse = jnp.logaddexp(lse, lse_s)
    o_new = (o * jnp.exp(lse - new_lse).transpose(0, 2, 1)[..., None]
             + o_s * jnp.exp(lse_s - new_lse).transpose(0, 2, 1)[..., None])
    return o_new, new_lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention_flash(q, k, v, axis_name: str, causal: bool = False,
                         scale: Optional[float] = None):
    """Ring attention with the Pallas flash kernel as the per-step block
    compute (VERDICT r1 #4: the kernel on the SP hot path). Forward: each
    step attends the local Q shard against the visiting K/V shard entirely
    in-kernel; partials merge by logsumexp; future shards are skipped (the
    kernel never launches for fully-masked steps). Backward: the standard
    memory-efficient ring trick — only (q, k, v, o, lse) per device is
    saved (O(S/P)); K/V re-rotate around the ring while dk/dv buffers
    counter-rotate back to their owners, each step running the
    FlashAttention-2 block backward against the GLOBAL logsumexp."""
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale)
    return o.astype(q.dtype)


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale):
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    lse0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    o0, lse0 = (pvary(t, axis_name) for t in (o0, lse0))
    perm = [(i, (i - 1) % p_size) for i in range(p_size)]

    def step(carry, step_idx):
        o, lse, k_cur, v_cur = carry
        if causal:
            src = (my_idx + step_idx) % p_size

            def self_block(_):
                return _flash_block(q, k_cur, v_cur, True, scale)

            def full_block(_):
                return _flash_block(q, k_cur, v_cur, False, scale)

            def skip_block(_):  # future shard: no kernel launch at all
                return (jnp.zeros((b, sq, h, d), jnp.float32),
                        jnp.full((b, h, sq), NEG_INF, jnp.float32))

            which = jnp.where(step_idx == 0, 0, jnp.where(src > my_idx, 2, 1))
            o_s, lse_s = lax.switch(which, [self_block, full_block,
                                            skip_block], operand=None)
        else:
            o_s, lse_s = _flash_block(q, k_cur, v_cur, False, scale)
        o, lse = _merge_blocks(o, lse, o_s, lse_s)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o, lse, k_nxt, v_nxt), None

    (o, lse, _, _), _ = lax.scan(step, (o0, lse0, k, v), jnp.arange(p_size))
    return o, lse


def _ring_flash_fwd(q, k, v, axis_name, causal, scale):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale)
    # O(S/P) residuals per device: local shards + local output + local lse
    return o.astype(q.dtype), (q, k, v, o.astype(q.dtype), lse)


def _ring_flash_bwd(axis_name, causal, scale, res, do):
    from flexflow_tpu.ops.pallas_kernels import flash_attention_bwd_pallas

    q, k, v, o, lse = res
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(d)
    # the block backward consumes the GLOBAL logsumexp (p = exp(s - LSE) is
    # the true global probability of each visiting block)
    lse8 = jnp.broadcast_to(lse.reshape(b * h, sq)[..., None],
                            (b * h, sq, 8))
    do = do.astype(q.dtype)
    # delta is loop-invariant (depends only on do and the final output);
    # compute once so the scan body doesn't re-emit it every ring step
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = delta.transpose(0, 2, 1)  # (B, H, Sq)
    perm = [(i, (i - 1) % p_size) for i in range(p_size)]

    def block_bwd(k_cur, v_cur, causal_flag):
        return flash_attention_bwd_pallas(q, k_cur, v_cur, o, lse8, do,
                                          causal_flag, scale_v,
                                          delta_precomputed=delta)

    def body(carry, step_idx):
        dq_acc, dk_buf, dv_buf, k_cur, v_cur = carry
        if causal:
            src = (my_idx + step_idx) % p_size

            def self_block(_):
                return block_bwd(k_cur, v_cur, True)

            def full_block(_):
                return block_bwd(k_cur, v_cur, False)

            def skip_block(_):
                return (jnp.zeros((b, sq, h, d), q.dtype),
                        jnp.zeros((b, sk, h, d), k.dtype),
                        jnp.zeros((b, sk, h, d), v.dtype))

            which = jnp.where(step_idx == 0, 0, jnp.where(src > my_idx, 2, 1))
            dq_s, dk_s, dv_s = lax.switch(which, [self_block, full_block,
                                                  skip_block], operand=None)
        else:
            dq_s, dk_s, dv_s = block_bwd(k_cur, v_cur, False)
        dq_acc = dq_acc + dq_s.astype(jnp.float32)
        dk_buf = dk_buf + dk_s.astype(jnp.float32)
        dv_buf = dv_buf + dv_s.astype(jnp.float32)
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_buf = lax.ppermute(dk_buf, axis_name, perm)
        dv_buf = lax.ppermute(dv_buf, axis_name, perm)
        return (dq_acc, dk_buf, dv_buf, k_cur, v_cur), None

    z = lambda shape: pvary(jnp.zeros(shape, jnp.float32), axis_name)
    init = (z((b, sq, h, d)), z((b, sk, h, d)), z((b, sk, h, d)), k, v)
    (dq_acc, dk_buf, dv_buf, _, _), _ = lax.scan(body, init,
                                                 jnp.arange(p_size))
    return (dq_acc.astype(q.dtype), dk_buf.astype(k.dtype),
            dv_buf.astype(v.dtype))


ring_attention_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None, dropout_rate: float = 0.0,
                   dropout_rng=None, use_flash: Optional[bool] = None):
    """Ring self-attention inside shard_map.

    q, k, v: (B, S_local, H, D) — the local sequence shard.
    Rotates K/V left around `axis_name`; after P steps every Q shard has
    attended to the full sequence. When the Pallas kernel applies (TPU or
    forced, no dropout), the per-step block compute runs in-kernel
    (ring_attention_flash); otherwise the pure-JAX online-softmax path.
    """
    if use_flash and dropout_rate > 0.0:
        raise ValueError(
            "use_flash=True is incompatible with attention dropout (the "
            "Pallas kernels have no dropout path); drop the flag to use the "
            "pure-JAX ring")
    if use_flash is None:
        import os

        from flexflow_tpu.ops.attention import flash_seq_cap

        cap = flash_seq_cap()
        use_flash = ((jax.default_backend() == "tpu"
                      or os.environ.get("FF_FORCE_FLASH_ATTENTION") == "1")
                     and dropout_rate == 0.0
                     # deployment escape hatch (FF_FLASH_MAX_SEQ): oversized
                     # local shards take the pure-JAX ring instead
                     and (not cap
                          or max(q.shape[1], k.shape[1]) <= cap))
    if use_flash:
        return ring_attention_flash(q, k, v, axis_name, causal, scale)
    p_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    # mark the fresh accumulators as device-varying over the ring axis so the
    # scan carry type matches after the first accumulation step
    m0, l0, o0 = (pvary(t, axis_name) for t in (m0, l0, o0))

    q_pos = my_idx * sq + jnp.arange(sq)  # global positions of local queries
    # per-device dropout stream: each (device, ring step) sees an
    # independent Bernoulli mask over its local (q block, k block) tile
    if dropout_rng is not None and dropout_rate > 0.0:
        dropout_rng = jax.random.fold_in(dropout_rng, my_idx)

    def step(carry, step_idx):
        m, l, o, k_cur, v_cur = carry
        # k_cur currently holds the shard originally owned by (my_idx + step)
        src = (my_idx + step_idx) % p_size
        if causal:
            k_pos = src * sk + jnp.arange(sk)
            mask = q_pos[:, None] >= k_pos[None, :]          # (Sq, Sk)
            mask = mask[None, None, :, :]                    # (1,1,Sq,Sk)
        else:
            mask = None
        step_rng = None
        if dropout_rng is not None and dropout_rate > 0.0:
            step_rng = jax.random.fold_in(dropout_rng, step_idx)
        m, l, o = _block_attend(q, k_cur, v_cur, m, l, o, scale, mask,
                                dropout_rng=step_rng,
                                dropout_rate=dropout_rate)
        # rotate: receive the next shard from the right neighbor
        perm = [(i, (i - 1) % p_size) for i in range(p_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, l, o, k_nxt, v_nxt), None

    (m, l, o, _, _), _ = lax.scan(step, (m0, l0, o0, k, v),
                                  jnp.arange(p_size))
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None,
                      dropout_rate: float = 0.0, dropout_rng=None):
    """Ulysses (DeepSpeed-style) SP inside shard_map: all-to-all swaps the
    sequence shard for a head shard, attention runs with full sequence on
    1/P of the heads, then swaps back. Requires num_heads % P == 0."""
    p_size = lax.axis_size(axis_name)
    b, sq, h, d = q.shape
    assert h % p_size == 0, f"heads {h} not divisible by seq-parallel {p_size}"

    def seq2head(x):
        # (B, S/P, H, D) -> (B, S, H/P, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    if dropout_rng is not None and dropout_rate > 0.0:
        # after the swap each device owns a disjoint head shard — fold the
        # device index in so head shards draw independent masks
        dropout_rng = jax.random.fold_in(dropout_rng, lax.axis_index(axis_name))
    qf, kf, vf = seq2head(q), seq2head(k), seq2head(v)
    out = blockwise_attention(qf, kf, vf, causal=causal, scale=scale,
                              dropout_rate=dropout_rate,
                              dropout_rng=dropout_rng)
    return head2seq(out)


def blockwise_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        block_size: int = 512,
                        dropout_rate: float = 0.0, dropout_rng=None):
    """Memory-efficient local attention: lax.scan over K/V blocks with online
    softmax (flash-attention recurrence in pure JAX — XLA keeps the working
    set at O(block) and fuses; the Pallas kernel in ops/pallas_kernels.py is
    the hand-tiled variant used on TPU when shapes allow)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if sk <= block_size:
        mask = None
        if causal:
            mask = (jnp.arange(sq)[:, None] + (sk - sq)
                    >= jnp.arange(sk)[None, :])[None, None]
        m, l, o = _block_attend(
            q, k, v,
            jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, sq, h, d), jnp.float32), scale, mask,
            dropout_rng=dropout_rng, dropout_rate=dropout_rate)
        return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    nblocks = (sk + block_size - 1) // block_size
    assert sk % block_size == 0, f"seq {sk} % block {block_size} != 0"
    kb = k.reshape(b, nblocks, block_size, h, d)
    vb = v.reshape(b, nblocks, block_size, h, d)
    q_pos = jnp.arange(sq) + (sk - sq)  # align causal diag when sq != sk

    def step(carry, blk):
        m, l, o = carry
        k_cur, v_cur, blk_idx = blk
        mask = None
        if causal:
            k_pos = blk_idx * block_size + jnp.arange(block_size)
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
        blk_rng = None
        if dropout_rng is not None and dropout_rate > 0.0:
            blk_rng = jax.random.fold_in(dropout_rng, blk_idx)
        m, l, o = _block_attend(q, k_cur, v_cur, m, l, o, scale, mask,
                                dropout_rng=blk_rng,
                                dropout_rate=dropout_rate)
        return (m, l, o), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, sq, h, d), jnp.float32))
    (m, l, o), _ = lax.scan(
        step, init,
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(nblocks)))
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)
