"""MultiHeadAttention.

Reference: src/ops/attention.cu (745 LoC, cuDNN cudnnMultiHeadAttnForward;
partitioning asserted batch-only at attention.cu:118-120).

TPU re-design supersedes that restriction: attention here is partitionable on
batch, heads ('model' axis — Megatron-style), and sequence ('seq' axis — ring
attention, flexflow_tpu/parallel/ring_attention.py). The dense path uses the
hand-tiled Pallas flash kernel (ops/pallas_kernels.py) when the backend is TPU
and the block grid divides the sequence (_flash_ok), falling back to an
einsum-built softmax that XLA fuses; the ring/Ulysses SP lowering is selected
when the strategy shards `seq`.

API parity: FFModel.multihead_attention mirrors flexflow_c.h's
flexflow_model_add_multihead_attention signature.
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import Op, WeightSpec

# past this sequence length, the non-flash dense path (CPU backend, attention
# dropout) switches from the fused einsum to the pure-JAX blockwise
# online-softmax scan — an einsum would materialize the S x S probability
# tensor (the scan takes one head size: key and value widths that differ
# keep the einsum there). The Pallas flash kernels themselves stream K/V
# tiles through the grid (round-3 rework), have NO sequence cap (VMEM use is
# O(block^2) regardless of S) and take key and value widths that differ.
BLOCKWISE_SEQ_THRESHOLD = 4096


def resolve_paged_attention_impl(impl=None) -> str:
    """Resolve an ``auto|pallas|einsum`` request (the engine's
    ``paged_attention_impl`` argument; None is ``auto``) to the concrete
    path of the paged decode attention and of the prefill page write:

      * ``pallas`` — the paged-attention kernel (ops/pallas_kernels.py
        paged_attention_fwd_pallas): page-table lookup inside the grid,
        only a slot's live pages stream through VMEM. Off-TPU it runs
        only in interpret mode, which the CPU test suite and CI tiers ask
        for (FF_PALLAS_INTERPRET=1) to execute the REAL kernel code path.
      * ``einsum`` — the page-gather + grouped einsum path, bitwise the
        dense-cache attention: the parity oracle, and the default where
        no native Mosaic backend exists.
      * ``auto`` — pallas on a TPU backend, einsum elsewhere.
    """
    if impl in (None, "", "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "einsum"
    if impl not in ("pallas", "einsum"):
        raise ValueError(
            f"paged_attention_impl={impl!r}: must be 'auto', 'pallas' "
            f"or 'einsum'")
    return impl


#: quantized KV-page storage (ISSUE 11): the paged pool stores int8 or
#: fp8 payload with one f32 scale per (page, kv head), so each page holds
#: 2-4x more tokens per HBM byte — the allocator, COW rule, radix trie
#: and router affinity are page-granular and never look inside a page.
#: Dequantization happens where the data is consumed (inside the Pallas
#: kernel's VMEM tiles, or fused into the einsum gather); wide KV is
#: never materialized in HBM.


def kv_storage_dtype(kv_dtype):
    """Resolve an FFConfig.kv_cache_dtype value to ``(storage_dtype,
    qmax)``. ``(None, None)`` = native (the compute dtype); a non-None
    dtype with ``qmax=None`` (bf16) is a plain cast — no scales; a qmax
    means symmetric scale quantization with per-page-per-head scales.
    Raises on unknown values and on 'fp8' under a jax build without
    ``jnp.float8_e4m3fn`` (the no-new-deps gate: fail loudly at engine
    construction, never on a silent fallback)."""
    if kv_dtype in (None, "", "native"):
        return None, None
    if kv_dtype in ("bf16", "bfloat16"):
        return jnp.bfloat16, None
    if kv_dtype == "int8":
        return jnp.int8, 127.0
    if kv_dtype == "fp8":
        fp8 = getattr(jnp, "float8_e4m3fn", None)
        if fp8 is None:
            raise ValueError(
                "kv_cache_dtype='fp8' needs a jax build with "
                "jnp.float8_e4m3fn; this build lacks it — use 'int8'")
        return fp8, float(jnp.finfo(fp8).max)
    raise ValueError(
        f"kv_cache_dtype={kv_dtype!r}: must be 'native', 'bf16', "
        f"'int8' or 'fp8'")


def storage_qmax(dtype) -> float:
    """The symmetric quantization ceiling of a storage dtype (127 for
    int8, finfo.max for fp8)."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return float(jnp.iinfo(dtype).max)
    return float(jnp.finfo(dtype).max)


def page_scale(pf, qmax: float):
    """Per-(page, kv-head) scale for a (..., page_size, KVH, D) float
    slab: amax over the page's positions and head dim."""
    return jnp.max(jnp.abs(pf.astype(jnp.float32)), axis=(-3, -1)) / qmax


def page_quantize(pf, scale, qmax: float, dtype):
    """Quantize (..., page_size, KVH, D) float against per-(page, head)
    ``scale`` (..., KVH). Values are clipped BEFORE the cast: an fp8
    overflow cast produces nan, not saturation. int8 rounds to nearest;
    fp8 rounding is the cast's. Requantization at an UNCHANGED scale is
    exact (round((q*s)/s) == q for |q| <= qmax), which is what makes the
    append path's unconditional page requant safe."""
    s = jnp.maximum(scale, 1e-12)[..., None, :, None]
    q = jnp.clip(pf.astype(jnp.float32) / s, -qmax, qmax)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        q = jnp.round(q)
    return q.astype(dtype)


def page_dequantize(q, scale):
    """(..., page_size, KVH, D) storage payload x (..., KVH) scales ->
    f32. The inverse of page_quantize; the einsum gather fuses this into
    the page lookup, the Pallas kernel applies it per VMEM tile."""
    return q.astype(jnp.float32) * scale[..., None, :, None]


def flash_seq_cap() -> int:
    """FF_FLASH_MAX_SEQ: deployment escape hatch capping flash-kernel
    sequence length (0/unset/garbage = unlimited). Consulted by the dense
    path (_flash_ok) and the ring/sequence-parallel per-shard gate."""
    import os

    try:
        return int(os.environ.get("FF_FLASH_MAX_SEQ", "0") or 0)
    except ValueError:
        return 0


def flash_eligible(config, causal: bool, sq: int, sk: int) -> bool:
    """Whether a dense attention of sq queries against sk keys takes the
    Pallas flash kernels: a static rule of the backend, the configuration
    and the two lengths (the head sizes do not enter: the kernels take key
    and value widths that differ). One rule for every op that calls
    `flash_attention` (MultiHeadAttention, ops/mla.py)."""
    import os

    if config is not None and not getattr(config, "use_flash_attention",
                                          True):
        return False
    force = os.environ.get("FF_FORCE_FLASH_ATTENTION") == "1"
    if jax.default_backend() != "tpu" and not force:
        return False  # interpret mode is for tests only
    if causal and sq > sk:
        # more queries than keys under bottom-right-aligned causality
        # leaves the first sq-sk rows with no live key (0/0 in the
        # online softmax); the einsum path's uniform-softmax answer for
        # such rows is equally meaningless, so don't pretend parity
        return False
    # escape hatch: the streaming kernels carry no architectural length
    # cap, but if a deployment's Mosaic build rejects some long-sequence
    # compile, FF_FLASH_MAX_SEQ routes those shapes to the blockwise
    # fallback without a code change (unset/0 = unlimited)
    cap = flash_seq_cap()
    if cap and max(sq, sk) > cap:
        return False
    for s in (sq, sk):
        if s % min(128, s) != 0:
            return False
    return True


def _apply_rope(x, theta: float, offset=0, rope_dim: int = 0):
    """Rotary position embedding (rotate-half convention) on (B,S,H,Dh).
    `rope_dim` > 0 turns the first `rope_dim` entries of a head only
    (rotate-half within them) and leaves the rest as they are.
    Angles are computed from absolute positions in f32 and the rotation is
    applied in f32 regardless of compute dtype (bf16 angles at position
    ~1000+ would lose the low-order bits that distinguish neighbors).
    `offset` shifts the absolute positions — the KV-cache decode path
    rotates a new token at its true position. Scalar (python int or
    traced) applies to every row; a (B,) array gives per-row offsets
    (ragged right-padded prompts)."""
    if rope_dim and rope_dim < x.shape[-1]:
        return jnp.concatenate(
            [_apply_rope(x[..., :rope_dim], theta, offset),
             x[..., rope_dim:]], axis=-1)
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    off = jnp.asarray(offset, jnp.float32)
    pos = off[..., None] + jnp.arange(s, dtype=jnp.float32)  # (S,) or (B,S)
    ang = pos[..., None] * freqs  # (..., S, half)
    if ang.ndim == 2:  # scalar offset: broadcast over batch
        ang = ang[None]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class MultiHeadAttention(Op):
    """Multi-head / grouped-query attention, global or windowed.

    A GLOBAL layer (`window=0`) sees every earlier key: its flash forward
    costs sq * sk / 2 logits a head under causality and its cache keeps
    every token (`cache_bytes_per_token` a token). A WINDOW layer sees the
    last `window` keys: `flops()` prices sq * window logits, the flash
    forward runs two key tiles a query tile whatever sk is, and a cache
    need keep `cache_tokens_kept` = min(context, window) tokens, which is
    what the serving pool gives it (`kv_keep`, runtime/kv_pool.py)."""

    op_type = OperatorType.OP_MULTIHEAD_ATTENTION
    needs_rng = True
    wants_shard_ctx = True  # executor passes (mesh, axis_map) for SP lowering
    # implements the cache protocol generate() and the serving engine drive
    # (init_cache ... gather_paged_kv); ops/mla.py is the other op that does
    kv_cache_protocol = True
    # `chunk_forward` takes a traced start (a chunk loop's body)
    traced_chunk_start = True
    kernel_phase = "core"   # profiler.scope_table: an unnamed Mosaic call

    def __init__(self, model, name, inputs, embed_dim: int, num_heads: int,
                 kdim: int = 0, vdim: int = 0, dropout: float = 0.0,
                 bias: bool = True, add_bias_kv: bool = False,
                 add_zero_attn: bool = False, causal: bool = False,
                 num_kv_heads: int = 0, rope: bool = False,
                 rope_theta: float = 10000.0, qk_norm=False,
                 eps: float = 1e-6, window: int = 0,
                 flash_chunks: bool = False, softmax_scale=None,
                 rope_dim: int = 0, sink=None, value_scale: float = 1.0):
        super().__init__(model, name, inputs)
        if add_bias_kv or add_zero_attn:
            raise NotImplementedError(
                "add_bias_kv/add_zero_attn are not supported yet "
                "(reference cuDNN MHA also lacked them)")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        # grouped-query attention (net-new vs the reference's cuDNN MHA):
        # k/v project to num_kv_heads and are broadcast to num_heads query
        # groups before the score matmul — k/v params and gradient-sync
        # volume shrink by heads/kv_heads
        self.num_kv_heads = num_kv_heads or num_heads
        assert num_heads % self.num_kv_heads == 0, (
            f"num_heads {num_heads} must be a multiple of num_kv_heads "
            f"{self.num_kv_heads}")
        # rotary position embedding, applied to q/k after projection and
        # BEFORE the attention-path dispatch: the op sees GLOBAL (B,S,H,D)
        # tensors, so positions are absolute even when a strategy shards
        # the sequence dim (ring/Ulysses lowering happens further down)
        self.rope = rope
        self.rope_theta = rope_theta
        # QK-norm (OLMoE, OLMo-2): RMSNorm with a learned scale over the
        # WHOLE q and k projections (all heads together), after the
        # projection and before the head split's RoPE. It lives in
        # _project_qkv, the one place every lowering shares, so the KV
        # cache holds k already normed and no attention kernel knows of it
        # "head" (EXAONE, Qwen3): the same norm over each head's own
        # entries, one learned vector of the head size for q and one for k
        if qk_norm not in (False, True, "head"):
            raise ValueError(
                f"qk_norm={qk_norm!r}: False, True (over all heads of a "
                "position) or 'head' (over each head's entries)")
        self.qk_norm = qk_norm
        self.eps = eps
        # a WINDOW layer (sliding-window attention): position i sees keys
        # i - window < j <= i, the position itself included; 0 = a global
        # layer, which sees every earlier key. `_sees` is the one
        # definition every path masks by; the flash forward and the paged
        # kernel take the window as a static parameter. A window layer
        # keeps only its window in the serving engine's pool (`kv_keep`):
        # its page table there is a ring indexed by SEQUENCE position.
        if window < 0 or (window and not causal):
            raise ValueError(
                f"{name}: window={window} needs causal attention and a "
                "positive size")
        self.window = int(window)
        # a prefill chunk attends its prefix through the flash forward
        # (bottom-right aligned) instead of the einsum whose f32 logits
        # are chunk x prefix x heads; off, `chunk_forward` is what the
        # prefix-hit prefill of every other model compiles
        self.flash_chunks = bool(flash_chunks)
        # kdim/vdim are total projection sizes (reference kProjSize*num_heads
        # semantics via cudnnSetAttnDescriptor, attention.cu:533-570)
        self.kdim = kdim if kdim > 0 else embed_dim
        self.vdim = vdim if vdim > 0 else embed_dim
        self.dropout = dropout
        self.bias = bias
        self.causal = causal
        assert embed_dim % num_heads == 0
        assert self.kdim % num_heads == 0 and self.vdim % num_heads == 0
        self.head_dim = embed_dim // num_heads
        self.qk_head_dim = self.kdim // num_heads
        self.v_head_dim = self.vdim // num_heads
        # what the logits q k^T are multiplied by before the softmax, in
        # every path (dense, flash, chunk, decode, both paged impls): the
        # convention 1 / sqrt(head size) unless the model states another
        # (Granite's `attention_multiplier`)
        self.softmax_scale = (1.0 / math.sqrt(self.qk_head_dim)
                              if softmax_scale is None
                              else float(softmax_scale))
        # rotary over the first `rope_dim` entries of a head (0 = all of
        # them): a partial rotary factor
        self.rope_dim = int(rope_dim)
        if rope:
            assert (self.rope_dim or self.qk_head_dim) % 2 == 0 \
                and self.rope_dim <= self.qk_head_dim, \
                "RoPE needs an even number of a head's entries"
        # a SINK: a learned logit a query head that joins the softmax's
        # denominator and adds no value, p_ij = exp(s_ij) / (exp(b_h) +
        # sum_j' exp(s_ij')), in every path that takes a softmax (`_softmax`,
        # the flash forward, the paged kernel). The argument is the standard
        # deviation of the seeded draw (a checkpoint's values replace it);
        # None = no sink and no weight, and every kernel is the one it was.
        self.sink = None if sink is None else float(sink)
        # v = value_scale * x Wv, applied where v is projected, so a cache
        # holds the scaled value and no attention path knows of it
        self.value_scale = float(value_scale)
        self.q_in = inputs[0].dims[-1]
        self.k_in = inputs[1].dims[-1]
        self.v_in = inputs[2].dims[-1]
        self.finalize()

    def output_shapes(self):
        q = self.inputs[0].dims
        return [tuple(q[:-1]) + (self.embed_dim,)], [self.inputs[0].dtype]

    def weights(self) -> List[WeightSpec]:
        kvh = self.num_kv_heads
        ws = [
            WeightSpec("wq", (self.q_in, self.num_heads, self.qk_head_dim),
                       init="glorot", fan=(self.q_in, self.kdim)),
            WeightSpec("wk", (self.k_in, kvh, self.qk_head_dim),
                       init="glorot",
                       fan=(self.k_in, kvh * self.qk_head_dim)),
            WeightSpec("wv", (self.v_in, kvh, self.v_head_dim),
                       init="glorot",
                       fan=(self.v_in, kvh * self.v_head_dim)),
            WeightSpec("wo", (self.num_heads, self.v_head_dim, self.embed_dim),
                       init="glorot", fan=(self.vdim, self.embed_dim)),
        ]
        if self.qk_norm == "head":
            ws += [WeightSpec("q_norm", (self.qk_head_dim,), init="one"),
                   WeightSpec("k_norm", (self.qk_head_dim,), init="one")]
        elif self.qk_norm:
            ws += [WeightSpec("q_norm", (self.kdim,), init="one"),
                   WeightSpec("k_norm", (kvh * self.qk_head_dim,),
                              init="one")]
        if self.sink is not None:
            ws.append(WeightSpec("sink", (self.num_heads,), init="normal",
                                 init_args=(0.0, self.sink)))
        if self.bias:
            ws += [WeightSpec("bias_q", (self.num_heads, self.qk_head_dim), init="zero"),
                   WeightSpec("bias_k", (kvh, self.qk_head_dim), init="zero"),
                   WeightSpec("bias_v", (kvh, self.v_head_dim), init="zero"),
                   WeightSpec("bias_o", (self.embed_dim,), init="zero")]
        return ws

    def _project_qkv(self, params, q, k, v, rope_offset=0):
        """Shared projection: (B,S,D) x (D,H,Hd) -> (B,S,H,Hd) for q and
        (B,S,KVH,Hd) for k/v, bias and RoPE applied, BEFORE any GQA
        broadcast — the KV cache stores this pre-broadcast layout."""
        with jax.named_scope("project"):
            qh = jnp.einsum("bsd,dhk->bshk", q, params["wq"])
            kh = jnp.einsum("bsd,dhk->bshk", k, params["wk"])
            vh = jnp.einsum("bsd,dhk->bshk", v, params["wv"])
            if self.bias:
                qh = qh + params["bias_q"]
                kh = kh + params["bias_k"]
                vh = vh + params["bias_v"]
            if self.qk_norm == "head":
                qh = self._head_rms_norm(qh, params["q_norm"])
                kh = self._head_rms_norm(kh, params["k_norm"])
            elif self.qk_norm:
                qh = self._whole_rms_norm(qh, params["q_norm"])
                kh = self._whole_rms_norm(kh, params["k_norm"])
            if self.value_scale != 1.0:
                vh = vh * jnp.asarray(self.value_scale, vh.dtype)
            if self.rope:
                qh = _apply_rope(qh, self.rope_theta, rope_offset,
                                 self.rope_dim)
                kh = _apply_rope(kh, self.rope_theta, rope_offset,
                                 self.rope_dim)
        return qh, kh, vh

    def _sink_of(self, params):
        """The (H,) sink logits in float32, or None for a layer without."""
        return (None if self.sink is None
                else params["sink"].astype(jnp.float32))

    @staticmethod
    def _softmax(logits, sink=None):
        """Softmax over the last axis of f32 `logits`; `sink` (broadcastable
        to logits[..., :1]) joins the denominator as one more logit whose
        probability is dropped."""
        if sink is None:
            return jax.nn.softmax(logits, axis=-1)
        sink = jnp.broadcast_to(sink, logits.shape[:-1] + (1,))
        return jax.nn.softmax(
            jnp.concatenate([logits, sink.astype(logits.dtype)], axis=-1),
            axis=-1)[..., :-1]

    def _whole_rms_norm(self, xh, scale):
        """RMSNorm of a (B, S, H, Hd) projection over all H*Hd entries of
        a position, statistics in f32."""
        x = xh.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=(-2, -1), keepdims=True)
        x = x * jax.lax.rsqrt(ms + self.eps) \
            * scale.astype(jnp.float32).reshape(xh.shape[-2:])
        return x.astype(xh.dtype)

    def _head_rms_norm(self, xh, scale):
        """RMSNorm of a (B, S, H, Hd) projection over each head's own Hd
        entries, statistics in f32."""
        x = xh.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(ms + self.eps) * scale.astype(jnp.float32)
        return x.astype(xh.dtype)

    def _sees(self, q_pos, k_pos):
        """Whether the query at sequence position `q_pos` sees the key at
        `k_pos` (broadcast against each other): causal, and inside the
        window where the layer has one."""
        seen = k_pos <= q_pos
        if self.window:
            seen = seen & (k_pos > q_pos - self.window)
        return seen

    def kv_keep(self):
        """What of a sequence's keys and values a cache must keep for this
        op: None = all of them, else the last `window` positions (the
        serving pool groups its ops by this: runtime/kv_pool.py)."""
        return self.window or None

    def _broadcast_kv(self, kh, vh):
        if self.num_kv_heads != self.num_heads:
            # GQA: broadcast each kv head to its query group; downstream
            # paths (flash / ring / einsum) then see plain MHA shapes
            rep = self.num_heads // self.num_kv_heads
            with jax.named_scope("project"):
                kh = jnp.repeat(kh, rep, axis=2)
                vh = jnp.repeat(vh, rep, axis=2)
        return kh, vh

    def _out_proj(self, params, ctx):
        """(B, S, H, d_v) head outputs -> (B, S, D): one matmul over the
        (B, S, H * d_v) rows as they lie (where the flash kernels leave
        them; a contraction over two dims makes XLA copy ctx into a layout
        of its own first)."""
        with jax.named_scope("out"):
            wo = params["wo"]
            out = ctx.reshape(ctx.shape[:2] + (-1,)) \
                @ wo.reshape(-1, wo.shape[-1])
            if self.bias:
                out = out + params["bias_o"]
        return out

    def forward(self, params, xs, *, training=False, rng=None, shard_ctx=None):
        q, k, v = xs[0], xs[1], xs[2]
        qh, kh, vh = self._project_qkv(params, q, k, v)
        scale = self.softmax_scale

        seq_axes = []
        if shard_ctx is not None:
            seq_axes = [ax for ax, d in (shard_ctx.get("axis_map") or {}).items()
                        if d == 1 and shard_ctx["mesh"].shape[ax] > 1]
        if seq_axes:
            if self.window:
                raise NotImplementedError(
                    f"{self.name}: ring / Ulysses attention has no window; "
                    "a window layer's sequence dim cannot be sharded")
            if self.sink is not None:
                raise NotImplementedError(
                    f"{self.name}: ring / Ulysses attention has no sink; a "
                    "layer with one cannot shard its sequence dim")
            kh, vh = self._broadcast_kv(kh, vh)
            with jax.named_scope("core"):
                ctx = self._sp_attention(qh, kh, vh, shard_ctx, seq_axes,
                                         scale, training, rng)
        else:
            ctx = self._dense_attention(qh, kh, vh, scale, training, rng,
                                        shard_ctx,
                                        sink=self._sink_of(params))
        return [self._out_proj(params, ctx)]

    # ---- KV-cache inference path (runtime/generation.py) -------------------
    #
    # Net-new vs the reference: its inference story is CompMode::
    # COMP_MODE_INFERENCE (ffconst.h:1-130) — the training graph run
    # forward-only, re-attending the full prefix every step. The TPU
    # rebuild adds the modern O(1)-per-token path: a static-shape KV cache
    # updated with lax.dynamic_update_slice (XLA-friendly: one program for
    # every decode step) storing PRE-broadcast kv heads, so GQA shrinks
    # cache HBM by heads/kv_heads.

    def init_cache(self, batch: int, max_len: int, dtype):
        return {
            "k": jnp.zeros((batch, max_len, self.num_kv_heads,
                            self.qk_head_dim), dtype),
            "v": jnp.zeros((batch, max_len, self.num_kv_heads,
                            self.v_head_dim), dtype),
        }

    def prefill_forward(self, params, xs, cache):
        """Full-prompt forward that also fills cache[:, :S]. Reuses the
        dense attention path (flash on TPU) for the prompt itself."""
        qh, kh, vh = self._project_qkv(params, xs[0], xs[1], xs[2])
        with jax.named_scope("project"):
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], kh.astype(cache["k"].dtype), (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], vh.astype(cache["v"].dtype), (0, 0, 0, 0)),
            }
        scale = self.softmax_scale
        ctx = self._dense_attention(qh, kh, vh, scale, False, None, None,
                                    sink=self._sink_of(params))
        return self._out_proj(params, ctx), new_cache

    def _grouped_cache_attention(self, qh, ck, cv, live, sink=None):
        """Shared cache-attention body for the decode and chunked-prefill
        paths: q (B, C, H, Dh) against cached k/v (B, L, KVH, Dh) with a
        `live` mask broadcastable to (B, KVH, G, C, L) and the layer's
        `sink` logits ((H,) f32 or None). The GQA grouping
        reshapes q to (KVH, G) groups — consecutive query heads share a
        kv head, matching _broadcast_kv's jnp.repeat layout — so the
        broadcast is never materialized. f32 scores/softmax."""
        b, c = qh.shape[0], qh.shape[1]
        kvh = self.num_kv_heads
        grp = self.num_heads // kvh
        scale = self.softmax_scale
        with jax.named_scope("core"):
            qg = qh.reshape(b, c, kvh, grp, self.qk_head_dim)
            logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, ck.astype(qh.dtype),
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(live, logits, jnp.finfo(jnp.float32).min)
            if sink is not None:
                sink = sink.reshape(1, kvh, grp, 1, 1)
            probs = self._softmax(logits, sink).astype(qh.dtype)
            ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs,
                             cv.astype(qh.dtype))
            return ctx.reshape(b, c, self.num_heads, self.v_head_dim)

    def chunk_forward(self, params, xs, cache, start):
        """Chunked prefill: a (B, C, D) slab of prompt positions
        [start, start+C) writes its k/v into the cache and attends the
        STATIC prefix slice [0, start+C) with the causal rule (position j
        attends idx <= start + j) — O(C * prefix) score memory, and the
        unwritten decode tail of the cache is never touched. Same mask
        and positions as the whole-prompt pass; logits are bitwise-equal
        to it on the einsum path (a flash-prefill backend accumulates in
        a different order, so there equality is within kernel tolerance —
        runtime/generation.py notes). A window layer attends only the
        slice that holds the chunk and the window before it. With
        `flash_chunks` the chunk takes the flash forward, bottom-right
        aligned, where the backend runs it (`flash_eligible`)."""
        qh, kh, vh = self._project_qkv(params, xs[0], xs[1], xs[2],
                                       rope_offset=start)
        with jax.named_scope("project"):
            ck = jax.lax.dynamic_update_slice(
                cache["k"], kh.astype(cache["k"].dtype), (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vh.astype(cache["v"].dtype), (0, start, 0, 0))
        c = qh.shape[1]
        if not isinstance(start, int):
            # a TRACED start: the body of a chunk loop compiled once
            # (runtime/generation.py `_prefill`, `loop`). No static slice
            # of the prefix exists, so the chunk attends the whole cache
            # under the same rule; the rows behind it are not seen
            if self.window or self.flash_chunks:
                raise NotImplementedError(
                    f"{self.name}: a chunk loop's traced start has no "
                    "window slice and no flash tile alignment")
            live = self._sees((start + jnp.arange(c))[:, None],
                              jnp.arange(ck.shape[1])[None, :])
            ctx = self._grouped_cache_attention(
                qh, ck, cv, live[None, None, None, :, :],
                sink=self._sink_of(params))
            return self._out_proj(params, ctx), {"k": ck, "v": cv}
        end = start + c  # python ints: a static slice of the live prefix
        lo = 0
        if self.window:
            # whole flash tiles back from the chunk, the window at least
            from flexflow_tpu.ops.pallas_kernels import _WINDOW_BLOCK

            lo = max(0, start - -(-self.window // _WINDOW_BLOCK)
                     * _WINDOW_BLOCK)
        ks, vs = ck[:, lo:end], cv[:, lo:end]
        if self.flash_chunks and flash_eligible(
                getattr(self.model, "config", None), True, c, end - lo):
            ctx = self._flash_dense(qh, ks.astype(qh.dtype),
                                    vs.astype(qh.dtype), self.softmax_scale,
                                    None, sink=self._sink_of(params))
        else:
            live = self._sees((start + jnp.arange(c))[:, None],
                              jnp.arange(lo, end)[None, :])
            ctx = self._grouped_cache_attention(
                qh, ks, vs, live[None, None, None, :, :],
                sink=self._sink_of(params))
        return self._out_proj(params, ctx), {"k": ck, "v": cv}

    def encode_kv(self, params, enc):
        """Cross-attention's static k/v, projected ONCE from the encoder
        states at the start of a seq2seq decode (runtime/
        seq2seq_generation.py) — every decode step reuses them, so the
        per-token cost of cross-attention is one q projection + one
        (1 x S_src) attention, never a re-projection of the source."""
        _, kh, vh = self._project_qkv(params, enc, enc, enc)
        return {"k": kh, "v": vh}

    def cross_forward_cached(self, params, xs, kv):
        """Cross-attention over the static encoder k/v (encode_kv) for a
        (B, C) decoder slab — C = prompt length at prefill, 1 per decode
        step. Non-causal: every query attends the whole source."""
        qh, _, _ = self._project_qkv(params, xs[0], xs[0], xs[0])
        live = jnp.ones((1, 1, 1, 1, kv["k"].shape[1]), bool)
        ctx = self._grouped_cache_attention(qh, kv["k"], kv["v"], live,
                                            sink=self._sink_of(params))
        return self._out_proj(params, ctx)

    def query_forward(self, params, xs, cache, rope_pos, row_lengths):
        """Read-only cache query (ragged CHUNKED prefill's gather pass,
        runtime/generation.py): a (B, 1) slab holding each row's LAST
        prompt token, whose k/v the chunk passes already wrote — compute
        only q at the row's own position (`rope_pos` = row_lengths - 1)
        and attend the row's live prefix idx < row_lengths. The cache is
        returned untouched (re-writing the slot would be idempotent but
        pointless work)."""
        qh, _, _ = self._project_qkv(params, xs[0], xs[1], xs[2],
                                     rope_offset=rope_pos)
        idx = jnp.arange(cache["k"].shape[1])
        live = idx[None, :] < row_lengths[:, None]
        if self.window:
            live = live & self._sees(rope_pos[:, None], idx[None, :])
        ctx = self._grouped_cache_attention(
            qh, cache["k"], cache["v"], live[:, None, None, None, :],
            sink=self._sink_of(params))
        return self._out_proj(params, ctx), cache

    def decode_forward(self, params, xs, cache, pos, rope_pos=None,
                       row_lengths=None, prompt_len=None):
        """One-token step: write this token's k/v at slot `pos` (traced
        scalar), attend q over the live cache prefix.

        Ragged right-padded prompts (runtime/generation.py): `row_lengths`
        (B,) marks each row's true prompt length and `prompt_len` the
        padded width; slots in [row_length, prompt_len) hold garbage k/v
        from pad positions and are masked out, and `rope_pos` (B,) rotates
        the new token at its LOGICAL position (row_length + step), not its
        cache slot."""
        qh, kh, vh = self._project_qkv(
            params, xs[0], xs[1], xs[2],
            rope_offset=pos if rope_pos is None else rope_pos)
        with jax.named_scope("project"):
            ck = jax.lax.dynamic_update_slice(
                cache["k"], kh.astype(cache["k"].dtype), (0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vh.astype(cache["v"].dtype), (0, pos, 0, 0))
        idx = jnp.arange(ck.shape[1])
        if row_lengths is None:
            live = self._sees(pos, idx)[None, :]
        else:
            live = (idx[None, :] < row_lengths[:, None]) \
                | ((idx[None, :] >= prompt_len) & (idx[None, :] <= pos))
            if self.window:
                # a slot past the pad holds the position its token has in
                # the sequence, not its index in the cache
                at = jnp.where(idx[None, :] < prompt_len, idx[None, :],
                               idx[None, :] - prompt_len
                               + row_lengths[:, None])
                live = live & self._sees(rope_pos[:, None], at)
        ctx = self._grouped_cache_attention(
            qh, ck, cv, live[:, None, None, None, :],
            sink=self._sink_of(params))
        return self._out_proj(params, ctx), {"k": ck, "v": cv}

    # ---- paged KV cache (runtime/serving.py) ------------------------------
    #
    # Continuous-batching serving splits the cache into a POOL of fixed
    # (page_size, KVH, Dh) blocks shared by every slot; a per-slot page
    # table maps logical position j to pool page table[j // page_size],
    # offset j % page_size. Long and short requests then share HBM instead
    # of every slot preallocating max_len — the serving-side analog of the
    # partition-don't-pad philosophy the training side applies to sharding.

    def init_paged_cache(self, num_pages: int, page_size: int, dtype,
                         kv_dtype=None):
        """A pool of `num_pages` KV pages. Page 0 is reserved by the
        serving engine as a scratch page (inactive slots write there), so
        callers size num_pages as 1 + worst-case live pages.

        ``kv_dtype`` (FFConfig.kv_cache_dtype) picks the storage:
        None/'native' stores ``dtype`` (the pre-quant pool), 'bf16'
        stores bfloat16 (plain cast), 'int8'/'fp8' store quantized
        payload plus per-(page, kv-head) f32 scales alongside — the
        ``k_scale``/``v_scale`` entries ride the same page ids as the
        payload, so the allocator/trie/COW machinery is untouched."""
        sdtype, qmax = kv_storage_dtype(kv_dtype)
        store = sdtype if sdtype is not None else dtype
        # heads narrower than the 128 lanes: `pack` neighbouring KV heads
        # share a row (`pool_pack`); at pack 1 the rows are (KVH, D)
        pack = self.pool_pack(quantized=qmax is not None)
        rows = (self.num_kv_heads // pack,)
        if self._flat_pack(pack):
            # ONE row a token: no row dim at all (a dim of one before the
            # lanes is stored padded to two sublanes)
            rows = ()
        pool = {
            "k": jnp.zeros((num_pages, page_size, *rows,
                            self.qk_head_dim * pack), store),
            "v": jnp.zeros((num_pages, page_size, *rows,
                            self.v_head_dim * pack), store),
        }
        if qmax is not None:
            pool["k_scale"] = jnp.zeros(
                (num_pages, self.num_kv_heads), jnp.float32)
            pool["v_scale"] = jnp.zeros(
                (num_pages, self.num_kv_heads), jnp.float32)
        return pool

    def pool_pack(self, quantized: bool = False) -> int:
        """How many neighbouring KV heads share one row of a pool page.
        A head narrower than the chip's 128 lanes (64) cannot be the minor
        dim of an array in HBM without padding: the device then stores the
        pool transposed, every reader and writer pays a copy of it, and
        Mosaic refuses to slice a page out of it. So the pool of such an op
        is (pages, page_size, KVH / pack, pack x D) with pack x D = 128: the
        same bytes in the same order as (pages, page_size, KVH, D), only
        the trailing dims merged, so whatever is gathered from it or
        scattered to it is reshaped at the boundary (`_pool_rows`,
        `_pool_heads`) and the pool itself never is. 1 (the shape every
        head of 128 has) for a quantized pool, whose scales are per KV
        head, and for a window layer's ring.

        A head whose width the lanes do not divide (keys of 192 beside
        values of 128) has the same trouble and one more: Mosaic refuses to
        copy a 192-lane slice of a page. Its pool is FLAT: pack = KVH, one
        row a token that holds every KV head side by side, (pages,
        page_size, KVH x D) with KVH x D whole lane tiles, for keys and
        values alike and for a window layer's ring too; the paged kernel
        then contracts a token's whole row against a query row that is zero
        outside its own head's lanes (`paged_attention_fwd_pallas`,
        `kv_heads`)."""
        d, dv, kvh = self.qk_head_dim, self.v_head_dim, self.num_kv_heads
        if quantized:
            return 1
        if (d % 128 or dv % 128) and d >= 128 and kvh > 1 \
                and not (kvh * d) % 128 and not (kvh * dv) % 128:
            return kvh
        if self.window or d != dv or d >= 128 or 128 % d:
            return 1
        pack = 128 // d
        return pack if kvh % pack == 0 else 1

    def _flat_pack(self, pack: int) -> bool:
        """Whether `pool_pack`'s answer is the flat layout (not the narrow
        heads' rows of 128 lanes)."""
        return pack > 1 and pack == self.num_kv_heads \
            and self.qk_head_dim >= 128

    @staticmethod
    def _pool_rows(x, pool):
        """Rows (.., KVH, D) in the pool's own trailing dims."""
        return x.reshape(*x.shape[:-2], *pool.shape[2:])

    def _pool_heads(self, x, d):
        """What was read from a pool (.., KVH / pack, pack x D), or from a
        flat one (.., KVH x D), as (.., KVH, D)."""
        lead = x.shape[:-1] if self._flat_pack(self.pool_pack()) \
            else x.shape[:-2]
        return x.reshape(*lead, self.num_kv_heads, d)

    def paged_prefill_write(self, cache, kh, vh, pages, impl="einsum"):
        """Scatter a slot's contiguous prefill k/v (1, L, KVH, Dh) into
        pool pages `pages` ((n_pages,) int32, n_pages = ceil(L /
        page_size)). The tail of the last page beyond L holds junk; it is
        either overwritten by decode tokens or masked by the live rule.
        Quantized pools ('k_scale' present) compute each page's
        per-(page, head) scale over the whole just-written page — the
        zero pad tail never inflates an amax — and replace scale AND
        payload (prefill only ever targets the request's own fresh
        pages, so a wholesale replace can never touch shared state).

        ``impl``: 'einsum' is the big-scatter parity oracle below;
        'pallas' routes to pallas_kernels.paged_prefill_write_pallas,
        which scatters page-at-a-time from VMEM (ISSUE 18) and is
        bitwise against the oracle (tests/test_pallas_paged.py)."""
        if impl == "pallas":
            from flexflow_tpu.ops.pallas_kernels import \
                paged_prefill_write_pallas
            return paged_prefill_write_pallas(cache, kh, vh, pages)
        with jax.named_scope("core"):
            return self._prefill_write_xla(cache, kh, vh, pages)

    def _prefill_write_xla(self, cache, kh, vh, pages):
        """`paged_prefill_write`'s 'einsum' branch: one scatter a tensor."""
        page_size = cache["k"].shape[1]
        n_pages = pages.shape[0]
        pad = n_pages * page_size - kh.shape[1]
        quantized = "k_scale" in cache
        out = dict(cache)

        def paged(x):
            x = x[0]                                        # (L, KVH, Dh)
            if pad:
                x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
            return x.reshape(n_pages, page_size, *x.shape[1:])

        for name, x in (("k", kh), ("v", vh)):
            pool = cache[name]
            if not quantized:
                out[name] = pool.at[pages].set(
                    self._pool_rows(paged(x), pool).astype(pool.dtype))
                continue
            qmax = storage_qmax(pool.dtype)
            pf = paged(x).astype(jnp.float32)
            scale = page_scale(pf, qmax)                    # (n_pages, KVH)
            out[name] = pool.at[pages].set(
                page_quantize(pf, scale, qmax, pool.dtype))
            out[name + "_scale"] = cache[name + "_scale"].at[pages].set(
                scale)
        return out

    def _paged_append(self, cache, kh, vh, page_ids, offs):
        """Write ONE token per slot at ``(page_ids[b], offs[b])`` —
        the decode-append half of the pool-write protocol. Full-width
        pools scatter the position in place. Quantized pools re-quantize
        the TARGET page against a running-max per-(page, head) scale:
        gather the page, dequantize at the current scale, insert the new
        token, grow the scale to cover it, requantize, scatter back.
        Requantization at an unchanged scale is exact (page_quantize),
        so older tokens only re-round when a genuinely larger token
        arrives — part of the documented per-dtype divergence budget
        (docs/serving.md). Appends only ever land in a request's own
        private pages (write_pos >= prompt_pad > the shared prefix), so
        the copy-on-write rule is preserved: published pages are never
        gathered OR scattered here."""
        quantized = "k_scale" in cache
        out = dict(cache)
        rows = jnp.arange(page_ids.shape[0])
        for name, x in (("k", kh), ("v", vh)):
            pool = cache[name]
            if not quantized:
                out[name] = pool.at[page_ids, offs].set(
                    self._pool_rows(x, pool).astype(pool.dtype))
                continue
            qmax = storage_qmax(pool.dtype)
            sc = cache[name + "_scale"]
            cur = sc[page_ids]                              # (B, KVH)
            pf = page_dequantize(pool[page_ids], cur)       # (B,ps,KVH,D)
            pf = pf.at[rows, offs].set(x.astype(jnp.float32))
            amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
            new = jnp.maximum(cur, amax / qmax)             # (B, KVH)
            out[name] = pool.at[page_ids].set(
                page_quantize(pf, new, qmax, pool.dtype))
            out[name + "_scale"] = sc.at[page_ids].set(new)
        return out

    def scatter_cache_tail(self, cache, contiguous, p0: int, pages,
                           impl="einsum"):
        """Write a request's contiguous prefill cache past position p0
        into its own fresh pool `pages` (paged_prefill_write on the k and
        v slices)."""
        return self.paged_prefill_write(
            cache, contiguous["k"][:, p0:], contiguous["v"][:, p0:], pages,
            impl=impl)

    def cache_bytes_per_token(self) -> int:
        """bf16 bytes one cached token takes in this op's pool. A window
        layer holds that for at most `cache_tokens_kept` tokens a
        sequence, whatever its length."""
        return self.num_kv_heads * (self.qk_head_dim + self.v_head_dim) * 2

    def cache_tokens_kept(self, context: int) -> int:
        """How many of a sequence's `context` tokens this op's cache has
        to hold: all of them, or the window."""
        return min(context, self.window) if self.window else context

    def decode_span_counts(self, context, page_size=None):
        """Counts a decode dispatch adds to its span beside the engine's
        own: none for plain attention."""
        return {}

    @staticmethod
    def paged_turn_pages(cache, width: int) -> int:
        """Pages a turn of the paged kernel takes on the pool `cache` under
        a page table `width` pages wide: a static of the shapes the kernel
        sees, for the engine's counters."""
        from flexflow_tpu.ops.pallas_kernels import paged_turn_pages

        ps, *rows, lanes = cache["k"].shape[1:]
        return paged_turn_pages(ps, rows[0] if rows else 1, width, lanes)

    def export_page(self, cache, page):
        """Slice pool page(s) out as the serializable migration payload
        — the unit both the prefill->decode fleet handoff and the
        HBM->host tier demotion move (runtime/kv_pool.py). ``page`` is a
        scalar or a (n,) index array (ONE gather per pool array serves a
        whole demotion sweep). Returns device arrays (the caller starts
        ``copy_to_host_async`` and resolves to numpy off the hot path);
        quantized pools include the pages' per-kv-head scales so a
        re-imported page is BITWISE the donor's — dequantized attention
        on the importer sees exactly what the donor's decode saw."""
        out = {"k": cache["k"][page], "v": cache["v"][page]}
        for name in ("k_scale", "v_scale"):
            if name in cache:
                out[name] = cache[name][page]
        return out

    def import_page(self, cache, page, payload):
        """Write exported page payload(s) back into the pool at
        ``page`` (scalar, or a (n,) traced index vector — the serving
        engine pads batches to a fixed width with scratch page 0, so
        ONE compiled writer serves every promotion/import batch) — the
        decode half of the handoff and the H2D tier promotion. Payload
        bytes are copied verbatim (no requantization: scales ride the
        payload), so export -> import round-trips bitwise. Only ever
        targets freshly allocated pages (the copy-on-write rule: a
        published page is never written), so a wholesale replace cannot
        touch shared state."""
        out = dict(cache)
        for name, x in payload.items():
            pool = cache[name]
            out[name] = pool.at[page].set(
                jnp.asarray(x).astype(pool.dtype))
        return out

    def gather_paged_kv(self, cache, pages):
        """Read ``pages`` ((n,) int32) out of the pool as a full-width
        (1, n * page_size, KVH, Dh) k/v view — what a prefix-cache hit
        prefill mounts READ-ONLY at the front of its contiguous cache.
        Quantized pools dequantize against the pages' scales here, so
        the borrower attends exactly the (lossy) values the donor's
        decode attention sees."""
        out = {}
        with jax.named_scope("gather"):
            for name in ("k", "v"):
                x = cache[name][pages]                      # (n,ps,KVH,D)
                if name + "_scale" in cache:
                    x = page_dequantize(x, cache[name + "_scale"][pages])
                x = self._pool_heads(x, self.qk_head_dim if name == "k"
                                     else self.v_head_dim)
                out[name] = x.reshape(1, -1, *x.shape[2:])
        return out

    def shared_members_cap(self) -> int:
        """Most slots one group of the paged kernel's shared-page form
        scores against a page fetched once (ops/pallas_kernels.py
        `shared_members_cap`): a static of the op's heads, for the engine
        that forms the groups."""
        from flexflow_tpu.ops.pallas_kernels import shared_members_cap

        return shared_members_cap(self.num_heads)

    def _paged_attention_ctx(self, qh, cache, page_table, write_pos,
                             row_len, prompt_pad, impl, sink=None,
                             shared=None):
        """Shared attention body of the paged decode/verify paths: q
        (B, S, H, Dh) against the updated pool through the per-slot page
        tables, write_pos (B, S) per-position frontiers. Two impls
        (resolve_paged_attention_impl):

          * ``einsum`` — gather the slot's pages into a logical
            (B, L_max, KVH, Dh) cache and run _grouped_cache_attention:
            bitwise the dense-cache computation (tests/test_serving.py),
            the parity oracle. The gather re-materializes the ENTIRE
            pool view in HBM every step; on a quantized pool the
            dequant fuses into the same gather (this branch is also the
            dequant parity oracle).
          * ``pallas`` — paged_attention_fwd_pallas: the page-table
            lookup happens INSIDE the kernel grid, so only the slot's
            live pages stream through VMEM; online softmax replaces the
            materialized (B, L_max) score row. Quantized pages
            dequantize per VMEM tile against their scalar-prefetched
            scales — the wide KV never exists in HBM. Numerics match
            the einsum path to kernel tolerance (accumulation order
            differs); greedy token streams are pinned identical by
            tests/test_pallas_paged.py and test_quantized_serving.py.

        ``shared`` (a decode step's; `pallas_kernels.pack_shared_groups`):
        the groups of slots whose rows begin with the same pool pages. The
        kernel streams such pages once a group on a pool at full width; a
        quantized pool and the einsum oracle read every slot's pages for
        the slot, as without it."""
        resolved = resolve_paged_attention_impl(impl)
        ck, cv = cache["k"], cache["v"]
        if resolved == "pallas":
            from flexflow_tpu.ops.pallas_kernels import \
                paged_attention_fwd_pallas

            scale = self.softmax_scale
            # directly under the op's scope: the device names an unnamed
            # Mosaic call after its innermost scope, and the benchmark's
            # readers know this one as `attn_<i>` (scope_table books it to
            # `kernel_phase`)
            return paged_attention_fwd_pallas(
                qh, ck, cv, page_table, write_pos, row_len, prompt_pad,
                scale, k_scales=cache.get("k_scale"),
                v_scales=cache.get("v_scale"), sink=sink,
                kv_heads=self.num_kv_heads,
                shared=None if "k_scale" in cache else shared)
        b = qh.shape[0]
        max_len = page_table.shape[1] * ck.shape[1]
        with jax.named_scope("gather"):
            gk, gv = ck[page_table], cv[page_table]  # (B, P, ps, KVH, D)
            if "k_scale" in cache:
                gk = page_dequantize(gk, cache["k_scale"][page_table])
                gv = page_dequantize(gv, cache["v_scale"][page_table])
            gk = self._pool_heads(gk, self.qk_head_dim)
            gv = self._pool_heads(gv, self.v_head_dim)
            gk = gk.reshape(b, max_len, *gk.shape[3:])
            gv = gv.reshape(b, max_len, *gv.shape[3:])
        with jax.named_scope("core"):
            idx = jnp.arange(max_len)
            live = (idx[None, None, :] < row_len[:, None, None]) \
                | ((idx[None, None, :] >= prompt_pad[:, None, None])
                   & (idx[None, None, :] <= write_pos[:, :, None]))
        return self._grouped_cache_attention(
            qh, gk, gv, live[:, None, None, :, :], sink=sink)

    def paged_decode_forward(self, params, xs, cache, page_table, write_pos,
                             rope_pos, row_len, prompt_pad, impl=None,
                             shared=None):
        """One continuous-batching decode step over the paged pool.

        xs[0]: (B_slots, 1, D) — each slot's last sampled token embedding
        path. Per-slot (B,) int32 arrays: `write_pos` the logical cache
        position this token occupies, `rope_pos` its LOGICAL sequence
        position (true prompt length + emitted count — bucket padding does
        not shift RoPE), `row_len` the true prompt length and `prompt_pad`
        the bucket-padded prompt width. Live rule per slot (identical to
        decode_forward's ragged rule, per-slot prompt_pad instead of a
        shared prompt_len): j < row_len  OR  prompt_pad <= j <= write_pos.

        The new token's k/v scatters into the pool at (page_table[b,
        write_pos // page_size], write_pos % page_size) — through the
        quantized-append protocol when the pool carries scales
        (_paged_append); attention then runs through
        _paged_attention_ctx — `impl` picks the page-gather einsum
        oracle or the Pallas paged kernel, `shared` names the slots whose
        rows begin with the same pages (a window layer's ring is a slot's
        own: it takes none)."""
        page_size = cache["k"].shape[1]
        qh, kh, vh = self._project_qkv(params, xs[0], xs[1], xs[2],
                                       rope_offset=rope_pos)
        if self.window:
            # a window layer's table is the slot's ring, and its rows are
            # addressed by SEQUENCE position: no bucket pad lies between
            # the prompt and the emitted tokens, the window is one run
            return self._paged_window_decode(params, qh, kh, vh, cache,
                                             page_table, rope_pos, impl)
        with jax.named_scope("project"):
            page_ids = jnp.take_along_axis(
                page_table, (write_pos // page_size)[:, None], axis=1)[:, 0]
            offs = write_pos % page_size
            cache = self._paged_append(cache, kh[:, 0], vh[:, 0], page_ids,
                                       offs)
        ctx = self._paged_attention_ctx(qh, cache, page_table,
                                        write_pos[:, None], row_len,
                                        prompt_pad, impl,
                                        sink=self._sink_of(params),
                                        shared=shared)
        return self._out_proj(params, ctx), cache

    def _paged_window_decode(self, params, qh, kh, vh, cache, ring, pos,
                             impl):
        """`paged_decode_forward` of a window layer. `ring` (B, R) int32:
        the slot's pages, the page of sequence positions [t * page_size,
        (t + 1) * page_size) in column t % R; `pos` (B,) the token's
        sequence position, where it is written and up to where it sees.
        A free slot (pos 0, a zeroed ring) writes and reads scratch page
        0, as on a global layer."""
        page_size, r = cache["k"].shape[1], ring.shape[1]
        with jax.named_scope("project"):
            page_ids = jnp.take_along_axis(
                ring, ((pos // page_size) % r)[:, None], axis=1)[:, 0]
            cache = self._paged_append(cache, kh[:, 0], vh[:, 0], page_ids,
                                       pos % page_size)
        resolved = resolve_paged_attention_impl(impl)
        if resolved == "pallas":
            from flexflow_tpu.ops.pallas_kernels import \
                paged_attention_fwd_pallas

            zero = jnp.zeros_like(pos)
            ctx = paged_attention_fwd_pallas(
                qh, cache["k"], cache["v"], ring, pos[:, None], zero, zero,
                self.softmax_scale,
                k_scales=cache.get("k_scale"), v_scales=cache.get("v_scale"),
                window=self.window, sink=self._sink_of(params),
                kv_heads=self.num_kv_heads)
            return self._out_proj(params, ctx), cache
        b = qh.shape[0]
        with jax.named_scope("gather"):
            gk, gv = cache["k"][ring], cache["v"][ring]  # (B, R, ps, KVH, D)
            if "k_scale" in cache:
                gk = page_dequantize(gk, cache["k_scale"][ring])
                gv = page_dequantize(gv, cache["v_scale"][ring])
            gk = self._pool_heads(gk, self.qk_head_dim)
            gv = self._pool_heads(gv, self.v_head_dim)
            gk = gk.reshape(b, r * page_size, *gk.shape[3:])
            gv = gv.reshape(b, r * page_size, *gv.shape[3:])
        with jax.named_scope("core"):
            # column c holds the newest logical page congruent to it
            last = (pos // page_size)[:, None]                    # (B, 1)
            col = jnp.arange(r)[None, :]
            page = last - (last - col) % r                        # (B, R)
            at = (page[:, :, None] * page_size
                  + jnp.arange(page_size)[None, None, :]).reshape(b, -1)
            live = (at >= 0) & self._sees(pos[:, None], at)
        ctx = self._grouped_cache_attention(
            qh, gk, gv, live[:, None, None, None, :],
            sink=self._sink_of(params))
        return self._out_proj(params, ctx), cache

    def scatter_window_tail(self, cache, contiguous, length, ring,
                            impl="einsum"):
        """What a prefill leaves of a window layer in the pool: the pages
        of the contiguous cache that hold the last window of the prompt's
        `length` (1,) positions, each into its column of the slot's `ring`
        (R,). The rest of the prompt's keys travelled in the program and
        are dropped with it."""
        r = ring.shape[0]
        return self._write_last_pages(
            cache, contiguous, length, r,
            lambda back, t: jax.lax.dynamic_slice_in_dim(ring, t % r, 1),
            impl)

    def _write_last_pages(self, cache, contiguous, length, count, page_of,
                          impl):
        """The `count` pages of the contiguous cache that end at the page of
        position `length` - 1 (as far as the cache has them), each written
        to pool page `page_of(back, t)` ((1,) int32; `back` pages before the
        last, logical page `t`) of `cache`. A page before the sequence's
        first is the first again: the same rows to wherever `page_of` says."""
        page_size = cache["k"].shape[1]
        k, v = contiguous["k"], contiguous["v"]
        pad = -k.shape[1] % page_size
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        last = (length[0] - 1) // page_size
        for back in range(min(count, k.shape[1] // page_size)):
            t = jnp.maximum(last - back, 0)
            with jax.named_scope("project"):
                ks = jax.lax.dynamic_slice_in_dim(k, t * page_size,
                                                  page_size, axis=1)
                vs = jax.lax.dynamic_slice_in_dim(v, t * page_size,
                                                  page_size, axis=1)
                page = page_of(back, t)
            cache = self.paged_prefill_write(cache, ks, vs, page, impl=impl)
        return cache

    def window_snapshot_pages(self, page_size: int) -> int:
        """Pages of `page_size` positions that hold the window before a
        page-aligned position: what a snapshot of this layer is."""
        return -(-self.window // page_size)

    def take_window_snapshot(self, snaps, contiguous, length, snap,
                             impl="einsum"):
        """A window layer's SNAPSHOT at the end of a prefill of `length`
        (1,) positions: the pages of the contiguous cache that end at the
        prompt's last page, into pages [snap * n, (snap + 1) * n) of the
        snapshot arrays `snaps` (the pool's own page format, n =
        `window_snapshot_pages`). Where `length` is a whole number of pages
        that is the window before position `length`, all a later request
        that matches the prompt needs of this layer; elsewhere `snap` is the
        scratch row 0 and nothing reads it."""
        n = self.window_snapshot_pages(snaps["k"].shape[1])
        return self._write_last_pages(
            snaps, contiguous, length, n,
            lambda back, t: jnp.reshape(snap * n + (n - 1 - back),
                                        (1,)).astype(jnp.int32), impl)

    def seed_window_cache(self, contiguous, snaps, snap, p0: int):
        """`take_window_snapshot`'s inverse for a prefix hit at the
        page-aligned match point `p0` (static): the pages of snapshot `snap`
        at positions [p0 - n * page_size, p0) of the contiguous cache, whose
        other rows stay zeros (no tail position's window reaches them)."""
        page_size = snaps["k"].shape[1]
        n = self.window_snapshot_pages(page_size)
        out = dict(contiguous)
        for back in range(n):
            t = max(p0 // page_size - 1 - back, 0)
            got = self.gather_paged_kv(
                snaps, jnp.reshape(snap * n + (n - 1 - back),
                                   (1,)).astype(jnp.int32))
            for name in ("k", "v"):
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    out[name], got[name].astype(out[name].dtype),
                    t * page_size, axis=1)
        return out

    def paged_verify_forward(self, params, xs, cache, page_table, write_pos,
                             rope_pos0, row_len, prompt_pad, impl=None):
        """Speculative-decode verify: a (B, S) slab of candidate tokens
        (S = K draft proposals + 1) scored against the paged pool in ONE
        dispatch (runtime/serving.py).

        Position i of the slab writes its k/v at logical position
        ``write_pos[b, i]`` (the host pre-computes write_pos0 + i clamped
        to the slot's budget) and attends with the decode live rule at its
        own frontier: j < row_len OR prompt_pad <= j <= write_pos[b, i] —
        causality within the slab falls out of the frontier, since slab
        position i's window includes exactly the slab writes <= i plus the
        committed history. k/v written for positions the host later
        REJECTS stay inside the slot's own pages past its write frontier;
        the next dispatch (verify or decode) overwrites them before any
        accepted position can attend them, so rejected-draft garbage is
        never observable. ``rope_pos0`` (B,) is the slab's first LOGICAL
        position; position i rotates at rope_pos0 + i. Attention runs
        through _paged_attention_ctx (same einsum-oracle/Pallas-kernel
        split as decode — the ONE kernel serves both shapes). On a
        quantized pool the slab's positions append SEQUENTIALLY through
        _paged_append (slab position i+1 may land in the page position i
        just requantized; the running-max scale must see them in order),
        so the final pool state is identical across impls — the
        bitwise-pool contract the parity tests pin."""
        if self.window:
            raise NotImplementedError(
                f"{self.name}: a window layer's ring of pages cannot take "
                "back the rows of rejected draft positions; speculative "
                "verification over window layers is not built")
        page_size = cache["k"].shape[1]
        qh, kh, vh = self._project_qkv(params, xs[0], xs[1], xs[2],
                                       rope_offset=rope_pos0)
        page_ids = jnp.take_along_axis(
            page_table, write_pos // page_size, axis=1)       # (B, S)
        offs = write_pos % page_size
        if "k_scale" in cache:
            # S sequential single-token appends = S page round-trips per
            # op per dispatch. Bounded: each is one (B, ps, KVH, D) page
            # vs the table-wide attention that follows, and S = K+1 is
            # small. A single final-scale pass would halve the traffic
            # when the slab stays in one page, but slab positions can
            # span pages — the per-position form is the one that is
            # correct for every (write_pos, page boundary) layout.
            with jax.named_scope("project"):
                for i in range(kh.shape[1]):
                    cache = self._paged_append(cache, kh[:, i], vh[:, i],
                                               page_ids[:, i], offs[:, i])
        else:
            cache = dict(cache)
            with jax.named_scope("project"):
                cache["k"] = cache["k"].at[page_ids, offs].set(
                    self._pool_rows(kh, cache["k"]).astype(
                        cache["k"].dtype))
                cache["v"] = cache["v"].at[page_ids, offs].set(
                    self._pool_rows(vh, cache["v"]).astype(
                        cache["v"].dtype))
        ctx = self._paged_attention_ctx(qh, cache, page_table, write_pos,
                                        row_len, prompt_pad, impl,
                                        sink=self._sink_of(params))
        return self._out_proj(params, ctx), cache

    def _flash_ok(self, qh, kh) -> bool:
        """Use the hand-tiled Pallas flash kernel (ops/pallas_kernels.py) on
        the dense path when the backend runs it natively and the block grid
        divides the sequence (`flash_eligible`). Role parity with the
        reference's tuned vendor kernel (attention.cu:244
        cudnnMultiHeadAttnForward)."""
        return flash_eligible(getattr(self.model, "config", None),
                              self.causal, qh.shape[1], kh.shape[1])

    def _dense_attention(self, qh, kh, vh, scale, training, rng,
                         shard_ctx=None, sink=None):
        """qh (B, S, H, .) against kh, vh (B, S, KVH, .) as projected: the
        flash kernels read a group's one key head through their index maps
        (nothing is repeated in HBM); XLA's forms get the broadcast."""
        use_dropout = training and self.dropout > 0.0 and rng is not None
        # a window or a sink under a gradient is XLA's masked attention: the
        # flash backward kernels carry neither
        if not use_dropout \
                and not ((self.window or sink is not None) and training) \
                and (sink is None or self.causal) \
                and self._flash_ok(qh, kh):
            return self._flash_dense(qh, kh, vh, scale, shard_ctx, sink)
        kh, vh = self._broadcast_kv(kh, vh)
        with jax.named_scope("core"):
            return self._xla_attention(qh, kh, vh, scale, training, rng,
                                       use_dropout, sink)

    def _xla_attention(self, qh, kh, vh, scale, training, rng, use_dropout,
                       sink=None):
        """`_dense_attention` where flash is refused: blockwise past
        BLOCKWISE_SEQ_THRESHOLD, else the plain einsum."""
        sq, sk = qh.shape[1], kh.shape[1]
        if max(sq, sk) > BLOCKWISE_SEQ_THRESHOLD and not self.window \
                and sink is None and self.qk_head_dim == self.v_head_dim:
            # long-context dense fallback for flash-refused shapes (CPU
            # backend, dropout, causal with sq > sk): pure-JAX blockwise
            # online-softmax scan (O(block) working set) with rematerialized
            # backward — an einsum here would materialize the S x S
            # probability tensor. Block size degrades to any divisor of sk
            # like _pick_block.
            from flexflow_tpu.parallel.ring_attention import blockwise_attention

            block = next((b for b in (512, 256, 128, 64, 32, 16, 8)
                          if sk % b == 0), sk)
            blk = functools.partial(blockwise_attention, causal=self.causal,
                                    scale=scale, block_size=block,
                                    dropout_rate=self.dropout if use_dropout
                                    else 0.0,
                                    dropout_rng=rng if use_dropout else None)
            return jax.checkpoint(blk)(qh, kh, vh)
        logits = jnp.einsum("bqhk,bshk->bhqs", qh, kh,
                            preferred_element_type=jnp.float32) * scale
        if self.causal:
            sq, sk = logits.shape[-2], logits.shape[-1]
            mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            if self.window:
                mask = self._sees((sk - sq + jnp.arange(sq))[:, None],
                                  jnp.arange(sk)[None, :])
            logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
        probs = self._softmax(
            logits, None if sink is None else sink[None, :, None, None]
        ).astype(qh.dtype)
        if training and self.dropout > 0.0 and rng is not None:
            keep = 1.0 - self.dropout
            probs = jnp.where(jax.random.bernoulli(rng, keep, probs.shape),
                              probs / keep, 0.0)
        return jnp.einsum("bhqs,bshk->bqhk", probs, vh)

    def _flash_dense(self, qh, kh, vh, scale, shard_ctx, sink=None):
        """Dense flash with multi-chip awareness. A pallas_call is a Mosaic
        custom call the XLA SPMD partitioner cannot split: left inside the
        GSPMD-partitioned program it would be replicated (all-gathers around
        attention — silent loss of data/tensor-parallel scaling). When the
        strategy shards the batch or head dim over a >1 mesh axis, run the
        kernel per-shard inside shard_map (embarrassingly parallel — no
        collectives), the same pattern the ring path uses for seq."""
        from flexflow_tpu.ops.pallas_kernels import (flash_attention,
                                                     flash_attention_window)

        def flash(q, k, v):
            # forward only with a window or a sink: `_dense_attention` saw
            # to it
            if self.window or sink is not None:
                return flash_attention_window(q, k, v, self.window or None,
                                              scale, sink=sink)
            return flash_attention(q, k, v, self.causal, scale)

        mesh = (shard_ctx or {}).get("mesh")
        if mesh is None:
            return flash(qh, kh, vh)
        from flexflow_tpu.parallel import shard_entries

        axis_map = (shard_ctx or {}).get("axis_map") or {}
        # indivisible groups drop out alone (GSPMD pads that dim instead),
        # keeping whatever parallelism remains valid
        ent = shard_entries(mesh, axis_map, qh.shape, (0, 2))
        if ent[0] is None and ent[2] is None:
            return flash(qh, kh, vh)
        if shard_entries(mesh, axis_map, kh.shape, (0, 2)) != ent:
            # the key heads do not divide as the query heads do: a shard's
            # groups would straddle devices, so every query head gets its own
            kh, vh = self._broadcast_kv(kh, vh)
        if sink is not None:
            raise NotImplementedError(
                f"{self.name}: the flash forward with a sink runs on one "
                "device's heads; a sharded batch or head dim is not built")

        spec = P(ent[0], None, ent[2], None)
        return jax.shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(qh, kh, vh)

    def _sp_attention(self, qh, kh, vh, shard_ctx, seq_axes, scale,
                      training=False, rng=None):
        """Sequence-parallel lowering: ring attention (default) or Ulysses
        over the mesh axes sharding the sequence dim. Attention dropout is
        applied inside the online-softmax recurrence (the Bernoulli mask hits
        the unnormalized probs, so strategy choice does not change model
        semantics)."""
        from jax.sharding import PartitionSpec as P

        from flexflow_tpu.parallel.ring_attention import (ring_attention,
                                                          ulysses_attention)

        mesh = shard_ctx["mesh"]
        axis_map = shard_ctx.get("axis_map") or {}
        mode = shard_ctx.get("sp_mode", "ring")
        if mode not in ("ring", "ulysses"):
            raise ValueError(f"sp_mode must be 'ring' or 'ulysses', got {mode!r}")
        if len(seq_axes) > 1:
            raise ValueError(
                f"sequence dim sharded over multiple mesh axes {seq_axes}; "
                f"ring/ulysses attention needs a single 'seq' axis — merge "
                f"them in the mesh or adjust the strategy")
        from flexflow_tpu.parallel import shard_entries

        # batch/head groups degrade alone when indivisible, like the dense
        # path; the seq axis is the SP lowering itself and stays
        ent = shard_entries(mesh, axis_map, qh.shape, (0, 2))
        spec = P(ent[0], seq_axes[0], ent[2], None)
        seq_axis = seq_axes[0]
        fn = ring_attention if mode == "ring" else ulysses_attention
        dropout_rate = self.dropout if (training and rng is not None) else 0.0

        if dropout_rate > 0.0:
            def inner(q, k, v, key):
                return fn(q, k, v, axis_name=seq_axis, causal=self.causal,
                          scale=scale, dropout_rate=dropout_rate,
                          dropout_rng=key)

            key_spec = P(*([None] * jnp.asarray(rng).ndim))
            return jax.shard_map(
                inner, mesh=mesh, in_specs=(spec, spec, spec, key_spec),
                out_specs=spec, check_vma=False)(qh, kh, vh, rng)

        def inner(q, k, v):
            return fn(q, k, v, axis_name=seq_axis, causal=self.causal,
                      scale=scale)

        return jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(qh, kh, vh)

    _contracted_output_dims = (2,)  # hidden dim comes from the wo contraction

    def partitionable_output_dims(self):
        # batch, seq (ring attention), hidden (head split)
        return [0, 1, 2]

    def partial_sum_axes(self, axis_map):
        # a head split (an axis on the hidden dim) shards wo on its input
        # side: each shard's output is one term of the sum over heads,
        # reduced before the op's own output constraint
        return super().partial_sum_axes(axis_map) + [
            ax for ax, d in (axis_map or {}).items() if d == 2]

    def single_axis_dims(self):
        # the ring/Ulysses lowering rotates around ONE named mesh axis; a
        # seq dim sharded over two axes is rejected at execution
        # (_sp_attention), so the search must not propose it
        return [1]

    def weight_partition(self, axis_map):
        # hidden-dim sharding => split heads (Megatron): shard the H dim of
        # wq/wk/wv and of wo's input side.
        ax = self.axes_for_dim(axis_map, 2)
        if ax is None:
            return super().weight_partition(axis_map)
        # GQA: k/v weights have num_kv_heads on their head dim; when the
        # head-shard degree does not divide it, those weights stay
        # replicated (their kv heads are broadcast to query groups in
        # forward anyway) while q/o still shard
        kv_ax = ax
        if self.num_kv_heads != self.num_heads and self.model.mesh is not None:
            from flexflow_tpu.parallel.mesh import mesh_shape_dict

            shape = mesh_shape_dict(self.model.mesh)
            deg = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                deg *= shape.get(a, 1)
            if self.num_kv_heads % deg != 0:
                kv_ax = None
        out = {
            "wq": P(None, ax, None),
            "wk": P(None, kv_ax, None),
            "wv": P(None, kv_ax, None),
            "wo": P(ax, None, None),
        }
        if self.qk_norm:
            # the norm's mean runs over every head: GSPMD adds the
            # cross-shard reduction, the scales stay whole
            out["q_norm"] = P(None)
            out["k_norm"] = P(None)
        if self.bias:
            out["bias_q"] = P(ax, None)
            out["bias_k"] = P(kv_ax, None)
            out["bias_v"] = P(kv_ax, None)
            out["bias_o"] = P(None)
        return out

    def flops(self):
        b, sq = self.inputs[0].dims[0], self.inputs[0].dims[1]
        sk = self.inputs[1].dims[1]
        kv_frac = self.num_kv_heads / self.num_heads  # GQA shrinks k/v proj
        proj = 2 * b * sq * self.q_in * self.kdim \
            + int(2 * b * sk * (self.k_in * self.kdim
                                + self.v_in * self.vdim) * kv_frac) \
            + 2 * b * sq * self.vdim * self.embed_dim
        # a window layer's query meets `window` keys at most, not all sk
        seen = min(sk, self.window) if self.window else sk
        attn = 2 * b * self.num_heads * sq * seen \
            * (self.qk_head_dim + self.v_head_dim)
        return proj + attn
