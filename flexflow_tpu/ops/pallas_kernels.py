"""Pallas TPU kernels.

Hand-tiled kernels for ops where XLA's default lowering leaves MXU/VMEM
performance on the table (the role src/ops/*.cu kernels played in the
reference; role parity with the tuned cuDNN MHA kernel the reference calls
at attention.cu:244). Currently: flash attention forward (online softmax)
and the FlashAttention-2 style backward (logsumexp saved from the forward;
per-tile recompute of the probs; separate dq and dk/dv kernels so each
output tile is written once). Keys and values may differ in width (q, k
(.., d_qk), v and the output (.., d_v): latent attention's expanded form has
keys of 192 and values of 128); the three calls carry stable names
(`flash_attention_fwd`, `flash_attention_bwd_dq`, `flash_attention_bwd_dkv`)
that a device trace shows.

Layout: q, k, v, o, dO and dq, dk, dv stay (B, S, H, d), which is (B, S,
H * d) by a reshape: where d_qk and d_v are whole numbers of 128 lanes the
kernels reach head i's (block, d) tile as block (b, j, i) of that view
through their index maps, and no transposed copy is made in either pass
(`_lane_heads` is the rule, read off the operands; a 64-wide or an undivided
192-wide head is copied to (B * H, S, d) around the same kernels). Latent
attention's 192-wide key goes in as its two parts, logits = q1 k1^T + q2
k2^T with ONE k2 row a token for all heads (`flash_attention` with pairs):
neither the concatenated key nor the broadcast rotary part exists. Grouped
queries work the same way: k and v keep their KVH heads and a query head's
index maps name its group's (`_head_at`), so no repeated copy is made either.

Streaming design: the opposing sequence is NOT staged in VMEM. Every flash
kernel runs on a 3-D grid (batch*heads, own-side blocks, opposing-side
blocks) whose innermost axis streams opposing-side tiles through VMEM while
f32 scratch accumulators (persistent across the sequential inner grid axis)
carry the online-softmax / gradient state, so VMEM use is O(block^2)
whatever the sequence length. The tile is 1024 x 1024 wherever the sequence
divides by it (`_OUTER_BLOCK`, `_pick_block` below it), and a step takes it
256 rows of the kernel's own side at a time (`_CHUNK`). Under causality a
tile is dead (skipped, its DMA clamped), interior (no mask is built) or
crossed by the diagonal (masked; where tiles are square and the offset a
whole number of them, each chunk multiplies only the part it can see):
`flash_tile_counts` counts the three classes by the kernels' own rule. On a
v5e the three kernels' steps are then bound by their MXU pushes (PERF.md
section 6, PR 33).

Kernels compile through Mosaic; interpret mode is an explicit request
(FF_PALLAS_INTERPRET=1, see _interpret).
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# one f32 lane tile: the width of the forward's m / l scratch rows
LANES = 128


def _interpret() -> bool:
    """Interpret mode is asked for, never fallen into: the CPU test suite
    (tests/conftest.py), the CPU CI tiers and `chip_smoke.py
    --cpu-rehearsal` set FF_PALLAS_INTERPRET=1. Everywhere else kernels
    compile through Mosaic, so a process that lands on a backend without
    it fails at its first pallas_call instead of quietly interpreting."""
    if os.environ.get("FF_PALLAS_INTERPRET") != "1":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "FF_PALLAS_INTERPRET=1 on a TPU backend: interpret mode is "
            "for CPU tests and rehearsals; unset it to compile the kernels")
    return True


def _compiler_params(semantics=("parallel", "parallel", "arbitrary")):
    """Outer grid axes are parallel (independent (bh, own-block) tiles); the
    innermost axis streams opposing-side tiles and must run sequentially —
    the scratch accumulators carry state across it."""
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _pick_block(seq: int, want: int) -> int:
    """Largest tile size <= want that divides seq (the guard in
    attention.flash_eligible only promises 128-divisibility, so a 512
    default must degrade for e.g. seq 640)."""
    for b in (want, 512, 256, 128, 64, 32, 16, 8):
        if b <= min(want, seq) and seq % b == 0:
            return b
    return seq


# The tile a grid step DMAs, and the rows of the kernel's own side (queries
# in the forward and dq, keys in dkv) its arithmetic takes at a time.
_OUTER_BLOCK = 1024
_CHUNK = 256
# a window layer's forward tile, where the window is no larger
_WINDOW_BLOCK = 512


def _resolve_blocks(sq: int, sk: int, want_q, want_k):
    """(block_q, block_k) of a flash kernel call: the tile one grid step
    DMAs, a pure function of the call. `_pick_block` down from
    `_OUTER_BLOCK` for each side the caller left None, down from the
    caller's own value for a side it pinned. Shapes are static, so this is
    resolved as the program traces and a warm program pays nothing."""
    return (_pick_block(sq, want_q if want_q is not None else _OUTER_BLOCK),
            _pick_block(sk, want_k if want_k is not None else _OUTER_BLOCK))


def _chunk_rows(block: int) -> int:
    """Rows of its own side a kernel's step takes at a time: `_CHUNK` where
    it divides the block, else the block whole (sequences under 128, blocks
    of 8 in tests). The chunks of a step are independent chains in one
    basic block (one's matmul beside another's exponentials), and the grain
    at which an aligned diagonal tile's dead part is left out."""
    return _CHUNK if block % _CHUNK == 0 else block


def _diagonal_aligned(block_q: int, block_k: int, offset: int) -> bool:
    """Whether every tile the diagonal crosses has it from its own first
    corner to its last (square tiles, the offset a whole number of them):
    then a diagonal tile's visible part is known when the kernel is traced,
    and each chunk multiplies only the opposing rows it can see."""
    return block_q == block_k and offset % block_k == 0


def flash_tile_counts(sq: int, sk: int, block_q: int, block_k: int,
                      offset: int, causal: bool,
                      window: Optional[int] = None) -> dict:
    """{"live", "masked", "dead"} tiles of ONE head's (sq, sk) logits cut
    into (block_q, block_k) tiles, by the rule the kernels branch on: a
    tile is dead when no query row of it sees a key of it, masked when it
    is live and some row does not see some key (the diagonal crosses it,
    or a window's lower edge does: the only tiles that build a mask), and
    `live` counts masked and interior tiles together. Row q sees key k
    where k <= q + offset and, under a `window`, q + offset - window < k.
    A windowed forward has no grid step for a tile wholly below the
    window (`_window_tiles`): `dead` still counts it, `steps` says how many
    steps the grid has."""
    live = masked = 0
    for qi in range(sq // block_q):
        for ki in range(sk // block_k):
            is_live, interior = _tile_classes(qi, ki, block_q, block_k,
                                              offset, window) if causal \
                else (True, True)
            live += is_live
            masked += is_live and not interior
    counts = {"live": live, "masked": masked,
              "dead": (sq // block_q) * (sk // block_k) - live}
    if causal and window is not None:
        counts["steps"] = (sq // block_q) * _window_tiles(
            sq, sk, block_q, block_k, window)
    return counts


def _tile_classes(qi, ki, block_q: int, block_k: int, offset: int,
                  window: Optional[int] = None):
    """(live, interior) of tile (qi, ki) under the causal rule and, where
    the layer has one, the window's lower edge: the ONE predicate, on the
    kernels' traced grid indices and on `flash_tile_counts`' python ints
    alike."""
    live = (qi + 1) * block_q + offset > ki * block_k
    interior = qi * block_q + offset >= (ki + 1) * block_k - 1
    if window is not None:
        # the tile's first query still sees its last key / its last query
        # already sees its first key
        low_live = (ki + 1) * block_k - 1 > qi * block_q + offset - window
        low_interior = ki * block_k > (qi + 1) * block_q - 1 + offset - window
        if isinstance(live, bool):
            return live and low_live, interior and low_interior
        return (jnp.logical_and(live, low_live),
                jnp.logical_and(interior, low_interior))
    return live, interior


def _window_first_tile(qi, block_q: int, block_k: int, offset: int,
                       window: int):
    """The first key tile query tile `qi` of a window layer can see."""
    first_key = qi * block_q + offset - window + 1
    if isinstance(qi, int):
        return max(first_key, 0) // block_k
    return jnp.maximum(first_key, 0) // block_k


def _window_tiles(sq: int, sk: int, block_q: int, block_k: int,
                  window: int) -> int:
    """Key tiles a windowed forward's grid walks for each query tile: from
    the first tile the window reaches to the diagonal's, at most."""
    offset = sk - sq
    return max(((qi + 1) * block_q - 1 + offset) // block_k
               - _window_first_tile(qi, block_q, block_k, offset, window) + 1
               for qi in range(sq // block_q))


def _run_tile(step, causal: bool, qi, ki, block_q, block_k, offset,
              window=None):
    """Run a kernel's `step(masked)` on the grid step's tile: a causal
    kernel holds two bodies, the interior tile's (no mask anywhere in it)
    and the masked tile's (the diagonal or a window's lower edge crosses
    it), and a dead tile runs neither; a non-causal kernel holds the
    unmasked body alone, under no branch."""
    if not causal:
        step(False)
        return
    live, interior = _tile_classes(qi, ki, block_q, block_k, offset, window)
    pl.when(interior)(functools.partial(step, False))
    pl.when(jnp.logical_and(live, jnp.logical_not(interior)))(
        functools.partial(step, True))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T, contracting the minor dims


def _dot_nt(a, b):
    return jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _row_minus_col(rows: int, cols: int):
    """int32 (rows, cols) of row index minus column index: with queries down
    the rows, the query `first` positions (offset added) past column 0's key
    sees column c from row r where this is >= -first; with keys down the
    rows (dkv), where it is <= first."""
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _reach(masked: bool, aligned: bool, r0: int, chunk: int, block_k: int,
           tile_first):
    """(width, first) for query rows r0 .. r0 + chunk of a tile: the leading
    keys of the tile they are multiplied against, and how far the first of
    these rows' queries is past the tile's first key (offset added). On an
    aligned diagonal tile both are known when the kernel is traced and the
    keys no row of the chunk sees are left out; elsewhere the width is the
    tile's and `first` follows from `tile_first`, the traced position of
    the tile's first query against its first key."""
    if masked and aligned:
        return min(block_k, r0 + chunk), r0
    return block_k, tile_first + r0


def _across(x, width: int):
    """(rows, 128) whose lanes are equal -> (rows, width) without a lane
    broadcast where the lanes divide the width."""
    if width % LANES == 0:
        return x if width == LANES else pltpu.repeat(x, width // LANES,
                                                     axis=1)
    return jnp.broadcast_to(x[:, 0:1], (x.shape[0], width))


# ---------------------------------------------------------------- forward


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *rest, block_q: int,
                      block_k: int, chunk: int, causal: bool, scale: float,
                      need_lse: bool, offset: int = 0,
                      window: Optional[int] = None, sink: bool = False,
                      pair: bool = False):
    """One (block_q, block_k) tile a grid step, `chunk` query rows at a
    time. The running max and sum live LANE-WIDE in their (block_q, 128)
    scratch: every lane of m holds the row's max, so it meets the logits'
    lane tiles and the output rows with no lane broadcast (a (rows, 1)
    statistic read from one lane costs a cross-lane permute a vector
    register wherever it meets a tile: PERF.md section 6, PR 33), and l
    holds one partial sum a lane (over the keys that fell on that lane), so
    a step adds lane tiles and only `_finish` reduces across lanes.

    With `sink` (static) one more input, the (B*H,) sink logits in SMEM: a
    row's running max starts at its head's sink and its sum at exp(sink -
    max) = 1, so the sink takes its share of the denominator and adds no
    value; without it the kernel is the one it always was.

    With `pair` (static) two more inputs, a second query and key whose
    product joins the logits: s = q k^T + q2 k2^T (latent attention's
    rotary part, one key for all heads)."""
    q2_ref = k2_ref = sink_ref = None
    if pair:
        q2_ref, k2_ref, rest = rest[0], rest[1], rest[2:]
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    o_ref, rest = rest[0], rest[1:]
    if need_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kt = ki = pl.program_id(2)      # the grid's step, and its key tile
    nk = pl.num_programs(2)
    dv = acc_scr.shape[1]
    aligned = window is None and _diagonal_aligned(block_q, block_k, offset)
    if window is not None:
        # a window layer's grid starts at the first tile the window reaches
        ki = kt + _window_first_tile(qi, block_q, block_k, offset, window)
    # the tile's first query against its first key, offset added
    tile_first = qi * block_q + offset - ki * block_k
    # a block the lanes do not divide (sequences under 128, blocks of 8 in
    # tests) keeps the whole row sum in every lane of l instead
    tiled = block_k % LANES == 0

    @pl.when(kt == 0)
    def _init():
        if sink_ref is None:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, sink_ref[bh])
            # the sink's 1 in ONE lane's partial sum where the lanes are
            # partial sums, in every lane where each holds the whole sum
            lane = jax.lax.broadcasted_iota(jnp.int32, l_scr.shape, 1)
            l_scr[...] = jnp.where(jnp.logical_or(lane == 0, not tiled),
                                   1.0, 0.0).astype(l_scr.dtype)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        for r0 in range(0, block_q, chunk):
            rows = pl.ds(r0, chunk)
            width, first = _reach(masked, aligned, r0, chunk, block_k,
                                  tile_first)
            q = q_ref[0, rows, :]   # native dtype into the MXU (bf16 fast
            # path; accumulation stays f32 via preferred_element_type)
            k = k_ref[0, 0:width, :]
            v = v_ref[0, 0:width, :]
            s = _dot_nt(q, k)                               # (chunk, width)
            if pair:
                s = s + _dot_nt(q2_ref[0, rows, :], k2_ref[0, 0:width, :])
            s = s * scale
            if masked:
                ahead = _row_minus_col(chunk, width)
                seen = ahead >= -first
                if window is not None:
                    seen = jnp.logical_and(seen, ahead < window - first)
                s = jnp.where(seen, s, NEG_INF)
            m_prev = m_scr[rows, :]                         # (chunk, 128)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _across(m_new, width))
            if tiled:
                l_add = p[:, 0:LANES]
                for j in range(LANES, width, LANES):
                    l_add = l_add + p[:, j:j + LANES]
            else:
                l_add = jnp.sum(p, axis=-1, keepdims=True)
            l_scr[rows, :] = l_scr[rows, :] * alpha + l_add
            acc_scr[rows, :] = acc_scr[rows, :] * _across(alpha, dv) \
                + _dot(p.astype(v.dtype), v)
            m_scr[rows, :] = m_new

    _run_tile(step, causal, qi, ki, block_q, block_k, offset, window)

    @pl.when(kt == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.sum(l, axis=-1, keepdims=True) if tiled else l[:, 0:1]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if need_lse:
            # lse lives in an 8-lane padded layout: Mosaic wants the last two
            # block dims divisible by (8, 128) OR equal to the array dims, and
            # a last dim of exactly 8 satisfies the 'equal' clause at 16x less
            # HBM than padding to a full 128-lane tile
            m = m_scr[:, 0:1]
            lse_ref[0] = jnp.broadcast_to(m + jnp.log(l),
                                          (q_ref.shape[1], 8))


def _lane_heads(*widths: int) -> bool:
    """Whether the flash kernels reach a head's tiles where the projections
    leave them: (B, S, H, d) is (B, S, H * d) by a reshape, and head i's
    (block, d) tile is the block (b, j, i) of that view when every width is
    a whole number of 128-lane tiles. A head the lanes do not divide (64,
    an undivided 192) is copied to (B * H, S, d) first. The one rule of the
    three kernels' wrappers, read off the operands."""
    return all(w % LANES == 0 for w in widths)


def _by_head(x, lanes: bool):
    """A (B, S, H, d) operand as the kernels' grid reads it: the reshape
    (B, S, H * d) where a lane block can take a head, else the transposed
    copy (B * H, S, d)."""
    b, s, h, d = x.shape
    if lanes:
        return x.reshape(b, s, h * d)
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_heads(y, b: int, h: int, lanes: bool):
    """`_by_head`'s inverse on a kernel's result: -> (B, S, H, d)."""
    if lanes:
        return y.reshape(b, y.shape[1], h, y.shape[2] // h)
    return y.reshape(b, h, y.shape[1], y.shape[2]).transpose(0, 2, 1, 3)


def _head_at(h: int, lanes: bool, rep: int = 1):
    """at(i, j): the block index of sequence block j for grid row i (batch
    x one of h heads) in `_by_head`'s view of an operand with h // rep
    heads: its own head at rep 1, else the key / value head its group of
    `rep` query heads shares (grouped-query attention: head i % h reads
    head (i % h) // rep, and nothing is repeated in HBM). The kernels'
    bodies see the same (1, block, d) tile either way."""
    if lanes:
        return lambda i, j: (i // h, j, (i % h) // rep)
    return lambda i, j: (i // rep, j, 0)


def _group_size(q, k, v) -> int:
    """Query heads a key / value head (1 without grouped queries)."""
    h, hk = q.shape[2], k.shape[2]
    assert h % hk == 0 and v.shape[2] == hk, (q.shape, k.shape, v.shape)
    return h // hk


def flash_attention_fwd_pallas(q, k, v, causal: bool, scale: float,
                               block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               need_lse: bool = True,
                               window: Optional[int] = None, sink=None,
                               q2=None, k2=None):
    """q (B, S, H, d_qk), k (B, S, KVH, d_qk), v (B, S, KVH, d_v) -> (out
    (B, S_q, H, d_v), lse (B*H, S_q, 8) | None). The key and value widths
    are independent (latent attention has keys of 192 and values of 128):
    the logit contracts d_qk, the accumulator and the output are d_v wide.
    KVH divides H (grouped-query attention): query head h reads key head
    h // (H / KVH) through the index maps, and k, v are never repeated.

    Layout: operands and result stay where the projections leave them. With
    d_qk and d_v whole numbers of 128 lanes (`_lane_heads`) the kernel reads
    head i's tiles out of the (B, S, H * d) view through its index maps and
    writes the output the same way: no transposed copy exists. Other widths
    (64, an undivided 192) are copied to (B * H, S, d) and back around the
    SAME kernel. `lse` is the kernels' own and keeps its (B * H, S_q, 8)
    form.

    `q2` (B, S_q, H, d_2), `k2` (B, S_k, d_2): a second contraction pair,
    logits = q k^T + q2 k2^T with ONE k2 row a token for all heads (latent
    attention's rotary key); needs every width a whole number of lane tiles.

    Grid: (B*H, S_q/block_q, S_k/block_k) - K/V tiles stream through the
    innermost axis. block_q/block_k default to `_resolve_blocks`' static
    rule down from `_OUTER_BLOCK`; explicit values pin the tile (degraded
    to a divisor of seq). need_lse=False (inference) skips materializing
    the logsumexp residual - it exists only for the VJP and costs more HBM
    writes than the output itself at small head dims.

    `window` (causal only): query i sees keys i - window < j <= i. A key
    tile wholly below the window has no grid step, the tile the lower edge
    crosses is masked like the diagonal's, the rest run as they do without
    one; with None the call is the one it always was.

    `sink` ((H,) f32, forward only): a logit a head that joins every row's
    softmax denominator and adds no value."""
    sq, sk = q.shape[1], k.shape[1]
    if window is not None:
        assert causal, "a window is a causal layer's"
        if block_q is None and block_k is None:
            # tiles near the window's size: a query tile reads its own
            # keys and the window before them, two tiles in all
            block_q = block_k = _WINDOW_BLOCK if window <= _WINDOW_BLOCK \
                else _OUTER_BLOCK
        block_q, block_k = _pick_block(sq, block_q), _pick_block(sk, block_k)
    else:
        block_q, block_k = _resolve_blocks(sq, sk, block_q, block_k)
    assert sq % block_q == 0 and sk % block_k == 0
    # sq > sk with causal would leave the first rows keyless (0/0 in the
    # online softmax) - refused upstream in attention.flash_eligible
    assert not (causal and sk < sq), "causal flash needs sq <= sk"
    return _flash_fwd_call(q, k, v, sink, q2, k2, causal=causal,
                           scale=float(scale), block_q=block_q,
                           block_k=block_k, need_lse=need_lse,
                           interpret=_interpret(), window=window)


# inline=True: traced once per shape and re-emitted under each caller's
# named scope, so a program's layers share one trace and one Mosaic lowering
# of each kernel (as `moe_expert_stream_pallas`): a kernel body of two tile
# classes times four chunks is some hundred jax calls to trace, and a
# five-layer step holds it fifteen times. What a cached trace must not
# freeze (interpret mode) is resolved by the callers above and comes in as
# a static argument.
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "need_lse", "interpret",
    "window"))
def _flash_fwd_call(q, k, v, sink=None, q2=None, k2=None, *, causal, scale,
                    block_q, block_k, need_lse, interpret, window=None):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    # cross-attention diagonal offset (bottom-right aligned causality)
    offset = sk - sq
    pair = q2 is not None
    lanes = _lane_heads(d, dv)
    if pair:
        d2 = q2.shape[3]
        assert lanes and _lane_heads(d2), (d, dv, d2)
    rep = _group_size(q, k, v)
    at, at_kv = _head_at(h, lanes), _head_at(h, lanes, rep)

    # the phase scopes here and below are the calling attention op's
    # (runtime/profiler.py PHASES); on the lane path `project` holds
    # reshapes alone
    with jax.named_scope("project"):
        operands = [_by_head(x, lanes) for x in (q, k, v)]
        if pair:
            operands += [_by_head(q2, True), k2]

    kernel = functools.partial(_flash_fwd_kernel, block_q=block_q,
                               block_k=block_k, chunk=_chunk_rows(block_q),
                               causal=causal, scale=scale,
                               need_lse=need_lse, offset=offset,
                               window=window, sink=sink is not None,
                               pair=pair)
    nk = sk // block_k
    if window is not None:
        # the grid's key axis starts at the first tile the window reaches
        # and is as long as the longest such run
        nk = _window_tiles(sq, sk, block_q, block_k, window)

        def k_tile(j, t):
            return jnp.minimum(
                t + _window_first_tile(j, block_q, block_k, offset, window),
                ((j + 1) * block_q - 1 + offset) // block_k)
    elif causal:
        # clamp dead (fully-masked) inner steps to the last live tile: the
        # revisited block is already VMEM-resident, so masked steps cost no
        # DMA (pl.when(live) already skips their compute)
        def k_tile(j, t):
            return jnp.minimum(t, ((j + 1) * block_q - 1 + offset) // block_k)
    else:
        def k_tile(j, t):
            return t

    def q_map(i, j, t):
        return at(i, j)

    def kv_map(i, j, t):
        return at_kv(i, k_tile(j, t))

    def rows_map(i, j, t):          # the kernels' own (B*H, S_q, 8) rows
        return (i, j, 0)

    out_specs = [pl.BlockSpec((1, block_q, dv), q_map)]
    out_shape = [jax.ShapeDtypeStruct(
        (b, sq, h * dv) if lanes else (b * h, sq, dv), q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((1, block_q, 8), rows_map))
        out_shape.append(jax.ShapeDtypeStruct((b * h, sq, 8), jnp.float32))
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, dv), kv_map),
    ]
    if pair:
        in_specs += [
            pl.BlockSpec((1, block_q, d2), q_map),
            # one row a token, the same block for every head
            pl.BlockSpec((1, block_k, d2),
                         lambda i, j, t: (i // h, k_tile(j, t), 0)),
        ]
    if sink is not None:
        # one logit a (batch, head) grid row, read as a scalar
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.tile(sink.astype(jnp.float32), b))
    with jax.named_scope("core"):
        outs = pl.pallas_call(
            kernel,
            grid=(b * h, sq // block_q, nk),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_q, LANES), jnp.float32),   # running max
                pltpu.VMEM((block_q, LANES), jnp.float32),   # running sum
                pltpu.VMEM((block_q, dv), jnp.float32),      # accumulator
            ],
            compiler_params=_compiler_params(),
            interpret=interpret, name="flash_attention_fwd",
        )(*operands)
    with jax.named_scope("out"):
        out = _from_heads(outs[0], b, h, lanes)
    return (out, outs[1]) if need_lse else (out, None)


# ---------------------------------------------------------------- backward


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, block_q: int, block_k: int, chunk: int,
                         causal: bool, scale: float, offset: int = 0,
                         pair: bool = False):
    """One q tile, k/v tiles streaming, `chunk` query rows at a time:
    dq = scale * sum_j ds_j @ k_j, ds = p * (do @ v^T - delta). With `pair`
    the logits hold q2 k2^T too and dq2 = scale * sum_j ds_j @ k2_j."""
    if pair:
        q2_ref, k2_ref, dq_ref, dq2_ref, dq_scr, dq2_scr = rest
    else:
        dq_ref, dq_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    aligned = _diagonal_aligned(block_q, block_k, offset)
    # the tile's first query against its first key, offset added
    tile_first = qi * block_q + offset - ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        if pair:
            dq2_scr[...] = jnp.zeros_like(dq2_scr)

    def step(masked: bool):
        for r0 in range(0, block_q, chunk):
            rows = pl.ds(r0, chunk)
            width, first = _reach(masked, aligned, r0, chunk, block_k,
                                  tile_first)
            q = q_ref[0, rows, :]
            do = do_ref[0, rows, :]
            lse = lse_ref[0, rows, 0:1]     # (chunk, 1): 8-lane layout
            delta = delta_ref[0, rows, 0:1]
            k = k_ref[0, 0:width, :]
            v = v_ref[0, 0:width, :]
            s = _dot_nt(q, k)                               # (chunk, width)
            if pair:
                k2 = k2_ref[0, 0:width, :]
                s = s + _dot_nt(q2_ref[0, rows, :], k2)
            p = jnp.exp(s * scale - lse)
            if masked:
                p = jnp.where(_row_minus_col(chunk, width) >= -first, p, 0.0)
            ds = (p * (_dot_nt(do, v) - delta)).astype(k.dtype)
            dq_scr[rows, :] = dq_scr[rows, :] + _dot(ds, k)
            if pair:
                dq2_scr[rows, :] = dq2_scr[rows, :] + _dot(ds, k2)

    _run_tile(step, causal, qi, ki, block_q, block_k, offset)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)
        if pair:
            dq2_ref[0] = (dq2_scr[...] * scale).astype(dq2_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *rest, block_q: int, block_k: int, chunk: int,
                          causal: bool, scale: float, offset: int = 0,
                          pair: bool = False, nq: Optional[int] = None):
    """One k tile, q/do tiles streaming, `chunk` keys at a time, on the
    TRANSPOSED logits s^T = k q^T (keys down the rows, queries along the
    lanes), so that neither product needs a transpose: dv = sum_i p_i^T @
    do_i, dk = scale * sum_i ds_i^T @ q_i, with lse and delta laid along
    lanes (lse_ref / delta_ref: (1, 1, block_q)). With `pair` the logits
    hold k2 q2^T too and dk2 = scale * sum_i ds_i^T @ q2_i is THIS head's
    share of the one k2's gradient (the caller sums the heads). With `nq`
    (static; grouped-query attention) the inner axis holds the `nq` q tiles
    of each query head of this key head's group, one head after another."""
    if pair:
        (q2_ref, k2_ref, dk_ref, dv_ref, dk2_ref,
         dk_scr, dv_scr, dk2_scr) = rest
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)
    step_i = pl.program_id(2)       # the grid's step, and its q tile
    steps = pl.num_programs(2)
    qi = step_i if nq is None else step_i % nq
    aligned = _diagonal_aligned(block_q, block_k, offset)
    # the tile's first query against its first key, offset added
    tile_first = qi * block_q + offset - ki * block_k

    @pl.when(step_i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if pair:
            dk2_scr[...] = jnp.zeros_like(dk2_scr)

    def step(masked: bool):
        for r0 in range(0, block_k, chunk):
            rows = pl.ds(r0, chunk)
            # the queries that see these keys of a diagonal tile, where
            # that is known
            start = min(r0, block_q) if masked and aligned else 0
            width = block_q - start
            k = k_ref[0, rows, :]
            v = v_ref[0, rows, :]
            q = q_ref[0, start:block_q, :]
            do = do_ref[0, start:block_q, :]
            lse = lse_ref[0, :, start:block_q]              # (1, width)
            delta = delta_ref[0, :, start:block_q]
            s = _dot_nt(k, q)                               # (chunk, width)
            if pair:
                q2 = q2_ref[0, start:block_q, :]
                s = s + _dot_nt(k2_ref[0, rows, :], q2)
            p = jnp.exp(s * scale - lse)
            if masked:
                # rows are keys here: the first streamed query is `first`
                # positions (offset added) past the chunk's first key
                first = start - r0 if aligned else tile_first - r0
                p = jnp.where(_row_minus_col(chunk, width) <= first, p, 0.0)
            dv_scr[rows, :] = dv_scr[rows, :] + _dot(p.astype(do.dtype), do)
            ds = (p * (_dot_nt(v, do) - delta)).astype(q.dtype)
            dk_scr[rows, :] = dk_scr[rows, :] + _dot(ds, q)
            if pair:
                dk2_scr[rows, :] = dk2_scr[rows, :] + _dot(ds, q2)

    _run_tile(step, causal, qi, ki, block_q, block_k, offset)

    @pl.when(step_i == steps - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)
        if pair:
            dk2_ref[0] = (dk2_scr[...] * scale).astype(dk2_ref.dtype)


def _head_row_sums(x):
    """(B, S, H, d) f32 -> (B * H, S): every head's row sums. Taken 8 rows
    of the sequence at a time where it divides: 8 is the f32 sublane tile,
    so the product of two (B, S, H * d) arrays that the lane path's
    kernels wrote is read where it lies (a plain sum over the 4-D view
    makes XLA copy the whole f32 product into a heads-on-sublanes layout
    first: PERF.md section 6, PR 52)."""
    b, s, h, d = x.shape
    r = 8 if s % 8 == 0 else 1
    sums = jnp.sum(x.reshape(b, s // r, r, h, d), axis=-1)
    return sums.reshape(b, s, h).transpose(0, 2, 1).reshape(b * h, s)


def flash_attention_bwd_pallas(q, k, v, o, lse, do, causal: bool,
                               scale: float, block_q: Optional[int] = None,
                               block_k: Optional[int] = None, dlse=None,
                               delta_precomputed=None, q2=None, k2=None):
    """(dq, dk, dv) of `flash_attention_fwd_pallas`'s output o (B, S_q, H,
    d_v) under the cotangent do, from its lse (B*H, S_q, 8): two calls,
    `flash_attention_bwd_dq` and `flash_attention_bwd_dkv`. Blocks as the
    forward's, and its layout rule (`_lane_heads`): q, k, v, o and do are
    read, dq, dk and dv written as (B, S, H | KVH, d) where the widths are
    whole lane tiles, through a transposed copy each otherwise; with KVH <
    H the dkv call's grid rows are the key heads and its inner axis walks
    the group's query heads. With the
    forward's second pair `q2`, `k2` the result is (dq, dk, dv, dq2 (B,
    S_q, H, d_2), dk2 (B, S_k, d_2): the heads' shares summed)."""
    sq, sk = q.shape[1], k.shape[1]
    block_q, block_k = _resolve_blocks(sq, sk, block_q, block_k)
    assert sq % block_q == 0 and sk % block_k == 0
    assert not (causal and sk < sq), "causal flash needs sq <= sk"
    return _flash_bwd_call(q, k, v, o, lse, do, dlse, delta_precomputed,
                           q2, k2, causal=causal, scale=float(scale),
                           block_q=block_q, block_k=block_k,
                           interpret=_interpret())


# inline=True: as `_flash_fwd_call`
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_bwd_call(q, k, v, o, lse, do, dlse, delta_precomputed, q2=None,
                    k2=None, *, causal, scale, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    offset = sk - sq
    pair = q2 is not None
    lanes = _lane_heads(d, dv)
    d2 = q2.shape[3] if pair else None
    assert not pair or (lanes and _lane_heads(d2)), (d, dv, d2)
    hk, rep = k.shape[2], _group_size(q, k, v)
    nq = sq // block_q
    at, at_kv = _head_at(h, lanes), _head_at(h, lanes, rep)

    with jax.named_scope("project"):
        qt, kt, vt, dot = (_by_head(x, lanes) for x in (q, k, v, do))
        extra = [_by_head(q2, True), k2] if pair else []
    # delta_i = rowsum(do_i * o_i) - the softmax-normalization term of ds,
    # read from do and o where they lie; an lse cotangent (if the lse output
    # is ever differentiated) folds in as ds = p * (dp - delta + dlse), i.e.
    # delta -= dlse. Loop callers (the ring backward) pass delta_precomputed
    # (B, H, S_q) to hoist this out of their scan body.
    with jax.named_scope("core"):
        if delta_precomputed is not None:
            delta = delta_precomputed.reshape(b * h, sq).astype(jnp.float32)
        else:
            delta = _head_row_sums(do.astype(jnp.float32)
                                   * o.astype(jnp.float32))
        if dlse is not None:
            delta = delta - dlse.reshape(b * h, sq).astype(jnp.float32)

    if causal:
        # dead-tile clamps (see forward): masked inner steps re-reference a
        # resident block instead of fetching one
        def k_tile(j, t):
            return jnp.minimum(t, ((j + 1) * block_q - 1 + offset) // block_k)

        def q_tile(j, t):
            # first q tile whose last row reaches this k tile: q_pos >=
            # j*block_k - offset (floor div handles the negative numerator)
            return jnp.maximum(t, (j * block_k - offset) // block_q)
    else:
        def k_tile(j, t):
            return t

        def q_tile(j, t):
            return t

    def own_q(i, j, t):             # the dq kernel's own side: grid axis 1
        return at(i, j)

    def rows_map(i, j, t):          # lse / delta rows: (B*H, S_q, 8)
        return (i, j, 0)

    def kv_map(i, j, t):
        return at_kv(i, k_tile(j, t))

    dq_in = [
        pl.BlockSpec((1, block_q, d), own_q),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, dv), kv_map),
        pl.BlockSpec((1, block_q, dv), own_q),
        pl.BlockSpec((1, block_q, 8), rows_map),
        pl.BlockSpec((1, block_q, 8), rows_map),
    ]
    dq_out = [pl.BlockSpec((1, block_q, d), own_q)]
    dq_shape = [jax.ShapeDtypeStruct(qt.shape, q.dtype)]
    dq_scratch = [pltpu.VMEM((block_q, d), jnp.float32)]
    if pair:
        dq_in += [pl.BlockSpec((1, block_q, d2), own_q),
                  pl.BlockSpec((1, block_k, d2),
                               lambda i, j, t: (i // h, k_tile(j, t), 0))]
        dq_out.append(pl.BlockSpec((1, block_q, d2), own_q))
        dq_shape.append(jax.ShapeDtypeStruct(extra[0].shape, q2.dtype))
        dq_scratch.append(pltpu.VMEM((block_q, d2), jnp.float32))
    with jax.named_scope("core"):
        dqs = pl.pallas_call(
            functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                              block_k=block_k, chunk=_chunk_rows(block_q),
                              causal=causal, scale=scale, offset=offset,
                              pair=pair),
            grid=(b * h, sq // block_q, sk // block_k),
            in_specs=dq_in, out_specs=dq_out, out_shape=dq_shape,
            scratch_shapes=dq_scratch,
            compiler_params=_compiler_params(),
            interpret=interpret, name="flash_attention_bwd_dq",
        )(qt, kt, vt, dot, lse,
          # delta in the same 8-lane padded layout as lse
          jnp.broadcast_to(delta[..., None], (b * h, sq, 8)), *extra)

    # the dkv kernel works on transposed logits, so its two per-query rows
    # lie along lanes. Its grid rows are the KEY heads (B * hk): the inner
    # axis streams the q tiles of each of the group's `rep` query heads in
    # turn into the one accumulator, so dk and dv leave at the keys' own
    # width (at rep 1 the grid it always was)
    rows = (b * h, 1, sq)
    own_kv = _head_at(hk, lanes)

    def own_k(i, j, t):
        return own_kv(i, j)

    if rep == 1:
        def q_side(i, j, t):        # (q's grid row, its sequence tile)
            return i, q_tile(j, t)
    else:
        def q_side(i, j, t):
            return ((i // hk) * h + (i % hk) * rep + t // nq,
                    q_tile(j, t % nq))

    def q_map(i, j, t):
        return at(*q_side(i, j, t))

    def row_map(i, j, t):
        row, tile = q_side(i, j, t)
        return (row, 0, tile)

    row_spec = pl.BlockSpec((1, 1, block_q), row_map)
    dkv_in = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), own_k),
        pl.BlockSpec((1, block_k, dv), own_k),
        pl.BlockSpec((1, block_q, dv), q_map),
        row_spec,
        row_spec,
    ]
    dkv_out = [pl.BlockSpec((1, block_k, d), own_k),
               pl.BlockSpec((1, block_k, dv), own_k)]
    dkv_shape = [jax.ShapeDtypeStruct(kt.shape, k.dtype),
                 jax.ShapeDtypeStruct(vt.shape, v.dtype)]
    dkv_scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, dv), jnp.float32)]
    if pair:
        dkv_in += [pl.BlockSpec((1, block_q, d2), q_map),
                   pl.BlockSpec((1, block_k, d2),
                                lambda i, j, t: (i // hk, j, 0))]
        # a key head's share of the one k2's gradient
        dkv_out.append(pl.BlockSpec((1, block_k, d2), own_k))
        dkv_shape.append(jax.ShapeDtypeStruct((b, sk, hk * d2), k2.dtype))
        dkv_scratch.append(pltpu.VMEM((block_k, d2), jnp.float32))
    with jax.named_scope("core"):
        dkvs = pl.pallas_call(
            functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                              block_k=block_k, chunk=_chunk_rows(block_k),
                              causal=causal, scale=scale, offset=offset,
                              pair=pair, nq=nq if rep > 1 else None),
            grid=(b * hk, sk // block_k, rep * nq),
            in_specs=dkv_in, out_specs=dkv_out, out_shape=dkv_shape,
            scratch_shapes=dkv_scratch,
            compiler_params=_compiler_params(),
            interpret=interpret, name="flash_attention_bwd_dkv",
        )(qt, kt, vt, dot, lse[..., 0].reshape(rows), delta.reshape(rows),
          *extra)

    with jax.named_scope("project"):
        grads = (_from_heads(dqs[0], b, h, lanes),
                 _from_heads(dkvs[0], b, hk, lanes),
                 _from_heads(dkvs[1], b, hk, lanes))
        if not pair:
            return grads
        dk2 = _from_heads(dkvs[2], b, hk, True)
        return grads + (_from_heads(dqs[1], b, h, True),
                        jnp.sum(dk2.astype(jnp.float32),
                                axis=2).astype(k2.dtype))


# ----------------------------------------------------- fused add+layernorm


def _add_ln_fwd_kernel(x_ref, r_ref, scale_ref, bias_ref, s_ref, y_ref,
                       *stat_refs, eps: float, need_stats: bool):
    x = x_ref[...]
    r = r_ref[...]
    s = x + r                                   # residual stream out
    sf = s.astype(jnp.float32)
    mean = jnp.mean(sf, axis=-1, keepdims=True)          # (bn, 1)
    # two-pass variance: E[(s-mean)^2], not E[s^2]-mean^2 — the one-pass
    # form catastrophically cancels in f32 when the row mean dwarfs its
    # spread (large residual streams in deep nets). The row is already in
    # registers, so the second pass costs no HBM traffic
    centered = sf - mean
    var = jnp.mean(centered * centered, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = centered * rstd * scale_ref[...] + bias_ref[...]
    s_ref[...] = s
    y_ref[...] = y.astype(y_ref.dtype)
    if need_stats:
        mean_ref, rstd_ref = stat_refs
        bn = x.shape[0]
        mean_ref[...] = jnp.broadcast_to(mean, (bn, 8))
        rstd_ref[...] = jnp.broadcast_to(rstd, (bn, 8))


# Mosaic's scoped-VMEM stack is 16 MiB on v5e; the add+LN row block is
# sized against half of it, leaving the rest to the compiler's own temps.
_ADD_LN_VMEM_BUDGET = 8 << 20


def add_ln_block_rows(n: int, d: int, dtype) -> int:
    """Rows per grid step of the fused add+LN kernel for an (n, d) input,
    or 0 when no legal block fits VMEM — the eligibility gate
    (ops/norm.py AddLayerNorm) declines on 0, so an oversized row is a
    named refusal at graph build and never a Mosaic allocation failure.
    Per row the pipeline holds the x, r, s, y tiles double-buffered plus
    about three live f32 temporaries of the row."""
    per_row = d * (4 * 2 * jnp.dtype(dtype).itemsize + 3 * 4)
    cap = _ADD_LN_VMEM_BUDGET // per_row
    fits = [b for b in (256, 128, 64, 32, 16, 8) if b <= cap]
    if not fits:
        return 0
    block = _pick_block(n, fits[0])
    return block if block <= cap else 0


def fused_add_layernorm_fwd_pallas(x, r, scale, bias, eps: float,
                                   need_stats: bool = True):
    """(N, D) x + r -> (s, ln(s)) in ONE HBM pass (the unfused graph writes
    s, re-reads it for the norm, and re-reads it again on the next block's
    residual path). need_stats=False (inference / no-grad primal) skips
    materializing the (N, 8) mean/rstd residuals, which exist only for the
    VJP — same pattern as the flash kernel's need_lse."""
    n, d = x.shape
    block_n = add_ln_block_rows(n, d, x.dtype)
    if not block_n:
        raise ValueError(
            f"fused add+layernorm: no row block of a ({n}, {d}) "
            f"{jnp.dtype(x.dtype).name} input fits VMEM")
    grid = (n // block_n,)
    scale2 = scale.reshape(1, d)
    bias2 = bias.reshape(1, d)
    out_specs = [pl.BlockSpec((block_n, d), lambda i: (i, 0)),
                 pl.BlockSpec((block_n, d), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((n, d), x.dtype),
                 jax.ShapeDtypeStruct((n, d), x.dtype)]
    if need_stats:
        out_specs += [pl.BlockSpec((block_n, 8), lambda i: (i, 0)),
                      pl.BlockSpec((block_n, 8), lambda i: (i, 0))]
        out_shape += [jax.ShapeDtypeStruct((n, 8), jnp.float32),
                      jax.ShapeDtypeStruct((n, 8), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_add_ln_fwd_kernel, eps=eps,
                          need_stats=need_stats),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret(),
    )(x, r, scale2, bias2)
    if need_stats:
        return outs
    return outs[0], outs[1], None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_add_layernorm(x, r, scale, bias, eps: float = 1e-5):
    """(s, y) = (x + r, layernorm(x + r) * scale + bias) fused: the residual
    add never round-trips HBM before the norm reads it. Backward is pure
    JAX (bandwidth-bound elementwise+reduce; XLA fuses it well)."""
    s, y, _, _ = fused_add_layernorm_fwd_pallas(x, r, scale, bias, eps,
                                                need_stats=False)
    return s, y


def _add_ln_fwd_rule(x, r, scale, bias, eps):
    s, y, mean, rstd = fused_add_layernorm_fwd_pallas(x, r, scale, bias, eps)
    return (s, y), (s, mean[:, 0:1], rstd[:, 0:1], scale)


def _add_ln_bwd_rule(eps, res, g):
    s, mean, rstd, scale = res
    gs, gy = g
    sf = s.astype(jnp.float32)
    gyf = gy.astype(jnp.float32)
    xhat = (sf - mean) * rstd
    dbias = jnp.sum(gyf, axis=0).astype(scale.dtype)
    dscale = jnp.sum(gyf * xhat, axis=0).astype(scale.dtype)
    t = gyf * scale.astype(jnp.float32)
    dsn = (t - jnp.mean(t, axis=-1, keepdims=True)
           - xhat * jnp.mean(t * xhat, axis=-1, keepdims=True)) * rstd
    d = (dsn + gs.astype(jnp.float32)).astype(s.dtype)
    return d, d, dscale, dbias


fused_add_layernorm.defvjp(_add_ln_fwd_rule, _add_ln_bwd_rule)


# ------------------------------------------------------------- public API


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """Flash attention: Pallas forward + FlashAttention-2 Pallas backward
    (logsumexp residual; per-tile prob recompute; no S x S materialization
    in either direction). q (B, S, H, d_qk), k (B, S, KVH, d_qk) and v (B,
    S, KVH, d_v) -> (B, S_q, H, d_v): the two widths need not agree, and
    KVH may be any divisor of H (a group of query heads shares one key
    head; dk and dv come back KVH heads wide); the default scale is
    d_qk^-0.5. Operands, result and gradients stay in that layout where the
    widths are whole numbers of 128 lanes (`flash_attention_fwd_pallas`).

    q and k may each come as a PAIR of parts whose products add up in the
    logit, (q1 (B, S, H, d_1), q2 (B, S, H, d_2)) against (k1 (B, S, H,
    d_1), k2 (B, S, d_2): one row a token for all heads): latent
    attention's [nope ; rope] key, never concatenated. d_qk is d_1 + d_2.
    Where d_1 and d_v are whole lane tiles the second part is padded to
    one and rides the kernels' second contraction; else the parts are
    joined and take the copied layout."""
    if not isinstance(q, tuple):
        s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
        return _flash(q, k, v, None, None, causal, s)
    (q, q2), (k, k2) = q, k
    s = scale if scale is not None \
        else 1.0 / math.sqrt(q.shape[-1] + q2.shape[-1])
    with jax.named_scope("project"):
        if _lane_heads(q.shape[-1], v.shape[-1]):
            pad = -q2.shape[-1] % LANES
            q2 = jnp.pad(q2, ((0, 0),) * 3 + ((0, pad),))
            k2 = jnp.pad(k2, ((0, 0),) * 2 + ((0, pad),))
        else:
            q = jnp.concatenate([q, q2], axis=-1)
            k = jnp.concatenate([k, jnp.broadcast_to(
                k2[:, :, None, :], k.shape[:3] + k2.shape[-1:])], axis=-1)
            q2 = k2 = None
    return _flash(q, k, v, q2, k2, causal, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k, v, q2, k2, causal: bool, scale: float):
    """`flash_attention` with its second pair (or None, None) in place."""
    out, _ = flash_attention_fwd_pallas(q, k, v, causal, scale,
                                        need_lse=False, q2=q2, k2=k2)
    return out


def flash_attention_window(q, k, v, window: Optional[int],
                           scale: Optional[float] = None, sink=None):
    """The causal flash forward of a window layer (query i sees keys
    i - window < j <= i, bottom-right aligned where sq < sk; None: every
    earlier key) and of a layer with a `sink` ((H,) logits in the softmax's
    denominator): the forward kernel alone, no VJP. A window or a sink under
    a gradient takes XLA's masked attention (ops/attention.py), not this."""
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, _ = flash_attention_fwd_pallas(q, k, v, True, s, need_lse=False,
                                        window=window, sink=sink)
    return out


def _flash_fwd_rule(q, k, v, q2, k2, causal, scale):
    o, lse = flash_attention_fwd_pallas(q, k, v, causal, scale, q2=q2, k2=k2)
    return o, (q, k, v, q2, k2, o, lse)


def _flash_bwd_rule(causal, scale, res, g):
    q, k, v, q2, k2, o, lse = res
    grads = flash_attention_bwd_pallas(q, k, v, o, lse, g, causal, scale,
                                       q2=q2, k2=k2)
    return grads if q2 is not None else grads + (None, None)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ----------------------------------------------------- paged attention
#
# Serving-side decode/verify attention over the paged KV pool
# (runtime/serving.py). The einsum path reassembles the ENTIRE pool into a
# dense (B, max_len, KVH, Dh) logical cache with ck[page_table].reshape(...)
# on every step — an HBM round-trip that grows with POOL size, not with the
# tokens a slot actually holds. This kernel leaves the pool in HBM and
# fetches, by hand, exactly the pages the live rule can reach: its time
# follows the pages it streams.
#
#   * The grid is the SLOTS (sequential). Inside a grid step a fori_loop
#     runs over the slot's live pages, 0 .. last_page[b], a TURN at a time,
#     so no step of any kind exists for a dead page. (Until PR 26 the grid
#     was (slots, table width) with dead steps clamped and pl.when-skipped,
#     on the assumption that a dead step costs nothing. It costs 0.13-0.15
#     us: with 16 x 66 steps of which 5 were live, that was nearly all of
#     the 157 us a call took in chat-steady — PERF.md section 6, PR 26.)
#   * A turn takes a BLOCK of g consecutive live pages of the slot, with g
#     from the shapes the call sees (`paged_turn_pages`): the smallest power
#     of two whose block has the 1024 columns (tokens x rows a token) of the
#     turn the kernel reaches its roof on, so ONE page from 8 rows a token
#     of 128 lanes up (heads of 128, 8 or 16 KV heads: the turn it always
#     was), two where two heads of 64 share a row of four a token, four at 2
#     KV heads of 128. The pages past a slot's last WHOLE block go one a
#     turn: nothing past the last live page is ever fetched (a dead page may
#     hold anything, and 0 x NaN in p v is NaN), and one body serves both.
#   * The turns of ALL slots form one stream, slot by slot, block by block
#     (`_page_stream`, shared with `dsa_index_scores`). A cursor in SMEM
#     walks it `_paged_ring(...) - 1` turns ahead of the arithmetic and
#     issues one DMA per page and tensor (a page of the pool is one
#     contiguous copy, all KV heads in it) into a ring of VMEM buffers, a
#     turn's copies onto one semaphore a tensor; because the cursor crosses
#     slot boundaries, the DMA queue never drains between slots, which
#     matters when most slots hold one or two pages. Every slot has >= 1
#     live page (the live rule admits j = 0 even for an inactive slot, whose
#     zeroed table row points at scratch page 0), so the cursor's advance is
#     O(1).
#   * A block of g > 1 pages LANDS DENSE: the wrapper views the pools as
#     (pages, page_size x rows, D), the same bytes in the same order, so the
#     turn's buffer is (g x page_size x rows, D) rows with no padding. Pages
#     of fewer rows a token than the dtype's sublane tile land padded to it
#     otherwise, and re-packing them costs the body more than their copy
#     does (4 rows of bf16: 0.22 us a page beside 0.32 us of DMA, whatever
#     the turn — PERF.md section 6, PR 45).
#   * A turn's arithmetic is one pass for all heads: the block is viewed
#     as (g * page_size * KVH, D) rows in the pool's own layout, all S * H
#     query rows are scored against it in one matmul, and a column whose
#     KV head is not the row's (h // G != c % KVH) is masked together
#     with the live rule; one online-softmax update, one p v. That spends
#     KVH x the FLOPs, on an MXU whose cost here is loading the block as
#     weights — the same whether 1 or S * H rows stream through — and it
#     removes the per-head strided slices of the page, which were what a
#     live page cost (1.5-1.9 us against 0.64 us for its DMA; now 0.69).
#
# The Flex-TPU analogue (PAPERS.md 2407.08700): keep the data resident in
# the compute unit; don't materialize the logical view in HBM.
#
# One kernel serves both serving shapes: S=1 is the continuous-batching
# decode step, S=K+1 the speculative-verify slab (per-position write
# frontiers). The live rule is exactly the einsum path's:
#   j < row_len  OR  prompt_pad <= j <= write_pos[b, i]
# and GQA grouping matches _grouped_cache_attention (query head h reads kv
# head h // group). The einsum page-gather stays as the parity oracle
# (tests/test_pallas_paged.py).

# VMEM the page ring may take: half the 16 MiB a kernel gets by default,
# the rest is for the scores, a dequantized page and Mosaic's own use
_PAGED_RING_BUDGET = 8 << 20
# turns in flight beyond which a deeper ring hides no more DMA latency
_PAGED_RING_MAX = 4
# columns (tokens x rows a token) of the turn the paged kernel reaches its
# roof on: a page of 128 tokens x 8 rows of 128 lanes (PERF.md section 6,
# PR 26 and PR 45). A narrower page is taken `paged_turn_pages` at a time
_PAGED_TURN_COLS = 1024
# pages of one index turn, and the most any turn takes: their (1, ps) f32
# score rows fill one sublane tile of the index kernel's output, so its turn
# ends in ONE unmasked store
_INDEX_BLOCK_PAGES = 8


def paged_turn_pages(ps: int, rows: int, width: int,
                     lanes: int = LANES) -> int:
    """Pages one turn of the paged kernel takes, from the shapes the call
    sees: the smallest power of two whose block has `_PAGED_TURN_COLS`
    columns (a page holds ps tokens x `rows` rows a token), at most the index
    kernel's block and the table's `width`; 1 from that many columns a page
    up. A FLAT pool (one row a token, `lanes` wide: every KV head side by
    side) counts a row as its lane tiles."""
    if rows == 1 and lanes > LANES:
        rows = lanes // LANES
    g = 1
    while g * ps * rows < _PAGED_TURN_COLS \
            and 2 * g <= min(_INDEX_BLOCK_PAGES, width):
        g *= 2
    return g


def _paged_ring(g: int, dtype, *pages) -> int:
    """Ring buffers of a stream whose turn takes g pages of each of the
    shapes `pages` ((ps, KVH, D) of K and of V, (ps, dI) of the index keys):
    as many as fit _PAGED_RING_BUDGET at the size a page takes in VMEM (minor
    dims padded to the dtype's tile), between 2 (fetch one turn while another
    is read) and _PAGED_RING_MAX."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    block = g * sum(
        math.prod(lead) * -(-r // sublanes) * sublanes
        * -(-d // LANES) * LANES * itemsize for *lead, r, d in pages)
    return int(max(2, min(_PAGED_RING_MAX, _PAGED_RING_BUDGET // block)))


def _page_stream(pt_ref, last_ref, pools, bufs, sem, cur, nbuf, g, *,
                 tail=False, first_ref=None, ring=None, n_ref=None):
    """The hand-issued page stream of the pool arrays `pools` (HBM; K and V,
    or the index keys alone) that share one page table, a TURN at a time:
    (prime, take, fetch_next). A turn is a block of g consecutive logical
    pages of a slot, starting at the slot's first live page (0, or
    first_ref[slot] of a window layer, whose table is a ring: logical page t
    in column t % ring) and stepping by g up to last_ref[slot]. With `tail`
    the pages past the slot's last WHOLE block are turns of one page each, so
    no page past last_ref[slot] is ever fetched; without it the last block is
    fetched whole (the caller pads the table, and what lies past the last
    live page is dead by its own rule). `cur` (SMEM, kept across grid steps)
    holds [slot, page] where the next turn to fetch starts and [2] the turns
    consumed; turn n of the stream lives in ring buffer n % nbuf (bufs[i]:
    (nbuf, g, *page)), its page copies signal that buffer's one semaphore a
    pool, and the cursor runs ahead across slots, so the DMA queue never
    drains between them. `n_ref` (SMEM, (1,)): the stream ends after that
    many of the grid's steps (the shared-page stream's live groups, which
    come first), not after all of them."""
    nb = pl.num_programs(0) if n_ref is None else n_ref[0]
    first_of = (lambda s: 0) if first_ref is None else (lambda s: first_ref[s])

    def start(fs, fp, b, pages):
        for i in range(pages):
            col = fp + i if ring is None else (fp + i) % ring
            page = pt_ref[fs, col]
            for j, (hbm, buf) in enumerate(zip(pools, bufs)):
                pltpu.make_async_copy(hbm.at[page], buf.at[b, i],
                                      sem.at[j, b]).start()

    def fetch_next(b):
        fs, fp = cur[0], cur[1]

        @pl.when(fs < nb)
        def _():
            last = last_ref[fs]
            if tail and g > 1:
                whole = fp + (g - 1) <= last

                @pl.when(whole)
                def _():
                    start(fs, fp, b, g)

                @pl.when(jnp.logical_not(whole))
                def _():
                    start(fs, fp, b, 1)

                nxt = fp + jnp.where(whole, g, 1)
            else:
                start(fs, fp, b, g)
                nxt = fp + g
            more = nxt <= last
            cur[0] = jnp.where(more, fs, fs + 1)
            cur[1] = jnp.where(more, nxt,
                               first_of(jnp.minimum(fs + 1, nb - 1)))

    def prime(ahead):
        """At the first grid step: the cursor to the stream's start and the
        first `ahead` turns fetched."""
        @pl.when(pl.program_id(0) == 0)
        def _():
            cur[0] = 0
            cur[1] = first_of(0)
            cur[2] = 0
            for i in range(ahead):
                fetch_next(i)

    def take(pages=g):
        """The next turn of the stream, a block (or, of a tail, one page),
        all its copies waited for at once (the semaphore counts bytes): its
        ring buffer."""
        n = cur[2]
        cur[2] = n + 1
        b = jax.lax.rem(n, nbuf)
        for j, buf in enumerate(bufs):
            got = buf.at[b] if pages == g else buf.at[b, pl.ds(0, pages)]
            pltpu.make_async_copy(got, got, sem.at[j, b]).wait()
        return b

    return prime, take, fetch_next


def _paged_attn_kernel(pt_ref, lp_ref, wp_ref, rl_ref, pp_ref, *rest,
                       s: int, h: int, kvh: int, ps: int, nbuf: int, g: int,
                       scale: float, quantized: bool = False,
                       window: Optional[int] = None, pack: int = 1,
                       sink: bool = False, flat: bool = False,
                       carry: bool = False):
    """One slot per grid step: score the slot's (S*H, Dqk) query rows
    against its live pages, a turn of g pages at a time (the pages past the
    last whole block one at a time), and fold each turn into the running
    online softmax (f32 m, l, acc carried by the loops). Scalar-prefetch
    refs: page table (B, P), last live page (B,), per-position write
    frontier (B, S), row_len (B,), prompt_pad (B,) — and, for a quantized
    pool, the per-(pool page, kv head) f32 k/v scales (P_pool, KVH): the
    quantized payload is what the DMA moves, and it dequantizes HERE, in
    VMEM, against the scales of the pool page it came from — the
    full-width KV never exists in HBM.

    k_hbm / v_hbm are the whole pools, left in HBM; `_page_stream` brings
    their pages in, nbuf - 1 turns ahead of the arithmetic: the turn fetched
    at the top of an iteration lands in the buffer the previous iteration
    finished reading.

    A `window` layer (one more scalar-prefetch ref, each slot's first live
    page) keeps its pages in a RING: the table is as wide as the ring and
    logical page t lives in column t % width. The slot's loops and the
    stream's cursor start at the window's first page, and a key at or
    below `frontier - window` is dead.

    `pack` > 1 (heads narrower than a lane tile, ops/attention.py
    `pool_pack`): a row of a page holds `pack` neighbouring KV heads side by
    side in its 128 lanes, `kvh / pack` such rows a token, and a query row
    carries its head's entries in the lanes of ITS KV head and zeros in the
    others. A column is then a (token, row of `pack` KV heads), a query row
    owns the column its KV head lies in, and its zeros keep the neighbours
    out of its scores; its context comes out in its own lanes of the 128.

    `flat` (heads whose width the lanes do not divide: keys of 192,
    ops/attention.py `pool_pack`): a token is ONE row of a page, all its KV
    heads side by side (KVH x D lanes, whole lane tiles), and a query row
    carries its head's entries in the lanes of its KV head and zeros in the
    others, so the contraction over the whole row IS the row's own head's
    logit: a column is a token, no column belongs to another head, and the
    context comes out KVH x Dv wide with the row's own head's lanes picked
    at the end.

    With `sink` (static) one more input after the queries, the (S*H, 1)
    sink logit of each query row: the running maximum starts there and the
    denominator at exp(sink - max) = 1, so the sink takes its share and adds
    no value; without it the kernel is the one it always was.

    With `carry` (static; the second half of the shared-page form,
    `_paged_shared_kernel`) two more scalar-prefetch refs, each slot's first
    page of its OWN (as a window layer's first page, on a table that is no
    ring) and its row of its group's partials (read only for its sign: -1,
    the slot is in no group), and three more inputs after the sink, the slot's
    (H, 128) running maximum and denominator (a row's value in every lane)
    and its (H, Dv) accumulator (of a flat pool the row's own KV head's Dv
    lanes): a slot of a group starts its online softmax from them, which
    IS the exact merge of the softmax over the shared pages with the softmax
    over its own, and streams its own pages only."""
    fp_ref = src_ref = None
    if window is not None or carry:
        fp_ref, rest = rest[0], rest[1:]
    if carry:
        src_ref, rest = rest[0], rest[1:]
    ks_ref = vs_ref = sink_ref = None
    if quantized:
        ks_ref, vs_ref, rest = rest[0], rest[1], rest[2:]
    q_ref, rest = rest[0], rest[1:]
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    if carry:
        m_ref, l_ref, acc_ref, rest = *rest[:3], rest[3:]
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, cur = rest
    b = pl.program_id(0)
    ring = pt_ref.shape[1] if window is not None else None
    grp = h // kvh
    heads_kv = kvh
    kvh = 1 if flat else kvh // pack    # rows a token takes in a page
    rows = s * h
    prime, take, fetch_next = _page_stream(
        pt_ref, lp_ref, (k_hbm, v_hbm), (k_buf, v_buf), sem, cur, nbuf, g,
        tail=True, first_ref=fp_ref, ring=ring)
    prime(nbuf - 1)

    # what does not change from turn to turn: each row's frontier; which
    # columns of a turn belong to a row's KV head, and each column's token
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    wp = jnp.full((rows, 1), wp_ref[b, 0], jnp.int32)
    for i in range(1, s):
        # slab position i attends at its OWN frontier wp[b, i]: in-slab
        # causality for the verify slab
        wp = jnp.where(row >= i * h, wp_ref[b, i], wp)
    rl = rl_ref[b]
    pp = pp_ref[b]
    q = q_ref[0]                                        # (S*H, Dqk)

    def columns(pages):
        col = jax.lax.broadcasted_iota(jnp.int32, (1, pages * ps * kvh), 1)
        if flat:    # a column is a token: every one is the row's own head's
            return True, col
        own_head = (row % h) // (grp * pack) == col % kvh   # (rows, cols)
        return own_head, col // kvh                         # tok (1, cols)

    def head_scales(sc_ref, page, d):
        # (KVH, d) tile of the page's per-head scales, from SMEM scalars
        kh = jax.lax.broadcasted_iota(jnp.int32, (kvh, d), 0)
        tile = jnp.full((kvh, d), sc_ref[page, 0], jnp.float32)
        for i in range(1, kvh):
            tile = jnp.where(kh == i, sc_ref[page, i], tile)
        return tile

    def dequantized(x, sc_ref, t):
        # each page of the turn against its own pool page's scales
        tiles = [head_scales(sc_ref,
                             pt_ref[b, t + i if ring is None
                                    else (t + i) % ring], x.shape[-1])
                 for i in range(x.shape[0])]
        return x.astype(jnp.float32) * jnp.stack(tiles)[:, None]

    def turn(pages):
        """The arithmetic of a turn of `pages` pages from logical page t."""
        cols = pages * ps * kvh
        own_head, tok = columns(pages)

        def body(t, state):
            m_prev, l_prev, acc = state
            fetch_next(jax.lax.rem(cur[2] + nbuf - 1, nbuf))
            buf = take(pages)
            # (pages, ps, KVH, D), or dense (pages, ps x KVH, D)
            k = k_buf[buf, :pages]
            v = v_buf[buf, :pages]
            if quantized:
                k = dequantized(k, ks_ref, t)
                v = dequantized(v, vs_ref, t)
            # mixed-width pool (kv_cache_dtype='bf16' under f32 compute) and
            # the dequantized page: both matmuls run at query precision,
            # matching the einsum oracle's cast
            k = k.reshape(cols, -1).astype(q.dtype)
            v = v.reshape(cols, -1).astype(q.dtype)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, cols)
            j = t * ps + tok
            live = (j < rl) | ((j >= pp) & (j <= wp))
            if window is not None:
                live = live & (j > wp - window)
            sc = jnp.where(live & own_head, sc, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(sc - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        return body

    first = 0 if fp_ref is None else fp_ref[b]
    state = (jnp.full((rows, 1), NEG_INF, jnp.float32),
             jnp.zeros((rows, 1), jnp.float32),
             jnp.zeros((rows, v_buf.shape[-1]), jnp.float32))
    if sink_ref is not None:
        state = (sink_ref[...], jnp.ones((rows, 1), jnp.float32), state[2])
    if carry:
        # a select, not arithmetic: the row of a slot in no group holds
        # whatever an idle grid step of the shared stream left there
        grouped, acc0 = src_ref[b] >= 0, acc_ref[0]
        if flat:
            # the group's stream kept the row's own KV head's lanes alone
            acc0 = jnp.concatenate(
                [jnp.where((row % h) // grp == i, acc0, 0.0)
                 for i in range(heads_kv)], axis=1)
        state = tuple(jnp.where(grouped, x, x0) for x, x0 in zip(
            (m_ref[0][:, 0:1], l_ref[0][:, 0:1], acc0), state))
    if g > 1:
        block, blocks = turn(g), (lp_ref[b] + 1 - first) // g
        state = jax.lax.fori_loop(
            0, blocks, lambda i, c, t0=first: block(t0 + i * g, c), state)
        first += blocks * g
    # at g = 1 every live page; else the pages past the slot's last whole
    # block, one a turn: a dead page is never fetched (its 0 x NaN in p v
    # would be NaN)
    _, l_fin, acc = jax.lax.fori_loop(first, lp_ref[b] + 1, turn(1), state)
    # every row has >= 1 live position (its own write frontier:
    # prompt_pad <= write_pos always holds, and the inactive-slot zeros
    # satisfy j == 0 <= write_pos == 0), so l > 0 — no guard. A turn in
    # which a row has NO live column leaves p = 1 everywhere while m is
    # still NEG_INF; the first live score then scales that away (alpha = 0)
    out = acc / l_fin
    if flat:
        # (rows, KVH x Dv): the row's own KV head's lane tiles
        dv = out.shape[1] // heads_kv
        own = (row % h) // grp
        picked = out[:, 0:dv]
        for i in range(1, heads_kv):
            picked = jnp.where(own == i, out[:, i * dv:(i + 1) * dv], picked)
        out = picked
    o_ref[0] = out.astype(o_ref.dtype)


# ---- a page several live slots hold, streamed once for all of them
#
# Live slots that were admitted on a prefix hit hold THE SAME pool pages in
# the leading columns of their table rows (runtime/kv_pool.py: the trie's
# pages are shared by reference). The per-slot kernel above fetches such a
# page once a slot. The shared-page form fetches it once a GROUP:
#
#   * `_paged_shared_kernel`, grid = the groups: the group's shared pages go
#     through the same page stream, and every fetched turn is scored against
#     the query rows of ALL the group's members, a sub-block of
#     `_SHARED_BLOCK_ROWS` rows (whole members) at a time, one online softmax
#     a row (a FLAT pool's rows a KV head at a time against that head's own
#     lane tiles: the per-slot kernel's zeros would cost a group of eight four
#     times its stream). Every token of a shared page is live for every
#     member (that is what makes the page one the group may share), so no
#     live rule is evaluated here. What it leaves is each member's partial
#     state (running maximum, denominator, accumulator, all f32).
#   * `_paged_attn_kernel` with `carry`: each slot streams its OWN pages (its
#     question, its answer, the page it writes) as ever, starting from its
#     group's partial state instead of from nothing. That start IS the exact
#     merge: m = max(m1, m2), l = l1 e^(m1 - m) + l2 e^(m2 - m), likewise the
#     accumulator; a sink's logit enters once, at the start of whichever
#     stream a slot's softmax begins in.
#
# Which slots form a group is the caller's to say (`pack_shared_groups`; the
# serving engine reads it off its page tables, runtime/kv_pool.py
# `shared_page_groups`): an engine that never admits a request on a prefix
# hit hands its programs no groups, and they hold the per-slot kernel alone.

# query rows one sub-block of a group's members takes through a fetched turn:
# the turn's keys are the MXU's weights, which cost the same to load whether
# 1 or 128 rows stream through them
_SHARED_BLOCK_ROWS = 128
# query rows of all the members one group scores against a fetched turn: what
# their query block and their f32 state (running maximum, denominator,
# accumulator: 128 lanes or more a row each, twice for the outputs' two
# buffers) may take of VMEM beside the page ring. A larger group is split
_SHARED_GROUP_ROWS = 512


def shared_members_cap(h: int) -> int:
    """Most members a group of the shared-page form may have at `h` query
    heads: `_SHARED_GROUP_ROWS` query rows in all (8 at 64 heads, 12 at 40,
    32 at 16)."""
    return max(1, _SHARED_GROUP_ROWS // h)


def _shared_block_members(m: int, rows: int) -> int:
    """Members a sub-block takes of groups of at most `m`, at `rows` query
    rows a member in one matmul: the most that divide `m` and put
    `_SHARED_BLOCK_ROWS` rows or fewer through it."""
    return max(d for d in range(1, m + 1)
               if m % d == 0 and (d == 1 or d * rows <= _SHARED_BLOCK_ROWS))


def pack_shared_groups(groups, slots: int, cap: int):
    """The shared-page form's two arguments, as numpy int32 arrays, from
    `groups`: [(member slots, shared pages)], each group's members at most
    `cap`, a slot in at most one group.

      * (slots // 2, 2 + cap): a row a group, the live ones first: its member
        count (0: no group), its shared pages, its members (padded with slot
        0, whose rows are then scored and dropped);
      * (slots, 2): a row a slot: the row `group * cap + position` of the
        partials its group's stream leaves for it (-1: in no group) and the
        first page of its own (the group's shared pages; 0).

    Every member's table row holds the same pool pages in its first `shared
    pages` columns, every token of them is live for it, and it writes none of
    them: the CALLER's promise, the kernel checks nothing."""
    import numpy as np

    packed = np.zeros((max(1, slots // 2), 2 + cap), np.int32)
    slot_of = np.zeros((slots, 2), np.int32)
    slot_of[:, 0] = -1
    for gi, (members, pages) in enumerate(groups):
        assert 2 <= len(members) <= cap and pages >= 1, (members, pages)
        packed[gi, 0], packed[gi, 1] = len(members), pages
        packed[gi, 2:2 + len(members)] = members
        for j, slot in enumerate(members):
            assert slot_of[slot, 0] < 0, (slot, groups)
            slot_of[slot] = gi * cap + j, pages
    return packed, slot_of


def _flat_head_lanes(j: int, d: int):
    """The whole lane tiles of a flat row that hold KV head j's `d` lanes
    (keys of 192: head 1's lanes 192..383 lie in tiles 128..383)."""
    return j * d // LANES * LANES, -(-(j + 1) * d // LANES) * LANES


def _lanes_spread(x, n: int):
    """x (rows, 128), the same value in every lane of a row -> (rows, n)."""
    if n == LANES:
        return x
    if n % LANES == 0:
        return pltpu.repeat(x, n // LANES, axis=1)
    return jnp.broadcast_to(x[:, 0:1], (x.shape[0], n))


def _paged_shared_kernel(pt_ref, lp_ref, n_ref, cnt_ref, q_ref, *rest,
                         h: int, kvh: int, ps: int, nbuf: int, g: int,
                         ms: int, scale: float, pack: int = 1,
                         sink: bool = False, flat: bool = False):
    """One GROUP per grid step: the group's shared pages streamed once (the
    leader's table row pt_ref[group], pages 0 .. lp_ref[group]) and each
    fetched turn scored against the members' query rows, `ms` members (ms x H
    rows) a sub-block, q_ref (1, subs, ms x H, Dqk). n_ref (1,): the live
    groups, which come first: a later grid step does nothing. cnt_ref: each
    group's members; only the sub-blocks that hold one are scored.

    The running state lives in the OUTPUT blocks, a sub-block a leading
    index: m_ref and l_ref (1, subs, ms x H, 128) hold the running maximum
    and the denominator, a row's value in every lane (a (rows, 1) value
    would be sliced out of and spread back over the lanes every turn),
    acc_ref (1, subs, ms x H, Dv) the accumulator. With `sink` the maximum
    starts at the sink's logit and the denominator at 1, as in the per-slot
    kernel. No live rule: every token of a shared page is live for every
    member.

    The rows of a sub-block lie (member, head) and meet every column of a
    turn in ONE matmul, a column of another KV head masked, as per slot
    (`pack` as there). A `flat` pool's sub-block lies (KV head, member, head
    of the group) instead and takes a matmul a KV HEAD: its rows against the
    whole lane tiles of the turn's rows that hold the head's key lanes (the
    query's zeros cancel a neighbour's lanes in a shared tile), its
    accumulator the head's own Dv lanes. The per-slot kernel pushes every
    query row through every lane tile of a flat row, all but one KV head's
    against zeros, which costs a lone slot nothing (its 64 rows take no
    longer than the tiles take to load) and a group of eight four times its
    stream. A flat pool's q_ref holds each row's window of those tiles alone
    (`_flat_windows`). The KV heads' chains are independent: all are scored,
    then all folded, and the state is read before and written after, so the
    scheduler may run them side by side."""
    sink_ref = None
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    k_hbm, v_hbm, m_ref, l_ref, acc_ref, k_buf, v_buf, sem, cur = rest
    gi = pl.program_id(0)
    grp = h // kvh
    heads_kv = kvh
    kvh = 1 if flat else kvh // pack    # rows a token takes in a page
    rows = ms * h
    prime, take, fetch_next = _page_stream(
        pt_ref, lp_ref, (k_hbm, v_hbm), (k_buf, v_buf), sem, cur, nbuf, g,
        tail=True, n_ref=n_ref)
    prime(nbuf - 1)
    # the row blocks of a sub-block that take a matmul each, and for a flat
    # pool the key and value lanes of each one's KV head
    parts = [(slice(None), slice(None), slice(None))]
    if flat:
        dk = k_buf.shape[-1] // heads_kv
        dv = v_buf.shape[-1] // heads_kv
        parts = [(slice(j * ms * grp, (j + 1) * ms * grp),
                  slice(*_flat_head_lanes(j, dk)),
                  slice(j * dv, (j + 1) * dv)) for j in range(heads_kv)]

    @pl.when(gi < n_ref[0])
    def _():
        subs = (cnt_ref[gi] + ms - 1) // ms
        m0 = jnp.full((rows, LANES), NEG_INF, jnp.float32)
        l0 = jnp.zeros((rows, LANES), jnp.float32)
        if sink_ref is not None:
            m0 = jnp.broadcast_to(sink_ref[...], (rows, LANES))
            l0 = jnp.ones((rows, LANES), jnp.float32)

        def start(i, _):
            m_ref[0, i], l_ref[0, i] = m0, l0
            acc_ref[0, i] = jnp.zeros(acc_ref.shape[2:], jnp.float32)
            return 0

        jax.lax.fori_loop(0, subs, start, 0)

        def turn(pages):
            cols = pages * ps * kvh
            own_head = None
            if not flat:
                row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
                col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
                own_head = (row % h) // (grp * pack) == col % kvh

            def sub(buf, i):
                kept = [(m_ref[0, i, at], l_ref[0, i, at], acc_ref[0, i, at])
                        for at, _, _ in parts]
                scored = []
                for at, lk, _ in parts:
                    # a flat pool's query rows hold their head's window alone
                    q = q_ref[0, i, at] if lk.start is None \
                        else q_ref[0, i, at, :lk.stop - lk.start]
                    k = k_buf[buf, :pages, ..., lk].reshape(cols, -1)
                    sc = jax.lax.dot_general(
                        q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    scored.append(sc if own_head is None
                                  else jnp.where(own_head, sc, NEG_INF))
                for (at, _, lv), sc, (m_prev, l_prev, acc) in zip(
                        parts, scored, kept):
                    v = v_buf[buf, :pages, ..., lv].reshape(cols, -1)
                    m_new = jnp.maximum(
                        m_prev, jnp.max(sc, axis=-1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(sc - _lanes_spread(m_new, cols))
                    l_ref[0, i, at] = l_prev * alpha + jnp.sum(
                        p, axis=-1, keepdims=True)
                    m_ref[0, i, at] = m_new
                    acc_ref[0, i, at] = acc * _lanes_spread(
                        alpha, acc.shape[-1]) + jnp.dot(
                            p.astype(q_ref.dtype), v.astype(q_ref.dtype),
                            preferred_element_type=jnp.float32)

            def body(_, c):
                fetch_next(jax.lax.rem(cur[2] + nbuf - 1, nbuf))
                buf = take(pages)

                def one(i, c):
                    sub(buf, i)
                    return c

                return jax.lax.fori_loop(0, subs, one, c)

            return body

        n_pages, first = lp_ref[gi] + 1, 0
        if g > 1:
            first = (n_pages // g) * g
            jax.lax.fori_loop(0, n_pages // g, turn(g), 0)
        # the pages past the last whole block, one a turn, as per slot
        jax.lax.fori_loop(first, n_pages, turn(1), 0)


def _flat_windows(q, kvh: int):
    """q (B, H, D) of a flat pool's op -> (B, H, W): each query row's entries
    where they lie in the whole lane tiles that hold its KV head's keys
    (`_flat_head_lanes`: at keys of 192, heads 1 and 3 start 64 lanes into
    their first tile), zeros in the rest of the window: a third of the row
    the per-slot kernel contracts."""
    b, h, d = q.shape
    spans = [_flat_head_lanes(j, d) for j in range(kvh)]
    width = max(hi - lo for lo, hi in spans)
    q = q.reshape(b, kvh, h // kvh, d)
    return jnp.concatenate(
        [jnp.pad(q[:, j:j + 1], ((0, 0),) * 3
                 + ((j * d - lo, width - d - (j * d - lo)),))
         for j, (lo, _) in enumerate(spans)], axis=1).reshape(b, h, width)


def _shared_partials(q, k_pages, v_pages, page_table, groups, src, sink_rows,
                     *, h, kvh, ps, g, nbuf, k_page, v_page, scale, pack,
                     flat):
    """The first half of the shared-page form: q (B, H, Dqk) as the per-slot
    kernel takes it (a packed pool's zeros in place; of a flat pool its
    heads' own D lanes), the pools as it streams them, `groups` as
    `pack_shared_groups` packs them, `src` (B,) each slot's row of the
    partials -> each SLOT's partial state ((B, H, 128) running maximum and
    the same of the denominator, a row's value in every lane, (B, H, Dv)
    accumulator, of a flat pool the row's own KV head's Dv lanes, f32;
    whatever an idle step left, for a slot in no group)."""
    n_groups, m = groups.shape[0], groups.shape[1] - 2
    grp = h // kvh
    ms = _shared_block_members(m, grp if flat else h)
    subs, rows = m // ms, ms * h
    dv = v_page[-1] // (kvh if flat else 1)
    count, pages, members = groups[:, 0], groups[:, 1], groups[:, 2:]
    # a sub-block's rows lie (member, head); of a flat pool (KV head,
    # member, head of the group): a KV head's rows together
    split = (kvh, grp) if flat else (1, h)

    def sub_blocks(x, n=subs):  # (.., n x ms, H, D) -> (.., n, ms x H, D)
        lead, d = x.shape[:-3], x.shape[-1]
        x = jnp.swapaxes(x.reshape(*lead, n, ms, *split, d), -4, -3)
        return x.reshape(*lead, n, rows, d)

    with jax.named_scope("core"):
        if flat:
            q = _flat_windows(q, kvh)
        # the live groups come first: their number ends the stream
        prefetch = [page_table[members[:, 0]].astype(jnp.int32),
                    jnp.maximum(pages - 1, 0),
                    jnp.sum(count > 0, dtype=jnp.int32)[None], count]
        operands = [sub_blocks(q[members])]
    in_specs = [pl.BlockSpec((1, subs, rows, q.shape[-1]),
                             lambda gi, *_: (gi, 0, 0, 0))]
    if sink_rows is not None:
        # one logit a query row, in a sub-block's order
        operands.append(sub_blocks(
            jnp.broadcast_to(sink_rows, (ms, h, 1)), 1)[0])
        in_specs.append(pl.BlockSpec((rows, 1), lambda gi, *_: (0, 0)))
    state = pl.BlockSpec((1, subs, rows, LANES), lambda gi, *_: (gi, 0, 0, 0))
    partials = pl.pallas_call(
        functools.partial(_paged_shared_kernel, h=h, kvh=kvh, ps=ps,
                          nbuf=nbuf, g=g, ms=ms, scale=scale, pack=pack,
                          sink=sink_rows is not None, flat=flat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_groups,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY),
                                 pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[
                state, state,
                pl.BlockSpec((1, subs, rows, dv),
                             lambda gi, *_: (gi, 0, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((nbuf, g, *k_page), k_pages.dtype),
                pltpu.VMEM((nbuf, g, *v_page), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, nbuf)),
                pltpu.SMEM((3,), jnp.int32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, subs, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, subs, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_groups, subs, rows, dv), jnp.float32)],
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=_interpret(),
    )(*prefetch, *operands, k_pages, v_pages)
    with jax.named_scope("core"):
        # a slot's rows out of its group's sub-block: only they are read
        at = jnp.maximum(src, 0)
        gi, sub, pos = at // m, at % m // ms, at % ms
        return tuple(
            x.reshape(n_groups, subs, split[0], ms, split[1], x.shape[-1])
            [gi, sub, :, pos].reshape(src.shape[0], h, x.shape[-1])
            for x in partials)


def paged_attention_fwd_pallas(q, k_pages, v_pages, page_table, write_pos,
                               row_len, prompt_pad, scale: float,
                               k_scales=None, v_scales=None,
                               window: Optional[int] = None, sink=None,
                               kv_heads: Optional[int] = None, shared=None):
    """Paged-pool attention: q (B, S, H, Dqk) against k_pages/v_pages
    ((P_pool, page_size, KVH, D)) through per-slot page tables
    ((B, pages_per_slot) int32) -> (B, S, H, Dv) context.

    write_pos (B, S) int32 is each slab position's logical write
    frontier (host-clamped, nondecreasing over S); row_len / prompt_pad
    (B,) the ragged-prompt live-rule bounds. The grid is the slots; each
    grid step loops over its slot's live pages only, a turn of
    `paged_turn_pages` pages at a time (one where a page has the columns,
    a block of 2, 4 or 8 of narrower pages, which then lands dense; the
    pages past the last whole block one a turn), whose DMAs were issued
    by hand some turns earlier (possibly during the previous slot) into a
    ring of VMEM buffers as deep as _paged_ring says for these shapes.
    HBM traffic and time both follow the LIVE pages, not the pool and not
    the table's width. A slot that is not active
    (write_pos == row_len == prompt_pad == 0) is indistinguishable from
    a one-token context and streams its one page (scratch page 0), as
    the oracle reads it. Inference-only: no VJP (the serving engine never
    differentiates through decode).

    ``k_scales``/``v_scales`` ((P_pool, KVH) f32, both or neither) mark
    a QUANTIZED pool (int8/fp8 payload, ISSUE 11): they ride the
    scalar-prefetch stream into SMEM next to the page table, and each
    page dequantizes in its VMEM buffer against its own scales before
    the score/context matmuls — per-page HBM traffic is the quantized
    bytes, and the full-width KV is never materialized anywhere. The
    einsum page-gather path applies the same dequant after its gather,
    staying the parity oracle.

    ``window`` marks a window layer: position j is live only where
    frontier - window < j as well, the page table is the slot's RING
    (logical page t in column t % width: width pages hold any window of at
    most (width - 1) * page_size + 1 positions) and the slot's pages are
    streamed from the window's first, not from page 0.

    ``sink`` ((H,) f32) marks a layer whose softmax carries a sink: a logit
    a query head in the denominator, no value.

    ``kv_heads`` marks a FLAT pool (ops/attention.py `pool_pack` == the KV
    heads): k_pages (P_pool, page_size, KVH x Dqk), v_pages (.., KVH x Dv),
    one row a token.

    ``shared`` (`pack_shared_groups`' two arrays; a decode step over a pool
    at full width that is no window's ring) marks the shared-page form:
    the slots of a group hold the same pool pages in their first columns,
    those pages are streamed once a group (`_paged_shared_kernel`) and each
    slot streams its own pages from the partial state that leaves it. Arrays
    that hold no group give the per-slot result at the cost of the idle
    grid steps; None is the per-slot kernel alone."""
    b, s, h, dqk = q.shape
    flat, q_heads = k_pages.ndim == 3, q
    if flat:
        # one row a token: seen as one "KV head" row of KVH x D lanes, the
        # page a dense (ps, lanes) block whatever the turn
        assert kv_heads and k_pages.shape[2] == kv_heads * dqk \
            and v_pages.shape[2] % kv_heads == 0 and k_scales is None, \
            (k_pages.shape, v_pages.shape, kv_heads)
        k_pages, v_pages = k_pages[:, :, None], v_pages[:, :, None]
    ps, kvh = k_pages.shape[1], k_pages.shape[2]
    dv = v_pages.shape[3]
    # a pool whose rows hold `pack` neighbouring KV heads of `dqk` side by
    # side (ops/attention.py `pool_pack`: heads narrower than the lanes)
    pack, d0 = (dv // dqk if k_pages.shape[3] != dqk else 1), dqk
    if flat:
        pack, kvh, d0 = 1, kv_heads, dv // kv_heads
        # query head i reads KV head i // grp: its entries go to that
        # head's lanes of the row, zeros to the others
        own = (jnp.arange(h) // (h // kvh))[:, None] == jnp.arange(kvh)
        q = (q[:, :, :, None, :] * own[None, None, :, :, None]
             .astype(q.dtype)).reshape(b, s, h, kvh * dqk)
        dqk = kvh * dqk
    elif pack > 1:
        assert k_pages.shape[3] == dv == LANES, (k_pages.shape, dqk)
        kvh = kvh * pack
        # query head i reads KV head i // grp: its entries go to that
        # head's lanes of the row, zeros to the others
        lane_of = (jnp.arange(h) // (h // kvh)) % pack              # (H,)
        q = (q[:, :, :, None, :]
             * (lane_of[:, None] == jnp.arange(pack))[None, None, :, :, None]
             .astype(q.dtype)).reshape(b, s, h, dv)
        dqk = dv
    assert h % kvh == 0, f"heads {h} not a multiple of kv heads {kvh}"
    assert (k_scales is None) == (v_scales is None), \
        "quantized pools carry BOTH k and v scales"
    quantized = k_scales is not None
    rows = 1 if flat else kvh // pack   # rows a token takes in a page
    g = paged_turn_pages(ps, rows, page_table.shape[1], dqk)
    k_page, v_page = (ps, rows, dqk), (ps, rows, dv)
    if flat or (g > 1 and not quantized):
        # a block LANDS DENSE: the pools seen as (pages, ps x rows, D), the
        # same bytes in the same order (XLA makes the reshape a bitcast of
        # the tiled pool), so a turn's g pages are one (g x ps x rows, D)
        # buffer with no sublane padding. A page of fewer rows a token than
        # the dtype's sublane tile (4 or 2 against bf16's 16) lands padded
        # to the tile otherwise and is re-packed by the body, 128 registers
        # a page and tensor whatever the turn: 0.22 us a page of 4 rows
        # against its 0.32 us copy (PERF.md section 6, PR 45). A quantized
        # pool keeps its rows apart: its scales are a head's
        k_page, v_page = (ps * rows, dqk), (ps * rows, dv)
        k_pages = k_pages.reshape(-1, *k_page)
        v_pages = v_pages.reshape(-1, *v_page)
    nbuf = _paged_ring(g, k_pages.dtype, k_page, v_page)
    # last live page per slot: the live rule's bound is max(write
    # frontier, prompt tail) — a serving dispatch always has write_pos
    # >= prompt_pad >= row_len, but the kernel honors the FULL rule so
    # a direct caller querying inside the prompt (write_pos < row_len)
    # still streams the prompt's pages. The slab's max frontier is its
    # final position's (host-built nondecreasing; jnp.max guards the
    # clamp-equal tail anyway).
    last_idx = jnp.maximum(jnp.max(write_pos, axis=1), row_len - 1)
    last_page = (last_idx // ps).astype(jnp.int32)

    # trailing prefetch refs ride into the index map as *_
    def slot_map(bi, *_):
        return (bi, 0, 0)

    prefetch = [page_table.astype(jnp.int32), last_page,
                write_pos.astype(jnp.int32), row_len.astype(jnp.int32),
                prompt_pad.astype(jnp.int32)]
    if window is not None:
        assert (page_table.shape[1] - 1) * ps + 1 >= window, \
            f"a ring of {page_table.shape[1]} pages of {ps} cannot hold a " \
            f"window of {window}"
        first_idx = jnp.maximum(jnp.min(write_pos, axis=1) - window + 1, 0)
        prefetch.append((first_idx // ps).astype(jnp.int32))
    if quantized:
        prefetch += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]
    operands = [q.reshape(b, s * h, dqk)]
    in_specs = [pl.BlockSpec((1, s * h, dqk), slot_map)]
    if sink is not None:
        # one logit a query row, the same block at every slot
        operands.append(jnp.tile(sink.astype(jnp.float32), s)[:, None])
        in_specs.append(pl.BlockSpec((s * h, 1), lambda bi, *_: (0, 0)))
    if shared is not None:
        assert s == 1 and window is None and not quantized, \
            "the shared-page form is a decode step's, over a full-width " \
            "pool that is no ring"
        groups, slot_of = (jnp.asarray(x, jnp.int32) for x in shared)
        m_run, l_run, acc = _shared_partials(
            q_heads[:, 0] if flat else operands[0], k_pages, v_pages,
            prefetch[0], groups,
            slot_of[:, 0], operands[1] if sink is not None else None, h=h,
            kvh=kvh, ps=ps, g=g, nbuf=nbuf, k_page=k_page, v_page=v_page,
            scale=scale, pack=pack, flat=flat)
        # each slot's first own page, and whether it is in a group at all
        prefetch += [slot_of[:, 1], slot_of[:, 0]]
        operands += [m_run, l_run, acc]
        in_specs += [pl.BlockSpec((1, h, LANES), slot_map),
                     pl.BlockSpec((1, h, LANES), slot_map),
                     pl.BlockSpec((1, h, acc.shape[-1]), slot_map)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b,),
        in_specs=in_specs + [
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, s * h, d0 if flat else dv), slot_map),
        scratch_shapes=[
            pltpu.VMEM((nbuf, g, *k_page), k_pages.dtype),
            pltpu.VMEM((nbuf, g, *v_page), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, nbuf)),
            pltpu.SMEM((3,), jnp.int32),               # the stream's state
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, s=s, h=h, kvh=kvh, ps=ps,
                          nbuf=nbuf, g=g, scale=scale, quantized=quantized,
                          window=window, pack=pack, sink=sink is not None,
                          flat=flat, carry=shared is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, s * h, d0 if flat else dv), q.dtype),
        # sequential: the cursor and the ring carry over from slot to slot
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=_interpret(),
    )(*prefetch, *operands, k_pages, v_pages)
    if pack > 1:
        # each head's context lies in the lanes of its KV head
        out = jnp.take_along_axis(
            out.reshape(b, s, h, pack, d0),
            lane_of[None, None, :, None, None], axis=3)
        return out.reshape(b, s, h, d0)
    return out.reshape(b, s, h, d0 if flat else dv)


def paged_prefill_write_pallas(cache, kh, vh, pages):
    """Prefill/append page scatter: write a (1, S, KVH, D) KV slab into
    the paged pool page-at-a-time from VMEM (ISSUE 18 tentpole (c)).

    The einsum oracle (attention.paged_prefill_write) materializes the
    page-reshaped slab and issues one big ``pool.at[pages].set`` —
    XLA's scatter lowering stages the whole slab through HBM. Here the
    grid is (n_pages,): each step DMAs ONE page-sized slab tile into
    VMEM and writes it (quantizing in-register when the pool is
    int8/fp8) to its pool page, so peak on-chip footprint is one page
    regardless of prompt length. ``pages`` rides the scalar-prefetch
    stream and drives the output index map — the paged-pool idiom of
    paged_attention_fwd_pallas, pointed at the write path.

    The pool (and, when quantized, the per-page scale planes) are
    aliased input->output so untouched pages survive: the grid only
    visits the scatter list, and every non-visited output block must
    retain the incoming pool bytes. Alias indices count the scalar-
    prefetch operand (pallas initializes outputs from the FULL operand
    list, prefetch included).

    Quantized pools recompute attention.page_scale / page_quantize
    inside the kernel via the imported helpers themselves — elementwise
    f32 ops, so interpret mode is BITWISE against the einsum oracle and
    the PR 11 published-state contract (scales + payload) holds; compiled
    natively, a payload element may sit one quantization step from the
    oracle's where the two f32 divisions differ in the last place.
    Returns a new cache dict with the k/v pools (and scales) replaced."""
    from flexflow_tpu.ops.attention import (page_quantize, page_scale,
                                            storage_qmax)

    pool_k, pool_v = cache["k"], cache["v"]
    ps, kvh = pool_k.shape[1], pool_k.shape[2]     # kvh: a quantized pool's
    # a page's trailing dims: (rows a token, lanes), or of a flat pool the
    # lanes alone
    tail_k, tail_v = pool_k.shape[2:], pool_v.shape[2:]
    zeros = (0,) * len(tail_k)
    n_pages = len(pages)
    quantized = "k_scale" in cache
    qmax = storage_qmax(pool_k.dtype) if quantized else 0.0

    def paged(x, tail):
        # identical host-side prep to the einsum oracle: pad the slab
        # tail to a page boundary, reshape to page-major tiles
        s = x.shape[1]
        pad = n_pages * ps - s
        x = x[0]
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
        return x.reshape(n_pages, ps, *tail)

    kp = paged(kh, tail_k)
    vp = paged(vh, tail_v)
    pages = jnp.asarray(pages, jnp.int32)

    def slab_map(t, pages_ref):
        return (t, 0, *zeros)

    def pool_map(t, pages_ref):
        return (pages_ref[t], 0, *zeros)

    def scale_map(t, pages_ref):
        return (pages_ref[t], 0, 0)

    def kernel(pages_ref, *refs):
        if quantized:
            (kp_ref, vp_ref, _pk, _pv, _ks, _vs,
             pk_out, pv_out, ks_out, vs_out) = refs
            for x_ref, p_out, s_out in ((kp_ref, pk_out, ks_out),
                                        (vp_ref, pv_out, vs_out)):
                pf = x_ref[...].astype(jnp.float32)   # (1, ps, kvh, d)
                scale = page_scale(pf, qmax)          # (1, kvh)
                p_out[...] = page_quantize(pf, scale, qmax, p_out.dtype)
                s_out[...] = scale[:, None, :]
        else:
            kp_ref, vp_ref, _pk, _pv, pk_out, pv_out = refs
            pk_out[...] = kp_ref[...].astype(pk_out.dtype)
            pv_out[...] = vp_ref[...].astype(pv_out.dtype)

    in_specs = [
        pl.BlockSpec((1, ps, *tail_k), slab_map),
        pl.BlockSpec((1, ps, *tail_v), slab_map),
        pl.BlockSpec((1, ps, *tail_k), pool_map),
        pl.BlockSpec((1, ps, *tail_v), pool_map),
    ]
    out_specs = [
        pl.BlockSpec((1, ps, *tail_k), pool_map),
        pl.BlockSpec((1, ps, *tail_v), pool_map),
    ]
    out_shape = [jax.ShapeDtypeStruct(pool_k.shape, pool_k.dtype),
                 jax.ShapeDtypeStruct(pool_v.shape, pool_v.dtype)]
    inputs = [kp, vp, pool_k, pool_v]
    # alias index = position in (prefetch + inputs); output index is
    # positional in out_shape
    aliases = {3: 0, 4: 1}
    if quantized:
        # the (P_pool, KVH) scale planes ride as (P_pool, 1, KVH): a
        # one-page block's last two dims then EQUAL the array's, which is
        # the only form Mosaic's (8, 128) block rule accepts for a row
        # this narrow
        ksc = cache["k_scale"][:, None, :]
        vsc = cache["v_scale"][:, None, :]
        in_specs += [pl.BlockSpec((1, 1, kvh), scale_map),
                     pl.BlockSpec((1, 1, kvh), scale_map)]
        out_specs += [pl.BlockSpec((1, 1, kvh), scale_map),
                      pl.BlockSpec((1, 1, kvh), scale_map)]
        out_shape += [jax.ShapeDtypeStruct(ksc.shape, ksc.dtype),
                      jax.ShapeDtypeStruct(vsc.shape, vsc.dtype)]
        inputs += [ksc, vsc]
        aliases = {3: 0, 4: 1, 5: 2, 6: 3}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pages,),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=_interpret(),
    )(pages, *inputs)
    out = dict(cache)
    out["k"], out["v"] = outs[0], outs[1]
    if quantized:
        out["k_scale"], out["v_scale"] = outs[2][:, 0], outs[3][:, 0]
    return out


# ------------------------------------------------------ MoE expert stream
#
# The dropless SwiGLU experts of a call that holds FEW tokens (a decode
# step, a short prefill bucket: ops/moe.py picks it from the call's static
# shape). At a few rows per expert the work is streaming each hit expert's
# three matrices out of HBM once; XLA's grouped matmul (`ragged_dot`) pushes
# a 256-row tile through the MXU for every weight tile although about four
# rows are live and ends MXU-bound at 61 % of the bandwidth roof (PERF.md
# section 6, PR 28). Here:
#
#   * The call's N <= 128 token rows stay resident in VMEM and ALL of them
#     are multiplied by every hit expert: up to the MXU tile's height a
#     weight tile costs its load whatever the rows, so nothing is sorted,
#     gathered or scattered and no (N*k, D) intermediate exists in HBM. A
#     row that did not choose the expert is dropped by SELECT on the
#     (N, E) gate matrix (MOE_NOT_CHOSEN marks "not chosen"), never by a
#     zero gate: a non-finite value in one row's result cannot reach
#     another row.
#   * Only experts with at least one live row stream: the per-expert row
#     counts arrive by scalar prefetch, the kernel lists the hit experts
#     in SMEM, and the loop runs n_hit x (F / chunk) times. Each step
#     takes one chunk of the expert's width F: w_gate[:, f] and
#     w_up[:, f] (D x chunk) and w_down[f, :] (chunk x D), copied by hand
#     into one of TWO VMEM buffers a matrix, one chunk ahead of the
#     arithmetic (the copies of the next chunk are issued before the wait
#     for this one, so the DMA queue never drains while the arithmetic of
#     a chunk is shorter than its copy; the chip read a third buffer
#     within 0.5 % of two, PERF.md section 6, PR 28).
#   * h = silu(x @ w_gate[:, f]) * (x @ w_up[:, f]) never leaves VMEM; the
#     gate-weighted sum over a token's experts accumulates in f32.

# `gates` value of a (row, expert) pair the row did not choose; a gate is
# a softmax output, so never negative
MOE_NOT_CHOSEN = -1.0
# token rows up to which every row can be multiplied by every hit expert
# for free: the MXU tile's height
MOE_STREAM_MAX_ROWS = 128
# VMEM the two buffers of each of the three matrices may take together
# (v5e: 128 MiB in all; the call raises its scoped limit to them plus its
# resident blocks)
_MOE_BUFFER_BUDGET = 24 << 20


def moe_stream_chunk(d: int, f: int, dtype, matrices: int = 3):
    """Columns of the experts' width `f` that one step of the stream takes:
    the widest chunk (F itself, or a multiple of the 128 lanes that divides
    it) whose `matrices` matrices (three of a SwiGLU expert, two of a relu^2
    one) over hidden size `d` fit _MOE_BUFFER_BUDGET twice. None where Mosaic
    could not tile the copies (D or F off the lanes)."""
    if d % LANES or f % LANES:
        return None
    one = matrices * d * jnp.dtype(dtype).itemsize  # bytes a column of F
    for chunk in range(f, 0, -LANES):
        if f % chunk == 0 and 2 * one * chunk <= _MOE_BUFFER_BUDGET:
            return chunk
    return None


def _moe_stream_kernel(sizes_ref, x_ref, g_ref, *refs, nf: int, chunk: int,
                       gated: bool):
    """The whole call in one invocation. `gated`: a SwiGLU expert's three
    matrices (gate, up, down) and buffers; otherwise a relu^2 expert's two
    (up, down): one ring, one loop, the matrix count and the activation the
    only static differences. Scalar prefetch: sizes (E,), the
    rows that chose each expert; the experts with any are listed in
    ids_ref (SMEM) first. x (N, D) and the gate matrix g (N, E) f32 sit in
    VMEM; the expert matrices stay in HBM. Chunk c of the stream is columns
    [f0, f0 + chunk) of expert ids[c // nf] with f0 = (c % nf) * chunk, and
    lives in buffer c % 2."""
    if gated:
        (wg_hbm, wu_hbm, wd_hbm, o_ref, gbuf, ubuf, dbuf, sem, acc_ref,
         ids_ref) = refs
    else:
        wu_hbm, wd_hbm, o_ref, ubuf, dbuf, sem, acc_ref, ids_ref = refs

    def list_hit(e, n_hit):
        @pl.when(sizes_ref[e] > 0)
        def _():
            ids_ref[n_hit] = e
        return n_hit + (sizes_ref[e] > 0).astype(jnp.int32)

    n_chunks = jax.lax.fori_loop(0, sizes_ref.shape[0], list_hit,
                                 jnp.int32(0)) * nf
    # plain lax on purpose: each jnp convenience (`%`, `//`, `where`,
    # `silu`) is a nested jit of several equations to trace and lower
    nf_, two = jnp.int32(nf), jnp.int32(2)

    def expert(c):
        return ids_ref[c] if nf == 1 else ids_ref[jax.lax.div(c, nf_)]

    def copies(e, f0, slot):
        ups = (wg_hbm, wu_hbm) if gated else (wu_hbm,)
        if nf == 1:
            srcs = (*(w.at[e] for w in ups), wd_hbm.at[e])
        else:
            srcs = (*(w.at[e, :, pl.ds(f0, chunk)] for w in ups),
                    wd_hbm.at[e, pl.ds(f0, chunk), :])
        bufs = (gbuf, ubuf, dbuf) if gated else (ubuf, dbuf)
        return [pltpu.make_async_copy(src, buf.at[slot], sem.at[i, slot])
                for i, (src, buf) in enumerate(zip(srcs, bufs))]

    def fetch(c):
        @pl.when(c < n_chunks)
        def _():
            f0 = 0 if nf == 1 else pl.multiple_of(
                jax.lax.rem(c, nf_) * chunk, chunk)
            for cp in copies(expert(c), f0, jax.lax.rem(c, two)):
                cp.start()

    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    fetch(jnp.int32(0))
    lane = jax.lax.broadcasted_iota(jnp.int32, g_ref.shape, 1)
    no_gate = jnp.zeros(g_ref.shape, jnp.float32)
    nothing = jnp.zeros(acc_ref.shape, jnp.float32)
    mm = functools.partial(jax.lax.dot_general,
                           dimension_numbers=(((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)

    def one_chunk(c, carry):
        fetch(c + 1)
        slot = jax.lax.rem(c, two)
        for cp in copies(0, 0, slot):     # a wait reads the size only
            cp.wait()
        x = x_ref[...]
        if gated:
            gate = mm(x, gbuf[slot])
            h = (gate * jax.lax.logistic(gate) * mm(x, ubuf[slot])
                 ).astype(x.dtype)
        else:
            up = jax.lax.max(mm(x, ubuf[slot]), jnp.float32(0))
            h = (up * up).astype(x.dtype)
        # this expert's column of the gate matrix, by select
        ge = jnp.sum(jax.lax.select(lane == expert(c), g_ref[...], no_gate),
                     axis=1, keepdims=True)                 # (N, 1)
        chosen = jnp.broadcast_to(ge != MOE_NOT_CHOSEN, nothing.shape)
        acc_ref[...] += jax.lax.select(chosen, ge * mm(h, dbuf[slot]),
                                       nothing)
        return carry

    jax.lax.fori_loop(0, n_chunks, one_chunk, 0)
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


# inline=True: traced once per shape and re-emitted under each caller's
# named scope, so the ten layers of a program (and the programs of an engine)
# share one trace and one Mosaic lowering of the kernel, and each call's
# instruction is still named after its own `moe_<i>` scope. Without it a
# 10-layer program traced and lowered the kernel ten times: +5 s of warm
# set-up in moe-chat-steady (PERF.md section 6, PR 28)
@functools.partial(jax.jit, inline=True)
def moe_expert_stream_pallas(x, gates, sizes, *weights):
    """Dropless experts over a few token rows, each hit expert's matrices
    streamed once: x (N, D), N <= MOE_STREAM_MAX_ROWS; gates (N, E) f32, a
    row's gate for each expert it chose and MOE_NOT_CHOSEN elsewhere (every
    entry of a dead row); sizes (E,) int32, the rows that chose each expert
    (an expert with none is not read); `weights` in x's dtype: w_gate, w_up
    (E, D, F), w_down (E, F, D) of SwiGLU experts, or w_up, w_down of
    relu^2 ones -> (N, D) in x's dtype: sum over a row's chosen experts of
    gate * (silu(x w_gate) * (x w_up)) w_down, or of gate * relu(x w_up)^2
    w_down, the products and the sum in f32. A row that chose no expert
    comes out zero. Inference-only: no VJP."""
    n, d = x.shape
    gated = len(weights) == 3
    e, _, f = weights[0].shape
    chunk = moe_stream_chunk(d, f, x.dtype, len(weights))
    if n > MOE_STREAM_MAX_ROWS or chunk is None:
        raise ValueError(
            f"{n} rows of experts ({d}, {f}) {x.dtype}: at most "
            f"{MOE_STREAM_MAX_ROWS} rows, D and F multiples of {LANES}")
    # rows to the dtype's sublane tile (16 for bf16, 8 for f32)
    itemsize = jnp.dtype(x.dtype).itemsize
    tile = 8 * max(1, 4 // itemsize)
    rows = -(-n // tile) * tile
    if rows != n:
        x = jnp.pad(x, ((0, rows - n), (0, 0)))
        gates = jnp.pad(gates, ((0, rows - n), (0, 0)),
                        constant_values=MOE_NOT_CHOSEN)
    # what VMEM holds beside the weight buffers: x, the output and the gate
    # matrix (E padded to the lanes), each twice (the pipeline double-
    # buffers its blocks); the f32 sum; and a chunk's values (the two
    # products and h over the chunk, the down product and its select over
    # D, in f32)
    resident = rows * (2 * (2 * d * itemsize + 4 * -(-e // LANES) * LANES)
                       + 4 * d + 4 * (len(weights) * chunk + 2 * d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i, *_: (0, 0)),
            pl.BlockSpec((rows, e), lambda i, *_: (0, 0)),
            *(pl.BlockSpec(memory_space=pl.ANY) for _ in weights),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i, *_: (0, 0)),
        scratch_shapes=[
            *(pltpu.VMEM((2, d, chunk), w.dtype) for w in weights[:-1]),
            pltpu.VMEM((2, chunk, d), weights[-1].dtype),
            pltpu.SemaphoreType.DMA((len(weights), 2)),
            pltpu.VMEM((rows, d), jnp.float32),             # the sum
            pltpu.SMEM((e,), jnp.int32),                    # hit experts
        ],
    )
    out = pl.pallas_call(
        functools.partial(_moe_stream_kernel, nf=f // chunk, chunk=chunk,
                          gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the buffers, the resident blocks, room for Mosaic's own
            vmem_limit_bytes=int(2 * len(weights) * d * chunk * itemsize
                                 + resident + (8 << 20))),
        interpret=_interpret(),
    )(sizes.astype(jnp.int32), x, gates.astype(jnp.float32), *weights)
    return out[:n]


# ---------------------------------------------------------------------------
# Latent (MLA) paged decode with a learned sparse selection (DeepSeek-V3.2)
# ---------------------------------------------------------------------------
#
# The cached state of a token is ONE latent row shared by all heads
# ([cKV ; kR ; zero pad], ops/mla.py) and one index key. A decode step reads
# the index pool in place and, of the latent pool, only the rows the
# selection kept:
#
#   dsa_index_scores  the grid is the slots and the stream `_page_stream`, as
#                     in `_paged_attn_kernel`; a turn of the slot's loop
#                     takes a BLOCK of _INDEX_BLOCK_PAGES = 8 pages: one wait for the
#                     block's 8 page copies (one semaphore a ring buffer,
#                     counting bytes), 8 products of a page's index keys
#                     (ps, dI), stationary in an MXU, with the slot's J index
#                     queries pushed through, ReLU x w and the sum over J in
#                     f32 (w broadcast along the lanes once a slot), one
#                     (8, ps) store of I_s = sum_j w_j ReLU(qI_j . kI_s), -inf
#                     where the live rule excludes s; then the 8 copies of the
#                     block a ring's depth ahead, into the buffer just read.
#                     The loop runs to the block of the slot's last live page;
#                     what that block fetches past it (the table's scratch
#                     page 0) is dead by the live rule. The ring holds whole
#                     blocks and runs ahead across slot boundaries. The other
#                     orientation (queries stationary, keys pushed) leaves J
#                     along the lanes, and the sum over J becomes lane
#                     reductions and a relayout (PERF.md section 6, PR 40).
#   mla_paged_core_gathered
#                     the H absorbed queries (H, W) against blocks of 128
#                     SELECTED latent rows: one matmul scores all heads, the
#                     online softmax accumulates p @ cKV; index_topk / 128
#                     turns a slot whatever the context, the only mask
#                     column < n_sel.
#
# Between them XLA finds the k-th largest score of each row (ops/mla.py
# `dsa_threshold`: 12 passes over a (slots, context) f32 array), turns the
# selection into a list of pool rows (`dsa_selected`) and gathers the listed
# rows into (slots, index_topk, W): Mosaic refuses a DMA of fewer than 8 rows
# of the tiled pool, and a page of 128 tokens nearly always holds a selected
# one (2048 of up to 33 k), so neither row DMAs nor skipped pages can do it
# inside a kernel (PERF.md section 6, PR 31). The core's name starts with
# `mla_paged_core`, which is what benchmark/dsa_trace.py counts as the core;
# the gather in front of it is an XLA fusion that no name marks.


def dsa_index_block_tokens(page_size: int) -> int:
    """Tokens one turn of `dsa_index_scores` fetches and scores: a slot's
    context is streamed in whole blocks of this many."""
    return _INDEX_BLOCK_PAGES * page_size


def _live_columns(page0, g, ps, rl, pp, wp):
    """(g, ps): the live rule on the columns (tokens) of pages [page0,
    page0 + g)."""
    j = (page0 + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 0)) * ps \
        + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
    return (j < rl) | ((j >= pp) & (j <= wp))


def _dsa_index_kernel(pt_ref, lp_ref, wp_ref, rl_ref, pp_ref, q_ref, w_ref,
                      k_hbm, o_ref, k_buf, sem, cur, *, ps: int, nbuf: int,
                      g: int):
    b = pl.program_id(0)
    # refill(buf) = the stream's fetch, called once the turn has read buffer
    # buf: the block a ring's depth ahead goes into the buffer just given
    # back
    prime, take, refill = _page_stream(pt_ref, lp_ref, (k_hbm,), (k_buf,),
                                       sem, cur, nbuf, g)
    prime(nbuf)
    q = q_ref[0]                                        # (J, dI)
    # across the lanes once a slot, not once a page
    w = jnp.broadcast_to(w_ref[0], (q.shape[0], ps))    # (J, ps) f32
    rl, pp, wp = rl_ref[b], pp_ref[b], wp_ref[b]
    o_ref[0] = jnp.full(o_ref.shape[1:], -jnp.inf, jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (g, ps), 0)

    def one_block(t, carry):
        buf = take()
        sc = jnp.zeros((g, ps), jnp.float32)
        # one product a page, each the same code: a column's arithmetic
        # does not depend on where in the block its page sits
        for i in range(g):
            k = k_buf[buf, i].astype(q.dtype)           # (ps, dI)
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=jnp.float32)
            sc = jnp.where(row == i, jnp.sum(jnp.maximum(s, 0.0) * w, axis=0,
                                             keepdims=True), sc)
        page0 = pl.multiple_of(t * g, g)
        # a page the block fetched past the slot's last live one (scratch
        # page 0 in the table) is dead by the same rule as a dead column.
        # + 0.0: a -0.0 would order below +0.0 in the threshold's key
        live = _live_columns(page0, g, ps, rl, pp, wp)
        o_ref[0, pl.ds(page0, g), :] = jnp.where(live, sc + 0.0, -jnp.inf)
        refill(buf)
        return carry

    jax.lax.fori_loop(0, lp_ref[b] // g + 1, one_block, 0)


# inline=True: as `moe_expert_stream_pallas`, so a program's layers share one
# trace and one Mosaic lowering of the kernel
@functools.partial(jax.jit, inline=True)
def dsa_index_scores_pallas(qi, w, ki_pages, page_table, write_pos, row_len,
                            prompt_pad):
    """Index scores of one decode step, read from the pool in place: qi
    (B, J, dI), w (B, J) f32, ki_pages (P_pool, ps, dI), page tables
    (B, P) -> (B, P * ps) f32, I_s = sum_j w_j ReLU(qi_j . kI_s) at the
    slot's live positions (j < row_len or prompt_pad <= j <= write_pos)
    and -inf elsewhere. A turn of the kernel takes a block of
    _INDEX_BLOCK_PAGES pages; a table that is no whole number of blocks is
    padded with the scratch page 0 and the output cut back."""
    b, jn, di = qi.shape
    ps = ki_pages.shape[1]
    p = page_table.shape[1]
    g = _INDEX_BLOCK_PAGES
    p_pad = -(-p // g) * g
    nbuf = _paged_ring(g, ki_pages.dtype, (ps, di))
    last = jnp.maximum(write_pos, row_len - 1) // ps
    prefetch = [jnp.pad(page_table.astype(jnp.int32), ((0, 0), (0, p_pad - p))),
                last.astype(jnp.int32), write_pos.astype(jnp.int32),
                row_len.astype(jnp.int32), prompt_pad.astype(jnp.int32)]

    def slot_map(bi, *_):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(b,),
        in_specs=[pl.BlockSpec((1, jn, di), slot_map),
                  pl.BlockSpec((1, jn, 1), slot_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, p_pad, ps), slot_map),
        scratch_shapes=[pltpu.VMEM((nbuf, g, ps, di), ki_pages.dtype),
                        pltpu.SemaphoreType.DMA((1, nbuf)),
                        pltpu.SMEM((3,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_dsa_index_kernel, ps=ps, nbuf=nbuf, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, p_pad, ps), jnp.float32),
        compiler_params=_compiler_params(("arbitrary",)),
        interpret=_interpret(), name="dsa_index_scores",
    )(*prefetch, qi, w.astype(jnp.float32)[:, :, None], ki_pages)
    return out[:, :p].reshape(b, p * ps)


def _mla_gathered_kernel(ns_ref, q_ref, g_ref, o_ref, *, blk: int,
                         scale: float, c: int):
    n = ns_ref[pl.program_id(0)]
    q = q_ref[0]                                        # (H, W)
    h = q.shape[0]

    def one_block(t, carry):
        m_prev, l_prev, acc = carry
        r0 = pl.multiple_of(t * blk, blk)
        rows = g_ref[0, pl.ds(r0, blk), :].astype(q.dtype)      # (blk, W)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, blk)
        col = r0 + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        s = jnp.where(col < n, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(rows.dtype), rows[:, :c],
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    # n >= 1 (a row's own token is live and chosen), so the first block
    # holds a real column and l > 0
    _, l_fin, acc = jax.lax.fori_loop(
        0, (n + blk - 1) // blk, one_block,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, c), jnp.float32)))
    o_ref[0] = (acc / l_fin).astype(o_ref.dtype)


# inline=True: as `moe_expert_stream_pallas`, so a program's layers share one
# trace and one Mosaic lowering of the kernel
@functools.partial(jax.jit, inline=True, static_argnames=("scale", "c"))
def mla_gathered_core_pallas(q_lat, rows, n_sel, lat_pages, *, scale: float,
                             c: int):
    """The attention core of one decode step over the SELECTED rows of the
    latent pool: q_lat (B, H, W) absorbed queries, lat_pages (P_pool, ps,
    W), `rows` (B, K) int32 rows of the pool seen as (P_pool * ps, W), of
    which each slot's first n_sel (B,) >= 1 are its selection (the rest
    any valid row) -> (B, H, c): softmax over those n_sel rows of q .
    latent * scale, times the latents' first c columns."""
    b, h, wdt = q_lat.shape
    k = rows.shape[1]
    # rows a matmul turn takes: a lane tile, or a smaller list whole
    blk = LANES if k >= LANES else -(-k // 16) * 16
    kp = -(-k // blk) * blk
    if kp != k:
        rows = jnp.pad(rows, ((0, 0), (0, kp - k)))
    # XLA's gather: Mosaic refuses a DMA of fewer than 8 rows of the tiled
    # pool (PERF.md section 6, PR 31)
    with jax.named_scope("gather"):
        gathered = lat_pages.reshape(-1, wdt).at[rows].get(
            mode="promise_in_bounds")                   # (B, kp, W)

    def slot_map(bi, *_):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[pl.BlockSpec((1, h, wdt), slot_map),
                  pl.BlockSpec((1, kp, wdt), slot_map)],
        out_specs=pl.BlockSpec((1, h, c), slot_map))
    with jax.named_scope("core"):
        return pl.pallas_call(
            functools.partial(_mla_gathered_kernel, blk=blk, scale=scale,
                              c=c),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, c), q_lat.dtype),
            compiler_params=_compiler_params(("arbitrary",)),
            interpret=_interpret(), name="mla_paged_core_gathered",
        )(n_sel.astype(jnp.int32), q_lat, gathered)


# ---- the DENSE latent core: no selection, every live page read in place
#
#   mla_paged_core_dense
#                     a latent attention WITHOUT an indexer attends every live
#                     row, so there is nothing to list and nothing to gather:
#                     the grid is the slots, `_page_stream` walks each slot's
#                     live pages through its page table (blocks of
#                     `mla_dense_turn_pages` pages, the pages past the last
#                     whole block one a turn: no page past the last live one
#                     is fetched), and a turn scores the H absorbed queries
#                     (H, W) against the block's rows in one matmul, folds it
#                     into the online softmax and adds p @ cKV (the rows'
#                     first c columns: K and V are one buffer). A page is
#                     streamed once a SLOT: slots that hold the same document
#                     each stream it (the shared-page form of the paged
#                     kernel above is not built for this core).

# pages of one turn of the dense latent core: 512 rows of 640 lanes (655 KB
# a ring buffer in bf16) against 64-128 query rows
_MLA_DENSE_TURN_PAGES = 4


def mla_dense_turn_pages(width: int) -> int:
    """Pages one turn of `mla_paged_core_dense` takes over a table `width`
    pages wide: the largest power of two up to _MLA_DENSE_TURN_PAGES (one
    itself) that the table holds."""
    return min(_MLA_DENSE_TURN_PAGES, 1 << (width.bit_length() - 1))


def _mla_dense_kernel(pt_ref, lp_ref, wp_ref, rl_ref, pp_ref, q_ref, lat_hbm,
                      o_ref, buf, sem, cur, *, ps: int, nbuf: int, g: int,
                      scale: float, c: int):
    b = pl.program_id(0)
    prime, take, fetch_next = _page_stream(
        pt_ref, lp_ref, (lat_hbm,), (buf,), sem, cur, nbuf, g, tail=True)
    prime(nbuf - 1)
    q = q_ref[0]                                        # (H, W)
    h, wdt = q.shape
    rl, pp, wp = rl_ref[b], pp_ref[b], wp_ref[b]

    def turn(pages):
        cols = pages * ps
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)

        def body(t, state):
            m_prev, l_prev, acc = state
            fetch_next(jax.lax.rem(cur[2] + nbuf - 1, nbuf))
            got = take(pages)
            rows = buf[got, :pages].reshape(cols, wdt).astype(q.dtype)
            s = jax.lax.dot_general(
                q, rows, _NT, preferred_element_type=jnp.float32) * scale
            j = t * ps + col
            s = jnp.where((j < rl) | ((j >= pp) & (j <= wp)), s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(rows.dtype), rows[:, :c],
                                        preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        return body

    state = (jnp.full((h, 1), NEG_INF, jnp.float32),
             jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, c), jnp.float32))
    first = 0
    if g > 1:
        block, blocks = turn(g), (lp_ref[b] + 1) // g
        state = jax.lax.fori_loop(
            0, blocks, lambda i, st: block(i * g, st), state)
        first = blocks * g
    # every slot has >= 1 live position (an inactive slot's zeros satisfy
    # j == 0 <= write_pos == 0), so l > 0; a turn in which no column is live
    # leaves p = 1 under m = NEG_INF, which the first live score scales away
    _, l_fin, acc = jax.lax.fori_loop(first, lp_ref[b] + 1, turn(1), state)
    o_ref[0] = (acc / l_fin).astype(o_ref.dtype)


# inline=True: as `moe_expert_stream_pallas`, so a program's layers share one
# trace and one Mosaic lowering of the kernel
@functools.partial(jax.jit, inline=True, static_argnames=("scale", "c"))
def mla_dense_core_pallas(q_lat, lat_pages, page_table, write_pos, row_len,
                          prompt_pad, *, scale: float, c: int):
    """The attention core of one decode step over EVERY live row of the
    latent pool, read in place: q_lat (B, H, W) absorbed queries, lat_pages
    (P_pool, ps, W), page tables (B, P), the live rule's bounds (B,) (a
    position j of a slot is live iff j < row_len or prompt_pad <= j <=
    write_pos) -> (B, H, c): softmax over the live rows of q . latent *
    scale, times the latents' first c columns."""
    b, h, wdt = q_lat.shape
    ps = lat_pages.shape[1]
    g = mla_dense_turn_pages(page_table.shape[1])
    nbuf = _paged_ring(g, lat_pages.dtype, (ps, wdt))
    last = jnp.maximum(write_pos, row_len - 1) // ps
    prefetch = [page_table.astype(jnp.int32), last.astype(jnp.int32),
                write_pos.astype(jnp.int32), row_len.astype(jnp.int32),
                prompt_pad.astype(jnp.int32)]

    def slot_map(bi, *_):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(b,),
        in_specs=[pl.BlockSpec((1, h, wdt), slot_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, c), slot_map),
        scratch_shapes=[pltpu.VMEM((nbuf, g, ps, wdt), lat_pages.dtype),
                        pltpu.SemaphoreType.DMA((1, nbuf)),
                        pltpu.SMEM((3,), jnp.int32)])
    with jax.named_scope("core"):
        return pl.pallas_call(
            functools.partial(_mla_dense_kernel, ps=ps, nbuf=nbuf, g=g,
                              scale=scale, c=c),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, c), q_lat.dtype),
            # sequential: the cursor and the ring carry over between slots
            compiler_params=_compiler_params(("arbitrary",)),
            interpret=_interpret(), name="mla_paged_core_dense",
        )(*prefetch, q_lat, lat_pages)


# ---------------------------------------------------------------------------
# Mamba-2 decode: one token of the recurrence over a pool of per-slot states
# ---------------------------------------------------------------------------
#
# The pool's H stays in HBM and is the kernel's output too (aliased). It is
# held (slots, groups, N, heads a group x P) float32 (ops/mamba.py says why
# and gives the logical view, `Mamba2Mixer.logical_state`): per group a tile
# with the state dimension N along the SUBLANES and (head, p) dense along the
# LANES. A grid step holds ONE slot's whole state in VMEM (4.19 MB at 8 x 128
# x 1024, 2.10 MB at 1 x 128 x 4096; there and back through the pipeline's
# two buffers each way) and works through it a 128-lane block at a time:
#
#   * the step's decay and dt x are ROWS over the group's (head, p) lanes
#     (the wrapper's `lane_rows`), spread along the sublanes as they load;
#   * B and C are columns of N, spread across the lanes ONCE a group (their
#     row spread along the sublanes and transposed: 16 registers each at N =
#     128) and reused by every lane block of the group, not once a head;
#   * `new = d * h + b * x` goes back in place, and `y = H C` adds `new * c`
#     over the N / 8 registers of the block with vector adds and ends in one
#     8 -> 1 sublane reduce a block: nothing a head crosses the lanes (N on
#     the lanes costs a 128-lane reduction a register and a one-lane column
#     of y a head, 52-56 bundles a head against 21: PERF.md section 6, PR 44);
#   * y leaves as a lane-dense row, (slots, d_inner) as the gate reads it.
#
# A DEAD slot is neither read nor written: the scalar-prefetched `src` sends
# its grid step to the block of the nearest live slot before it (the first
# live one after it for the leading dead slots), and the pipeline neither
# fetches nor writes back a block whose index did not change, while the body
# runs for live steps only.

# the state dimension's granule: a float32 register's sublanes
MAMBA_STATE_ROWS = 8


def _mamba_update_kernel(src_ref, live_ref, h_ref, d_ref, x_ref, b_ref, c_ref,
                         o_ref, y_ref):
    s = pl.program_id(0)
    groups, n, q = h_ref.shape[1:]

    @pl.when(live_ref[s] > 0)
    def _():
        def group(g, carry):
            # (1, N) rows -> (N, LANES) columns spread across the lanes
            b = jnp.broadcast_to(b_ref[0, g], (LANES, n)).T
            c = jnp.broadcast_to(c_ref[0, g], (LANES, n)).T

            def block(j, carry):
                at = (0, g, slice(None),
                      pl.ds(pl.multiple_of(j * LANES, LANES), LANES))
                new = d_ref[at] * h_ref[at] + b * x_ref[at]
                o_ref[at] = new
                y_ref[at] = jnp.sum(new * c, axis=0, keepdims=True)
                return carry

            return jax.lax.fori_loop(0, q // LANES, block, carry)

        jax.lax.fori_loop(0, groups, group, 0)

    @pl.when(live_ref[s] == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    # nothing live at all: every step sits on slot 0's block, which then
    # goes back as it came
    @pl.when(jnp.logical_and(s == 0, src_ref[0] < 0))
    def _():
        o_ref[...] = h_ref[...]


@functools.partial(jax.jit, inline=True)
def mamba_state_update_pallas(h, decay, dtx, bm, cm, live):
    """One token of `H <- decay H + dtx B^T`, `y = H C` on a pool of states,
    live slots only, in place: h (S, G, N, H / G x P) f32 (the held layout;
    aliased to the output), decay (S, H) f32, dtx (S, H, P) f32, bm, cm (S,
    G, N) f32, live (S,) bool -> (y (S, H, P) f32, zero for a dead slot; h).
    A group's lanes are a multiple of 128 and N of `MAMBA_STATE_ROWS`."""
    from flexflow_tpu.ops.mamba import lane_rows

    s, g, n, q = h.shape
    idx = jnp.arange(s, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.min(jnp.where(live, idx, s))
    any_live = first < s
    src = jnp.where(before >= 0, before, first)
    # `src[0] < 0` tells the kernel nothing is live; the index map clamps it
    src = jnp.where(any_live, src, -1).astype(jnp.int32)

    def state(i, src, live):
        return (jnp.maximum(src[i], 0), 0, 0, 0)

    def own(i, *_):
        return (i, 0, 0, 0)

    row, col = pl.BlockSpec((1, g, 1, q), own), pl.BlockSpec((1, g, 1, n), own)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[pl.BlockSpec((1, g, n, q), state), row, row, col, col],
        out_specs=[pl.BlockSpec((1, g, n, q), state), row],
    )
    block = g * n * q * 4
    new, y = pl.pallas_call(
        _mamba_update_kernel,
        name="mamba_state_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(h.shape, h.dtype),
                   jax.ShapeDtypeStruct((s, g, 1, q), jnp.float32)],
        # operand 2 (after the two prefetched scalars) is the pool
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(4 * block + (16 << 20))),
        interpret=_interpret(),
    )(src, live.astype(jnp.int32), h, *lane_rows(decay, dtx, g),
      bm.reshape(s, g, 1, n), cm.reshape(s, g, 1, n))
    return y.reshape(dtx.shape), new


# ---- power retention: the one-step state update ------------------------------
# ops/retention.py holds a sequence's state as S (KV, ND, hd, hd) float32: by
# phi's diagonals (ND = hd / 2 + 1), the value along the sublanes, the key
# index a along the lanes; z (KV, ND, hd) beside it. A decode step is
#     S <- decay S + phi(k)[d, a] v[v],   num_i[v] = sum_{d, a} phi(q_i)[d, a] S[d, v, a]
# and the same of z with v = 1: a pure stream, every element of a live slot's
# state read once, decayed, given one product and written back (2 x 34 MB a
# slot and layer at hd 128, 8 KV heads) for 2 + 2 R FLOPs an element. One grid
# step is one (KV head, slot) block of 4.26 MB, aliased in and out; the SLOT
# is the inner grid axis, so a dead slot's step maps to the block of the
# nearest live slot before it and nothing is fetched or written for it
# (`_mamba_update_kernel`'s rule). phi is formed here from the step's tile of
# rows, phi(x)[d] = c_d x roll(x, d) / hd: ND lane rotations of ONE register
# that holds a KV group's R query heads, its key, its value and its decay.

# rows of the step's tile: R query heads, k, v, the decay; one f32 register
RETENTION_TILE_ROWS = 8
# value rows a turn of the inner loop carries in registers (R accumulators of
# this many rows each)
_RETENTION_STRIP = 32


def _retention_update_kernel(src_ref, live_ref, s_ref, z_ref, x_ref, o_ref,
                             zo_ref, num_ref, den_ref, ph_ref, acc_ref, *,
                             group: int, coef):
    slot = pl.program_id(1)
    nd, hd = s_ref.shape[2], s_ref.shape[4]
    strip = min(_RETENTION_STRIP, hd)
    r_k, r_v, r_dec = group, group + 1, group + 2

    @pl.when(live_ref[slot] > 0)
    def _():
        x = x_ref[0, 0]                                    # (8, hd)
        # phi of every row at once: row i < R is phi(q_i), row R is phi(k)
        for d in range(nd):
            rolled = pltpu.roll(x, d, 1) if d else x
            ph_ref[d] = x * rolled * coef[d]
        dec = x[r_dec:r_dec + 1]                           # (1, hd), all equal
        # the value along the sublanes, the same in every lane
        vcol = jnp.broadcast_to(x[r_v:r_v + 1], (hd, hd)).T

        for st in range(hd // strip):
            rows = pl.ds(st * strip, strip)
            vs = vcol[st * strip:(st + 1) * strip]

            def diagonal(d, carry, rows=rows, vs=vs, first=(st == 0)):
                acc, dacc = carry
                t = ph_ref[d]                              # (8, hd)
                new = dec * s_ref[0, 0, d, rows, :] + vs * t[r_k:r_k + 1]
                o_ref[0, 0, d, rows, :] = new
                acc = tuple(a + new * t[i:i + 1] for i, a in enumerate(acc))
                if first:
                    zn = dec * z_ref[0, 0, pl.ds(d, 1), :] + t[r_k:r_k + 1]
                    zo_ref[0, 0, pl.ds(d, 1), :] = zn
                    dacc = dacc + t * zn
                return acc, dacc

            zero = jnp.zeros((strip, hd), jnp.float32)
            acc, dacc = jax.lax.fori_loop(
                0, nd, diagonal,
                ((zero,) * group,
                 jnp.zeros((RETENTION_TILE_ROWS, hd), jnp.float32)))
            for i in range(group):
                acc_ref[i, rows, :] = acc[i]
            if st == 0:
                # rows < R: phi(q_i) . z, still spread over the lanes
                den_ref[0, 0] = dacc
        for i in range(group):
            # sum over a (the lanes): as a row over the values
            num_ref[0, 0, i:i + 1, :] = jnp.sum(acc_ref[i].T, axis=0,
                                                keepdims=True)

    @pl.when(live_ref[slot] == 0)
    def _():
        num_ref[...] = jnp.zeros(num_ref.shape, num_ref.dtype)
        den_ref[...] = jnp.zeros(den_ref.shape, den_ref.dtype)

    # nothing live at all: every step of this KV head sits on slot 0's block,
    # which then goes back as it came
    @pl.when(jnp.logical_and(slot == 0, src_ref[0] < 0))
    def _():
        o_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]


@functools.partial(jax.jit, inline=True)
def retention_state_update_pallas(st, z, decay, q, k, v, live):
    """One token of `S <- decay S + phi(k) v^T`, `z <- decay z + phi(k)`,
    `num_i = phi(q_i)^T S`, `den_i = phi(q_i) . z` on a pool of states, live
    slots only, in place: st (S, KV, ND, hd, hd), z (S, KV, ND, hd) f32 (the
    held layout of ops/retention.py; aliased to the outputs), decay (S, KV)
    f32, q (S, KV, R, hd), k, v (S, KV, hd) f32, live (S,) bool -> (num (S,
    KV, R, hd), den (S, KV, R), zero for a dead slot; st; z). hd is one
    register's lanes and R + 3 rows fit its sublanes."""
    from flexflow_tpu.ops.retention import phi_coefficients

    s, kv, nd, hd, _ = st.shape
    group = q.shape[2]
    rows = RETENTION_TILE_ROWS
    assert group + 3 <= rows, (group, rows)
    idx = jnp.arange(s, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    first = jnp.min(jnp.where(live, idx, s))
    src = jnp.where(before >= 0, before, first)
    # `src[0] < 0` tells the kernel nothing is live; the index map clamps it
    src = jnp.where(first < s, src, -1).astype(jnp.int32)
    # the step's tile: R query heads, k, v, the decay across the lanes
    x = jnp.concatenate(
        [q, k[:, :, None], v[:, :, None],
         jnp.broadcast_to(decay[..., None, None], (s, kv, 1, hd)),
         jnp.zeros((s, kv, rows - group - 3, hd), jnp.float32)], axis=2)
    coef = tuple(float(c) for c in phi_coefficients(hd))

    def state5(g, i, src, live):
        return (jnp.maximum(src[i], 0), g, 0, 0, 0)

    def state4(g, i, src, live):
        return (jnp.maximum(src[i], 0), g, 0, 0)

    def own(g, i, *_):
        return (i, g, 0, 0)

    tile = pl.BlockSpec((1, 1, rows, hd), own)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(kv, s),
        in_specs=[pl.BlockSpec((1, 1, nd, hd, hd), state5),
                  pl.BlockSpec((1, 1, nd, hd), state4), tile],
        out_specs=[pl.BlockSpec((1, 1, nd, hd, hd), state5),
                   pl.BlockSpec((1, 1, nd, hd), state4), tile, tile],
        scratch_shapes=[pltpu.VMEM((nd, rows, hd), jnp.float32),
                        pltpu.VMEM((group, hd, hd), jnp.float32)],
    )
    block = nd * hd * hd * 4
    new, zn, num, den = pl.pallas_call(
        functools.partial(_retention_update_kernel, group=group, coef=coef),
        name="retention_state_update",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(st.shape, st.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((s, kv, rows, hd), jnp.float32),
                   jax.ShapeDtypeStruct((s, kv, rows, hd), jnp.float32)],
        # operands 2 and 3 (after the two prefetched scalars): S and z
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(4 * block + (24 << 20))),
        interpret=_interpret(),
    )(src, live.astype(jnp.int32), st, z, x)
    return num[:, :, :group], den[:, :, :group].sum(-1), new, zn
