"""Measured-cost block-size autotuner for the Pallas kernel tier.

The original FlexFlow thesis (PAPERS.md, "Beyond Data and Model
Parallelism") drives every placement decision from MEASURED on-device
costs; the same discipline applies one level down, to kernel tile sizes.
A hardcoded tile cannot know where a given chip generation's MXU/VMEM
balance tips (the flash kernels' static default is what a v5e measured:
``ops/pallas_kernels._OUTER_BLOCK``). This module makes block choice a
measurement:

  * ``tune_flash_attention`` sweeps ``(block_q, block_k)`` candidates for
    one (seq, head_dim, dtype) shape through the dispatch-floor timing
    harness ``search/measure.py`` already uses for op costs (per-call
    min, null-dispatch floor subtracted, scalar-fetch forcing);
  * winners persist to an on-disk JSON table keyed by **(kernel,
    shape-sig incl. dtype, device kind, jax version)** — a bf16-measured
    entry can never be served for an fp32 query, and a jax/libtpu
    version bump invalidates every old row by key mismatch instead of
    silently serving stale tiles;
  * ``ops/pallas_kernels._resolve_blocks`` consults ``lookup_blocks`` at
    trace time, falling back to the static ``_pick_block`` heuristic on
    a miss (a machine that never ran the tuner has no table).

Re-run the tuner after a hardware/jax change::

    python -m flexflow_tpu.search.kernel_tune --seq 4096 --head-dim 128 \
        --dtype bfloat16

Table location: ``FF_KERNEL_TUNE_TABLE`` if set, else
``~/.cache/flexflow_tpu/kernel_tune.json``. ``hits``/``misses`` counters
(``stats()``) ride ServingEngine.stats() for observability.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, Optional, Sequence, Tuple

# (block_q, block_k) sweep grid; illegal candidates (not dividing the
# sequence) are skipped per shape
DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (128, 128), (128, 256), (256, 128), (256, 256),
    (256, 512), (512, 256), (512, 512),
    (512, 1024), (1024, 512), (1024, 1024))

# in-memory table cache: {path: (file_stat_sig, {key: entry})} — keyed by
# the file's (mtime_ns, size) so an out-of-process re-tune (the documented
# `python -m flexflow_tpu.search.kernel_tune` flow) is picked up by the
# NEXT trace in a long-lived consumer without a restart. Lookups happen at
# trace time only, so the stat() is off every warm path. The machinery
# lives in search/table_store.py (shared with the op-cost DB, ISSUE 19);
# `_TABLES` aliases its cache so existing fixtures keep working.
from flexflow_tpu.search import table_store as _store

_TABLES: Dict[str, Tuple] = _store._CACHE
_stat_sig = _store.stat_sig
_STATS = {"hits": 0, "misses": 0, "illegal": 0}
_WARNED_ILLEGAL = set()


def default_table_path() -> str:
    env = os.environ.get("FF_KERNEL_TUNE_TABLE", "")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "flexflow_tpu",
                        "kernel_tune.json")


def device_key() -> str:
    """Device-identity half of the table key: backend, chip kind, jax
    version — measure._env_signature, the ONE environment probe every
    persisted cost key shares (table_store.env_key, shared with the
    op-cost DB). A version bump (jax or the libtpu it pins) changes
    Mosaic codegen, so old timings stop matching new executables —
    they must miss, not mislead."""
    return _store.env_key()


def shape_sig(*, seq_q: int, seq_k: int, head_dim: int, dtype,
              batch: int, heads: int, causal: bool) -> str:
    """Shape half of the key. EVERYTHING the sweep's timing depends on
    is in the signature — dtype (bf16/f32 tiles have different VMEM
    footprints and MXU throughput), batch*heads (the grid's parallel
    extent), and causality (dead-tile clamps change the work per
    block): a winner for one configuration is noise for another, so a
    mismatch must MISS to the static heuristic, never approximate."""
    import numpy as np

    return (f"sq{int(seq_q)}|sk{int(seq_k)}|d{int(head_dim)}"
            f"|b{int(batch)}|h{int(heads)}"
            f"|{'causal' if causal else 'full'}|{np.dtype(dtype).name}")


def _entry_key(kernel: str, sig: str, dev: Optional[str] = None) -> str:
    return f"{kernel}|{dev or device_key()}|{sig}"


def load_table(path: Optional[str] = None, reload: bool = False) -> Dict:
    """Entries dict for `path` (default table), cached in-process and
    invalidated by the file's (mtime, size) — a table written after the
    process's first lookup (another process's re-tune, a test fixture)
    is served on the next call, never silently shadowed by a cached
    empty read. ``reload=True`` forces the re-read regardless."""
    path = path or default_table_path()
    return _store.load(path, reload=reload)


def reload(path: Optional[str] = None) -> Dict:
    return load_table(path, reload=True)


def lookup_blocks(kernel: str, *, seq_q: int, seq_k: int, head_dim: int,
                  dtype, batch: int, heads: int, causal: bool,
                  path: Optional[str] = None) \
        -> Optional[Tuple[int, int]]:
    """Tuned (block_q, block_k) for this exact (kernel, shape, dtype,
    batch, heads, causal) on THIS device/jax version, or None (cold
    fallback — the caller's static heuristic applies). Legality is
    checked HERE: an entry whose blocks no longer divide the sequence
    (corrupt/hand-edited row) counts as a MISS + illegal, never a hit —
    the hit counter means 'a tuned pick actually governed this trace'."""
    entries = load_table(path)
    e = entries.get(_entry_key(
        kernel, shape_sig(seq_q=seq_q, seq_k=seq_k, head_dim=head_dim,
                          dtype=dtype, batch=batch, heads=heads,
                          causal=causal)))
    if e and isinstance(e.get("blocks"), (list, tuple)) \
            and len(e["blocks"]) == 2:
        bq, bk = int(e["blocks"][0]), int(e["blocks"][1])
        if 0 < bq <= seq_q and seq_q % bq == 0 \
                and 0 < bk <= seq_k and seq_k % bk == 0:
            _STATS["hits"] += 1
            return bq, bk
        note_illegal(kernel, (bq, bk), (seq_q, seq_k))
    _STATS["misses"] += 1
    return None


def note_illegal(kernel: str, blocks, shape):
    """A persisted entry that no longer divides the query shape (e.g. a
    table tuned at seq 4096 consulted at 4097 would never key-match, but
    a corrupt/hand-edited row can): log once, count, fall back."""
    _STATS["illegal"] += 1
    tag = (kernel, tuple(blocks), tuple(shape))
    if tag in _WARNED_ILLEGAL:
        return
    _WARNED_ILLEGAL.add(tag)
    from flexflow_tpu.logger import fflogger

    fflogger.warning(
        "kernel_tune: table entry %s blocks=%s does not divide shape %s "
        "— using the static heuristic", kernel, blocks, shape)


def stats() -> Dict[str, int]:
    return dict(_STATS)


def reset_stats():
    for k in _STATS:
        _STATS[k] = 0


def record(kernel: str, sig: str, blocks: Optional[Tuple[int, int]],
           seconds: float, candidates: Optional[Dict] = None,
           path: Optional[str] = None, impl: Optional[str] = None,
           extra: Optional[Dict] = None) -> str:
    """Persist one winner (atomic tmp+rename write, the checkpoint.py
    discipline) and refresh the in-memory cache. Returns the key.
    ``blocks`` entries serve the block tuner (lookup_blocks);
    ``impl`` entries serve the paged-attention impl choice
    (lookup_paged_impl) — an entry can carry either or both."""
    path = path or default_table_path()
    entries = load_table(path, reload=True)
    key = _entry_key(kernel, sig)
    entries[key] = {
        "seconds": float(seconds),
        "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if blocks is not None:
        entries[key]["blocks"] = [int(blocks[0]), int(blocks[1])]
    if impl is not None:
        entries[key]["impl"] = str(impl)
    if extra:
        entries[key].update(extra)
    if candidates:
        entries[key]["candidates"] = {
            f"{bq}x{bk}": float(s) for (bq, bk), s in candidates.items()}
    _store.publish(path, entries)
    return key


def lookup_paged_impl(*, page_size: int, pages_per_slot: int,
                      head_dim: int, dtype, batch: int, heads: int,
                      s: int = 1, path: Optional[str] = None) \
        -> Optional[str]:
    """Measured paged-attention impl ('pallas' | 'einsum') for one
    serving shape on THIS device/jax version, or None (the caller's
    backend heuristic applies). ``dtype`` is the POOL STORAGE dtype —
    int8/fp8/f32/bf16 — so a winner measured on quantized pages (whose
    bandwidth/compute balance differs: the kernel streams half the
    bytes but adds a dequant multiply per tile) can never be served
    for a full-width pool. Consulted by ServingEngine under
    paged_attention_impl='auto' at construction time only, with the
    DECODE slab shape ``s=1`` — decode dominates a serving engine's
    dispatches, and the engine picks ONE impl for its life; entries
    tuned at verify shapes (``--slab`` > 1) are comparison data, not
    steering input."""
    entries = load_table(path)
    sig = shape_sig(seq_q=s, seq_k=pages_per_slot * page_size,
                    head_dim=head_dim, dtype=dtype, batch=batch,
                    heads=heads, causal=True)
    e = entries.get(_entry_key("paged_fwd", sig))
    if e and e.get("impl") in ("pallas", "einsum"):
        _STATS["hits"] += 1
        return e["impl"]
    _STATS["misses"] += 1
    return None


def tune_paged_attention(*, page_size: int = 16, pages_per_slot: int = 8,
                         head_dim: int = 64, kv_heads: int = 2,
                         heads: int = 4, slots: int = 4, s: int = 1,
                         dtype="float32", kv_dtype: Optional[str] = None,
                         warmup: int = 1, iters: int = 3,
                         path: Optional[str] = None,
                         verbose: bool = False) -> Dict:
    """Measure the Pallas paged-attention kernel against the einsum
    page-gather at ONE serving shape — optionally on a QUANTIZED pool
    (``kv_dtype`` = 'int8' | 'fp8' | 'bf16': the kernel variant that
    dequantizes in VMEM vs the gather that dequantizes in HBM) — and
    persist the winning impl to the same table the block tuner uses.
    The pool's storage dtype is the signature's dtype, so int8 and
    full-width entries can never shadow each other. ServingEngine
    consults the entry under paged_attention_impl='auto'
    (lookup_paged_impl). Off-TPU the kernel runs only in interpret
    mode (FF_PALLAS_INTERPRET=1): the sweep exercises the full tune->persist->consume path (the CI
    smoke + bench demonstration), it just measures the interpreter —
    einsum wins there by construction, which is itself the right
    'auto' answer for a CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.attention import (kv_storage_dtype,
                                            page_dequantize, page_quantize,
                                            page_scale)
    from flexflow_tpu.ops.pallas_kernels import paged_attention_fwd_pallas
    from flexflow_tpu.search import measure

    sdtype, qmax = kv_storage_dtype(kv_dtype)
    store = sdtype if sdtype is not None else jnp.dtype(dtype)
    rs = np.random.RandomState(0)
    pool_pages = 1 + slots * pages_per_slot
    max_len = pages_per_slot * page_size

    def mk(d):
        x = jnp.asarray(rs.randn(pool_pages, page_size, kv_heads, d),
                        jnp.float32)
        if qmax is None:
            return x.astype(store), None
        sc = page_scale(x, qmax)
        return page_quantize(x, sc, qmax, store), sc

    kq, ks = mk(head_dim)
    vq, vs = mk(head_dim)
    q = jnp.asarray(rs.randn(slots, s, heads, head_dim), dtype)
    table = jnp.asarray(
        1 + np.arange(slots * pages_per_slot).reshape(slots,
                                                      pages_per_slot),
        jnp.int32)
    wp = jnp.minimum(
        jnp.full((slots,), max_len - s, jnp.int32)[:, None]
        + jnp.arange(s, dtype=jnp.int32)[None, :], max_len - 1)
    row_len = jnp.full((slots,), page_size, jnp.int32)
    prompt_pad = jnp.full((slots,), 2 * page_size, jnp.int32)
    scale = 1.0 / math.sqrt(head_dim)
    grp = heads // kv_heads

    def pallas_step(q_, k_, v_):
        out = paged_attention_fwd_pallas(q_, k_, v_, table, wp, row_len,
                                         prompt_pad, scale, k_scales=ks,
                                         v_scales=vs)
        return jnp.sum(out.astype(jnp.float32))

    def einsum_step(q_, k_, v_):
        # standalone mirror of MultiHeadAttention._paged_attention_ctx's
        # einsum branch (the tuner is model-free, so it cannot call the
        # op method); drift between the two bodies is caught by the
        # kernel-vs-oracle parity tests (test_pallas_paged /
        # test_quantized_serving), which pin the SAME pair of
        # computations against each other
        gk, gv = k_[table], v_[table]
        if qmax is not None:
            gk = page_dequantize(gk, ks[table])
            gv = page_dequantize(gv, vs[table])
        gk = gk.reshape(slots, max_len, kv_heads, head_dim)
        gv = gv.reshape(slots, max_len, kv_heads, head_dim)
        idx = jnp.arange(max_len)
        live = (idx[None, None, :] < row_len[:, None, None]) \
            | ((idx[None, None, :] >= prompt_pad[:, None, None])
               & (idx[None, None, :] <= wp[:, :, None]))
        qg = q_.reshape(slots, s, kv_heads, grp, head_dim)
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                            gk.astype(q_.dtype),
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(live[:, None, None, :, :], logits,
                           jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1).astype(q_.dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, gv.astype(q_.dtype))
        return jnp.sum(out.astype(jnp.float32))

    timed = {}
    for impl, step in (("einsum", einsum_step), ("pallas", pallas_step)):
        timed[impl] = measure.time_scalar_program(
            jax.jit(step), q, kq, vq, warmup=warmup, iters=iters)
        if verbose:
            print(f"[kernel_tune] paged_fwd ps{page_size} "
                  f"pps{pages_per_slot} d{head_dim} "
                  f"{np.dtype(store).name} {impl}: "
                  f"{timed[impl] * 1e3:.3f} ms")
    best = min(timed, key=timed.get)
    sig = shape_sig(seq_q=s, seq_k=max_len, head_dim=head_dim,
                    dtype=store, batch=slots, heads=heads, causal=True)
    record("paged_fwd", sig, None, timed[best],
           candidates=None, path=path, impl=best,
           extra={f"{k}_seconds": float(v) for k, v in timed.items()})
    rec = {
        "kernel": "paged_fwd", "sig": sig, "device": device_key(),
        "impl": best, "kv_dtype": np.dtype(store).name,
        "seconds": timed[best],
        "candidates": {k: float(v) for k, v in timed.items()},
    }
    if verbose:
        print(f"[kernel_tune] paged winner {best} -> "
              f"{path or default_table_path()}")
    return rec


def lookup_paged_prefill_impl(*, page_size: int, pages_per_slot: int,
                              head_dim: int, dtype, batch: int,
                              heads: int, path: Optional[str] = None) \
        -> Optional[str]:
    """Measured paged prefill/append WRITE impl ('pallas' | 'einsum')
    for one serving geometry on THIS device/jax version, or None (the
    caller's backend heuristic applies). Mirrors lookup_paged_impl but
    keys the 'paged_prefill' kernel: ``dtype`` is the POOL STORAGE
    dtype (a quantized write adds an in-kernel quantize but streams
    half the bytes, so winners can't be shared across widths), and the
    signature's seq_k is the slot capacity — the slab length the write
    path scatters at its long-context worst case. Consulted by
    ServingEngine under paged_attention_impl='auto' at construction
    time only (ISSUE 18)."""
    entries = load_table(path)
    sig = shape_sig(seq_q=page_size, seq_k=pages_per_slot * page_size,
                    head_dim=head_dim, dtype=dtype, batch=batch,
                    heads=heads, causal=False)
    e = entries.get(_entry_key("paged_prefill", sig))
    if e and e.get("impl") in ("pallas", "einsum"):
        _STATS["hits"] += 1
        return e["impl"]
    _STATS["misses"] += 1
    return None


def tune_paged_prefill(*, page_size: int = 16, pages_per_slot: int = 8,
                       head_dim: int = 64, kv_heads: int = 2,
                       heads: int = 4, slots: int = 4,
                       dtype="float32", kv_dtype: Optional[str] = None,
                       warmup: int = 1, iters: int = 3,
                       path: Optional[str] = None,
                       verbose: bool = False) -> Dict:
    """Measure the Pallas page-at-a-time prefill/append write kernel
    against the einsum big-scatter oracle at ONE serving geometry —
    the slab is the slot's FULL capacity (pages_per_slot * page_size),
    the long-context worst case ISSUE 18 targets — optionally on a
    QUANTIZED pool, and persist the winning impl under the
    'paged_prefill' kernel key. ServingEngine consults the entry under
    paged_attention_impl='auto' (lookup_paged_prefill_impl). Off-TPU
    the kernel runs only in interpret mode (FF_PALLAS_INTERPRET=1): the
    sweep exercises the full
    tune->persist->consume path, it just measures the interpreter —
    einsum wins there by construction, the right 'auto' answer for a
    CPU backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.attention import (kv_storage_dtype,
                                            page_quantize, page_scale)
    from flexflow_tpu.ops.pallas_kernels import paged_prefill_write_pallas
    from flexflow_tpu.search import measure

    sdtype, qmax = kv_storage_dtype(kv_dtype)
    store = sdtype if sdtype is not None else jnp.dtype(dtype)
    rs = np.random.RandomState(0)
    n_pages = pages_per_slot
    pool_pages = 1 + slots * pages_per_slot
    slab_len = n_pages * page_size

    kh = jnp.asarray(rs.randn(1, slab_len, kv_heads, head_dim), dtype)
    vh = jnp.asarray(rs.randn(1, slab_len, kv_heads, head_dim), dtype)
    pool_k = jnp.zeros((pool_pages, page_size, kv_heads, head_dim), store)
    pool_v = jnp.zeros_like(pool_k)
    cache = {"k": pool_k, "v": pool_v}
    if qmax is not None:
        cache["k_scale"] = jnp.zeros((pool_pages, kv_heads), jnp.float32)
        cache["v_scale"] = jnp.zeros((pool_pages, kv_heads), jnp.float32)
    pages = jnp.asarray(1 + np.arange(n_pages), jnp.int32)

    def pallas_step(kh_, vh_, pk, pv):
        out = paged_prefill_write_pallas(
            dict(cache, k=pk, v=pv), kh_, vh_, pages)
        return jnp.sum(out["k"].astype(jnp.float32)) \
            + jnp.sum(out["v"].astype(jnp.float32))

    def einsum_step(kh_, vh_, pk, pv):
        # standalone mirror of MultiHeadAttention.paged_prefill_write's
        # einsum branch (the tuner is model-free); drift is caught by
        # the kernel-vs-oracle parity tests (test_pallas_paged)
        total = jnp.float32(0.0)
        for x, pool in ((kh_, pk), (vh_, pv)):
            pf = x[0].reshape(n_pages, page_size, kv_heads, head_dim)
            if qmax is None:
                out = pool.at[pages].set(pf.astype(pool.dtype))
            else:
                pf = pf.astype(jnp.float32)
                sc = page_scale(pf, qmax)
                out = pool.at[pages].set(
                    page_quantize(pf, sc, qmax, pool.dtype))
            total = total + jnp.sum(out.astype(jnp.float32))
        return total

    timed = {}
    for impl, step in (("einsum", einsum_step), ("pallas", pallas_step)):
        timed[impl] = measure.time_scalar_program(
            jax.jit(step), kh, vh, pool_k, pool_v,
            warmup=warmup, iters=iters)
        if verbose:
            print(f"[kernel_tune] paged_prefill ps{page_size} "
                  f"pps{pages_per_slot} d{head_dim} "
                  f"{np.dtype(store).name} {impl}: "
                  f"{timed[impl] * 1e3:.3f} ms")
    best = min(timed, key=timed.get)
    sig = shape_sig(seq_q=page_size, seq_k=slab_len, head_dim=head_dim,
                    dtype=store, batch=slots, heads=heads, causal=False)
    record("paged_prefill", sig, None, timed[best],
           candidates=None, path=path, impl=best,
           extra={f"{k}_seconds": float(v) for k, v in timed.items()})
    rec = {
        "kernel": "paged_prefill", "sig": sig, "device": device_key(),
        "impl": best, "kv_dtype": np.dtype(store).name,
        "seconds": timed[best],
        "candidates": {k: float(v) for k, v in timed.items()},
    }
    if verbose:
        print(f"[kernel_tune] paged_prefill winner {best} -> "
              f"{path or default_table_path()}")
    return rec


def static_blocks(seq_q: int, seq_k: int) -> Tuple[int, int]:
    """What the cold fallback would pick — recorded next to tuned picks
    so benches/tests can state whether tuning CHANGED the decision."""
    from flexflow_tpu.ops.pallas_kernels import _OUTER_BLOCK, _pick_block

    return _pick_block(seq_q, _OUTER_BLOCK), _pick_block(seq_k, _OUTER_BLOCK)


def tune_flash_attention(seq_q: int, seq_k: Optional[int] = None, *,
                         head_dim: int = 64, dtype="float32",
                         batch: int = 1, heads: int = 4,
                         causal: bool = True,
                         candidates: Optional[Sequence] = None,
                         warmup: int = 1, iters: int = 3,
                         path: Optional[str] = None,
                         verbose: bool = False) -> Dict:
    """Sweep (block_q, block_k) for the flash FORWARD kernel at one
    shape, persist the winner, return the decision record::

        {"kernel", "sig", "blocks", "static", "changed", "seconds",
         "candidates": {(bq, bk): seconds}}

    Timing goes through measure.time_scalar_program — the same
    dispatch-floor harness the strategy search trusts for op costs (the
    kernel call is wrapped in a scalar-reducing jit so each timed call
    fetches 4 bytes). Off-TPU the kernels run only in interpret mode
    (FF_PALLAS_INTERPRET=1): the sweep still exercises the full tune->persist->consume path (the CI
    smoke), it just measures the interpreter."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas_kernels import flash_attention_fwd_pallas
    from flexflow_tpu.search import measure

    seq_k = seq_k or seq_q
    scale = 1.0 / math.sqrt(head_dim)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(batch, seq_q, heads, head_dim), dtype)
    k = jnp.asarray(rs.randn(batch, seq_k, heads, head_dim), dtype)
    v = jnp.asarray(rs.randn(batch, seq_k, heads, head_dim), dtype)

    cand = [tuple(c) for c in (candidates or DEFAULT_CANDIDATES)]
    legal = [(bq, bk) for bq, bk in cand
             if bq <= seq_q and seq_q % bq == 0
             and bk <= seq_k and seq_k % bk == 0]
    if not legal:
        raise ValueError(
            f"no legal (block_q, block_k) candidate for seq_q={seq_q}, "
            f"seq_k={seq_k} in {cand}")

    timed: Dict[Tuple[int, int], float] = {}
    for bq, bk in legal:
        def step(q_, k_, v_, bq=bq, bk=bk):
            out, _ = flash_attention_fwd_pallas(
                q_, k_, v_, causal, scale, block_q=bq, block_k=bk,
                need_lse=False)
            return jnp.sum(out.astype(jnp.float32))

        dt = measure.time_scalar_program(jax.jit(step), q, k, v,
                                         warmup=warmup, iters=iters)
        timed[(bq, bk)] = dt
        if verbose:
            print(f"[kernel_tune] flash_fwd sq{seq_q} sk{seq_k} "
                  f"d{head_dim} {jnp.dtype(dtype).name} "
                  f"block ({bq}, {bk}): {dt * 1e3:.3f} ms")
    best = min(timed, key=timed.get)
    sig = shape_sig(seq_q=seq_q, seq_k=seq_k, head_dim=head_dim,
                    dtype=dtype, batch=batch, heads=heads, causal=causal)
    record("flash_fwd", sig, best, timed[best], candidates=timed,
           path=path)
    static = static_blocks(seq_q, seq_k)
    rec = {
        "kernel": "flash_fwd", "sig": sig, "device": device_key(),
        "blocks": list(best), "static": list(static),
        "changed": tuple(best) != tuple(static),
        "seconds": timed[best],
        "candidates": {f"{bq}x{bk}": s for (bq, bk), s in timed.items()},
    }
    if verbose:
        print(f"[kernel_tune] winner {best} (static {static}, "
              f"changed={rec['changed']}) -> {path or default_table_path()}")
    return rec


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(
        description="Tune flash-attention block sizes (default) or the "
                    "paged-attention impl choice (--paged, optionally on "
                    "a quantized pool via --kv-dtype) on this device and "
                    "persist the winners (consulted automatically at "
                    "trace / engine-construction time).")
    p.add_argument("--paged", action="store_true",
                   help="tune the paged-attention kernel-vs-einsum "
                        "choice instead of flash blocks")
    p.add_argument("--paged-prefill", action="store_true",
                   help="tune the paged prefill/append WRITE "
                        "kernel-vs-einsum choice (ISSUE 18) instead of "
                        "flash blocks")
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--pages-per-slot", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=2)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--slab", type=int, default=1,
                   help="query slab length (1 = decode — the entry "
                        "engines steer by; K+1 = verify, recorded for "
                        "comparison only)")
    p.add_argument("--kv-dtype", type=str, default="native",
                   choices=("native", "bf16", "int8", "fp8"),
                   help="pool storage dtype for --paged (part of the "
                        "table key)")
    p.add_argument("--seq", "--seq-q", dest="seq_q", type=int,
                   default=None)
    p.add_argument("--seq-k", type=int, default=None)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--dtype", type=str, default="float32",
                   choices=("float32", "bfloat16"))
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--no-causal", action="store_true")
    p.add_argument("--iters", type=int, default=3)
    p.add_argument("--candidates", type=str, default="",
                   help="e.g. '128x128,256x256' (default: built-in grid)")
    p.add_argument("--table", type=str, default="",
                   help="table path (default FF_KERNEL_TUNE_TABLE or "
                        "~/.cache/flexflow_tpu/kernel_tune.json)")
    args = p.parse_args(argv)
    if args.paged_prefill:
        rec = tune_paged_prefill(
            page_size=args.page_size, pages_per_slot=args.pages_per_slot,
            head_dim=args.head_dim, kv_heads=args.kv_heads,
            heads=args.heads, slots=args.slots, dtype=args.dtype,
            kv_dtype=(None if args.kv_dtype == "native"
                      else args.kv_dtype),
            iters=args.iters, path=args.table or None, verbose=True)
        print(json.dumps(rec))
        return 0
    if args.paged:
        rec = tune_paged_attention(
            page_size=args.page_size, pages_per_slot=args.pages_per_slot,
            head_dim=args.head_dim, kv_heads=args.kv_heads,
            heads=args.heads, slots=args.slots, s=args.slab,
            dtype=args.dtype,
            kv_dtype=(None if args.kv_dtype == "native"
                      else args.kv_dtype),
            iters=args.iters, path=args.table or None, verbose=True)
        print(json.dumps(rec))
        return 0
    if args.seq_q is None:
        p.error("--seq is required (or pass --paged)")
    cand = None
    if args.candidates:
        cand = []
        for part in args.candidates.split(","):
            bq, _, bk = part.partition("x")
            cand.append((int(bq), int(bk)))
    rec = tune_flash_attention(
        args.seq_q, args.seq_k, head_dim=args.head_dim, dtype=args.dtype,
        batch=args.batch, heads=args.heads, causal=not args.no_causal,
        candidates=cand, iters=args.iters, path=args.table or None,
        verbose=True)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
