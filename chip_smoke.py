#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the normal train path and the normal serve path of ONE
model at full width on the attached TPU, then sweeps every Pallas kernel at
the shape classes those paths can route to:

  device    platform must be `tpu`; versions, compile cache, tune table
  build     models/llama.llama_lm at hidden 2048 / 16 heads of 128 / 4 KV
            heads / SwiGLU 5504 / vocab 32000, bf16 compute, FFModel.compile
  train     SingleDataLoader + FFModel.fit() at sequence 2048: finite,
            falling loss, flash fwd+bwd Mosaic calls in the lowered step
  serve     the same FFModel through make_serving_engine(): warmup(), then
            mixed-length requests x 64 new tokens; every request done, both
            paged impls `pallas`, zero compiles after warmup, the kernel
            against the einsum oracle on the engine's own pool
  kernels   each ops/pallas_kernels.py entry point x shape class, compiled
            natively and compared with its oracle
  four_chip (only with >= 4 devices) the train leg on data=2 x model=2 with
            the strategy from the repo's own native search

No subprocess, no retry, no fallback: a failed phase is named on stderr and
the exit code is 1. Exit 0 and the final JSON line mean every phase passed
on a TPU. Weights and data are random from a seed; no throughput, MFU or
latency is printed — this script defines no metric.

`--cpu-rehearsal` walks the same code at a tiny size with the kernels in
interpret mode, to debug control flow before spending chip time. It prints
that it is NOT a chip result, prints no JSON verdict and exits 64, never 0.
"""

import argparse
import contextlib
import dataclasses
import functools
import gc
import json
import math
import os
import sys
import time
from typing import Callable, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
REHEARSAL_PASSED = 64

# environment switches that reroute around kernels or change what `auto`
# resolves to: the smoke runs with none of them
REROUTING_ENV = ("FF_FLASH_MAX_SEQ", "FF_FORCE_FLASH_ATTENTION")


@dataclasses.dataclass(frozen=True)
class Sizes:
    hidden: int
    heads: int
    kv_heads: int
    vocab: int
    depth: int
    seq: int            # training sequence length
    train_steps: int
    page_size: int      # 0 = FFConfig default (128)
    max_bucket: int     # largest prompt bucket the serve leg reaches
    prompt_lens: tuple
    new_tokens: int
    spec_k: int         # speculative slab = spec_k + 1 positions
    ln_rows: int        # fused add+LN rows swept at the model's hidden

    @property
    def head_dim(self):
        return self.hidden // self.heads

    @property
    def max_seq_len(self):
        """Serving context: the largest bucket plus whole pages for the
        generated tokens."""
        ps = self.page_size or 128
        return self.max_bucket + ps * math.ceil(self.new_tokens / ps)


# Full width of the model and its full depth (no cut: with f32 master
# weights, SGD and batch 1 x 2048 tokens the whole run peaks at 4.9 GB of the
# chip's 16 GB). Four prompt-length classes keep the serve leg to four prefill
# buckets (64/256/1024/2048): a cold run compiles for ~7 min of the 1200 s
# limit.
FULL = Sizes(hidden=2048, heads=16, kv_heads=4, vocab=32_000, depth=16,
             seq=2048, train_steps=4, page_size=0, max_bucket=2048,
             prompt_lens=(48, 200, 700, 1500) * 3, new_tokens=64, spec_k=4,
             ln_rows=2048)
REHEARSAL = Sizes(hidden=128, heads=4, kv_heads=2, vocab=256, depth=2,
                  seq=128, train_steps=3, page_size=16, max_bucket=128,
                  prompt_lens=(6, 20, 50, 100) * 2, new_tokens=8, spec_k=2,
                  ln_rows=64)

# Step-1 loss, one chip vs data=2 x model=2: same seed, same sample, bf16
# matmuls with f32 accumulation. Tensor parallelism only changes the order
# of the f32 partial sums, so the two losses (~ln(vocab) = 10.4) differ in
# the last bf16 digits of the activations: 2e-2 relative is ~5 bf16 ulps.
FOUR_CHIP_LOSS_RTOL = 2e-2

# Paged kernel vs einsum oracle on bf16 K/V: both accumulate in f32 and feed
# bf16 probabilities to the PV matmul, but the kernel normalises after the
# accumulation (online softmax) and the oracle before it, so outputs of
# magnitude <= 1 differ by a few bf16 ulps (2^-8): 2e-2 absolute + relative.
BF16_TOL = 2e-2
F32_TOL = 2e-3


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def require(cond, msg):
    """A check that survives `python -O` (assert does not)."""
    if not cond:
        raise RuntimeError(msg)


@contextlib.contextmanager
def phase(name, cache_entries=None):
    """Name the phase on failure and re-raise: nothing is caught and passed
    over, so the first failed phase ends the process non-zero.
    `cache_entries()` counts the compile cache, to show what the phase
    added to it (nothing, on a warm run)."""
    log(f"PHASE {name}")
    t0 = time.perf_counter()
    n0 = cache_entries() if cache_entries else 0
    try:
        yield
    except BaseException as e:
        print(f"[chip_smoke] FAILED phase={name}: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        raise
    added = f", +{cache_entries() - n0} cache entries" if cache_entries else ""
    log(f"PHASE {name} ok ({time.perf_counter() - t0:.1f} s wall incl. "
        f"set-up{added})")


# ----------------------------------------------------------------- oracles


def dense_attention_oracle(q, k, v, causal):
    """Plain f32 softmax attention on (B, S, H, D) — the flash oracle."""
    import jax
    import jax.numpy as jnp

    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(q.shape[-1])
    if causal:
        sq, sk = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq), s,
                      -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), vf)


def add_ln_oracle(x, r, scale, bias, eps=1e-5):
    import jax
    import jax.numpy as jnp

    s = x + r
    sf = s.astype(jnp.float32)
    mean = jnp.mean(sf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(sf - mean), axis=-1, keepdims=True)
    y = (sf - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    return s, y.astype(x.dtype)


# -------------------------------------------------------------- kernel sweep


@dataclasses.dataclass
class KernelCase:
    """One kernel x shape class: `kernel(*args)` is the Pallas path,
    `oracle(*args)` its reference; both return pytrees compared leaf by
    leaf within `tol` (absolute + relative). `refusal()` asks the selector
    that would route to this kernel whether it declines the class."""
    name: str
    make_args: Callable        # (np.random.RandomState) -> tuple of arrays
    kernel: Callable
    oracle: Callable
    tol: float
    refusal: Callable[[], Optional[str]] = lambda: None


def kernel_cases(z: Sizes):
    """Every public entry point of ops/pallas_kernels.py at each shape class
    the trainer/engine can route to it under `auto` for a model of `z`.
    Shared with scripts/aot_kernel_check.py, which compiles the same list
    against the compile-only v5e topology."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.attention import (page_quantize, page_scale,
                                            storage_qmax)
    from flexflow_tpu.ops.norm import fused_add_ln_refusal

    h, kvh, d = z.heads, z.kv_heads, z.head_dim
    ps = z.page_size or 128
    pps = math.ceil(z.max_seq_len / ps)
    slots = 4
    pool_pages = 1 + slots * pps + max(pps, slots * pps // 2)
    bf16 = jnp.bfloat16
    cases = []

    # the model's attention op, graph only (no parameters): its paged
    # methods are both the call the engine routes to the kernel
    # (impl="pallas") and the repo's einsum oracle (impl="einsum")
    graph = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
    x = graph.create_tensor([1, 8, z.hidden], name="x")
    graph.multihead_attention(x, x, x, z.hidden, h, causal=True, bias=False,
                              num_kv_heads=kvh, rope=True, name="attn")
    attn = graph.ops[-1]

    def as_cache(pool_k, pool_v, *sc):
        cache = {"k": pool_k, "v": pool_v}
        if sc:
            cache["k_scale"], cache["v_scale"] = sc
        return cache

    def qkv(b, s, heads):
        def make(rs):
            return tuple(jnp.asarray(rs.randn(b, s, heads, d) * 0.5, bf16)
                         for _ in range(3))
        return make

    # flash forward as prefill runs it (no lse): a sub-tile bucket, a
    # one-block bucket and the multi-block maximum
    for s in sorted({min(64, z.seq), min(256, z.seq), z.max_bucket}):
        cases.append(KernelCase(
            f"flash_fwd prefill b1 s{s} h{h} d{d} bf16", qkv(1, s, h),
            lambda q, k, v: pk.flash_attention(q, k, v, True, None),
            lambda q, k, v: dense_attention_oracle(q, k, v, True), BF16_TOL))

    # flash forward + backward as the train step runs it, whole and as the
    # per-shard call under model=2 head sharding
    def flash_grads(fn):
        def run(q, k, v):
            def pulled(q, k, v):
                out = fn(q, k, v).astype(jnp.float32)
                w = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                            ).reshape(out.shape)
                return jnp.sum(out * w)

            return jax.grad(pulled, argnums=(0, 1, 2))(q, k, v)
        return run

    for heads in sorted({h, h // 2}, reverse=True):
        cases.append(KernelCase(
            f"flash_fwd+bwd train b1 s{z.seq} h{heads} d{d} bf16",
            qkv(1, z.seq, heads),
            flash_grads(lambda q, k, v: pk.flash_attention(q, k, v, True,
                                                           None)),
            flash_grads(lambda q, k, v: dense_attention_oracle(q, k, v,
                                                               True)),
            # gradients sum ~seq bf16 products per element
            4 * BF16_TOL))

    # grouped-query heads as the dense train path hands them over: k and v
    # keep their own few heads, the kernels' index maps find a query head's
    # group, and the dkv kernel sums the group into one key head
    def qkv_grouped(rs):
        return tuple(jnp.asarray(rs.randn(1, z.seq, n, d) * 0.5, bf16)
                     for n in (h, max(1, h // 4), max(1, h // 4)))

    def every_head(x):
        return jnp.repeat(x, h // x.shape[2], axis=2)

    cases.append(KernelCase(
        f"flash_fwd+bwd train b1 s{z.seq} h{h} kvh{max(1, h // 4)} d{d} bf16",
        qkv_grouped,
        flash_grads(lambda q, k, v: pk.flash_attention(q, k, v, True, None)),
        flash_grads(lambda q, k, v: dense_attention_oracle(
            q, every_head(k), every_head(v), True)),
        # a key's gradient sums its group's heads too
        8 * BF16_TOL))

    # the same at key and value widths that differ (latent attention's
    # expanded form: keys half as wide again as the values)
    def qkv_apart(rs):
        return tuple(jnp.asarray(rs.randn(1, z.seq, h, w) * 0.5, bf16)
                     for w in (d + d // 2, d + d // 2, d))

    cases.append(KernelCase(
        f"flash_fwd+bwd train b1 s{z.seq} h{h} dqk{d + d // 2} dv{d} bf16",
        qkv_apart,
        flash_grads(lambda q, k, v: pk.flash_attention(q, k, v, True, None)),
        flash_grads(lambda q, k, v: dense_attention_oracle(q, k, v, True)),
        4 * BF16_TOL))

    # and with the key handed in its two parts (latent attention's own
    # form: a head's part, and ONE rotary part a token, here head 0's): the
    # kernels' second contraction pair on operands that stay (B, S, H * d)
    def parts(q, k):
        return ((q[..., :d], q[..., d:]), (k[..., :d], k[:, :, 0, d:]))

    def one_rotary_key(k):
        return jnp.concatenate(
            [k[..., :d], jnp.broadcast_to(k[:, :, :1, d:],
                                          k.shape[:3] + (d // 2,))], -1)

    cases.append(KernelCase(
        f"flash_fwd+bwd train b1 s{z.seq} h{h} dqk{d}+{d // 2} apart dv{d} "
        "bf16", qkv_apart,
        flash_grads(lambda q, k, v: pk.flash_attention(*parts(q, k), v, True,
                                                       None)),
        flash_grads(lambda q, k, v: dense_attention_oracle(
            q, one_rotary_key(k), v, True)),
        4 * BF16_TOL))

    # paged attention: decode (S=1) and the speculative-verify slab
    # (S=K+1), on the native pool and on both quantized pools
    def paged_args(s, store):
        def make(rs):
            q = jnp.asarray(rs.randn(slots, s, h, d) * 0.5, bf16)
            kf = rs.randn(pool_pages, ps, kvh, d).astype(np.float32) * 0.5
            vf = rs.randn(pool_pages, ps, kvh, d).astype(np.float32) * 0.5
            table = rs.permutation(np.arange(1, 1 + slots * pps)).reshape(
                slots, pps).astype(np.int32)
            # ragged slots: a short one inside its first page, one that
            # ends exactly on a page edge, two long ones
            row_len = np.asarray([3, ps, z.max_seq_len // 2,
                                  z.max_seq_len - 2 * s - 3], np.int32)
            pad = np.asarray([8, ps, z.max_seq_len // 2 + 5,
                              z.max_seq_len - 2 * s - 1], np.int32)
            wp = (pad + s)[:, None] + np.arange(s, dtype=np.int32)[None]
            wp = np.minimum(wp, z.max_seq_len - 1).astype(np.int32)
            if store is None:
                pool = (jnp.asarray(kf, bf16), jnp.asarray(vf, bf16))
                scales = ()
            else:
                qmax = storage_qmax(store)
                ks, vs = page_scale(kf, qmax), page_scale(vf, qmax)
                pool = (page_quantize(kf, ks, qmax, store),
                        page_quantize(vf, vs, qmax, store))
                scales = (ks, vs)
            return (q, *pool, jnp.asarray(table), jnp.asarray(wp),
                    jnp.asarray(row_len), jnp.asarray(pad), *scales)
        return make

    def paged(impl):
        return lambda q, kp, vp, pt, wp, rl, pp, *sc: \
            attn._paged_attention_ctx(q, as_cache(kp, vp, *sc), pt, wp, rl,
                                      pp, impl)

    stores = {"bf16": None, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
    for s in (1, z.spec_k + 1):
        for label, store in stores.items():
            cases.append(KernelCase(
                f"paged_attention {'decode' if s == 1 else 'verify'} "
                f"slots{slots} S{s} h{h} kvh{kvh} d{d} page{ps} {label}",
                paged_args(s, store), paged("pallas"), paged("einsum"),
                BF16_TOL))

    # the paged kernel's shared-page form (a page several slots hold,
    # streamed once a group) at the pools of the two document cells:
    # MiMo-V2's flat row a token (4 KV heads, keys of 192 beside values of
    # 128, with a sink) and granite's two heads of 64 a row. Eight slots: a
    # document three hold, one two hold and a third held by the three the
    # cap leaves no room for elsewhere, one slot alone, one idle
    from flexflow_tpu.ops.attention import MultiHeadAttention
    from flexflow_tpu.runtime.kv_pool import shared_page_groups

    def doc_op(name, heads, kv, dqk, dv, sink):
        return MultiHeadAttention(
            graph, name, [x, x, x], z.hidden, heads, kdim=heads * dqk,
            vdim=heads * dv, bias=False, causal=True, num_kv_heads=kv,
            sink=sink)

    def shared_args(op, cap):
        def make(rs):
            b, doc = 8, max(2, pps // 2)
            width = doc + 3
            cache = op.init_paged_cache(1 + b * width, ps, bf16)
            pool = [jnp.asarray(rs.randn(*a.shape) * 0.5, bf16)
                    for a in (cache["k"], cache["v"])]
            table = rs.permutation(np.arange(1, 1 + b * width)).reshape(
                b, width).astype(np.int32)
            for holders in ((0, 2, 5), (1, 4), (3,)):
                table[list(holders), :doc] = table[holders[0], :doc]
            table[7] = 0
            row_len = np.asarray([doc * ps + 3 + 5 * i for i in range(7)]
                                 + [0], np.int32)
            pad = np.where(row_len > 0, doc * ps + ps // 2, 0).astype(
                np.int32)
            wp = np.where(row_len > 0, pad + 1 + np.arange(b) * (ps // 4),
                          0).astype(np.int32)[:, None]
            groups = shared_page_groups(table, row_len // ps, cap)
            assert sorted(groups) == [([0, 2, 5], doc), ([1, 4], doc)], groups
            q = jnp.asarray(
                rs.randn(b, 1, op.num_heads, op.qk_head_dim) * 0.5, bf16)
            return (q, *pool, jnp.asarray(table), jnp.asarray(wp),
                    jnp.asarray(row_len), jnp.asarray(pad),
                    *(jnp.asarray(a) for a in pk.pack_shared_groups(
                        groups, b, cap)),
                    jnp.asarray(rs.randn(op.num_heads), jnp.float32))
        return make

    def shared(op, impl):
        return lambda q, kp, vp, pt, wp, rl, pp, groups, slot_of, sink: \
            op._paged_attention_ctx(
                q, {"k": kp, "v": vp}, pt, wp, rl, pp, impl,
                sink=sink if op.sink is not None else None,
                shared=(groups, slot_of))

    doc_shapes = [("mimo-flat", 64, 4, 192, 128, 1.0),
                  ("granite-packed", 32, 8, 64, 64, None)] if z is FULL \
        else [("flat", 8, 2, 192, 128, 1.0), ("packed", 8, 4, 64, 64, None)]
    for label, heads, kv, dqk, dv, sink in doc_shapes:
        op = doc_op(f"attn_{label}", heads, kv, dqk, dv, sink)
        cases.append(KernelCase(
            f"paged_attention shared pages {label} slots8 h{heads} kvh{kv} "
            f"dqk{dqk} dv{dv} page{ps} bf16",
            shared_args(op, min(op.shared_members_cap(), 8)),
            shared(op, "pallas"), shared(op, "einsum"), BF16_TOL))

    # prefill page write: a whole-bucket slab, a slab whose tail pads its
    # last page, quantized pools
    def write_args(s, store):
        def make(rs):
            n = math.ceil(s / ps)
            kh = jnp.asarray(rs.randn(1, s, kvh, d) * 0.5, bf16)
            vh = jnp.asarray(rs.randn(1, s, kvh, d) * 0.5, bf16)
            pages = jnp.asarray(
                rs.permutation(np.arange(1, pool_pages))[:n], jnp.int32)
            shape = (pool_pages, ps, kvh, d)
            pool = (jnp.zeros(shape, store or bf16),) * 2
            scales = ((jnp.zeros((pool_pages, kvh), jnp.float32),) * 2
                      if store is not None else ())
            return (kh, vh, pages, *pool, *scales)
        return make

    def pool_values(cache):
        # what attention will read back: payload x scale. A quantized
        # payload may sit one step apart where the two f32 divisions differ
        # in the last place, so pools compare as numbers, not bit patterns
        return {n: cache[n].astype(jnp.float32)
                * (cache[n + "_scale"][:, None, :, None]
                   if n + "_scale" in cache else 1.0) for n in ("k", "v")}

    def write(impl):
        return lambda kh, vh, pages, *pool: pool_values(
            attn.paged_prefill_write(as_cache(*pool), kh, vh, pages,
                                     impl=impl))

    for s, label in ((z.max_bucket, "bf16"), (z.max_bucket - ps // 2, "bf16"),
                     (z.max_bucket, "int8"), (z.max_bucket, "fp8")):
        store = stores[label]
        cases.append(KernelCase(
            f"paged_prefill_write s{s} kvh{kvh} d{d} page{ps} {label}",
            write_args(s, store),
            write("pallas"), write("einsum"),
            # a plain pool is a copy (exact); one int8 step is amax/127
            # (~2e-2 here), one fp8-e4m3 step 2^-3 of the value
            {"bf16": 0.0, "int8": BF16_TOL, "fp8": 0.13}[label]))

    # the expert-stream kernel as a decode step (32 rows) and the largest
    # prefill bucket it takes (128) call it: 8 SwiGLU experts of width
    # hidden / 2 (OLMoE's ratio), top-2, experts 6 and 7 hit by nobody; at
    # the real size also a hidden size between the multiples of 1024 whose
    # experts stream in two chunks of their width
    def stream_args(n, d, f, matrices=3, experts=8, k=2):
        def make(rs):
            gates = np.full((n, experts), pk.MOE_NOT_CHOSEN, np.float32)
            for r in range(n):
                gates[r, rs.choice(experts - 2, k, replace=False)] = \
                    rs.rand(k)
            sizes = (gates != pk.MOE_NOT_CHOSEN).sum(0).astype(np.int32)
            w = [jnp.asarray(rs.randn(experts, a, b) / math.sqrt(a), bf16)
                 for a, b in ((d, f),) * (matrices - 1) + ((f, d),)]
            return (jnp.asarray(rs.randn(n, d), bf16),
                    jnp.asarray(gates), jnp.asarray(sizes), *w)
        return make

    def stream_oracle(x, gates, sizes, *w):
        xf = x.astype(jnp.float32)
        y = jnp.zeros(xf.shape, jnp.float32)
        wd = w[-1]
        with jax.default_matmul_precision("highest"):
            for e in range(wd.shape[0]):
                up = xf @ w[-2][e].astype(jnp.float32)
                hid = (jax.nn.silu(xf @ w[0][e].astype(jnp.float32)) * up
                       if len(w) == 3 else jnp.square(jax.nn.relu(up)))
                col = gates[:, e:e + 1]
                y += jnp.where(col != pk.MOE_NOT_CHOSEN,
                               col * (hid @ wd[e].astype(jnp.float32)), 0.0)
        return y

    stream_shapes = [(rows, z.hidden, max(128, z.hidden // 2), 3)
                     for rows in (32, 128)]
    # two-matrix relu^2 experts (a latent narrower than their width)
    stream_shapes.append((32, max(128, z.hidden // 2), z.hidden, 2))
    if z is FULL:
        stream_shapes += [(32, 2560, 1024, 3), (32, 1024, 2688, 2)]
    for rows, dim, width, matrices in stream_shapes:
        cases.append(KernelCase(
            f"moe_expert_stream n{rows} e8 k2 d{dim} f{width} x{matrices} "
            f"bf16 chunk{pk.moe_stream_chunk(dim, width, bf16, matrices)}",
            stream_args(rows, dim, width, matrices),
            pk.moe_expert_stream_pallas, stream_oracle,
            # h rounds to bf16 before the down projection (2^-8 of
            # values of order 1), outputs of order 1
            BF16_TOL))

    # the Mamba-2 decode update over a pool of float32 states, live slots
    # only (dead ones scattered, leading and trailing), against XLA's loop
    from flexflow_tpu.ops.mamba import mamba_state_update

    def update_args(s, nh, p, n, g):
        def make(rs):
            live = np.ones((s,), bool)
            live[[0, s // 2, s - 1]] = False
            # the pool in the layout the op holds: (slots, groups, N,
            # heads a group x P)
            return (jnp.asarray(rs.randn(s, g, n, nh // g * p) * 0.1,
                                jnp.float32),
                    jnp.asarray(rs.rand(s, nh), jnp.float32),
                    jnp.asarray(rs.randn(s, nh, p), jnp.float32),
                    jnp.asarray(rs.randn(s, g, n), jnp.float32),
                    jnp.asarray(rs.randn(s, g, n), jnp.float32),
                    jnp.asarray(live))
        return make

    for shape in [(8, 16, 64, 128, 2)] + ([(32, 128, 64, 128, 8),
                                           (16, 64, 64, 128, 1)]
                                          if z is FULL else []):
        cases.append(KernelCase(
            "mamba_state_update slots{} h{} p{} n{} g{} f32".format(*shape),
            update_args(*shape), pk.mamba_state_update_pallas,
            mamba_state_update,
            # float32 throughout; y sums n products of order 1
            1e-4))

    # the power-retention decode update over a pool of float32 states (S by
    # phi's diagonals, z beside it), live slots only, against XLA's loop;
    # phi is formed in the kernel by lane rotations
    from flexflow_tpu.ops.retention import retention_state_update

    def retention_args(s, kv, r, hd):
        def make(rs):
            live = np.ones((s,), bool)
            live[[0, s // 2, s - 1]] = False
            nd = hd // 2 + 1
            f = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.float32)
            return (f(s, kv, nd, hd, hd) * 0.1, f(s, kv, nd, hd) * 0.1,
                    jnp.asarray(rs.uniform(0.9, 1.0, (s, kv)), jnp.float32),
                    f(s, kv, r, hd), f(s, kv, hd), f(s, kv, hd),
                    jnp.asarray(live))
        return make

    for shape in [(4, 2, 5, 128)] + ([(24, 8, 5, 128)] if z is FULL else []):
        cases.append(KernelCase(
            "retention_state_update slots{} kv{} r{} hd{} f32".format(*shape),
            retention_args(*shape), pk.retention_state_update_pallas,
            retention_state_update,
            # float32 throughout; a read-out sums 8320 products of order
            # 1e-2 against XLA's HIGHEST-precision einsum
            1e-4))

    # fused add+layernorm at the model's hidden, forward (inference) and
    # with the backward's saved statistics; plus the 4096 x 4096 width the
    # README's encoder runs, which the row-block budget must fit
    def ln_args(n, dim):
        def make(rs):
            return (jnp.asarray(rs.randn(n, dim), bf16),
                    jnp.asarray(rs.randn(n, dim), bf16),
                    jnp.asarray(1 + 0.1 * rs.randn(dim), jnp.float32),
                    jnp.asarray(0.1 * rs.randn(dim), jnp.float32))
        return make

    def ln_grads(fn):
        def run(x, r, scale, bias):
            def loss(x, r, scale, bias):
                s, y = fn(x, r, scale, bias)
                return (jnp.sum(jnp.sin(s.astype(jnp.float32)))
                        + jnp.sum(jnp.cos(y.astype(jnp.float32))))
            return jax.grad(loss, argnums=(0, 1, 2, 3))(x, r, scale, bias)
        return run

    ln_shapes = [(z.ln_rows, z.hidden)]
    if z is FULL:
        ln_shapes.append((4096, 4096))
    for n, dim in ln_shapes:
        refusal = functools.partial(fused_add_ln_refusal, n, dim, bf16)
        cases.append(KernelCase(
            f"fused_add_layernorm fwd n{n} d{dim} bf16", ln_args(n, dim),
            lambda x, r, s, b: pk.fused_add_layernorm(x, r, s, b, 1e-5),
            add_ln_oracle, BF16_TOL, refusal))
        cases.append(KernelCase(
            f"fused_add_layernorm fwd+bwd n{n} d{dim} bf16",
            ln_args(n, dim),
            ln_grads(lambda x, r, s, b: pk.fused_add_layernorm(x, r, s, b,
                                                               1e-5)),
            # dscale/dbias sum n bf16-rounded terms per column
            ln_grads(add_ln_oracle), BF16_TOL * math.sqrt(n), refusal))
    return cases


def max_error(got, want, tol):
    """Largest |got - want| over the leaves, and whether every element is
    inside tol * (1 + |want|)."""
    import jax
    import numpy as np

    worst, ok = 0.0, True
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        require(g.shape == w.shape, f"shape {g.shape} vs oracle {w.shape}")
        require(np.isfinite(g).all(), "non-finite kernel output")
        err = np.abs(g - w)
        worst = max(worst, float(err.max()))
        ok = ok and bool((err <= tol * (1 + np.abs(w))).all())
    return worst, ok


def run_kernel_sweep(z: Sizes, rehearsal):
    import jax
    import numpy as np

    how = "interpreted" if rehearsal else "compiled"
    failed = []
    for i, case in enumerate(kernel_cases(z)):
        reason = case.refusal()
        if reason is not None:
            log(f"kernel {case.name}: refused({reason})")
            continue
        args = case.make_args(np.random.RandomState(1000 + i))
        got = jax.block_until_ready(jax.jit(case.kernel)(*args))
        want = jax.block_until_ready(jax.jit(case.oracle)(*args))
        err, ok = max_error(got, want, case.tol)
        log(f"kernel {case.name}: {how} max_err={err:.3g} "
            f"tol={case.tol:.3g} {'ok' if ok else 'MISMATCH'}")
        if not ok:
            failed.append(case.name)
    require(not failed, f"kernels disagree with their oracles: {failed}")


# --------------------------------------------------------------- the model


def build_model(z: Sizes, batch, mesh_shape, rehearsal, **cfg_kw):
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer)
    from flexflow_tpu.models.llama import llama_lm

    cfg = FFConfig(batch_size=batch, mesh_shape=mesh_shape, seed=0,
                   compute_dtype="float32" if rehearsal else "bfloat16",
                   **cfg_kw)
    if z.page_size:
        cfg.kv_page_size = z.page_size
    ff = FFModel(cfg)
    tokens, logits = llama_lm(ff, batch, seq_len=z.seq, hidden=z.hidden,
                              layers=z.depth, heads=z.heads,
                              kv_heads=z.kv_heads, ffn_hidden=0,
                              vocab_size=z.vocab)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    return ff, tokens


def mosaic_calls(ff, batch):
    """{kernel name: count} of Mosaic custom calls in the lowered train
    step. Lowering only (no second compile): a pallas_call lowers to a
    `tpu_custom_call` whose backend config carries the call's `name=`."""
    import jax

    sharded = ff.executor.shard_batch(batch)
    key = jax.random.split(ff._rng)[1]
    text = ff._train_step.lower(ff.params, ff.opt_state, ff.bn_state,
                                sharded, key).as_text()
    names = ("flash_attention_fwd", "flash_attention_bwd_dq",
             "flash_attention_bwd_dkv")
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    return {n: sum(n in ln for ln in calls) for n in names}, len(calls)


def train_leg(ff, tokens, z: Sizes, batch, need_mosaic):
    """fit() one repeated seeded batch for z.train_steps epochs of one step
    each; returns the per-step losses. `need_mosaic` makes flash forward and
    backward Mosaic calls in the lowered step a requirement."""
    import numpy as np

    from flexflow_tpu import SingleDataLoader
    from flexflow_tpu.keras.callbacks import Callback

    rs = np.random.RandomState(0)
    sample = rs.randint(0, z.vocab, (1, z.seq)).astype(np.int32)
    x = np.repeat(sample, batch, axis=0)          # the same sample per row
    y = np.roll(x, -1, axis=1)[..., None].astype(np.int32)

    loaders = (SingleDataLoader(ff, tokens, x),
               SingleDataLoader(ff, ff.label_tensor, y))
    if hasattr(ff._train_step, "lower"):
        counts, total = mosaic_calls(
            ff, {dl.name: a for dl, a in zip(loaders, (x, y))})
        log(f"train step lowers to {total} Mosaic custom calls: {counts}")
        require(not need_mosaic or all(counts.values()),
                f"flash forward AND backward must be Mosaic calls in the "
                f"train step, found {counts} — attention was routed around "
                f"the kernels")
    else:
        # a strategy that places ops on device blocks runs one program per
        # block (PlacementExecutor): there is no single step to lower
        require(not need_mosaic, "the train step is not one jitted program")
        log("train step is per-block placement programs: Mosaic calls not "
            "counted")

    class StepLosses(Callback):
        def __init__(self):
            super().__init__()
            self.losses = []

        def on_epoch_end(self, epoch):
            # the f32 loss of the epoch's one step, as fit() itself prints
            self.losses.append(float(self.model._last_loss))

    rec = StepLosses()
    t0 = time.perf_counter()
    ff.fit(epochs=z.train_steps, callbacks=[rec], verbose=False)
    log(f"train: fit() {z.train_steps} steps in "
        f"{time.perf_counter() - t0:.1f} s (set-up: includes the compile)")
    losses = [float(v) for v in rec.losses]
    log(f"train: losses {['%.4f' % v for v in losses]}")
    require(len(losses) == z.train_steps, f"expected {z.train_steps} losses")
    require(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall on a repeated batch: {losses}")
    return losses


def serve_leg(ff, z: Sizes, rehearsal):
    import jax
    import jax.numpy as jnp
    import numpy as np

    # FFConfig defaults decide everything but the context length; the
    # rehearsal alone names `pallas`, because off-TPU `auto` is einsum
    kw = {"paged_attention_impl": "pallas"} if rehearsal else {}
    eng = ff.make_serving_engine(max_seq_len=z.max_seq_len, **kw)
    st = eng.stats()
    log(f"serve: slots={eng.slots} page_size={eng.page_size} "
        f"kv_pages={eng.num_pages} decode impl={st['paged_attention_impl']} "
        f"prefill-write impl={st['paged_prefill_impl']}")
    require(st["paged_attention_impl"] == "pallas"
            and st["paged_prefill_impl"] == "pallas",
            f"paged impls must resolve to pallas, got "
            f"{st['paged_attention_impl']}/{st['paged_prefill_impl']}")

    rs = np.random.RandomState(1)
    prompts = [rs.randint(1, z.vocab, (n,)).astype(np.int32)
               for n in z.prompt_lens]
    t0 = time.perf_counter()
    warm = eng.warmup(prompts, max_new_tokens=z.new_tokens)
    log(f"serve: warmup compiled {warm['programs']} programs in "
        f"{time.perf_counter() - t0:.1f} s (set-up)")
    compiles = eng.recompile_count
    reqs = eng.run(prompts, max_new_tokens=z.new_tokens)
    states = [r.state for r in reqs]
    log(f"serve: {len(reqs)} requests, prompt lengths "
        f"{sorted(set(z.prompt_lens))}, states {sorted(set(states))}, "
        f"compiles after warmup {eng.recompile_count - compiles}")
    require(all(s == "done" for s in states), f"request states {states}")
    require(all(len(r.tokens) == z.new_tokens
                and all(0 <= t < z.vocab for t in r.tokens) for r in reqs),
            "a request emitted the wrong number of tokens or one outside "
            "the vocabulary")
    require(eng.recompile_count == compiles,
            f"{eng.recompile_count - compiles} compiles after warmup()")

    # compiled kernel vs einsum oracle on the engine's OWN pool: the K/V
    # the traffic above wrote, read back through page tables over it
    op = eng.gen.attn_ops[0]
    cache = eng.kv.pool[op.name]
    pps = eng.pages_per_slot
    table = rs.permutation(np.arange(1, eng.num_pages))[:eng.slots * pps]
    table = jnp.asarray(table.reshape(eng.slots, pps), jnp.int32)
    length = pps * eng.page_size
    row_len = jnp.asarray(rs.randint(1, length // 2, (eng.slots,)), jnp.int32)
    pad = row_len + 3
    wp = (pad + rs.randint(0, length // 2 - 4, (eng.slots,)))[:, None]
    q = jnp.asarray(rs.randn(eng.slots, 1, op.num_heads, op.qk_head_dim)
                    * 0.5, cache["k"].dtype)
    outs = {impl: jax.block_until_ready(jax.jit(
        functools.partial(op._paged_attention_ctx, impl=impl))(
            q, cache, table, wp.astype(jnp.int32), row_len, pad))
        for impl in ("pallas", "einsum")}
    tol = F32_TOL if rehearsal else BF16_TOL
    err, ok = max_error(outs["pallas"], outs["einsum"], tol)
    log(f"serve: paged kernel vs einsum oracle on the engine's pool "
        f"max_err={err:.3g} tol={tol:.3g}")
    require(ok, f"paged kernel disagrees with the einsum oracle: {err}")
    return eng


def four_chip_leg(z: Sizes, one_chip_loss, rehearsal):
    import jax

    mesh_shape = {"data": 2, "model": 2}
    ff, tokens = build_model(z, 2, mesh_shape, rehearsal, search_budget=2000,
                             enable_parameter_parallel=True)
    sim = ff._search_summary["simulator"]
    log(f"four_chip: strategy from the repo's search, simulator={sim}")
    require(sim == "native",
            "the strategy search did not run the native C++ simulator")
    named = [(op, w, a) for op, ws in ff.params.items()
             for w, a in ws.items()
             if "model" in jax.tree_util.tree_leaves(tuple(a.sharding.spec))]
    require(named, "the search sharded no weight over the `model` axis")
    # the search may also place an op on a block of the mesh, so look for a
    # `model`-sharded weight whose shards span all four chips
    spread = [(op, w, a) for op, w, a in named
              if len({s.device for s in a.addressable_shards}) == 4]
    log(f"four_chip: {len(named)} weights name `model`, {len(spread)} of "
        f"them with shards on 4 distinct devices")
    require(spread, "no `model`-sharded weight has shards on four devices")
    op, w, a = spread[0]
    log(f"four_chip: e.g. {op}.{w} shape={a.shape} spec={a.sharding.spec} "
        f"shards={[(str(s.device), s.data.shape) for s in a.addressable_shards]}")
    losses = train_leg(ff, tokens, z, 2, need_mosaic=False)
    for d in jax.devices()[:4]:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        log(f"four_chip: {d} bytes_in_use={used}")
        require(rehearsal or used > 0, f"{d} holds no bytes")
    rel = abs(losses[0] - one_chip_loss) / abs(one_chip_loss)
    log(f"four_chip: step-1 loss {losses[0]:.4f} vs one chip "
        f"{one_chip_loss:.4f} (rel {rel:.2e}, tol {FOUR_CHIP_LOSS_RTOL})")
    require(rel <= FOUR_CHIP_LOSS_RTOL,
            f"step-1 loss differs from the one-chip leg by {rel:.2e}")


# --------------------------------------------------------------------- main


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny size, kernels in interpret mode; NOT a chip "
                         "result, exits 64 when every phase passes")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    z = REHEARSAL if rehearsal else FULL
    t_start = time.perf_counter()

    with phase("device"):
        set_env = [v for v in REROUTING_ENV if os.environ.get(v)]
        require(rehearsal or not set_env,
                f"{set_env} set: the smoke runs with no kernel rerouting")
        if rehearsal:
            log("CPU REHEARSAL — NOT A CHIP RESULT: tiny size, interpreted "
                "kernels, counts only")
            os.environ["FF_PALLAS_INTERPRET"] = "1"
            os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
        sys.path.insert(0, REPO)
        import jax

        from flexflow_tpu import _env
        from flexflow_tpu.search import cost_db

        if rehearsal:
            _env.force_cpu_devices(4)
        devs = jax.devices()
        device = {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(devs)}
        from importlib import metadata

        versions = {}
        for pkg in ("jax", "jaxlib", "libtpu"):
            try:
                versions[pkg] = metadata.version(pkg)
            except metadata.PackageNotFoundError:
                versions[pkg] = "absent"
        log(f"platform={device['platform']} device_kind={device['kind']} "
            f"devices={device['count']} versions={versions}")
        require(rehearsal or device["platform"] == "tpu",
                f"jax found platform {device['platform']!r}, not a TPU: "
                f"nothing here is a chip result")
        cache_dir = _env.resolve_compilation_cache()
        entries0 = _env.compilation_cache_entries(cache_dir)
        log(f"compile cache {cache_dir}: {entries0} entries at start")
        log(f"cost DB: {cost_db.resolve_path() or 'off'}")

    entries = functools.partial(_env.compilation_cache_entries, cache_dir)
    with phase("build", entries):
        t0 = time.perf_counter()
        ff, tokens = build_model(z, 1, {"data": 1}, rehearsal)
        n_params = sum(int(a.size) for ws in ff.params.values()
                       for a in ws.values())
        log(f"llama_lm hidden={z.hidden} heads={z.heads}x{z.head_dim} "
            f"kv_heads={z.kv_heads} vocab={z.vocab} depth={z.depth} "
            f"params={n_params / 1e6:.0f}M, compile() "
            f"{time.perf_counter() - t0:.1f} s (set-up)")

    with phase("train", entries):
        # interpreted kernels lower to plain HLO: only a chip run can count
        losses = train_leg(ff, tokens, z, 1, need_mosaic=not rehearsal)

    with phase("serve", entries):
        eng = serve_leg(ff, z, rehearsal)

    with phase("kernels", entries):
        run_kernel_sweep(z, rehearsal)

    if len(devs) >= 4:
        with phase("four_chip", entries):
            del eng, ff, tokens
            gc.collect()
            four_chip_leg(z, losses[0], rehearsal)
    else:
        log(f"four_chip leg skipped: {len(devs)} device(s), needs 4")

    stats = devs[0].memory_stats() or {}
    log(f"peak HBM on {devs[0]}: {stats.get('peak_bytes_in_use', 0)} bytes "
        f"of {stats.get('bytes_limit', 0)}")
    entries1 = _env.compilation_cache_entries(cache_dir)
    log(f"compile cache {cache_dir}: {entries0} -> {entries1} entries; "
        f"total {time.perf_counter() - t_start:.1f} s, set-up included")
    if rehearsal:
        log("REHEARSAL PASSED — not a chip result, no verdict printed")
        return REHEARSAL_PASSED
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
