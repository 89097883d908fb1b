"""Quantized serving tier (ISSUE 11): int8/fp8 KV pages with in-kernel
dequant + weight-only int8/fp8 serving matmuls.

Correctness anchors:
  * scale round-trip — per-(page, head) quantization error is bounded by
    scale/2 (int8), and REQUANTIZATION at an unchanged scale is exact:
    the append path's unconditional page requant cannot drift tokens
    whose page scale never grew;
  * per-OUTPUT-CHANNEL weight scales are strictly no worse than a
    per-tensor baseline on every zoo layer they quantize (the satellite
    regression pin);
  * pallas-vs-einsum parity on quantized pools: pool state BITWISE
    (the write/requant protocol is shared code), attention to kernel
    tolerance, greedy engine streams token-IDENTICAL with prefix cache
    + speculation + the kernel path all live;
  * copy-on-write survives quantization: a donor's published pages —
    payload AND scales — are bitwise untouched by borrower traffic;
  * quantized engines stay on the one-program contract (recompile
    flatness) and expose the capacity observability keys;
  * full-width divergence budget: quantized KV/weights are lossy by
    design — the budget pinned here is the documented per-dtype bar
    (docs/serving.md "Quantized tier"), not token identity.

All quantized paths run on CPU: the Pallas kernel in interpret mode is
the REAL kernel code path (the ISSUE-7 routing rule).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.ops.attention import (kv_storage_dtype, page_dequantize,
                                        page_quantize, page_scale,
                                        storage_qmax)
from flexflow_tpu.runtime.generation import Generator

VOCAB = 89
TOL = dict(rtol=2e-5, atol=2e-5)
# documented per-dtype divergence budgets vs the full-width path: the
# minimum fraction of greedy positions that must match, measured over
# short mixed streams on the tiny zoo model (deterministic at a pinned
# seed — this is a regression bar, not a statistical test). See
# docs/serving.md "Quantized tier" for the budget rationale.
DIVERGENCE_BUDGET = {"int8": 0.6, "fp8": 0.6}

HAS_FP8 = getattr(jnp, "float8_e4m3fn", None) is not None


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    # kv_heads=2 < heads=4: GQA grouping always exercised
    _, logits = llama_lm(model, 2, seq_len=16, hidden=64, layers=2,
                         heads=4, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


@pytest.fixture(scope="module")
def attn(ff):
    return next(op for op in ff.ops
                if type(op).__name__ == "MultiHeadAttention")


# ---- knobs & helpers -------------------------------------------------------


def test_config_validation_and_flags():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 kv_cache_dtype="int4")
    with pytest.raises(ValueError, match="serve_weight_dtype"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 serve_weight_dtype="bf16")
    cfg = FFConfig.parse_args(["--kv-cache-dtype", "int8",
                               "--serve-weight-dtype", "fp8"])
    assert cfg.kv_cache_dtype == "int8"
    assert cfg.serve_weight_dtype == "fp8"
    # defaults keep the pre-quant behavior
    assert FFConfig.parse_args([]).kv_cache_dtype == "native"
    assert FFConfig.parse_args([]).serve_weight_dtype == "native"


def test_kv_storage_dtype_mapping():
    assert kv_storage_dtype(None) == (None, None)
    assert kv_storage_dtype("native") == (None, None)
    sd, qm = kv_storage_dtype("bf16")
    assert sd == jnp.bfloat16 and qm is None
    sd, qm = kv_storage_dtype("int8")
    assert sd == jnp.int8 and qm == 127.0
    if HAS_FP8:
        sd, qm = kv_storage_dtype("fp8")
        assert sd == jnp.float8_e4m3fn
        assert qm == float(jnp.finfo(jnp.float8_e4m3fn).max)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        kv_storage_dtype("int4")
    assert storage_qmax(jnp.int8) == 127.0


def test_scale_round_trip_and_same_scale_requant_exact():
    """int8: |dequant(quant(x)) - x| <= scale/2 per element; and the
    append-path invariant — requantizing at an UNCHANGED scale is the
    identity on the stored payload, for int8 AND fp8."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(3, 8, 2, 16) * 5.0, jnp.float32)
    for dt in ("int8",) + (("fp8",) if HAS_FP8 else ()):
        sdtype, qmax = kv_storage_dtype(dt)
        sc = page_scale(x, qmax)                       # (3, 2)
        q = page_quantize(x, sc, qmax, sdtype)
        deq = page_dequantize(q, sc)
        if dt == "int8":
            bound = np.asarray(sc)[:, None, :, None] / 2 + 1e-6
            assert (np.abs(np.asarray(deq - x)) <= bound).all()
        # same-scale requant: bitwise identity on the payload
        q2 = page_quantize(deq, sc, qmax, sdtype)
        np.testing.assert_array_equal(np.asarray(q).view(np.uint8),
                                      np.asarray(q2).view(np.uint8))


def test_per_channel_no_worse_than_per_tensor(ff):
    """The satellite regression pin: per-output-channel weight scales
    must give a max-abs dequant error STRICTLY no worse than a
    per-tensor scale on every zoo layer the quantizer touches — and on
    3-D attention weights (per-head channels) strictly better
    somewhere, or the upgrade did nothing."""
    gen = Generator(ff, quantize="int8")
    qp = gen._quantized_params()
    checked = strict_win = 0
    for op_name, ws in ff.params.items():
        for w_name, w in ws.items():
            if not (w.ndim >= 2 and jnp.issubdtype(w.dtype, jnp.floating)):
                continue
            wf = np.asarray(w, np.float32)
            entry = qp[op_name][w_name]
            deq = np.asarray(entry["q"], np.float32) * np.asarray(entry["s"])
            err_channel = np.abs(deq - wf).max()
            s_tensor = max(np.abs(wf).max() / 127.0, 1e-12)
            q_t = np.clip(np.round(wf / s_tensor), -127, 127)
            err_tensor = np.abs(q_t * s_tensor - wf).max()
            assert err_channel <= err_tensor + 1e-9, (
                f"{op_name}/{w_name}: per-channel err {err_channel} > "
                f"per-tensor {err_tensor}")
            checked += 1
            if err_channel < err_tensor * 0.999:
                strict_win += 1
    assert checked >= 4, "the zoo model must expose quantizable layers"
    assert strict_win >= 1, \
        "per-channel scales never beat per-tensor anywhere"


@pytest.mark.skipif(not HAS_FP8, reason="jax build lacks float8_e4m3fn")
def test_fp8_weight_quantization_finite():
    """fp8 weight-only: quantized tree stores float8_e4m3fn with finite
    payload (overflow would cast to nan — the clip-before-cast rule)."""
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=8, hidden=32, layers=1, heads=2,
                         kv_heads=2, vocab_size=37)
    ff.compile(final_tensor=logits)
    gen = Generator(ff, quantize="fp8")
    qp = gen._quantized_params()
    seen = 0
    for ws in qp.values():
        for v in ws.values():
            if isinstance(v, dict) and "q" in v:
                assert v["q"].dtype == jnp.float8_e4m3fn
                assert bool(jnp.isfinite(
                    v["q"].astype(jnp.float32)).all())
                seen += 1
    assert seen >= 4
    with pytest.raises(ValueError, match="quantize"):
        Generator(ff, quantize="int4")


# ---- pool write protocol ---------------------------------------------------


def test_prefill_write_sets_scales_pad_tail_harmless(attn):
    """paged_prefill_write on a quantized pool: per-(page, head) scales
    land next to the payload, and the zero pad tail of the last page
    never inflates a scale (the amax is the real tokens')."""
    rs = np.random.RandomState(5)
    pool = attn.init_paged_cache(6, 4, jnp.float32, kv_dtype="int8")
    kh = jnp.asarray(rs.randn(1, 6, 2, 16), jnp.float32)   # 1.5 pages
    vh = jnp.asarray(rs.randn(1, 6, 2, 16), jnp.float32)
    out = attn.paged_prefill_write(pool, kh, vh, jnp.asarray([2, 4],
                                                            jnp.int32))
    assert out["k"].dtype == jnp.int8
    # page 4 holds tokens 4..5 + 2 pad zeros: its scale is the amax of
    # the REAL tokens only
    want = np.abs(np.asarray(kh[0, 4:6], np.float32)).max(axis=(0, 2)) / 127
    np.testing.assert_allclose(np.asarray(out["k_scale"][4]), want,
                               rtol=1e-6)
    # untouched pages keep scale 0 (nothing cached there yet)
    assert float(out["k_scale"][1].max()) == 0.0


def test_append_requant_exact_when_scale_unchanged(attn):
    """Appending a token SMALLER than the page's running max must leave
    every previously stored element bitwise unchanged — the same-scale
    requant exactness the protocol relies on (a growing scale re-rounds,
    which is the documented divergence budget, not silent drift)."""
    rs = np.random.RandomState(7)
    pool = attn.init_paged_cache(4, 4, jnp.float32, kv_dtype="int8")
    big = jnp.asarray(rs.randn(1, 4, 2, 16) * 8.0, jnp.float32)
    pool = attn.paged_prefill_write(pool, big, big,
                                    jnp.asarray([1], jnp.int32))
    before_k = np.asarray(pool["k"][1]).copy()
    small = jnp.asarray(rs.randn(1, 2, 16) * 0.1, jnp.float32)
    out = attn._paged_append(pool, small[0][None], small[0][None],
                             jnp.asarray([1], jnp.int32),
                             jnp.asarray([2], jnp.int32))
    after_k = np.asarray(out["k"][1])
    # positions 0, 1, 3 never re-round; position 2 holds the new token
    for pos in (0, 1, 3):
        np.testing.assert_array_equal(before_k[pos], after_k[pos])
    np.testing.assert_array_equal(np.asarray(pool["k_scale"][1]),
                                  np.asarray(out["k_scale"][1]))


def test_quantized_decode_and_verify_pallas_matches_einsum(ff, attn):
    """Kernel parity on a quantized pool: the in-kernel dequant against
    scalar-prefetched scales must match the dequantizing einsum gather
    (the oracle) to kernel tolerance; the write/requant halves are
    shared code, so the returned pools must be BITWISE equal."""
    rs = np.random.RandomState(11)
    params = {k: jnp.asarray(v) for k, v in ff.params[attn.name].items()}
    for dt in ("int8",) + (("fp8",) if HAS_FP8 else ()):
        pool = attn.init_paged_cache(10, 4, jnp.float32, kv_dtype=dt)
        kh = jnp.asarray(rs.randn(1, 14, 2, 16), jnp.float32)
        vh = jnp.asarray(rs.randn(1, 14, 2, 16), jnp.float32)
        pool = attn.paged_prefill_write(
            pool, kh, vh, jnp.asarray([5, 2, 7, 1], jnp.int32))
        table = jnp.asarray([[5, 2, 7, 1], [3, 6, 4, 8]], jnp.int32)
        x = jnp.asarray(rs.randn(2, 1, attn.q_in), jnp.float32)
        wp = jnp.asarray([9, 13], jnp.int32)
        rope = jnp.asarray([4, 7], jnp.int32)
        rl = jnp.asarray([3, 7], jnp.int32)
        pad = jnp.asarray([8, 8], jnp.int32)
        oe, ce = attn.paged_decode_forward(
            params, [x, x, x], pool, table, wp, rope, rl, pad,
            impl="einsum")
        op_, cp = attn.paged_decode_forward(
            params, [x, x, x], pool, table, wp, rope, rl, pad,
            impl="pallas")
        np.testing.assert_allclose(np.asarray(oe), np.asarray(op_), **TOL)
        for n in ce:
            np.testing.assert_array_equal(np.asarray(ce[n]),
                                          np.asarray(cp[n]),
                                          err_msg=f"{dt}/{n}")
        # verify slab (per-position frontiers + sequential appends)
        s = 3
        xs_ = jnp.asarray(rs.randn(2, s, attn.q_in), jnp.float32)
        wps = jnp.minimum(
            jnp.asarray([9, 11], jnp.int32)[:, None]
            + jnp.arange(s)[None, :], 13)
        ve, cve = attn.paged_verify_forward(
            params, [xs_, xs_, xs_], pool, table, wps, rope, rl, pad,
            impl="einsum")
        vp, cvp = attn.paged_verify_forward(
            params, [xs_, xs_, xs_], pool, table, wps, rope, rl, pad,
            impl="pallas")
        np.testing.assert_allclose(np.asarray(ve), np.asarray(vp), **TOL)
        for n in cve:
            np.testing.assert_array_equal(np.asarray(cve[n]),
                                          np.asarray(cvp[n]),
                                          err_msg=f"{dt}/verify/{n}")


# ---- engine-level contracts ------------------------------------------------


@pytest.mark.slow  # ~40 s: two engines
def test_engine_token_identity_pallas_vs_einsum_quantized(ff):
    """THE parity pin: a greedy serving run on an int8 pool with int8
    weights, prefix cache ON and speculation ON emits exactly the same
    streams under impl='pallas' (interpret-mode kernel) and
    impl='einsum' — quantization changes numbers, never the
    pallas/einsum contract."""
    rs = np.random.RandomState(17)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (L,)).astype(np.int32)])
               for L in (2, 5, 1, 4)] \
        + [rs.randint(1, VOCAB, (6,)).astype(np.int32)]
    outs = {}
    for impl in ("einsum", "pallas"):
        eng = ff.make_serving_engine(
            serve_slots=2, kv_page_size=4, max_seq_len=64,
            kv_cache_dtype="int8", weight_dtype="int8",
            draft_model=ff, speculate_k=2, paged_attention_impl=impl)
        reqs = eng.run(prompts, max_new_tokens=5)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        outs[impl] = [np.asarray(r.tokens, np.int32) for r in reqs]
        st = eng.stats()
        assert st["kv_cache_dtype"] == "int8"
        assert st["weight_dtype"] == "int8"
        assert st["prefix_hits"] > 0 and st["spec_accepted"] > 0
    for a, b in zip(outs["einsum"], outs["pallas"]):
        np.testing.assert_array_equal(
            a, b, err_msg="quantized pallas serving changed the greedy "
                          "stream vs the einsum oracle")


@pytest.mark.slow  # ~35 s
def test_divergence_budget_vs_full_width(ff):
    """Quantized KV (+ weights) is lossy by design: greedy streams may
    diverge from the full-width path. The documented per-dtype budget
    (DIVERGENCE_BUDGET) is the floor on positionwise agreement over a
    pinned mixed workload — deterministic at this seed, so a numerics
    regression (not mere divergence) trips it."""
    rs = np.random.RandomState(23)
    prompts = [rs.randint(1, VOCAB, (int(n),)).astype(np.int32)
               for n in (6, 11, 3, 9)]
    ref = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64)
    want = [np.asarray(r.tokens, np.int32)
            for r in ref.run(prompts, max_new_tokens=6)]
    dtypes = ["int8"] + (["fp8"] if HAS_FP8 else [])
    for dt in dtypes:
        eng = ff.make_serving_engine(
            serve_slots=2, kv_page_size=4, max_seq_len=64,
            kv_cache_dtype=dt, weight_dtype=dt,
            paged_attention_impl="pallas")
        reqs = eng.run(prompts, max_new_tokens=6)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        got = [np.asarray(r.tokens, np.int32) for r in reqs]
        agree = float(np.mean([np.mean(a == b)
                               for a, b in zip(want, got)]))
        assert agree >= DIVERGENCE_BUDGET[dt], (
            f"{dt}: greedy agreement {agree:.3f} below the documented "
            f"budget {DIVERGENCE_BUDGET[dt]}")


@pytest.mark.slow  # ~15 s
def test_cow_isolation_quantized(ff):
    """Copy-on-write survives quantization: borrowers mounting a cached
    prefix write tails/decodes into their OWN pages — the donor's
    published pages are bitwise untouched in payload AND scales."""
    rs = np.random.RandomState(29)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (L,)).astype(np.int32)])
               for L in (2, 6, 4)]
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, kv_cache_dtype="int8")
    eng.run([prompts[0]], max_new_tokens=4)      # publish the prefix
    pc = eng.prefix_cache
    shared = []
    node = pc.root
    while node.children:
        node = next(iter(node.children.values()))
        shared.append(node.page)
    assert len(shared) >= 2
    shared = np.asarray(shared, np.int32)
    names = ("k", "v", "k_scale", "v_scale")
    before = {op.name: {n: np.asarray(eng.kv.pool[op.name][n][shared]).copy()
                        for n in names}
              for op in eng.gen.attn_ops}
    reqs = eng.run(prompts[1:], max_new_tokens=4)
    for r in reqs:
        assert r.state == "done" and r.prefix_tokens >= 8
    for op in eng.gen.attn_ops:
        for n in names:
            np.testing.assert_array_equal(
                before[op.name][n],
                np.asarray(eng.kv.pool[op.name][n][shared]),
                err_msg=f"shared quantized page of {op.name}/{n} was "
                        f"written in place (COW violated)")
    st = eng.stats()
    assert st["kv_pages_shared"] == 0  # all retired
    assert st["prefix_refs_live"] == 0


def test_recompile_flat_quantized(ff):
    """The one-program contract survives the quantized tier: after
    bucket warmup, mixed same-bucket traffic on an int8 pool with int8
    weights compiles nothing new (weights quantized once at init)."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, kv_cache_dtype="int8",
                                 weight_dtype="int8",
                                 paged_attention_impl="pallas")
    rs = np.random.RandomState(31)
    eng.run([rs.randint(1, VOCAB, (5,)).astype(np.int32),
             rs.randint(1, VOCAB, (12,)).astype(np.int32)],
            max_new_tokens=4)                     # warm buckets 8 + 16
    warm = eng.recompile_count
    eng.run([rs.randint(1, VOCAB, (n,)).astype(np.int32)
             for n in (6, 3, 9, 14, 2)], max_new_tokens=6)
    assert eng.recompile_count == warm, \
        "warm quantized traffic must not recompile"


def test_prefix_sharing_is_blind_to_the_pool_dtype(ff):
    """The radix trie, the COW rule and the allocator never look inside a
    page: the same skewed shared-prefix traffic finds the same hits on a
    bf16 pool and on an int8 pool with int8 weights, and both leave the
    pool whole after drain and flush."""
    rs = np.random.RandomState(41)
    system = rs.randint(1, VOCAB, (12,)).astype(np.int32)   # 3 full pages
    prompts = [np.concatenate([system, rs.randint(
        1, VOCAB, (n,)).astype(np.int32)]) for n in (2, 5, 1, 4, 3)] \
        + [rs.randint(1, VOCAB, (7,)).astype(np.int32)]
    seen = []
    for kw in (dict(kv_cache_dtype="bf16"),
               dict(kv_cache_dtype="int8", weight_dtype="int8")):
        eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                     max_seq_len=64, **kw)
        reqs = eng.run(prompts, max_new_tokens=3)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        st = eng.drain()
        assert st["prefix_refs_live"] == 0
        assert st["free_pages"] + st["kv_pages_cached"] \
            == st["kv_pages"] - 1
        seen.append((st["prefix_hits"], st["prefix_lookups"]))
        eng.flush_prefix_cache()
        assert eng.stats()["free_pages"] == st["kv_pages"] - 1
    assert seen[0] == seen[1] and seen[0][0] >= 4, seen


def test_stats_observability(ff):
    """The router's signals: dtypes, bytes-per-token (scales
    included), tokens-per-pool-GB and the capacity multiplier — and the
    bf16 pool halves an f32 pool without any scale machinery."""
    e8 = ff.make_serving_engine(serve_slots=1, kv_page_size=8,
                                max_seq_len=32, kv_cache_dtype="int8")
    ebf = ff.make_serving_engine(serve_slots=1, kv_page_size=8,
                                 max_seq_len=32, kv_cache_dtype="bf16")
    enat = ff.make_serving_engine(serve_slots=1, kv_page_size=8,
                                  max_seq_len=32)
    s8, sbf, snat = e8.stats(), ebf.stats(), enat.stats()
    assert s8["kv_cache_dtype"] == "int8"
    assert sbf["kv_cache_dtype"] == "bfloat16"
    assert snat["kv_cache_dtype"] == "float32"
    assert s8["weight_dtype"] == "native"
    # f32 native -> bf16 is exactly 2x; bf16 -> int8 is ~2x minus the
    # scale sliver (per-page-per-head f32 scales)
    assert snat["kv_bytes_per_token"] == 2 * sbf["kv_bytes_per_token"]
    assert 1.7 < sbf["kv_bytes_per_token"] / s8["kv_bytes_per_token"] <= 2
    assert s8["tokens_per_pool_gb"] > 1.7 * sbf["tokens_per_pool_gb"]
    assert s8["kv_capacity_vs_bf16"] > 1.7
    assert sbf["kv_capacity_vs_bf16"] == 1.0
    assert s8["kv_effective_page_capacity"] > 8  # > page_size tokens
    assert s8["kv_pool_bytes"] < sbf["kv_pool_bytes"] \
        < snat["kv_pool_bytes"]
    h = e8.health()
    assert h["kv_cache_dtype"] == "int8" and h["weight_dtype"] == "native"
    assert h["tokens_per_pool_gb"] == s8["tokens_per_pool_gb"]


def test_weight_dtype_conflict_and_validation(ff):
    with pytest.raises(ValueError, match="weight_dtype"):
        ff.make_serving_engine(weight_dtype="int4", max_seq_len=32)
    with pytest.raises(ValueError, match="conflicts"):
        ff.make_serving_engine(weight_dtype="int8", quantize="fp8",
                               max_seq_len=32)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        ff.make_serving_engine(kv_cache_dtype="int4", max_seq_len=32)
    # legacy quantize= keeps working and is reported as the weight dtype
    eng = ff.make_serving_engine(serve_slots=1, kv_page_size=8,
                                 max_seq_len=32, quantize="int8")
    assert eng.stats()["weight_dtype"] == "int8"


@pytest.mark.slow  # ~15 s
def test_bf16_pool_serves(ff):
    """kv_cache_dtype='bf16' under f32 compute: a plain-cast pool (no
    scales) that halves pool bytes; streams complete and the pool
    really stores bfloat16."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, kv_cache_dtype="bf16",
                                 paged_attention_impl="pallas")
    rs = np.random.RandomState(37)
    reqs = eng.run([rs.randint(1, VOCAB, (n,)).astype(np.int32)
                    for n in (5, 9, 3)], max_new_tokens=5)
    assert [r.state for r in reqs] == ["done"] * 3
    for op in eng.gen.attn_ops:
        assert eng.kv.pool[op.name]["k"].dtype == jnp.bfloat16
        assert "k_scale" not in eng.kv.pool[op.name]
