"""Multi-tenant serving (ISSUE 14): per-slot sampling + the paged LoRA
adapter pool.

Correctness anchors:
  * the host adapter allocator is a pure state machine: refcounts pin
    resident pages, LRU evicts at refcount 0 only, a pinned-full pool
    refuses (the request waits), geometry is validated at register;
  * the per-slot sampler is counter-based: a draw depends only on
    (seed, stream, token index) — never the slot, the engine key, or
    the other slots — and temperature-0 rows are bitwise argmax;
  * a LoRA adapter served from the pool produces EXACTLY the stream of
    a model whose Linear kernels were merged with a@b*scale (the
    gathered segmented matmul is the merged matmul, distributed);
  * the zero adapter is byte-invisible: base stream, unchanged;
  * N tenants with mixed sampling configs share one engine with ZERO
    recompiles after warmup (the acceptance criterion's pin);
  * the prefix cache never crosses tenants (the trie is namespaced by
    adapter), and eviction under adapter-pool pressure re-faults
    cleanly.
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.ops import sampling as S
from flexflow_tpu.runtime.lora import LoraAdapterPool

VOCAB = 31
RANK = 4


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (L,)).astype(np.int32) for L in lengths]


def _mk_engine(ff, **kw):
    kw.setdefault("serve_slots", 2)
    kw.setdefault("kv_page_size", 4)
    kw.setdefault("max_seq_len", 64)
    return ff.make_serving_engine(**kw)


def _adapter_weights(geometry, seed, scale=0.3, rank=RANK, ops=None):
    rs = np.random.RandomState(seed)
    out = {}
    for name, (din, dout) in geometry.items():
        if ops is not None and name not in ops:
            continue
        out[name] = {"a": (rs.randn(din, rank) * scale).astype(np.float32),
                     "b": (rs.randn(rank, dout) * scale).astype(np.float32)}
    return out


# ---- host allocator state machine (pure, no model) ------------------------


class _FakeOp:
    def __init__(self, name, din, dout):
        self.name, self.in_dim, self.out_dim = name, din, dout


def _mk_pool(pages=2, rank=RANK):
    return LoraAdapterPool(pages, rank,
                           [_FakeOp("l1", 8, 12), _FakeOp("l2", 12, 8)])


def _reg(pool, name, seed=0):
    pool.register(name, _adapter_weights(pool.geometry, seed))


def test_pool_register_validates_geometry():
    pool = _mk_pool()
    with pytest.raises(ValueError, match="not a LoRA-targeted"):
        pool.register("x", {"nope": {"a": np.zeros((8, RANK)),
                                     "b": np.zeros((RANK, 12))}})
    with pytest.raises(ValueError, match="pool geometry"):
        pool.register("x", {"l1": {"a": np.zeros((8, RANK + 1)),
                                   "b": np.zeros((RANK + 1, 12))}})
    with pytest.raises(ValueError, match="non-empty"):
        pool.register("x", {})
    with pytest.raises(KeyError, match="not registered"):
        pool.checkout("ghost")


def test_pool_checkout_release_refcounts_and_hits():
    pool = _mk_pool(pages=2)
    _reg(pool, "a")
    page, ent = pool.checkout("a")          # fault
    assert ent is not None and page in (1, 2)
    p2, ent2 = pool.checkout("a")           # residency hit, same page
    assert p2 == page and ent2 is None
    assert pool.live_refs() == 2 and pool.pages_in_use() == 1
    pool.release("a")
    pool.release("a")
    assert pool.live_refs() == 0
    with pytest.raises(AssertionError, match="underflow"):
        pool.release("a")
    st = pool.stats()
    assert st["adapter_faults"] == 1 and st["adapter_hits"] == 1


def test_pool_lru_eviction_prefers_oldest_ref0():
    pool = _mk_pool(pages=2)
    for n in ("a", "b", "c"):
        _reg(pool, n)
    pa, _ = pool.checkout("a")
    pool.release("a")
    pb, _ = pool.checkout("b")
    pool.release("b")
    # 'a' is the older ref-0 resident: 'c' must take ITS page
    pc, ent = pool.checkout("c")
    assert ent is not None and pc == pa
    assert pool.lookup_page("a") is None
    assert pool.lookup_page("b") == pb
    assert pool.stats()["adapter_evictions"] == 1
    # re-faulting 'a' evicts 'b' (the only ref-0 page left)
    pa2, ent = pool.checkout("a")
    assert ent is not None and pa2 == pb


def test_pool_pinned_full_refuses_and_recovers():
    pool = _mk_pool(pages=1)
    _reg(pool, "a")
    _reg(pool, "b")
    pool.checkout("a")
    assert pool.checkout("b") is None       # pinned full: caller waits
    pool.release("a")
    page, ent = pool.checkout("b")          # eviction unblocks
    assert ent is not None and page == 1


def test_pool_reregister_replaces_unless_pinned():
    pool = _mk_pool(pages=1)
    _reg(pool, "a")
    pool.checkout("a")
    # pinned: swapping weights under a live slot is rejected
    with pytest.raises(ValueError, match="pinned"):
        _reg(pool, "a", seed=9)
    pool.release("a")
    # resident-but-unpinned: replacement drops the device copy, so the
    # next checkout FAULTS the new weights in (never serves stale ones)
    assert pool.lookup_page("a") is not None
    _reg(pool, "a", seed=9)
    assert pool.lookup_page("a") is None
    page, ent = pool.checkout("a")
    assert ent is not None and page == 1
    pool.release("a")


# ---- the per-slot sampler (pure jax) --------------------------------------


def test_sampler_greedy_rows_bitwise_argmax():
    rs = np.random.RandomState(0)
    logits = rs.randn(4, VOCAB).astype(np.float32)
    toks = np.asarray(S.sample_tokens(
        logits, np.zeros(4, np.float32), np.ones(4, np.float32),
        np.zeros(4, np.int32), np.arange(4, dtype=np.int32),
        np.zeros(4, np.int32)))
    np.testing.assert_array_equal(toks, np.argmax(logits, -1))


def test_sampler_slot_invariant_counter_rng():
    """A request's draw depends only on (seed, counter): permuting the
    rows permutes the tokens — nothing leaks across slots."""
    rs = np.random.RandomState(1)
    logits = rs.randn(4, VOCAB).astype(np.float32)
    temps = np.full(4, 0.8, np.float32)
    tps = np.asarray([1.0, 0.9, 0.7, 1.0], np.float32)
    tks = np.asarray([0, 5, 0, 3], np.int32)
    seeds = np.asarray([3, 5, 7, 9], np.int32)
    ctrs = np.asarray([0, 2, 4, 6], np.int32)
    t = np.asarray(S.sample_tokens(logits, temps, tps, tks, seeds, ctrs))
    perm = np.asarray([2, 0, 3, 1])
    t2 = np.asarray(S.sample_tokens(
        logits[perm], temps[perm], tps[perm], tks[perm], seeds[perm],
        ctrs[perm]))
    np.testing.assert_array_equal(t2, t[perm])


def test_sampler_top_k_top_p_masks():
    rs = np.random.RandomState(2)
    logits = rs.randn(3, VOCAB).astype(np.float32)
    # top_k=1 concentrates all mass at argmax
    p = np.asarray(S.sampling_probs(
        logits, np.ones(3, np.float32), np.ones(3, np.float32),
        np.ones(3, np.int32)))
    np.testing.assert_array_equal(np.argmax(p, -1), np.argmax(logits, -1))
    assert np.allclose(p.max(-1), 1.0)
    # top_k=k: exactly k nonzero probs
    k = 5
    pk = np.asarray(S.sampling_probs(
        logits, np.ones(3, np.float32), np.ones(3, np.float32),
        np.full(3, k, np.int32)))
    assert ((pk > 0).sum(-1) == k).all()
    # tiny top_p keeps only the head of the distribution
    pp = np.asarray(S.sampling_probs(
        logits, np.ones(3, np.float32), np.full(3, 1e-6, np.float32),
        np.zeros(3, np.int32)))
    assert ((pp > 0).sum(-1) == 1).all()
    # probabilities always sum to 1
    assert np.allclose(pk.sum(-1), 1.0, atol=1e-5)


# The keep-set, from the module docstring's definition, in plain numpy:
# rank the warped row in descending order with ties by vocabulary index;
# top-k keeps ranks < top_k, top-p keeps the ranked prefix whose
# exclusive cumulative mass is < top_p; rank 0 always survives. The mass
# is summed in f64 here and in f32 (in another order) by the program, so
# a cutoff whose exclusive mass is within _MASS_TOL of top_p may fall on
# either side: the oracle gives the range [m_lo, m_hi] of cutoffs it
# allows, and m_lo == m_hi wherever top-k binds or the margin is wide.
_MASS_TOL = 4e-6


def _oracle_row(row, temp, top_p, top_k):
    w = (row / np.float32(temp if temp > 0 else 1.0)).astype(np.float32)
    order = np.argsort(-w, kind="stable")
    ranks = np.empty(w.size, np.int64)
    ranks[order] = np.arange(w.size)
    e = np.exp(w[order].astype(np.float64) - float(w.max()))
    p = e / e.sum()
    excl = np.cumsum(p) - p
    tp = float(np.float32(top_p))
    m_lo = max(1, int((excl < tp - _MASS_TOL).sum()))
    m_hi = max(1, int((excl < tp + _MASS_TOL).sum()))
    if top_k > 0:
        m_lo, m_hi = min(m_lo, top_k), min(m_hi, top_k)
    return w, ranks, m_lo, m_hi


def _ranked_masked_warped(logits, temps, top_ps, top_ks):
    """The full ranking this sampler used to compute (two argsorts, two
    vocabulary-wide gathers), kept as the reference: (masked, whether
    each row's nucleus was a prefix of the ranking)."""
    import jax
    import jax.numpy as jnp
    safe_t = jnp.where(temps > 0.0, temps, 1.0)[:, None]
    warped = jnp.asarray(logits, jnp.float32) / safe_t
    order = jnp.argsort(-warped, axis=-1)
    ranks = jnp.argsort(order, axis=-1)
    k = jnp.asarray(top_ks, jnp.int32)[:, None]
    keep_k = (k <= 0) | (ranks < k)
    sorted_probs = jnp.take_along_axis(
        jax.nn.softmax(warped, axis=-1), order, axis=-1)
    csum = jnp.cumsum(sorted_probs, axis=-1)
    keep_sorted = (csum - sorted_probs) < jnp.asarray(top_ps)[:, None]
    keep = keep_k & jnp.take_along_axis(keep_sorted, ranks, axis=-1)
    is_prefix = ~jnp.any(keep_sorted[:, 1:] & ~keep_sorted[:, :-1], axis=-1)
    return jnp.where(keep, warped, -jnp.inf), is_prefix


_TOP_KS = (0, 1, 5, 50, 1 << 20)        # the last is >= V for every V
_TOP_PS = (1.0, 0.9, 0.5, 1e-6)
_TEMPS = (0.7, 1.0, 0.0, 1.3, 0.5)


def _sampler_case(kind, vocab):
    """One row per (top_k, top_p) pair, temperatures cycling through
    _TEMPS (0 included): (logits, temps, top_ps, top_ks, seeds, ctrs)."""
    rs = np.random.RandomState(vocab + len(kind))
    n = len(_TOP_KS) * len(_TOP_PS)
    x = rs.randn(n, vocab).astype(np.float32)
    if kind == "bf16":           # bf16-valued logits: 8 bits, many ties
        x = (x.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
    elif kind == "equal":        # every entry ties with every other
        x[:] = np.float32(1.5)
    elif kind == "cutoff-ties":
        # 6 distinct values: every cutoff, top-k or top-p, falls inside
        # a group of tied entries
        x = rs.randint(0, 6, (n, vocab)).astype(np.float32)
    else:
        assert kind == "f32"
    tks, tps = np.meshgrid(_TOP_KS, _TOP_PS, indexing="ij")
    return (x, np.resize(np.asarray(_TEMPS, np.float32), n),
            tps.ravel().astype(np.float32), tks.ravel().astype(np.int32),
            np.arange(n, dtype=np.int32) + 11,
            np.arange(n, dtype=np.int32) * 3)


_SAMPLER_CASES = [(kind, vocab)
                  for kind in ("f32", "bf16", "equal", "cutoff-ties")
                  for vocab in (97, 1000, 92544)]


@pytest.mark.parametrize("kind,vocab", _SAMPLER_CASES)
def test_sampler_keep_set_matches_numpy_oracle(kind, vocab):
    import jax
    x, temps, tps, tks, seeds, ctrs = _sampler_case(kind, vocab)
    masked = np.asarray(S._masked_warped(x, temps, tps, tks))
    want = np.empty_like(masked)
    for i in range(x.shape[0]):
        w, ranks, m_lo, m_hi = _oracle_row(x[i], temps[i], tps[i], tks[i])
        m = int(np.isfinite(masked[i]).sum())
        assert m_lo <= m <= m_hi, (i, temps[i], tps[i], tks[i])
        want[i] = np.where(ranks < m, w, -np.inf)
    # ranks < m exactly, ties by vocabulary index, kept values untouched
    np.testing.assert_array_equal(masked, want)
    probs = np.asarray(S.sampling_probs(x, temps, tps, tks))
    toks = np.asarray(S.sample_tokens(x, temps, tps, tks, seeds, ctrs))
    hot = temps > 0
    np.testing.assert_array_equal(probs[hot] > 0, np.isfinite(want[hot]))
    greedy = np.argmax(x, -1)
    np.testing.assert_array_equal(np.argmax(probs[~hot], -1), greedy[~hot])
    assert ((probs[~hot] > 0).sum(-1) == 1).all()
    draws = np.asarray(jax.vmap(jax.random.categorical)(
        S.slot_keys(seeds, ctrs, S.TAG_TARGET), want))
    np.testing.assert_array_equal(toks, np.where(hot, draws, greedy))


@pytest.mark.parametrize("kind,vocab", _SAMPLER_CASES)
def test_sampler_bitwise_equals_the_full_ranking(kind, vocab):
    """The threshold computation returns the ranking's array bit for bit
    wherever the ranking's nucleus was a prefix (the definition); where
    rounding in the cumulative sum broke that (mass == top_p to the ulp,
    deep in the tail), it keeps as many entries, as a prefix."""
    x, temps, tps, tks, _, _ = _sampler_case(kind, vocab)
    got = np.asarray(S._masked_warped(x, temps, tps, tks))
    ref, is_prefix = map(np.asarray,
                         _ranked_masked_warped(x, temps, tps, tks))
    assert is_prefix.any()
    np.testing.assert_array_equal(got[is_prefix], ref[is_prefix])
    np.testing.assert_array_equal(np.isfinite(got).sum(-1),
                                  np.isfinite(ref).sum(-1))


def test_sampler_program_holds_one_sort_and_no_wide_gather():
    """The ranking cannot come back unnoticed: at the serving cells'
    shape the lowered sampler holds ONE sort (values only) and no gather
    with a vocabulary-wide result (the [B, 1] threshold lookup is the
    only one)."""
    import re
    import jax
    import jax.numpy as jnp
    b, v = 16, 92544
    row = [jax.ShapeDtypeStruct((b,), d) for d in
           (jnp.float32, jnp.float32, jnp.int32, jnp.int32, jnp.int32)]
    text = jax.jit(S.sample_tokens).lower(
        jax.ShapeDtypeStruct((b, v), jnp.float32), *row).as_text()
    sorts = re.findall(r'"?stablehlo\.sort"?\(([^)]*)\)', text)
    assert len(sorts) == 1 and "," not in sorts[0], sorts
    gathers = re.findall(r'"?stablehlo\.gather"?\(.*->\s*tensor<([^>]*)>',
                         text)
    assert gathers, "the threshold lookup is a gather"
    wide = [g for g in gathers
            if int(np.prod([int(d) for d in g.split("x")[:-1]])) >= v]
    assert not wide, wide
    assert "stablehlo.dynamic_gather" not in text


def test_residual_sample_math():
    """q = 0 degenerates to p; a one-hot residual is deterministic."""
    p = np.zeros((2, VOCAB), np.float32)
    q = np.zeros((2, VOCAB), np.float32)
    p[0, 7] = 1.0                       # residual == p: always token 7
    p[1] = 1.0 / VOCAB
    q[1] = p[1].copy()
    q[1, 3] = 0.0                       # residual mass only at 3
    p[1, 3] = 2.0 / VOCAB
    toks = np.asarray(S.residual_sample(
        p, q, np.asarray([1, 2], np.int32), np.asarray([0, 0], np.int32)))
    assert toks[0] == 7
    assert toks[1] == 3


# ---- engine integration ---------------------------------------------------


@pytest.mark.slow  # ~40 s: merged-weights oracle compiles a second model
def test_lora_stream_matches_merged_weights(ff):
    """The pooled gathered-LoRA stream is EXACTLY the stream of a model
    whose Linear kernels were merged with a@b*(alpha/rank) — and the
    zero adapter is byte-invisible."""
    eng = _mk_engine(ff, adapter_pool_pages=2, lora_rank=RANK)
    geo = eng.lora.geometry
    prompts = _prompts(0, [5, 9])
    eng.register_adapter("t0", _adapter_weights(geo, 0))
    zero = {n: {"a": np.zeros((g[0], RANK), np.float32),
                "b": np.zeros((RANK, g[1]), np.float32)}
            for n, g in geo.items()}
    eng.register_adapter("zero", zero)
    base = eng.run(list(prompts), max_new_tokens=6)
    withz = eng.run(list(prompts), max_new_tokens=6, adapter="zero")
    for b, z in zip(base, withz):
        assert b.tokens == z.tokens, "zero adapter must be invisible"
    witht = eng.run(list(prompts), max_new_tokens=6, adapter="t0")

    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    merged = FFModel(cfg)
    _, logits = llama_lm(merged, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    merged.compile(final_tensor=logits)
    # same init seeds -> same base weights; merge the adapter in
    w0 = _adapter_weights(geo, 0)
    for name in geo:
        kern = np.asarray(merged.params[name]["kernel"])
        merged.params[name]["kernel"] = \
            kern + w0[name]["a"] @ w0[name]["b"]
    for r in witht:
        solo = merged.generate(r.prompt[None, :], max_new_tokens=6)
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), solo[0, r.prompt.size:],
            err_msg="pooled LoRA diverged from merged-weight oracle")


def test_eight_tenants_mixed_sampling_zero_recompiles(ff):
    """>= 8 concurrent LoRA tenants with mixed sampling configs on ONE
    engine: zero recompiles after warmup(), per-tenant isolation (each
    greedy tenant's stream matches its solo run), eviction under
    adapter-pool pressure re-faults cleanly."""
    eng = _mk_engine(ff, serve_slots=4, adapter_pool_pages=5,
                     lora_rank=RANK)
    geo = eng.lora.geometry
    names = [f"tenant{i}" for i in range(8)]
    for i, n in enumerate(names):
        eng.register_adapter(n, _adapter_weights(geo, i))
    prompts = _prompts(1, [5, 9, 3, 7])
    eng.warmup(list(prompts))
    # warm one request per tenant so fault-in writes are done too (the
    # writer program itself was compiled at engine construction)
    for n in names:
        eng.run([prompts[0]], max_new_tokens=2, adapter=n)
    warm = eng.recompile_count
    reqs = []
    for i, n in enumerate(names):
        reqs.append(eng.submit(prompts[i % len(prompts)], 6, adapter=n,
                               temperature=(0.0 if i % 2 == 0 else 0.9),
                               top_p=(1.0 if i % 3 else 0.9),
                               top_k=(0 if i % 2 else 5), seed=100 + i))
    while eng.step():
        pass
    assert [r.state for r in reqs] == ["done"] * 8
    assert eng.recompile_count == warm, \
        "mixed tenants/sampling configs must not recompile warm programs"
    st = eng.stats()
    assert st["adapter_evictions"] >= 1, \
        "8 tenants through 5 pages must exercise the LRU"
    assert st["adapter_refs_live"] == 0
    assert st["sampled_requests"] >= 4
    # greedy tenants are reproducible: re-run tenant0's request solo
    again = eng.run([prompts[0]], max_new_tokens=6,
                    adapter=names[0], temperature=0.0)[0]
    assert again.tokens == reqs[0].tokens
    assert eng.recompile_count == warm
    # every tenant has its own labeled series in the scrape
    from flexflow_tpu.runtime import telemetry

    text = telemetry.registry().to_prometheus()
    assert "ff_serving_requests_total" in text
    assert "ff_serving_adapter_ttft_seconds" in text
    assert not [n for n in names if f'adapter="{n}"' not in text]


def test_sampled_slot_steps_counts_dispatched_sampled_slots(ff):
    """stats()["sampled_slot_steps"]: live slots with temperature > 0 x
    the steps of each decode dispatch, the same count the dispatch's
    span carries; greedy traffic leaves it at 0."""
    from flexflow_tpu.runtime import telemetry
    k = 2
    eng = _mk_engine(ff, serve_slots=4, decode_chunk=k)
    prompts = _prompts(9, [5, 9, 3, 7])
    eng.run(prompts, max_new_tokens=7)
    assert eng.stats()["sampled_slot_steps"] == 0
    assert eng.stats()["occupied_slot_steps"] > 0
    since = telemetry.now_us()
    reqs = [eng.submit(p, 7, temperature=t, top_p=0.9, top_k=5, seed=i)
            for i, (p, t) in enumerate(zip(prompts, (0.8, 0.0, 1.2, 0.0)))]
    while eng.step():
        pass
    assert [r.state for r in reqs] == ["done"] * 4
    # a request's first token comes from its prefill; the others from
    # ceil((tokens - 1) / k) dispatches of k steps, each counted whole
    want = sum(-(-(len(r.tokens) - 1) // k) * k
               for r in reqs if r.temperature > 0)
    st = eng.stats()
    assert st["sampled_slot_steps"] == want > 0
    disp = [e["args"] for e in
            telemetry.tracer().events(name="decode_dispatch")
            if e["pid"] == eng._tm_track and e["ts"] >= since]
    assert sum(d["sampled"] * d["k"] for d in disp) == want


def test_adapter_prefix_cache_isolation(ff):
    """The radix trie is namespaced per adapter: the same prompt under
    two tenants never shares prefix pages (their KV differs), while the
    same tenant hits its own cache."""
    eng = _mk_engine(ff, adapter_pool_pages=2, lora_rank=RANK)
    geo = eng.lora.geometry
    eng.register_adapter("x", _adapter_weights(geo, 3))
    eng.register_adapter("y", _adapter_weights(geo, 4))
    long = _prompts(5, [13])[0]
    h0 = eng.stats()["prefix_hits"]
    eng.run([long], max_new_tokens=3, adapter="x")
    eng.run([long], max_new_tokens=3, adapter="x")
    h1 = eng.stats()["prefix_hits"]
    assert h1 > h0, "same tenant must hit its own prefix"
    eng.run([long], max_new_tokens=3, adapter="y")
    assert eng.stats()["prefix_hits"] == h1, \
        "tenant y must NOT hit tenant x's pages"
    eng.run([long], max_new_tokens=3)   # base model: its own namespace
    assert eng.stats()["prefix_hits"] == h1


def test_reregister_flushes_stale_namespace_kv(ff):
    """Replacing an adapter's weights must flush its prefix-cache
    namespace: KV cached under the OLD weights serving a hit for the
    NEW ones would splice two weight versions into one stream. The
    post-replacement stream must equal a fresh engine's cold stream
    under the new weights."""
    eng = _mk_engine(ff, adapter_pool_pages=2, lora_rank=RANK)
    geo = eng.lora.geometry
    long = _prompts(7, [13])[0]
    eng.register_adapter("t", _adapter_weights(geo, 0))
    eng.run([long], max_new_tokens=4, adapter="t")  # publishes ns pages
    assert eng.stats()["kv_pages_cached"] > 0
    free0 = eng.stats()["free_pages"]
    eng.register_adapter("t", _adapter_weights(geo, 8))  # REPLACE
    assert eng.stats()["free_pages"] > free0, \
        "replacement must flush the namespace's cached pages"
    got = eng.run([long], max_new_tokens=4, adapter="t")[0]
    cold = _mk_engine(ff, adapter_pool_pages=2, lora_rank=RANK)
    cold.register_adapter("t", _adapter_weights(geo, 8))
    want = cold.run([long], max_new_tokens=4, adapter="t")[0]
    assert got.tokens == want.tokens, \
        "stale namespaced KV leaked across an adapter replacement"


def test_router_register_prevalidates_across_fleet(ff):
    """A fleet-wide adapter replacement must mutate NOTHING when any
    replica still has live slots pinned to it — a partial fan-out would
    serve two weight versions under one name."""
    router = ff.make_serving_router(replicas=2, start=False,
                                    serve_slots=2, kv_page_size=4,
                                    max_seq_len=64, adapter_pool_pages=2,
                                    lora_rank=RANK)
    try:
        geo = router.engines[0].lora.geometry
        w1 = _adapter_weights(geo, 0)
        router.register_adapter("t", w1)
        # pin the adapter on replica 1 only (simulates in-flight work)
        router.engines[1].lora.checkout("t")
        w2 = _adapter_weights(geo, 9)
        with pytest.raises(ValueError, match="pinned.*replica"):
            router.register_adapter("t", w2)
        # NOTHING changed anywhere: both replicas still serve w1
        for eng in router.engines:
            np.testing.assert_array_equal(
                eng.lora.registry["t"]["payload"][next(iter(geo))]["a"],
                w1[next(iter(geo))]["a"])
        router.engines[1].lora.release("t")
        router.register_adapter("t", w2)    # unpinned: replaces fleet-wide
        for eng in router.engines:
            np.testing.assert_array_equal(
                eng.lora.registry["t"]["payload"][next(iter(geo))]["a"],
                w2[next(iter(geo))]["a"])
    finally:
        router.close()


def test_submit_validation_and_stats_keys(ff):
    eng = _mk_engine(ff)
    p = _prompts(6, [5])[0]
    with pytest.raises(ValueError, match="temperature"):
        eng.submit(p, 4, temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        eng.submit(p, 4, top_p=1.5)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(p, 4, top_k=-1)
    with pytest.raises(ValueError, match="no adapter pool"):
        eng.submit(p, 4, adapter="x")
    eng2 = _mk_engine(ff, adapter_pool_pages=1)
    with pytest.raises(ValueError, match="not registered"):
        eng2.submit(p, 4, adapter="ghost")
    with pytest.raises(RuntimeError, match="no adapter pool"):
        eng.register_adapter("x", {})
    # adapter-pool + sampling stats keys are pinned (PR-13 superset
    # discipline: scrape collectors export every numeric key)
    st = eng2.stats()
    for key in ("adapter_pool_pages", "adapters_registered",
                "adapters_resident", "adapter_pages_in_use",
                "adapter_pool_occupancy", "adapter_lookups",
                "adapter_hits", "adapter_faults", "adapter_evictions",
                "adapter_refs_live", "sampled_requests", "lora_rank",
                "serve_temperature", "serve_top_p", "serve_top_k",
                "spec_accept_by_adapter", "requests_by_adapter"):
        assert key in st, f"stats() lost pinned key {key}"
    assert st["adapter_pool_pages"] == 1


def test_config_knobs_validation_and_flags():
    """FFConfig guards + parse_args flags (ISSUE 14 satellite)."""
    with pytest.raises(ValueError, match="serve_temperature"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 serve_temperature=-0.1)
    with pytest.raises(ValueError, match="serve_top_p"):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_top_p=0.0)
    with pytest.raises(ValueError, match="serve_top_p"):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_top_p=1.2)
    with pytest.raises(ValueError, match="serve_top_k"):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_top_k=-1)
    with pytest.raises(ValueError, match="serve_adapter_pool_pages"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 serve_adapter_pool_pages=-1)
    with pytest.raises(ValueError, match="serve_lora_rank"):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_lora_rank=0)
    cfg = FFConfig.parse_args([
        "--batch-size", "2", "--serve-temperature", "0.7",
        "--serve-top-p", "0.9", "--serve-top-k", "40",
        "--serve-adapter-pool-pages", "16", "--serve-lora-rank", "4"])
    assert cfg.serve_temperature == 0.7 and cfg.serve_top_p == 0.9
    assert cfg.serve_top_k == 40
    assert cfg.serve_adapter_pool_pages == 16 and cfg.serve_lora_rank == 4
    dflt = FFConfig.parse_args(["--batch-size", "2"])
    assert dflt.serve_temperature == 0.0 and dflt.serve_top_p == 1.0
    assert dflt.serve_adapter_pool_pages == 0
