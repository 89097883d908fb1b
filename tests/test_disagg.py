"""Disaggregated prefill/decode fleet + tiered prefix cache (ISSUE 12).

Engine- and router-level integration of the two coupled perf layers:

  * role split — ``prefill`` replicas absorb long-prompt admission and
    hand the finished prompt's KV pages (+ quantized scales, draft pool
    included) to ``decode`` replicas as a serialized page slab; the
    decode-side submit admits as a prefix HIT, so greedy streams stay
    token-identical to a single-replica run, and a dead prefill tier
    degrades to the cold path (the crash drill);
  * tiered cache — ref-0 pages demote to pinned host memory under pool
    pressure and promote back on a trie match, so a prefix working set
    larger than the HBM pool keeps hitting; migrations are bitwise, so
    token identity vs an untiered cold engine holds exactly.

The tier state machine alone is pinned host-only in
tests/test_tiered_prefix.py; the FF_FAULT grammar in tests/test_elastic.
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject

VOCAB = 61
PS = 4


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=2,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def _mixed_prompts(seed, n=8, sys_len=16):
    """Skewed shared-prefix mix: half share a sys_len-token system
    prompt (sys_len/PS full pages), half are distinct background."""
    rs = np.random.RandomState(seed)
    system = rs.randint(1, VOCAB, (sys_len,)).astype(np.int32)
    out = []
    for i in range(n):
        if i % 2 == 0:
            out.append(np.concatenate(
                [system, rs.randint(1, VOCAB, (3,)).astype(np.int32)]))
        else:
            out.append(rs.randint(
                1, VOCAB, (int(rs.randint(5, 12)),)).astype(np.int32))
    return out


def _arm(monkeypatch, spec):
    monkeypatch.setenv("FF_FAULT", spec)
    faultinject.reset()


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


# ---- knobs and validation (host-side, tier-1 fast) ------------------------


def test_config_knobs_and_validation(ff):
    with pytest.raises(ValueError, match="host_kv_pages"):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, host_kv_pages=-1)
    with pytest.raises(ValueError, match="serve_replica_roles"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 serve_replica_roles="prefill,turbo")
    cfg = FFConfig.parse_args(
        ["--host-kv-pages", "64",
         "--serve-replica-roles", "prefill,decode,decode"])
    assert cfg.host_kv_pages == 64
    assert cfg.serve_replica_roles == "prefill,decode,decode"
    # engine-side guards
    with pytest.raises(ValueError, match="host_kv_pages"):
        ff.make_serving_engine(host_kv_pages=-3)
    with pytest.raises(ValueError, match="prefix cache"):
        ff.make_serving_engine(host_kv_pages=8, prefix_cache=False)
    # router-side guards
    with pytest.raises(ValueError, match="one role per replica"):
        ff.make_serving_router(replicas=3, roles=["prefill", "decode"],
                               start=False)
    with pytest.raises(ValueError, match="unknown role"):
        ff.make_serving_router(replicas=2, roles=["prefill", "gpu"],
                               start=False)
    with pytest.raises(ValueError, match="nowhere to decode"):
        ff.make_serving_router(replicas=2,
                               roles=["prefill", "prefill"], start=False)
    with pytest.raises(ValueError, match="handoff_min_pages"):
        ff.make_serving_router(replicas=2, handoff_min_pages=0,
                               start=False)
    router = ff.make_serving_router(replicas=2,
                                    roles="prefill,decode", start=False)
    try:
        assert router.roles == ["prefill", "decode"]
        st = router.stats()
        assert st["roles"] == ["prefill", "decode"]
        assert st["handoffs"] == 0 and st["handoff_fallbacks"] == 0
        assert st["per_replica"][0]["role"] == "prefill"
        fleet = st["fleet"]
        for key in ("prefix_hit_rate", "pages_by_tier", "handoffs",
                    "tier_demotions", "tier_promotions", "per_role",
                    "spec_accept_rate"):
            assert key in fleet, f"fleet rollup missing {key}"
        assert fleet["pages_by_tier"] == {"hbm": 0, "host": 0}
        assert set(fleet["per_role"]) == {"prefill", "decode"}
        assert fleet["per_role"]["prefill"]["replicas"] == 1
    finally:
        router.close()


def test_prefill_only_requires_prefix_cache(ff):
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=48, prefix_cache=False)
    with pytest.raises(RuntimeError, match="prefix cache"):
        eng.prefill_into_cache(np.arange(1, 9, dtype=np.int32))
    assert eng.export_prefix_slab(np.arange(1, 9, dtype=np.int32)) is None
    assert eng.import_prefix_slab({"page_size": PS, "tokens": [],
                                   "payload": []}) == 0


def test_dispatch_skips_saturated_prefill_tier(ff):
    """A saturated prefill tier must not stall the whole fleet: a
    phase-"prefill" queue head that cannot place is skipped, and direct
    work behind it still dispatches to the decode side (FIFO is per
    role tier, not fleet-wide)."""
    router = ff.make_serving_router(
        replicas=2, roles=["prefill", "decode"], serve_slots=2,
        kv_page_size=PS, max_seq_len=48, start=False)
    try:
        # saturate the prefill replica's outstanding ledger to its cap
        for i in range(router._cap):
            router._outstanding[0][10_000 + i] = (None, None)
        long_p = np.arange(1, 20, dtype=np.int32)   # handoff-eligible
        short_p = np.arange(1, 4, dtype=np.int32)   # sub-page: direct
        a = router.submit(long_p, 4)
        b = router.submit(short_p, 4)
        with router._lock:
            router._dispatch_locked()
        assert a.state == "queued" and a.phase == "prefill", \
            "the blocked long prompt must stay queued for the " \
            "prefill tier"
        assert b.state == "dispatched" and b.replica == 1, \
            "direct work behind a blocked prefill head must still flow"
        router._outstanding[0].clear()
    finally:
        router.close()


# ---- engine-level handoff primitives --------------------------------------


def test_slab_roundtrip_bitwise_and_token_identity(ff):
    """prefill_into_cache -> export -> import on a second engine: the
    imported pages are BITWISE the donor's, the subsequent submit admits
    as a hit, and the stream equals a cold engine's run exactly."""
    prompts = _mixed_prompts(21)
    cold = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                  max_seq_len=48)
    want = [list(r.tokens) for r in cold.run(prompts, max_new_tokens=6)]

    donor = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                   max_seq_len=48)
    published = donor.prefill_into_cache(prompts[0])
    assert published == prompts[0].size // PS
    assert donor.stats()["prefill_only_requests"] == 1
    assert donor.stats()["completed"] == 0, \
        "prefill-only admission must not count as a completion"
    slab = donor.export_prefix_slab(prompts[0])
    assert slab is not None and len(slab["payload"]) == published
    # not-fully-cached prefixes refuse to export (caller goes cold)
    assert donor.export_prefix_slab(
        np.arange(1, 9, dtype=np.int32)) is None

    imp = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=48)
    with pytest.raises(ValueError, match="page_size"):
        imp.import_prefix_slab({**slab, "page_size": PS * 2})
    n = imp.import_prefix_slab(slab)
    assert n == published
    st = imp.stats()
    assert st["prefix_slab_imports"] == 1
    assert st["prefix_pages_imported"] == published
    # bitwise pool equality: the imported pages hold the donor's bytes
    donor_path = donor.prefix_cache.match(prompts[0], published)
    imp_path = imp.prefix_cache.match(prompts[0], published)
    for op in donor.gen.attn_ops:
        for dn, im in zip(donor_path, imp_path):
            np.testing.assert_array_equal(
                np.asarray(donor.kv.pool[op.name]["k"][dn.page]),
                np.asarray(imp.kv.pool[op.name]["k"][im.page]))
            np.testing.assert_array_equal(
                np.asarray(donor.kv.pool[op.name]["v"][dn.page]),
                np.asarray(imp.kv.pool[op.name]["v"][im.page]))
    # a second import of the same slab is a no-op (chunks cached)
    assert imp.import_prefix_slab(slab) == 0
    got = [list(r.tokens) for r in imp.run(prompts, max_new_tokens=6)]
    assert got == want, "handoff-imported prefix changed the stream"
    assert imp.stats()["prefix_hits"] >= 1


@pytest.mark.slow  # ~30 s — the
# quantized leg: slabs carry scales, so int8 pages round-trip bitwise
def test_quantized_slab_handoff_is_bitwise(ff):
    prompts = _mixed_prompts(23)
    kw = dict(serve_slots=2, kv_page_size=PS, max_seq_len=48,
              kv_cache_dtype="int8")
    donor = ff.make_serving_engine(**kw)
    ref = ff.make_serving_engine(**kw)
    published = donor.prefill_into_cache(prompts[0])
    assert ref.prefill_into_cache(prompts[0]) == published
    slab = donor.export_prefix_slab(prompts[0])
    assert all("k_scale" in p[("t", k[1])]
               for p in slab["payload"] for k in p if k[0] == "t"), \
        "quantized slabs must carry the per-page scales"
    imp = ff.make_serving_engine(**kw)
    assert imp.import_prefix_slab(slab) == published
    # identity under int8 KV: importer vs a reference engine seeded by
    # the SAME prefill-only primitive (hit-vs-cold is not bitwise under
    # lossy KV, but the handoff moves pages bitwise, so two engines
    # with identical published state stream identically)
    want = [list(r.tokens) for r in ref.run(prompts, max_new_tokens=6)]
    got = [list(r.tokens) for r in imp.run(prompts, max_new_tokens=6)]
    assert got == want
    # and the slab pages landed bitwise, scales included
    dpath = donor.prefix_cache.match(prompts[0], published)
    ipath = imp.prefix_cache.match(prompts[0], published)
    op = donor.gen.attn_ops[0]
    for dn, im in zip(dpath, ipath):
        np.testing.assert_array_equal(
            np.asarray(donor.kv.pool[op.name]["k"][dn.page]),
            np.asarray(imp.kv.pool[op.name]["k"][im.page]))
        np.testing.assert_array_equal(
            np.asarray(donor.kv.pool[op.name]["k_scale"][dn.page]),
            np.asarray(imp.kv.pool[op.name]["k_scale"][im.page]))


def test_import_refuses_dtype_mismatch_and_host_tail(ff):
    """Two slab-import guards: (a) a payload whose storage dtype does
    not match the importer's pool is rejected loudly (import_page casts
    silently — a bf16/f32 slab into an int8 pool would publish
    saturating-cast garbage served as a prefix hit); (b) an import may
    not extend the trie below a host-resident tail (it would break the
    hbm*-then-host* invariant) — it no-ops, and normal admission
    promotes + prefills instead, token-identically."""
    rs = np.random.RandomState(41)
    long_p = rs.randint(1, VOCAB, (4 * PS + 2,)).astype(np.int32)
    cold = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                  max_seq_len=48)
    want = [list(r.tokens) for r in cold.run([long_p], max_new_tokens=5)]
    donor = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                   max_seq_len=48)
    donor.prefill_into_cache(long_p)
    slab_long = donor.export_prefix_slab(long_p)
    slab_short = donor.export_prefix_slab(long_p[:2 * PS])
    # (a) dtype mismatch: full-width slab into an int8 pool
    q = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                               max_seq_len=48, kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        q.import_prefix_slab(slab_long)
    # (b) host-resident tail: import refuses, admission recovers
    imp = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                 max_seq_len=48, host_kv_pages=32)
    assert imp.import_prefix_slab(slab_short) == 2
    with imp._lock:
        imp.kv.make_room(imp.kv.free_pages + 2)
    assert imp.stats()["kv_pages_host"] == 2
    assert imp.import_prefix_slab(slab_long) == 0, \
        "import below a host-resident tail must refuse"
    got = [list(r.tokens) for r in imp.run([long_p], max_new_tokens=5)]
    assert got == want, "the promote-then-prefill fallback diverged"
    assert imp.stats()["tier_promotions"] == 2


# ---- role-split fleet ------------------------------------------------------


@pytest.mark.slow  # ~35 s
def test_role_split_fleet_token_identity_and_handoff(ff):
    """1 prefill + 2 decode: long prompts route through the prefill
    replica (prefill-only, no completions there), hand off as slabs,
    and every stream equals the single-replica run."""
    prompts = _mixed_prompts(25, n=10)
    ref = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=48)
    want = [list(r.tokens) for r in ref.run(prompts, max_new_tokens=6)]

    router = ff.make_serving_router(
        replicas=3, roles=["prefill", "decode", "decode"],
        serve_slots=2, kv_page_size=PS, max_seq_len=48, start=False)
    try:
        router.warmup(prompts[:4], max_new_tokens=2)
        base_done = [e.stats()["completed"] for e in router.engines]
        reqs = router.run(prompts, max_new_tokens=6, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        got = [list(r.tokens) for r in reqs]
        assert got == want, "role split changed a greedy stream"
        st = router.stats()
        assert st["handoffs"] >= 1, "no prompt ever handed off"
        assert any(r.handoff for r in reqs)
        # the prefill replica prefilled but completed NOTHING routed
        eng0 = router.engines[0].stats()
        assert eng0["prefill_only_requests"] >= 1
        assert router.engines[0].stats()["completed"] == base_done[0]
        assert sum(e.stats()["completed"] - b for e, b in
                   zip(router.engines, base_done)) == len(prompts)
        # rollup reflects the handoff ledger
        assert st["fleet"]["handoffs"] == st["handoffs"]
        assert st["fleet"]["prefix_slab_exports"] >= 1
        assert st["fleet"]["prefix_slab_imports"] >= 1
        assert st["fleet"]["per_role"]["decode"]["replicas"] == 2
    finally:
        router.close()


@pytest.mark.slow  # ~35 s — the
# drill: the prefill tier dies mid-handoff, work falls back cold
def test_prefill_replica_crash_cold_path_fallback(ff, monkeypatch):
    prompts = _mixed_prompts(27, n=10)
    ref = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=48)
    want = [list(r.tokens) for r in ref.run(prompts, max_new_tokens=6)]
    router = ff.make_serving_router(
        replicas=3, roles=["prefill", "decode", "decode"],
        serve_slots=2, kv_page_size=PS, max_seq_len=48,
        decode_chunk=2, start=False)
    try:
        router.warmup(prompts[:4], max_new_tokens=2)
        warm_compiles = [e.recompile_count for e in router.engines]
        _arm(monkeypatch, "crash(2)@replica:0")
        reqs = router.run(prompts, max_new_tokens=6, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts), \
            "a prefill-tier death must never strand work"
        assert [list(r.tokens) for r in reqs] == want
        st = router.stats()
        assert st["fenced"] == 1
        assert st["alive"] == 2
        # survivors (decode replicas) compiled NOTHING: the cold-path
        # fallback runs only programs their warmup built
        for r in (1, 2):
            assert router.engines[r].recompile_count \
                == warm_compiles[r], f"survivor {r} recompile leak"
        # exactly-once: engine completions == routed requests
        assert st["completed"] == len(prompts)
        assert all(r.losses <= 1 for r in reqs)
    finally:
        router.close()


# ---- tiered cache, engine-integrated --------------------------------------


def test_tiered_cache_outhits_untired_and_stays_identical(ff):
    """Working set ~3x the pool: the tiered engine demotes instead of
    dying and promotes on re-match — hit where the untiered engine goes
    cold — while staying token-identical to a pressure-free engine."""
    rs = np.random.RandomState(31)
    prompts = [rs.randint(1, VOCAB, (9,)).astype(np.int32)
               for _ in range(6)]
    kw = dict(serve_slots=1, kv_page_size=PS, max_seq_len=32,
              kv_pages=12)
    tiered = ff.make_serving_engine(host_kv_pages=64, **kw)
    untired = ff.make_serving_engine(**kw)
    roomy = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                   max_seq_len=32)
    want = [[list(r.tokens) for r in roomy.run(prompts, max_new_tokens=4)]
            for _ in range(2)]
    got_t = [[list(r.tokens) for r in tiered.run(prompts, max_new_tokens=4)]
             for _ in range(2)]
    got_u = [[list(r.tokens) for r in untired.run(prompts, max_new_tokens=4)]
             for _ in range(2)]
    assert got_t == want and got_u == want, \
        "tier migrations must never change a greedy stream"
    ts, us = tiered.stats(), untired.stats()
    assert ts["tier_demotions"] > 0 and ts["tier_promotions"] > 0
    assert ts["prefix_hits"] > us["prefix_hits"], (
        f"host tier bought no hits: tiered {ts['prefix_hits']} vs "
        f"untiered {us['prefix_hits']}")
    assert ts["kv_pages_host"] > 0
    snap = tiered.drain()
    assert snap["prefix_refs_live"] == 0
    assert snap["tier_pending_migrations"] == 0, \
        "drain must quiesce the ordered publisher"


def test_tier_faults_fall_back_token_identical(ff, monkeypatch):
    rs = np.random.RandomState(33)
    prompts = [rs.randint(1, VOCAB, (9,)).astype(np.int32)
               for _ in range(6)]
    roomy = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                   max_seq_len=32)
    want = [list(r.tokens) for r in roomy.run(prompts, max_new_tokens=4)]
    kw = dict(serve_slots=1, kv_page_size=PS, max_seq_len=32,
              kv_pages=12, host_kv_pages=64)
    _arm(monkeypatch, "d2h_fail@migrate:2,h2d_fail@promote:1")
    eng = ff.make_serving_engine(**kw)
    for _ in range(2):
        got = [list(r.tokens)
               for r in eng.run(prompts, max_new_tokens=4)]
        assert got == want, "a failed migration changed a stream"
    st = eng.stats()
    assert st["tier_demote_failures"] == 1
    assert st["tier_promote_failures"] == 1
    assert st["completed"] == 12 and st["failed"] == 0


# a gotcha learned three times over, now an API contract: warmup() drives
# every (bucket, matched_pages) variant the same traffic will reach
def test_warmup_drives_every_variant_zero_recompiles_after(ff):
    prompts = _mixed_prompts(35, n=8)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=48, kv_pages=48,
                                 host_kv_pages=32)
    info = eng.warmup(prompts, max_new_tokens=6)
    assert info["programs"] > 0 and info["requests"] == 2 * len(prompts)
    assert ("page_import",) in info["variants"], \
        "a tiered engine's warmup must warm the page-import writer"
    rc = eng.recompile_count
    for _ in range(3):
        reqs = eng.run(prompts, max_new_tokens=6)
        assert all(r.state == "done" for r in reqs)
    assert eng.recompile_count == rc, (
        f"{eng.recompile_count - rc} programs compiled after warmup — "
        f"the (bucket, matched_pages) variant sweep missed one")


@pytest.mark.slow  # ~25 s
def test_warmup_learns_interleaved_prefill_variants(ff):
    """ISSUE 18: chunk-interleaved admission adds the prefill_ichunk /
    prefill_ifinal program families. warmup() must drive them too — an
    interleave-enabled engine's timed window compiles nothing."""
    prompts = _mixed_prompts(37, n=8)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=48, kv_pages=48,
                                 prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    info = eng.warmup(prompts, max_new_tokens=6)
    fams = {v[0] for v in info["variants"] if isinstance(v, tuple)}
    assert "prefill_ichunk" in fams and "prefill_ifinal" in fams, \
        f"warmup missed the interleaved prefill programs: {sorted(fams)}"
    rc = eng.recompile_count
    for _ in range(3):
        reqs = eng.run(prompts, max_new_tokens=6)
        assert all(r.state == "done" for r in reqs)
    assert eng.recompile_count == rc, (
        f"{eng.recompile_count - rc} programs compiled after warmup — "
        f"the interleaved chunk sweep missed a (bucket, start) variant")
