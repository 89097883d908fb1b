"""Sequence-parallel prefill (ISSUE 18 tentpole, layer b).

A monster prompt's page-aligned prefix splits into contiguous sequence
shards across the fleet's prefill replicas: shard i imports its
predecessors' slabs (so its KV attends the true full prefix), prefills
its contiguous span through the NORMAL bucket programs, and exports a
PARTIAL-PREFIX slab (``export_prefix_slab(start_page=)``). The decode
replica merges the shards by importing them in order through the
partial-prefix ``import_prefix_slab`` — which must compose mid-prefix
while refusing gapped merges.

Pinned here, at engine level (the merge algebra) and router level (the
fleet path):

  * 2- and 3-shard merges land the decode pool BITWISE identical to a
    single-replica prefill — full-width pools and int8 pools (scale
    planes included, the PR 11 published-state contract);
  * a shard slab arriving before its predecessors is refused (0 pages,
    nothing published) — never a gapped prefix;
  * the router's sharded handoff is greedy-token-identical to a plain
    single-engine run, and the new fleet counters account it.
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm

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


def _prompt(seed, length):
    rs = np.random.RandomState(seed)
    return rs.randint(1, VOCAB, (length,)).astype(np.int32)


def _engine(ff, **kw):
    kw.setdefault("serve_slots", 2)
    kw.setdefault("kv_page_size", PS)
    kw.setdefault("max_seq_len", 32)
    return ff.make_serving_engine(**kw)


def _prefix_pages(eng, prompt, n_pages):
    """Every pool plane (k/v and, when quantized, the scale planes) of
    the prompt's first ``n_pages`` cached pages, as host arrays keyed
    (op, plane) — the published state two engines must agree on
    bitwise."""
    path = eng.prefix_cache.match(prompt, n_pages)
    assert len(path) == n_pages, "prefix not fully cached"
    out = {}
    for op in eng.gen.attn_ops:
        pool = eng.kv.pool[op.name]
        for plane in pool:
            out[(op.name, plane)] = np.stack(
                [np.asarray(pool[plane][nd.page]) for nd in path])
    return out


def _shard_bounds(last, shards):
    """Contiguous page spans, remainder to the front — the router's
    split (ServingRouter._seq_parallel_prefill)."""
    base, rem = divmod(last, shards)
    bounds, s = [], 0
    for i in range(shards):
        e = s + base + (1 if i < rem else 0)
        bounds.append((s, e))
        s = e
    return bounds


def _merge_sharded(ff, prompt, shards, **engine_kw):
    """Run the sequence-parallel protocol by hand: one engine per
    shard, cumulative predecessor imports, partial exports, then merge
    everything into a fresh decode engine. Returns (decode_engine,
    n_pages)."""
    last = prompt.size // PS
    slabs = []
    for s_pg, e_pg in _shard_bounds(last, shards):
        eng = _engine(ff, **engine_kw)
        for slab in slabs:          # predecessors first: KV attends
            assert eng.import_prefix_slab(slab) > 0   # the true prefix
        assert eng.prefill_into_cache(prompt[:e_pg * PS]) == e_pg
        slab = eng.export_prefix_slab(prompt[:e_pg * PS], start_page=s_pg)
        assert slab is not None and slab["start_page"] == s_pg
        assert len(slab["payload"]) == e_pg - s_pg
        slabs.append(slab)
    dec = _engine(ff, **engine_kw)
    for slab in slabs:
        assert dec.import_prefix_slab(slab) > 0
    return dec, last


def test_shard_bounds_cover_contiguously():
    """The router's page split: contiguous, exhaustive, remainder to
    the front so no shard is more than one page bigger than another."""
    for last in (2, 5, 6, 7, 64):
        for shards in (2, 3, 4):
            if shards > last:
                continue
            bounds = _shard_bounds(last, shards)
            assert bounds[0][0] == 0 and bounds[-1][1] == last
            sizes = [e - s for s, e in bounds]
            assert all(b[0] == a[1] for a, b in zip(bounds, bounds[1:]))
            assert max(sizes) - min(sizes) <= 1
            assert sorted(sizes, reverse=True) == sizes


@pytest.mark.slow  # model fixture
@pytest.mark.parametrize("shards", [2, 3])
def test_sharded_merge_bitwise_full_width(ff, shards):
    prompt = _prompt(41, 24)        # 6 full pages: bounds 3+3 / 2+2+2
    ref = _engine(ff)
    assert ref.prefill_into_cache(prompt) == 6
    want = _prefix_pages(ref, prompt, 6)
    dec, last = _merge_sharded(ff, prompt, shards)
    got = _prefix_pages(dec, prompt, last)
    assert got.keys() == want.keys()
    for key in want:
        assert (got[key] == want[key]).all(), \
            f"{shards}-shard merge diverged from single-replica at {key}"
    assert dec.stats()["partial_slab_imports"] == shards - 1


@pytest.mark.parametrize("shards", [2, 3])
def test_sharded_merge_bitwise_int8(ff, shards):
    """The quantized published-state contract (PR 11) must survive the
    merge: int8 pages AND their per-page scale rows land bitwise what a
    single replica publishes. The reference is a single replica
    EXTENDING the same prefix boundaries (not one cold full-prompt
    pass): under quantized KV the tail past a cached boundary attends
    the dequantized prefix, so the boundary placement is part of the
    published state — sharding must be invisible given the same
    boundaries, which is exactly what the decode replica observes."""
    kw = dict(kv_cache_dtype="int8")
    prompt = _prompt(43, 24)
    ref = _engine(ff, **kw)
    for _, e_pg in _shard_bounds(6, shards):
        assert ref.prefill_into_cache(prompt[:e_pg * PS]) == e_pg
    want = _prefix_pages(ref, prompt, 6)
    assert any(plane == "k_scale" for _, plane in want), \
        "int8 pool must expose scale planes"
    dec, last = _merge_sharded(ff, prompt, shards, **kw)
    got = _prefix_pages(dec, prompt, last)
    for key in want:
        assert (got[key] == want[key]).all(), \
            f"int8 {shards}-shard merge diverged at {key}"


def test_gapped_shard_slab_refused(ff):
    """Shard 1's slab arriving before shard 0 has merged must be
    refused outright: publishing pages past a gap would cache a prefix
    whose middle was never written."""
    prompt = _prompt(47, 24)
    (s0, e0), (s1, e1) = _shard_bounds(6, 2)
    a = _engine(ff)
    assert a.prefill_into_cache(prompt[:e0 * PS]) == e0
    slab0 = a.export_prefix_slab(prompt[:e0 * PS], start_page=s0)
    b = _engine(ff)
    assert b.import_prefix_slab(slab0) == e0
    assert b.prefill_into_cache(prompt) == 6
    slab1 = b.export_prefix_slab(prompt, start_page=s1)
    dec = _engine(ff)
    assert dec.import_prefix_slab(slab1) == 0      # gap: refused
    assert dec.stats()["partial_slab_imports"] == 0
    assert dec.prefix_cache.match(prompt, 6) == []
    # in order, the same slabs merge cleanly
    assert dec.import_prefix_slab(slab0) == e0
    assert dec.import_prefix_slab(slab1) == e1 - s1
    assert dec.stats()["partial_slab_imports"] == 1


def test_partial_export_bounds_validated(ff):
    prompt = _prompt(53, 24)
    eng = _engine(ff)
    assert eng.prefill_into_cache(prompt) == 6
    with pytest.raises(ValueError, match="start_page"):
        eng.export_prefix_slab(prompt, start_page=6)
    with pytest.raises(ValueError, match="start_page"):
        eng.export_prefix_slab(prompt, start_page=-1)
    # start_page=0 stays the whole-prefix slab of the disagg handoff
    whole = eng.export_prefix_slab(prompt)
    assert whole["start_page"] == 0 and len(whole["payload"]) == 6


def test_router_seq_parallel_token_identity(ff):
    """Fleet leg: a disaggregated router with seq_parallel_shards=2
    must emit exactly the single-engine greedy streams for prompts long
    enough to shard, count them in the fleet rollup, and leave short
    prompts on the plain single-replica handoff."""
    prompts = [_prompt(59, 24), _prompt(61, 26), _prompt(67, 7)]
    eng = _engine(ff, serve_slots=2, max_seq_len=64)
    want = [list(r.tokens) for r in eng.run(prompts, max_new_tokens=5)]
    router = ff.make_serving_router(
        replicas=3, roles="prefill,prefill,decode",
        seq_parallel_shards=2, handoff_min_pages=2,
        serve_slots=2, kv_page_size=PS, max_seq_len=64)
    try:
        reqs = router.run(prompts, max_new_tokens=5)
        assert [r.state for r in reqs] == ["done"] * 3
        got = [list(r.tokens) for r in reqs]
        assert got == want, "sharded fleet changed a greedy stream"
        fleet = router.stats()["fleet"]
        # 24 and 26 tokens = 6 full pages >= 2 shards * 2 min pages;
        # the 7-token prompt (1 page) stays on the plain handoff
        assert fleet["seq_parallel_prefills"] == 2
        assert fleet["partial_slab_imports"] >= 2
        assert fleet["prefill_chunks_interleaved"] == 0
    finally:
        router.close()
