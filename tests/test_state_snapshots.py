"""A prefix hit for a model that keeps a recurrent state (runtime/kv_pool.py,
runtime/serving.py): a trie node may carry a SNAPSHOT of every state op's
state after exactly the tokens of its path; a hit seeds the attention ops'
caches from the matched pages and the state ops' from the snapshot, and runs
the tail from there. Tiny sizes, float32, CPU; the model is Granite 4.0-H's
shape (tests/test_granite_hybrid.py), the reference its plain one.
"""

import numpy as np
import pytest

import reference_granite_hybrid as ref
from flexflow_tpu.runtime.kv_pool import RadixPrefixCache
from test_granite_hybrid import LOGIT_ATOL, SIZES, VOCAB, build, margins

PS = 8      # page size


@pytest.fixture(scope="module")
def ff():
    return build(batch=1)


def engine(ff, **kw):
    args = dict(serve_slots=2, kv_page_size=PS, kv_pages=48, max_seq_len=96,
                prefix_cache=True, state_snapshots=3, decode_chunk=2)
    args.update(kw)
    return ff.make_serving_engine(**args)


def tokens(n, seed):
    return np.random.RandomState(seed).randint(1, VOCAB, (n,)) \
        .astype(np.int32)


def serve(eng, prompt, new=6, read_state=False):
    """The request run to its end; with `read_state` the slot's state while
    it is still seated (after `new` - 2 emitted tokens at the earliest)."""
    req = eng.submit(prompt, new)
    state = None
    while eng.pending():
        eng.step()
        if read_state and state is None and req.slot >= 0 \
                and len(req.tokens) >= 2:
            state = (eng.slot_state(req.slot), len(req.tokens))
    return (req, state) if read_state else req


def leak_free(eng):
    st = eng.stats()
    assert st["prefix_refs_live"] == 0
    assert st["free_pages"] + st["kv_pages_cached"] == eng.num_pages - 1
    eng.flush_prefix_cache()
    st = eng.stats()
    assert st["free_pages"] == eng.num_pages - 1
    assert st["state_snapshots_held"] == 0


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_a_hit_through_a_snapshot_is_the_request_served_cold(impl):
    """The same request cold (prefix_cache=False) and through its document's
    pages + snapshot: the emitted tokens lie within rounding of the
    reference's maximum either way, and the state the slot holds after the
    same tokens is the same state. Tolerance: both are float32; the hit's
    scan starts at the document's end, so its chunks fall elsewhere than the
    cold prefill's (sums in another order: 1e-5 relative on a state of order
    1), nothing more."""
    n = 128 if impl == "pallas" else 16
    ff = build(batch=1, state_size=n)
    sizes = {**SIZES, "mamba_d_state": n}
    doc, q = tokens(5 * PS, 1), tokens(5, 2)
    prompt = np.concatenate([doc, q])
    cold, cold_state = serve(engine(ff, prefix_cache=False,
                                    paged_attention_impl=impl), prompt,
                             read_state=True)
    eng = engine(ff, paged_attention_impl=impl)
    assert eng.prefill_into_cache(doc) == 5
    hit, hit_state = serve(eng, prompt, read_state=True)
    assert hit.prefix_tokens == doc.size and cold.prefix_tokens == 0
    assert margins(ff, hit, sizes).max() <= 2 * LOGIT_ATOL
    assert margins(ff, cold, sizes).max() <= 2 * LOGIT_ATOL
    assert hit.tokens == cold.tokens
    assert hit_state[1] == cold_state[1]
    for op in ("mamba_0", "mamba_2", "mamba_3"):
        for k in ("h", "conv"):
            a, b = hit_state[0][op][k], cold_state[0][op][k]
            assert np.abs(a - b).max() <= 1e-5 * max(1.0, np.abs(b).max())
    st = eng.stats()
    assert (st["state_snapshot_hits"], st["state_snapshots_taken"],
            st["state_snapshots_held"]) == (1, 1, 1)
    assert st["prefix_hit_tokens"] == doc.size
    leak_free(eng)


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_the_seated_state_after_a_hit_is_the_references(ff, impl):
    """The hit's state against the reference's recurrence from token 0 over
    document + question + emitted tokens (check (c) of the benchmark cell):
    the snapshot taken, seated and resumed from in the layout the op holds,
    the decode steps by XLA's loop or the interpreted kernel (one group of
    128 lanes, N = 16), and `slot_state` handing out (H, P, N) as the
    reference writes it."""
    doc, q = tokens(4 * PS, 3), tokens(3, 4)
    eng = engine(ff, paged_attention_impl=impl)
    assert eng.kv.snapshots["mamba_0"]["h"].shape[1:] == (1, 16, 8 * 16)
    eng.prefill_into_cache(doc)
    req, (state, n) = serve(eng, np.concatenate([doc, q]), new=8,
                            read_state=True)
    seq = np.concatenate([doc, q, req.tokens[:n - 1]]).astype(np.int32)
    want = {}
    ref.forward(ff.params, seq, SIZES, states=want)
    for op, st in want.items():
        for k in st:
            np.testing.assert_allclose(state[op][k], np.asarray(st[k]),
                                       atol=1e-5, rtol=1e-5)


def test_a_wrong_snapshot_is_seen(ff):
    """Another document's snapshot under this document's pages: the state
    the slot then holds is far from the reference's over the same tokens
    (the emitted tokens of this tiny model need not move: states are
    compared, as the cell's check (c) does)."""
    a, b, q = tokens(4 * PS, 5), tokens(4 * PS, 6), tokens(4, 7)
    eng = engine(ff)
    eng.prefill_into_cache(a)
    eng.prefill_into_cache(b)
    snaps = eng.kv.snapshots
    for name in snaps:      # rows 1 and 2 swap places
        snaps[name] = {k: v.at[np.array([1, 2])].set(v[np.array([2, 1])])
                       for k, v in snaps[name].items()}
    req, (state, n) = serve(eng, np.concatenate([a, q]), read_state=True)
    assert req.prefix_tokens == a.size
    seq = np.concatenate([a, q, req.tokens[:n - 1]]).astype(np.int32)
    want = {}
    ref.forward(ff.params, seq, SIZES, states=want)
    h = np.asarray(want["mamba_0"]["h"])
    assert np.linalg.norm(state["mamba_0"]["h"] - h) > 0.1 * np.linalg.norm(h)


def test_a_cold_prompt_of_whole_pages_publishes_pages_and_one_snapshot(ff):
    eng = engine(ff)
    doc = tokens(3 * PS, 8)
    first = serve(eng, doc)
    st = eng.stats()
    assert first.prefix_tokens == 0
    assert (st["state_snapshots_taken"], st["kv_pages_cached"]) == (1, 3)
    # its continuation resumes from it
    again = serve(eng, np.concatenate([doc, tokens(5, 9)]))
    assert again.prefix_tokens == doc.size
    assert margins(ff, again).max() <= 2 * LOGIT_ATOL
    leak_free(eng)


def test_a_prompt_that_ends_inside_a_page_publishes_nothing(ff):
    eng = engine(ff)
    serve(eng, tokens(3 * PS + 3, 10))
    st = eng.stats()
    assert st["kv_pages_cached"] == 0 and st["state_snapshots_taken"] == 0
    assert st["free_pages"] == eng.num_pages - 1
    assert eng.prefill_into_cache(tokens(2 * PS + 1, 11)) == 2
    assert eng.stats()["kv_pages_cached"] == 0      # 2 full pages, none kept


def test_pages_without_a_snapshot_on_the_path_prefill_cold(ff):
    """A document of 5 pages is resident with its one snapshot on page 5; a
    prompt that shares only its first 3 pages finds pages on its path and no
    snapshot: it prefills cold, and leases none of them."""
    eng = engine(ff)
    doc = tokens(5 * PS, 12)
    eng.prefill_into_cache(doc)
    other = np.concatenate([doc[:3 * PS], tokens(6, 13)])
    req = serve(eng, other)
    assert req.prefix_tokens == 0
    assert eng.stats()["state_snapshot_hits"] == 0
    assert margins(ff, req).max() <= 2 * LOGIT_ATOL
    # and a prefix of whole pages of it gets a snapshot of its own on the
    # EXISTING page-3 node, resumed from by the next such prompt
    assert eng.prefill_into_cache(doc[:3 * PS]) == 3
    assert eng.stats()["state_snapshots_held"] == 2
    assert eng.stats()["kv_pages_cached"] == 5      # no page twice
    req = serve(eng, other)
    assert req.prefix_tokens == 3 * PS
    assert margins(ff, req).max() <= 2 * LOGIT_ATOL
    leak_free(eng)


def test_a_snapshot_leaves_with_its_node_and_its_id_is_reused(ff):
    """Three snapshot ids, four documents: the fourth takes the id of the
    least recently used document, whose pages leave with it."""
    eng = engine(ff, state_snapshots=3)
    docs = [tokens(2 * PS, 20 + i) for i in range(4)]
    for d in docs[:3]:
        eng.prefill_into_cache(d)
    serve(eng, np.concatenate([docs[0], tokens(3, 30)]))    # 0 is recent
    assert eng.stats()["state_snapshots_held"] == 3
    eng.prefill_into_cache(docs[3])
    st = eng.stats()
    assert (st["state_snapshots_held"], st["state_snapshots_evicted"],
            st["state_snapshots_taken"]) == (3, 1, 4)
    assert st["kv_pages_cached"] == 6
    hits = [serve(eng, np.concatenate([d, tokens(3, 31)])).prefix_tokens
            for d in (docs[0], docs[1], docs[2], docs[3])]
    assert hits == [2 * PS, 0, 2 * PS, 2 * PS]      # document 1 left
    leak_free(eng)


def test_page_pressure_evicts_a_document_with_its_snapshot(ff):
    eng = engine(ff, kv_pages=20, state_snapshots=4)
    a, b = tokens(6 * PS, 40), tokens(6 * PS, 41)
    eng.prefill_into_cache(a)
    eng.prefill_into_cache(b)
    # 12 of 19 pages cached; a cold request of 9 pages needs 2 of them
    req = serve(eng, tokens(5 * PS + 2, 42), new=6)
    assert req.state == "done"
    st = eng.stats()
    assert st["state_snapshots_evicted"] == 1
    assert st["kv_pages_cached"] == 6       # a whole document left, no less
    leak_free(eng)


def test_what_is_still_refused(ff):
    with pytest.raises(ValueError, match="host_kv_pages must be 0"):
        engine(ff, host_kv_pages=4)
    with pytest.raises(ValueError, match="speculate_k must be 0"):
        engine(ff, draft_model=ff, speculate_k=2)
    with pytest.raises(ValueError, match="at least one snapshot"):
        engine(ff, state_snapshots=0)
    eng = engine(ff)
    doc = tokens(2 * PS, 50)
    eng.prefill_into_cache(doc)
    with pytest.raises(NotImplementedError, match="no page slab carries"):
        eng.export_prefix_slab(doc)
    with pytest.raises(NotImplementedError, match="no page slab carries"):
        eng.import_prefix_slab({})


def test_an_engine_without_a_prefix_cache_has_no_snapshot_arrays(ff):
    eng = engine(ff, prefix_cache=False)
    assert eng.kv.snapshots is None and eng.state_snapshots == 0
    serve(eng, tokens(2 * PS, 51))
    key = next(k for k in eng._programs if k[0] == "prefill")
    # the parent's thirteen arguments and the slot: no array, no row
    assert len(eng._registered[key].args) == 14
    st = eng.stats()
    assert st["state_snapshot_pool_bytes"] == st["state_snapshots_held"] == 0


def test_the_prefill_span_says_whether_it_resumed_from_a_snapshot(ff):
    from flexflow_tpu.runtime import telemetry

    eng = engine(ff)
    doc = tokens(2 * PS, 52)
    serve(eng, doc)
    serve(eng, np.concatenate([doc, tokens(3, 53)]))
    spans = [e for e in telemetry.tracer().events(name="prefill")
             if "snapshot" in e["args"]]
    cold, hit = spans[-2], spans[-1]
    per = eng.stats()["state_snapshot_pool_bytes"] // 4
    assert (cold["args"]["snapshot"], cold["args"]["snapshot_bytes"]) \
        == (0, per)
    assert (hit["args"]["snapshot"], hit["args"]["snapshot_bytes"]) \
        == (1, per)


# ---- the trie alone: host side, no device --------------------------------

def chunks(*ids):
    return [t for i in ids for t in [i] * PS]


def test_trie_match_ends_at_the_deepest_snapshot():
    trie = RadixPrefixCache(PS, snapshots=2)
    prompt = chunks(1, 2, 3, 4)
    created, took = trie.insert_snapshot(prompt, [], 0, [11, 12, 13],
                                         trie.snapshot_id())
    assert took and [n.page for n in created] == [11, 12, 13]
    assert [n.snap for n in created] == [0, 0, 1]
    assert [n.page for n in trie.match(prompt, 4)] == [11, 12, 13]
    assert trie.match(prompt, 2) == []          # pages, no snapshot
    assert trie.match(chunks(1, 2, 9), 3) == []
    trie.release(created)
    assert trie.snapshots_held == 1


def test_trie_snapshot_on_an_existing_node_keeps_the_callers_pages_private():
    trie = RadixPrefixCache(PS, snapshots=2)
    long = chunks(1, 2, 3)
    a, _ = trie.insert_snapshot(long, [], 0, [11, 12, 13],
                                trie.snapshot_id())
    created, took = trie.insert_snapshot(long[:2 * PS], [], 0, [21, 22],
                                         trie.snapshot_id())
    assert took and created == [] and trie.pages == 3
    assert [n.page for n in trie.match(long[:2 * PS] + [5], 2)] == [11, 12]
    # a second snapshot on the same node is the caller's to hand back
    _, took = trie.insert_snapshot(long, [], 0, [31, 32, 33], 7)
    assert not took
    trie.release(a)


@pytest.mark.parametrize("pressure", [True, False], ids=["evict", "flush"])
def test_trie_eviction_takes_the_path_its_snapshot_made_reachable(pressure):
    trie = RadixPrefixCache(PS, snapshots=3)
    a, _ = trie.insert_snapshot(chunks(1, 2, 3), [], 0, [11, 12, 13],
                                trie.snapshot_id())
    b, _ = trie.insert_snapshot(chunks(1, 2), [], 0, [21, 22],
                                trie.snapshot_id())
    trie.release(a)
    if pressure:
        # one page asked: the leaf leaves ALONE, page 2 carries a snapshot
        assert trie.evict(1) == [13]
        assert trie.snapshots_held == 1 and trie.pages == 2
        assert sorted(trie.evict(5)) == [11, 12]
    else:
        assert sorted(trie.evict(99, pressure=False)) == [11, 12, 13]
    assert trie.snapshots_held == 0 and trie.pages == 0
    assert trie.snapshots_evicted == 2
    assert sorted(trie._free_snaps) == [1, 2, 3]


def test_trie_evict_snapshot_spares_mounted_and_protected_leaves():
    trie = RadixPrefixCache(PS, snapshots=2)
    a, _ = trie.insert_snapshot(chunks(1, 2), [], 0, [11, 12],
                                trie.snapshot_id())
    b, _ = trie.insert_snapshot(chunks(3), [], 0, [21], trie.snapshot_id())
    assert trie.snapshot_id() == 0                  # both ids are out
    assert trie.evict_snapshot() == []              # both leaves mounted
    trie.release(a)
    trie.release(b)
    assert trie.evict_snapshot(protect=a) == [21]
    assert sorted(trie.evict_snapshot()) == [11, 12]    # the chain with it
    assert trie.snapshots_held == 0


def test_trie_forget_stops_at_the_next_snapshot():
    trie = RadixPrefixCache(PS, snapshots=2)
    a, _ = trie.insert_snapshot(chunks(1, 2, 3), [], 0, [11, 12, 13],
                                trie.snapshot_id())
    trie.insert_snapshot(chunks(1), [], 0, [21], trie.snapshot_id())
    trie.release(a)
    assert sorted(trie.forget(chunks(1, 2, 3))) == [12, 13]
    assert [n.page for n in trie.match(chunks(1, 5), 1)] == [11]


def test_trie_host_tier_and_snapshots_do_not_mix():
    with pytest.raises(ValueError, match="host tier moves pages only"):
        RadixPrefixCache(PS, host_pages=4, d2h=lambda p: None,
                         h2d=lambda p, q: None, snapshots=2)
