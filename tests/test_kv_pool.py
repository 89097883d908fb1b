"""The KV page pool alone: who holds which page, and when it comes back.

Host bookkeeping only (the style of tests/test_tiered_prefix.py): no
engine, no attention op, no compiled program — the pool's page movers
are replaced by fakes, so every rule of the reserve / commit / publish /
release protocol of runtime/kv_pool.py is pinned as host logic. The
engine-integrated paths (real pools, token identity) live in
tests/test_serving.py and tests/test_disagg.py.
"""

from types import SimpleNamespace

import jax
import numpy as np
import pytest

from flexflow_tpu.runtime import faultinject
from flexflow_tpu.runtime.kv_pool import KVPagePool

PS = 2          # page size: tiny, so prompts stay readable
PAGES = 9       # scratch page 0 + 8 usable

_NO_OPS = SimpleNamespace(
    attn_ops=[], _compute_dtype=lambda: np.float32,
    model=SimpleNamespace(mesh=jax.sharding.Mesh(
        np.asarray(jax.devices()[:1]), ("data",))))


class FakePool(KVPagePool):
    """A pool over no attention op whose movers only record: payloads
    are dicts naming the page they were read from."""

    def __init__(self, num_pages=PAGES, host_pages=0, prefix_cache=True):
        self.written = []       # (page, payload) h2d writes
        super().__init__(_NO_OPS, None, num_pages, PS, pages_per_slot=4,
                         kv_dtype=None, prefix_cache=prefix_cache,
                         host_pages=host_pages, page_import=None)

    def d2h(self, pages):
        return lambda: [{("t", "fake"): {"from": int(p)}} for p in pages]

    def h2d(self, pages, payloads):
        self.written.extend((int(p), pl) for p, pl in zip(pages, payloads))


def prompt_of(n_tokens, base=1):
    return np.arange(base, base + n_tokens, dtype=np.int32)


def conserved(pool, *leases):
    """free + trie-owned + leased-private == num_pages less the scratch
    page, and no page is counted twice."""
    trie = pool.prefix_cache
    owned = ([n.page for n in trie._iter_nodes() if n.tier == "hbm"]
             if trie is not None else [])
    private = [p for lease in leases for p in lease.private]
    pages = list(pool._free_pages) + owned + private
    assert sorted(pages) == list(range(1, pool.num_pages)), pages
    assert trie is None or trie.pages == len(owned)


def admit(pool, prompt, n_pages, ns=None):
    """What admission does: reserve with the last token left to prefill,
    commit. None when the pool is short."""
    lease = pool.reserve(prompt, ns, (prompt.size - 1) // PS, n_pages,
                         hold=True)
    if lease is not None:
        pool.commit(lease)
    return lease


def publish_only(pool, prompt, ns=None, ok=True):
    """What prefill_into_cache does around its prefill."""
    lease = pool.reserve(prompt, ns, (prompt.size - 1) // PS,
                         -(-prompt.size // PS), hold=False)
    if lease is None or not lease.need:
        return lease
    pool.commit(lease)
    pool.publish(lease, prompt, ns, ok)
    return lease


def demote_all(pool):
    pool.make_room(pool.num_pages - 1)
    assert pool.prefix_cache.wait_migrations(5)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


# ---- the life of a lease ---------------------------------------------------


def test_pages_conserved_across_reserve_commit_publish_release():
    pool = FakePool()
    conserved(pool)
    prompt = prompt_of(5)                       # 2 full pages + 1 token
    lease = pool.reserve(prompt, None, 2, 4, hold=True)
    assert lease.need == 4 and lease.matched == []
    assert pool.free_pages == PAGES - 1, "reserve alone takes no page"
    pool.commit(lease)
    assert len(lease.pages) == 4 and lease.private == lease.pages
    conserved(pool, lease)
    assert pool.publish(lease, prompt, None, ok=True) == 2
    # the two full pages are the trie's now, the partial page and the
    # decode page stay private
    assert lease.private == lease.pages[2:]
    assert [n.page for n in lease.nodes] == lease.pages[:2]
    conserved(pool, lease)
    pool.release(lease)
    assert lease.nodes == [] and lease.private == []
    assert pool.free_pages == PAGES - 1 - 2
    assert pool.prefix_cache.live_refs() == 0
    conserved(pool)
    assert pool.flush() == 2 and pool.free_pages == PAGES - 1


def test_second_holder_mounts_shared_pages_and_writes_only_fresh_ones():
    pool = FakePool()
    prompt = prompt_of(5)
    a = admit(pool, prompt, 3)
    pool.publish(a, prompt, None, ok=True)
    b = admit(pool, prompt, 3)
    assert len(b.matched) == 2 and b.need == 1
    assert b.pages[:2] == a.pages[:2], "the cached prefix is shared"
    assert not set(b.private) & set(a.pages), "COW: fresh pages only"
    assert pool.prefix_cache.shared_pages() == 2
    assert pool.publish(b, prompt, None, ok=True) == 0
    conserved(pool, a, b)
    pool.release(a)
    assert pool.prefix_cache.live_refs() == 2, "b still mounts the prefix"
    pool.release(b)
    assert pool.prefix_cache.live_refs() == 0
    conserved(pool)


@pytest.mark.parametrize("hold", [True, False])
def test_hold_keeps_references_and_a_publisher_ends_at_publish(hold):
    pool = FakePool()
    trie = pool.prefix_cache
    prompt = prompt_of(4)                       # 2 full pages, no tail
    lease = pool.reserve(prompt, None, 1, 2, hold=hold)
    pool.commit(lease)
    assert pool.publish(lease, prompt, None, ok=True) == 2
    assert trie.pages == 2
    # only a request's lease is an admission in the hit statistics
    assert trie.lookups == (1 if hold else 0)
    if hold:
        assert trie.live_refs() == 2 and len(lease.nodes) == 2
        assert trie.evict(2) == [], "a mounted page is not evictable"
        pool.release(lease)
    # published pages sit warm at refcount 0, evictable like any other
    assert trie.live_refs() == 0 and lease.nodes == []
    assert pool.free_pages == PAGES - 1 - 2
    conserved(pool)
    assert len(trie.evict(2)) == 2


@pytest.mark.parametrize("hold", [True, False])
def test_failed_prefill_publishes_nothing_and_returns_every_page(hold):
    pool = FakePool()
    prompt = prompt_of(5)
    lease = pool.reserve(prompt, None, 2, 3, hold=hold)
    pool.commit(lease)
    assert pool.publish(lease, prompt, None, ok=False) == 0
    assert pool.prefix_cache.pages == 0, "a NaN prefill must not be cached"
    if hold:
        assert len(lease.private) == 3, "the request retires with its pages"
        pool.release(lease)
    assert pool.free_pages == PAGES - 1
    assert pool.prefix_cache.match(prompt, 2) == []
    conserved(pool)


def test_publisher_of_a_cached_prompt_gets_nothing_and_evicts_nothing():
    pool = FakePool(num_pages=5)                # 4 usable pages
    prompt, other = prompt_of(5), prompt_of(4, base=50)
    publish_only(pool, prompt)                  # 2 cached, 1 returned
    publish_only(pool, other)                   # 2 cached: pool is full
    assert pool.free_pages == 0
    again = publish_only(pool, prompt)
    assert again.need == 0 and len(again.matched) == 2
    assert pool.prefix_cache.pages == 4 and pool.prefix_cache.evictions == 0
    again = pool.reserve(prompt, None, 2, 3, hold=False)
    pool.release(again)                         # un-committed: a no-op
    assert pool.prefix_cache.live_refs() == 0
    conserved(pool)


# ---- pressure --------------------------------------------------------------


def test_shortfall_reserves_nothing_and_moves_no_refcount():
    pool = FakePool(num_pages=5)
    trie = pool.prefix_cache
    prompt = prompt_of(5)
    a = admit(pool, prompt, 3)
    pool.publish(a, prompt, None, ok=True)
    refs, lookups, free = trie.live_refs(), trie.lookups, pool.free_pages
    # b shares 2 pages but needs 2 fresh ones; 1 is free and the cached
    # pages are mounted by a, so nothing can be evicted
    assert admit(pool, prompt, 4) is None
    assert (trie.live_refs(), trie.lookups, pool.free_pages) \
        == (refs, lookups, free)
    assert [n.ref for n in a.nodes] == [1, 1]
    conserved(pool, a)
    pool.release(a)
    assert admit(pool, prompt, 4) is not None, "a retirement unblocks it"


def test_make_room_evicts_cold_pages_but_never_the_matched_path():
    pool = FakePool(num_pages=6)                # 5 usable pages
    hot, cold = prompt_of(5), prompt_of(4, base=50)
    publish_only(pool, hot)
    publish_only(pool, cold)
    assert pool.free_pages == 1
    # 4 fresh pages are one more than the pool can free: every cold page
    # goes, the just-matched path is never the victim
    assert admit(pool, hot, 6) is None
    assert pool.prefix_cache.match(cold, 2) == [], "the cold prefix went"
    assert len(pool.prefix_cache.match(hot, 2)) == 2
    assert pool.prefix_cache.evictions == 2 and pool.free_pages == 3
    lease = admit(pool, hot, 5)                 # 2 shared + 3 fresh
    assert lease is not None and len(lease.matched) == 2
    conserved(pool, lease)
    # without a trie the pool cannot make room at all
    bare = FakePool(num_pages=3, prefix_cache=False)
    only = admit(bare, hot, 2)
    assert only is not None and admit(bare, hot, 1) is None
    conserved(bare, only)


# ---- host tier -------------------------------------------------------------


def test_host_resident_match_is_promoted_into_fresh_pages():
    pool = FakePool(host_pages=8)
    prompt = prompt_of(7)                       # 3 full pages + 1 token
    publish_only(pool, prompt)
    demote_all(pool)
    assert pool.prefix_cache.host_used == 3 and pool.free_pages == PAGES - 1
    lease = admit(pool, prompt, 5)
    assert [n.tier for n in lease.matched] == ["hbm"] * 3
    assert lease.need == 2 and len(pool.written) == 3
    assert [p for p, _ in pool.written] == lease.pages[:3]
    assert pool.free_pages == PAGES - 1 - 5
    conserved(pool, lease)


def test_promotion_failing_mid_path_truncates_match_and_returns_pages(
        monkeypatch):
    pool = FakePool(host_pages=8)
    prompt = prompt_of(7)
    publish_only(pool, prompt)
    demote_all(pool)
    monkeypatch.setenv("FF_FAULT", "h2d_fail@promote:2")
    faultinject.reset()
    lease = admit(pool, prompt, 5)
    # the second host page failed: one page promoted, the rest of the
    # prompt prefills cold into fresh pages, the third promotion target
    # went back to the free list
    assert len(lease.matched) == 1 and lease.need == 4
    assert pool.prefix_cache.promote_failures == 1
    assert pool.prefix_cache.host_used == 0, "the failed tail was killed"
    assert len(pool.written) == 1
    assert pool.free_pages == PAGES - 1 - 5
    conserved(pool, lease)
    pool.publish(lease, prompt, None, ok=True)
    pool.release(lease)
    assert len(pool.prefix_cache.match(prompt, 3)) == 3
    conserved(pool)


# ---- page slabs ------------------------------------------------------------


def slab_of(prompt, start_page=0, ns=None):
    last = prompt.size // PS
    return {"page_size": PS, "tokens": prompt[:last * PS].copy(), "ns": ns,
            "start_page": start_page,
            "payload": [{("t", "fake"): {"chunk": j}}
                        for j in range(start_page, last)]}


def test_slab_roundtrip_skips_cached_pages_and_publishes_at_refcount_0():
    donor, imp = FakePool(), FakePool()
    prompt = prompt_of(6)
    publish_only(donor, prompt)
    slab = donor.export_slab(prompt, None)
    assert [p[("t", "fake")]["from"] for p in slab["payload"]] \
        == [n.page for n in donor.prefix_cache.match(prompt, 3)]
    assert donor.export_slab(prompt_of(6, base=9), None) is None
    publish_only(imp, prompt[:2 * PS])          # first two pages cached
    assert imp.import_slab(slab_of(prompt)) == 1
    assert [pl[("t", "fake")]["chunk"] for _, pl in imp.written] == [2]
    path = imp.prefix_cache.match(prompt, 3)
    assert len(path) == 3 and imp.prefix_cache.live_refs() == 0
    assert imp.import_slab(slab_of(prompt)) == 0, "all cached: no-op"
    conserved(imp)
    with pytest.raises(ValueError, match="page_size"):
        imp.import_slab({**slab_of(prompt), "page_size": PS + 1})


def test_slab_with_a_gap_or_under_a_host_tail_imports_nothing():
    pool = FakePool(host_pages=8)
    prompt = prompt_of(8)                       # 4 full pages
    # a partial slab whose predecessors have not merged yet
    assert pool.import_slab(slab_of(prompt, start_page=2)) == 0
    assert pool.import_slab(slab_of(prompt[:2 * PS])) == 2
    assert pool.import_slab(slab_of(prompt, start_page=2)) == 2
    assert len(pool.prefix_cache.match(prompt, 4)) == 4
    conserved(pool)
    # demote the path: an import below a host-resident tail would break
    # the hbm*-then-host* invariant
    demote_all(pool)
    longer = np.concatenate([prompt, prompt_of(2, base=90)])
    free, written = pool.free_pages, len(pool.written)
    assert pool.import_slab(slab_of(longer)) == 0
    assert (pool.free_pages, len(pool.written)) == (free, written)
    assert pool.prefix_cache.host_used == 4
    conserved(pool)


def test_slab_lands_as_far_as_the_pool_has_room():
    pool = FakePool(num_pages=4)                # 3 usable pages
    held = admit(pool, prompt_of(3, base=70), 1)
    prompt = prompt_of(8)
    assert pool.import_slab(slab_of(prompt)) == 2, "a valid shorter prefix"
    assert len(pool.prefix_cache.match(prompt, 4)) == 2
    assert pool.free_pages == 0
    conserved(pool, held)


# ---- whole-namespace operations -------------------------------------------


def test_flush_forget_and_namespace_flush_return_pages_to_the_free_list():
    pool = FakePool()
    prompt = prompt_of(4)
    publish_only(pool, prompt, ns="tenant-a")
    publish_only(pool, prompt, ns="tenant-b")
    publish_only(pool, prompt)
    assert pool.prefix_cache.pages == 6, "namespaces share no page"
    pool.flush_namespace("tenant-a")
    assert pool.prefix_cache.match(prompt, 2, ns="tenant-a") == []
    assert len(pool.prefix_cache.match(prompt, 2, ns="tenant-b")) == 2
    conserved(pool)
    pool.forget(prompt)
    assert pool.prefix_cache.match(prompt, 2) == []
    assert pool.free_pages == PAGES - 1 - 2
    held = admit(pool, prompt_of(5), 3, ns="tenant-b")
    assert len(held.matched) == 2
    with pytest.raises(ValueError, match="mounted"):
        pool.flush_namespace("tenant-b")
    assert pool.flush() == 0, "a mounted page survives a flush"
    conserved(pool, held)
    pool.release(held)
    assert pool.flush() == 2 and pool.free_pages == PAGES - 1
    conserved(pool)


# ---- window groups: a ring of pages a slot beside the global table --------

from flexflow_tpu.runtime.kv_pool import WindowPageGroup  # noqa: E402


class _WindowOp:
    """An attention op as the pool sees it: a name, what it keeps, and
    pool arrays of as many pages as it is given."""

    def __init__(self, name, keep):
        self.name, self._keep = name, keep

    def kv_keep(self):
        return self._keep

    def init_paged_cache(self, num_pages, page_size, cdtype, kv_dtype=None):
        return {"k": np.zeros((num_pages, page_size, 1, 2), np.float32)}


def window_pool(prefix_cache=False, draft=None, slots=3, snapshots=0):
    gen = SimpleNamespace(
        attn_ops=[_WindowOp("attn_global_0", None),
                  _WindowOp("attn_window_1", 5),
                  _WindowOp("attn_window_2", 5)],
        _compute_dtype=lambda: np.float32, model=_NO_OPS.model)
    return KVPagePool(gen, draft, PAGES, PS, pages_per_slot=4,
                      kv_dtype=None, prefix_cache=prefix_cache,
                      host_pages=0, page_import=None, slots=slots,
                      snapshots=snapshots)


@pytest.mark.parametrize("window,page,ring", [(5, 2, 4), (4, 4, 2),
                                              (3, 8, 2), (128, 128, 2),
                                              (9, 4, 4)])
def test_a_window_group_never_holds_more_than_its_ring(window, page, ring):
    """Whatever the context length: a prefill of any length seats at most
    `ring` = ceil(window / page) + 1 pages, decoding on recycles them, and
    release returns all of them."""
    g = WindowPageGroup(window, page, slots=2)
    assert g.ring == ring and g.num_pages == 1 + 2 * ring
    for length in (1, page - 1, page, page + 1, window, 7 * page + 3, 1000):
        g.seat(0, length)
        assert g.held(0) == min(ring, (length - 1) // page + 1)
        assert g.free_pages + g.held_pages == g.num_pages - 1
        before = g.recycled
        for position in range(length, length + 5 * ring * page):
            g.reach(0, position)
            assert g.held(0) <= ring
            # the page of every position the window still sees is in its
            # column, and no two columns share a pool page
            row = g.tables[0]
            held = row[row > 0]
            assert len(set(held.tolist())) == held.size == g.held(0)
            for at in range(max(0, position - window + 1), position + 1):
                assert row[(at // page) % ring] > 0
        turned = (length + 5 * ring * page - 1) // page - (length - 1) // page
        took = g.held(0) - min(ring, (length - 1) // page + 1)
        assert g.recycled - before == turned - took
        g.release(0)
        assert g.held(0) == 0 and not g.tables[0].any()
        assert g.free_pages == g.num_pages - 1


def test_window_groups_slots_do_not_share_pages():
    g = WindowPageGroup(5, 2, slots=3)
    for slot, length in enumerate((3, 40, 7)):
        g.seat(slot, length)
    for step in range(30):
        for slot, length in enumerate((3, 40, 7)):
            g.reach(slot, length + step)
    pages = g.tables[g.tables > 0]
    assert len(set(pages.tolist())) == pages.size == g.held_pages == 12
    g.release(1)
    assert g.held_pages == 8 and g.free_pages == 4
    with pytest.raises(AssertionError, match="seated twice"):
        g.seat(0, 3)


def test_the_pool_groups_its_ops_by_what_they_keep():
    """The global op's arrays are the pool's size and answer to the free
    list; the two window ops share ONE group, whose arrays are a ring a
    slot; seating the rings takes nothing from the free list."""
    pool = window_pool()
    assert sorted(pool.window_groups) == [5]
    g = pool.window_groups[5]
    assert pool.pool["attn_global_0"]["k"].shape[0] == PAGES
    assert pool.pool["attn_window_1"]["k"].shape[0] \
        == pool.pool["attn_window_2"]["k"].shape[0] == g.num_pages \
        == 1 + pool.slots * 4
    free = pool.free_pages
    pool.seat_windows(1, 9)
    pool.reach_windows(1, 30)
    assert pool.free_pages == free and g.held(1) == 4
    assert pool.window_tables()[5].shape == (pool.slots, 4)
    assert (pool.window_tables(1)[5] > 0).all()
    pool.release_windows(1)
    assert g.held_pages == 0


def test_a_pool_with_window_groups_refuses_a_draft_pool():
    with pytest.raises(ValueError, match="no\\s+draft model"):
        window_pool(draft=SimpleNamespace(attn_ops=[]))


def test_a_trie_over_window_groups_holds_a_snapshot_a_window_op():
    """Since PR 46 a window layer is a citizen of the snapshot protocol: under
    a prefix cache the pool holds, for each window op, ids + 1 times the
    pages of one window (ring - 1) in the op's own page format, and the trie
    counts the ids; the ops that keep everything get none."""
    pool = window_pool(prefix_cache=True, snapshots=2)
    assert sorted(pool.snapshots) == ["attn_window_1", "attn_window_2"]
    ring = pool.window_groups[5].ring
    assert pool.snapshots["attn_window_1"]["k"].shape[0] == 3 * (ring - 1)
    assert pool.prefix_cache.snapshots == 2
    assert window_pool(prefix_cache=False, snapshots=2).snapshots is None
