"""Tiered (HBM -> host) prefix cache: the tier state machine alone.

Sub-second pure-host unit tests (ISSUE 12 satellite) for
runtime/kv_pool.py RadixPrefixCache's host tier — no engine, no device,
no compiles: the D2H/H2D callables are injected fakes, so demote/promote
ordering under the ordered publisher, the cross-tier refcount rules, the
host-tier LRU and the abandoned-migration generation check are all
pinned as host logic. The engine-integrated paths (real pools, real
token identity) live in tests/test_disagg.py.
"""

import threading
import time

import numpy as np
import pytest

from flexflow_tpu.runtime import faultinject
from flexflow_tpu.runtime.kv_pool import RadixPrefixCache

PS = 2  # page size: tiny, so prompts stay readable


class FakeIO:
    """Injected batched D2H/H2D (the engine's real callables move page
    LISTS — one gather per demotion sweep, one padded writer dispatch
    per promotion batch): page payloads are dicts; ``gate(page)`` makes
    that page's publish wait on an Event (the deterministic in-flight
    window every ordering/abandonment test needs)."""

    def __init__(self):
        self.gates = {}
        self.published = []     # resolve completion order (page ids)
        self.written = []       # (page, payload) h2d writes
        self.h2d_boom = False

    def gate(self, page):
        ev = self.gates[page] = threading.Event()
        return ev

    def d2h(self, pages):
        def resolve():
            out = []
            for page in pages:
                ev = self.gates.get(page)
                if ev is not None:
                    assert ev.wait(30), \
                        f"gate for page {page} never opened"
                self.published.append(page)
                out.append({"page": page, "bytes": f"kv-{page}"})
            return out

        return resolve

    def h2d(self, pages, payloads):
        if self.h2d_boom:
            raise RuntimeError("injected H2D loss")
        self.written.extend(
            (int(p), pl) for p, pl in zip(pages, payloads))


def make_cache(host_pages=8):
    io = FakeIO()
    return RadixPrefixCache(PS, host_pages=host_pages,
                            d2h=io.d2h, h2d=io.h2d), io


def publish(cache, prompt, pages):
    """Publish ``pages`` for ``prompt`` the way a finished prefill does:
    inserted at ref 1, released to the warm ref-0 cached state."""
    prompt = np.asarray(prompt, np.int32)
    matched = cache.match(prompt, len(prompt) // PS)
    created = cache.insert(prompt, matched,
                           len(matched), list(pages))
    cache.release(created)
    return matched + created


def prompt_of(*chunks):
    return np.asarray([t for c in chunks for t in c], np.int32)


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


# ---- demote / promote round trip -----------------------------------------


def test_demote_publishes_and_promote_restores_bitwise_payload():
    cache, io = make_cache()
    path = publish(cache, prompt_of((1, 2), (3, 4)), [5, 6])
    freed = cache.evict(2)
    # leaf-first cascade: the deep page reclaims first, pages free
    # immediately (the D2H snapshot already started)
    assert sorted(freed) == [5, 6]
    assert [n.tier for n in path] == ["host", "host"]
    assert cache.pages == 0 and cache.host_used == 2
    assert cache.demotions == 2
    assert cache.wait_migrations(5)
    # promotion hands the SAME payload back through h2d
    assert cache.promote(path[0], 9)
    assert path[0].tier == "hbm" and path[0].page == 9
    assert io.written == [(9, {"page": 5, "bytes": "kv-5"})] \
        or io.written == [(9, {"page": 6, "bytes": "kv-6"})]
    assert cache.promotions == 1
    assert cache.host_used == 1 and cache.pages == 1
    # a re-match walks through the promoted page again
    m = cache.match(prompt_of((1, 2), (3, 4)), 2)
    assert [n.tier for n in m] == ["hbm", "host"]


def test_ordered_publisher_resolves_in_submission_order():
    cache, io = make_cache()
    publish(cache, prompt_of((1, 2)), [3])
    publish(cache, prompt_of((5, 6)), [4])
    g3, g4 = io.gate(3), io.gate(4)
    cache.match(prompt_of((1, 2)), 1)    # page 3 is now the NEWER use
    freed = cache.evict(2)
    assert sorted(freed) == [3, 4]
    # open the gates out of order: the ordered publisher still resolves
    # strictly in submission order (LRU order: 4 demoted first)
    g3.set()
    time.sleep(0.05)
    assert io.published == [], \
        "publish for page 3 must wait behind the earlier submission"
    g4.set()
    assert cache.wait_migrations(5)
    assert io.published == [4, 3]


def test_promote_waits_for_inflight_publish():
    cache, io = make_cache()
    (node,) = publish(cache, prompt_of((1, 2)), [3])
    gate = io.gate(3)
    cache.evict(1)
    assert node.tier == "host" and node.hostdata is None
    got = {}

    def promoter():
        got["ok"] = cache.promote(node, 7)

    t = threading.Thread(target=promoter)
    t.start()
    time.sleep(0.05)
    assert t.is_alive(), "promote must wait for the pending publish"
    gate.set()
    t.join(10)
    assert got["ok"] and node.tier == "hbm" and node.page == 7
    assert io.written[0][0] == 7


# ---- refcount rules across tiers -----------------------------------------


def test_refcount_rules_across_tiers():
    cache, _ = make_cache()
    (node,) = publish(cache, prompt_of((1, 2)), [3])
    # a mounted page never demotes
    cache.acquire([node])
    assert cache.evict(1) == []
    assert node.tier == "hbm"
    cache.release([node])
    # a demoted page cannot be mounted without promotion
    cache.evict(1)
    assert node.tier == "host"
    with pytest.raises(AssertionError, match="promoted before"):
        cache.acquire([node])
    assert cache.live_refs() == 0
    # promoted -> mountable again
    assert cache.promote(node, 9)
    cache.acquire([node])
    assert cache.live_refs() == 1
    cache.release([node])


def test_path_tier_invariant_hbm_then_host():
    """Demotion is deep-first (a node with an HBM child never demotes),
    so every root->node path reads hbm* then host* — the rule that keeps
    a mounted prefix from sitting below a host page."""
    cache, _ = make_cache()
    a, b, c = publish(cache, prompt_of((1, 2), (3, 4), (5, 6)),
                      [3, 4, 5])
    cache.evict(1)
    assert [n.tier for n in (a, b, c)] == ["hbm", "hbm", "host"]
    cache.evict(1)
    assert [n.tier for n in (a, b, c)] == ["hbm", "host", "host"]
    cache.evict(1)
    assert [n.tier for n in (a, b, c)] == ["host", "host", "host"]
    assert cache.wait_migrations(5)
    # promotion is root-first through _promote-style walks: promoting
    # the HEAD restores hbm->host ordering, never host->hbm
    assert cache.promote(a, 9)
    assert [n.tier for n in (a, b, c)] == ["hbm", "host", "host"]


# ---- host-tier LRU --------------------------------------------------------


def test_host_lru_evicts_oldest_for_real():
    cache, _ = make_cache(host_pages=2)
    n1 = publish(cache, prompt_of((1, 2)), [3])[0]
    n2 = publish(cache, prompt_of((5, 6)), [4])[0]
    n3 = publish(cache, prompt_of((7, 8)), [5])[0]
    cache.match(prompt_of((1, 2)), 1)   # n1 is the warmest
    freed = cache.evict(3)
    assert sorted(freed) == [3, 4, 5]
    assert cache.wait_migrations(5)
    # capacity 2: the third demotion killed the host tier's oldest
    assert cache.host_used == 2
    assert cache.host_evictions == 1
    tiers = {id(n): n.tier for n in (n1, n2, n3)}
    assert list(tiers.values()).count("host") == 2
    assert n1.tier == "host", "the warmest page must survive the LRU"
    # the killed prefix is gone from the trie entirely
    dead = n2 if n2.tier != "host" else n3
    assert cache.match(prompt_of(tuple(dead.chunk)), 1) == []


# ---- abandoned migrations (generation check) ------------------------------


def test_abandoned_migration_publish_is_dropped():
    """A node killed while its D2H publish is still in flight must NOT
    be resurrected by the late-completing payload — the generation
    check drops it (the PipelineLoader abandoned-pull rule applied to
    page migration)."""
    cache, io = make_cache()
    (node,) = publish(cache, prompt_of((1, 2)), [3])
    gate = io.gate(3)
    cache.evict(1)
    gen_at_demote = node.gen
    # flush kills the host copy while the publish is pending
    cache.evict(cache.host_pages + 8, pressure=False)
    assert node.tier == "reaped" and node.gen > gen_at_demote
    gate.set()
    assert cache.wait_migrations(5)
    assert node.hostdata is None, "late publish resurrected a dead node"
    assert cache.host_used == 0
    assert cache.match(prompt_of((1, 2)), 1) == []


def test_promote_after_republish_same_tokens_uses_new_generation():
    """Kill a host copy, republish the same chunk with a NEW page, then
    let the OLD publish land: the new node must be untouched (its own
    generation), and promoting it serves the new payload."""
    cache, io = make_cache()
    (old,) = publish(cache, prompt_of((1, 2)), [3])
    gate = io.gate(3)
    cache.evict(1)
    cache.evict(99, pressure=False)         # old copy dies, publish open
    (new,) = publish(cache, prompt_of((1, 2)), [6])
    gate.set()
    assert cache.wait_migrations(5)
    assert new.tier == "hbm" and new.page == 6
    cache.evict(1)
    assert cache.wait_migrations(5)
    assert new.hostdata == {"page": 6, "bytes": "kv-6"}


# ---- failure injection ----------------------------------------------------


def test_d2h_fail_page_dies_as_today(monkeypatch):
    monkeypatch.setenv("FF_FAULT", "d2h_fail@migrate:1")
    faultinject.reset()
    cache, io = make_cache()
    publish(cache, prompt_of((1, 2)), [3])
    publish(cache, prompt_of((5, 6)), [4])
    freed = cache.evict(2)
    # both pages free either way; the failed one's node is GONE (no
    # host copy), the second demotes normally
    assert sorted(freed) == [3, 4]
    assert cache.demote_failures == 1 and cache.demotions == 1
    assert cache.host_used == 1
    alive = [p for p in ((1, 2), (5, 6))
             if cache.match(prompt_of(p), 1)]
    assert len(alive) == 1
    assert cache.wait_migrations(5)


def test_d2h_fail_on_parent_reaps_selected_child_cleanly(monkeypatch):
    """Cascade corner (found by the engine identity tests): the sweep
    selects the leaf, then d2h_fail fires on its PARENT — the kill
    reaps the already-selected child too. The child's page must free
    exactly once and never reach the snapshot (a page -1 gather would
    read junk and double-free)."""
    monkeypatch.setenv("FF_FAULT", "d2h_fail@migrate:2")
    faultinject.reset()
    cache, io = make_cache()
    publish(cache, prompt_of((1, 2), (3, 4)), [5, 6])
    freed = cache.evict(2)
    assert sorted(freed) == [5, 6], "both pages free, each exactly once"
    assert all(p >= 0 for p in freed)
    assert cache.pages == 0 and cache.host_used == 0
    assert cache.demote_failures == 1
    assert cache.match(prompt_of((1, 2)), 1) == []
    assert cache.wait_migrations(5)
    assert io.published == [], "nothing may publish after the kill"


def test_h2d_fail_falls_back_cold(monkeypatch):
    monkeypatch.setenv("FF_FAULT", "h2d_fail@promote:1")
    faultinject.reset()
    cache, io = make_cache()
    (n1,) = publish(cache, prompt_of((1, 2)), [3])
    (n2,) = publish(cache, prompt_of((5, 6)), [4])
    cache.evict(2)
    assert cache.wait_migrations(5)
    assert not cache.promote(n1, 9), "injected h2d_fail must fail"
    assert cache.promote_failures == 1
    assert n1.tier == "reaped", "a failed promotion kills the host copy"
    assert cache.match(prompt_of((1, 2)), 1) == []
    # the next promotion (occurrence 2) succeeds — no sticky state
    assert cache.promote(n2, 9)
    assert n2.tier == "hbm"


def test_h2d_exception_falls_back_cold():
    cache, io = make_cache()
    (node,) = publish(cache, prompt_of((1, 2)), [3])
    cache.evict(1)
    assert cache.wait_migrations(5)
    io.h2d_boom = True
    assert not cache.promote(node, 9)
    assert cache.promote_failures == 1 and node.tier == "reaped"


# ---- compatibility and plumbing ------------------------------------------


def test_tier_off_is_the_old_evict():
    cache = RadixPrefixCache(PS)        # host_pages=0: no callables OK
    publish(cache, prompt_of((1, 2), (3, 4)), [3, 4])
    freed = cache.evict(2)
    assert sorted(freed) == [3, 4]
    assert cache.host_used == 0 and cache.demotions == 0
    assert cache.match(prompt_of((1, 2)), 1) == []
    with pytest.raises(ValueError, match="d2h and h2d"):
        RadixPrefixCache(PS, host_pages=4)


def test_flush_kills_both_tiers():
    cache, _ = make_cache()
    publish(cache, prompt_of((1, 2)), [3])
    publish(cache, prompt_of((5, 6)), [4])
    cache.evict(1)                       # one page host-resident
    assert cache.wait_migrations(5)
    freed = cache.evict(99, pressure=False)
    assert len(freed) == 1               # only the HBM page frees bytes
    assert cache.pages == 0 and cache.host_used == 0
    assert cache.evictions == 1, "flush must stay out of the pressure " \
                                 "signal"


def test_depth1_tier_events_feed_affinity():
    cache, _ = make_cache(host_pages=1)
    (n1,) = publish(cache, prompt_of((1, 2)), [3])
    publish(cache, prompt_of((5, 6)), [4])
    cache.evict(1)
    assert cache.wait_migrations(5)
    assert cache.promote(n1, 9) or True  # n1 may or may not be the LRU pick
    cache.evict(1)                       # second demotion overflows cap 1
    assert cache.wait_migrations(5)
    events = cache.drain_tier_events()
    assert events, "depth-1 transitions must be recorded"
    assert all(isinstance(k, tuple) and t in ("host", "hbm", None)
               for k, t in events)
    assert cache.drain_tier_events() == [], "drain must pop"


def test_forget_then_reinsert_is_clean():
    cache, _ = make_cache()
    p = prompt_of((1, 2), (3, 4))
    publish(cache, p, [3, 4])
    freed = cache.forget(p)
    assert sorted(freed) == [3, 4]
    assert cache.match(p, 2) == []
    publish(cache, p, [5, 6])
    assert [n.page for n in cache.match(p, 2)] == [5, 6]
