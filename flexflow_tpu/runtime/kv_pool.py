"""The KV page pool: the pages, who holds them, and what moves them.

Three layers, the arrows one way only::

    ServingEngine (queue, slots, per-slot arrays, program table, tick)
          |  reserve / commit / publish / release / export / import
          v
    KVPagePool: free list . per-holder Lease . device arrays (target,
          |     draft) . page movers (d2h, h2d) . slab validation
          |  match / insert / acquire / release / evict / promote
          v
    RadixPrefixCache (+ host tier): which prompt prefix is in which page

The engine asks the pool for pages and gives them back; it never names
the free list or mutates the trie. The pool has no lock of its own:
every method runs under the caller's engine lock (the trie keeps its
``prefix-cache`` condition for the tier publisher thread). One pool
geometry, and two kinds of attention page: an op says what of a sequence
it keeps (`kv_keep()`: everything, or a window), and the pool groups its
ops by that. The ops that keep everything share the one page table the
free list, the leases and the trie are about; each window size has a
`WindowPageGroup` beside it, a ring of pages a slot, with pool arrays of
its own size. A configuration whose layers keep different state says so
here, not in the scheduler.
"""

from __future__ import annotations

import collections
import heapq
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from flexflow_tpu.logger import fflogger
from flexflow_tpu.runtime import faultinject, locks


class _TrieNode:
    """One cached KV page: the page_size-token chunk it encodes (its edge
    label from the parent), the pool page id holding its k/v, and the
    refcount of live requests whose page tables reference it.

    Tiering: ``tier`` is "hbm" (``page`` is a live pool page),
    "host" (the page was demoted — ``page`` is -1 and ``hostdata`` holds
    the pinned host copy, None while the async D2H publish is still in
    flight) or "dead" (a failed migration marked it for lazy reaping).
    ``gen`` is the migration generation: every demote/kill bumps it, so a
    late-completing publish for an abandoned migration is dropped by the
    ordered publisher instead of resurrecting a reused node."""

    __slots__ = ("chunk", "page", "parent", "children", "ref", "last_use",
                 "tier", "hostdata", "gen", "snap")

    def __init__(self, chunk, page, parent):
        self.chunk = chunk
        self.page = page
        self.parent = parent
        self.children = {}
        self.ref = 0
        self.last_use = 0
        self.tier = "hbm"
        self.hostdata = None
        self.gen = 0
        # a model with recurrent-state ops: the row of the pool's snapshot
        # arrays that holds every such op's state after EXACTLY the tokens
        # of the path that ends here; 0 = none
        self.snap = 0


class RadixPrefixCache:
    """Radix/trie index over prompt token prefixes at PAGE granularity.

    Each trie edge is exactly ``page_size`` tokens, so a path of depth d
    names a d-page prompt prefix and maps it to the d pool pages holding
    its KV — the page, not the token, is the unit of sharing because the
    pool scatters, gathers and refcounts pages. A page's KV at position j
    depends only on tokens [0..j] (causal attention), so any request
    whose prompt starts with the same ``d * page_size`` tokens can mount
    those pages read-only and prefill just its tail.

    TIERED (HBM -> host) CACHE: with ``host_pages > 0`` a
    refcount-0 page reclaimed under pool pressure MIGRATES to a pinned
    host-memory tier instead of dying — the node stays in the trie with
    ``tier == "host"``, its HBM page frees immediately, and the page
    payload (pool storage bytes + quantized scales, target AND draft
    pools) publishes to host memory on ONE ordered background publisher
    thread (the async-checkpointing pattern, runtime/checkpoint.py): the
    D2H starts in device order before the page can be reused, resolves
    off the hot path, and a generation check drops the publish if the
    node was killed/reused meanwhile. A later match against a
    host-resident edge PROMOTES it back: allocate a fresh HBM page, H2D
    the payload (bitwise — export/import never requantize), mount. The
    effective shared-prefix corpus is then host-RAM-sized, not
    HBM-sized. Tier invariant: on any root->node path the tiers read
    ``hbm* host*`` — demotion picks nodes with no HBM children,
    promotion walks the matched path root-down — so a mounted (hbm,
    ref>0) prefix never sits below a host page. The host tier itself is
    LRU-bounded at ``host_pages``: overflow evicts the oldest host leaf
    for real. Failure policy (FF_FAULT ``d2h_fail@migrate:<n>`` /
    ``h2d_fail@promote:<n>``): a failed demotion means the page dies
    exactly as it did without the tier; a failed promotion kills the
    host copy and falls back to cold prefill — never a stall, never a
    corrupt page mounted.

    Ownership protocol (the copy-on-write rule lives HERE, not in the
    kernels): a page in the trie is never written again — its producer
    published it only after prefill, and every borrower's tail/decode
    writes land in freshly allocated pages past the matched prefix.
    ``ref`` counts live requests mounting the page; retirement decrefs.
    A refcount-0 page stays cached (warm for the next hit) until
    ``evict()`` reclaims it under pool pressure, LRU-first and leaves
    only — an interior page must outlive its children, since a match
    walks through it. All host-side, O(prompt/page_size) per lookup;
    ``evict()`` walks the whole trie per pressure call, which is fine at
    the pool sizes this engine runs (hundreds of pages) — a
    persistently-maintained ref-0-leaf LRU makes reclaim O(need) if
    pool sizes grow by orders of magnitude.

    SNAPSHOTS (``snapshots`` > 0: the model keeps a recurrent state beside
    its pages, ops/mamba.py). The pages of a prefix are only half of what a
    borrower needs: the state ops' state AFTER the prefix is the other, and
    it cannot be computed from the pages. So a node may carry a snapshot id
    (``node.snap``, a row of the pool's snapshot arrays, ids 1..snapshots;
    row 0 is the scratch row a program that takes no snapshot writes), and
    the trie keeps ONE invariant: no page is cached that no snapshot makes
    reachable, i.e. every leaf carries a snapshot. ``match`` returns the
    path to the deepest node that carries one; ``insert_snapshot`` publishes
    a path only together with the snapshot on its last node; a snapshot
    leaves with its node (eviction, flush, forget: ``_kill_subtree`` hands
    the id back), and the ancestors it alone made reachable leave with it.
    The trie owns ids, free list and counts; the arrays are the pool's."""

    def __init__(self, page_size: int, host_pages: int = 0,
                 d2h=None, h2d=None, snapshots: int = 0):
        self.page_size = int(page_size)
        self.root = _TrieNode(None, -1, None)
        self.pages = 0          # HBM-page-holding nodes currently cached
        self.lookups = 0
        self.hits = 0
        self.tokens_saved = 0   # prefill positions served from cache
        self.evictions = 0      # PRESSURE evictions only (flushes don't
        #                         count — they are not a pool signal)
        self._tick = 0          # monotonic LRU clock (bumped per lookup)
        # incremental mirrors of the trie's refcount state, so stats()
        # and the per-tick health() probe never walk the trie
        self._live_refs = 0     # sum of node.ref
        self._shared = 0        # nodes with ref > 1 right now
        # ---- host tier ----
        # d2h(pages) -> resolver() -> [payload, ...]: starts the async
        # copy of a LIST of pool pages host-ward (one batched gather per
        # demotion sweep) and returns the callable the ordered publisher
        # resolves off the hot path; h2d(pages, payloads): writes
        # payloads back into fresh pool pages (one batched writer
        # dispatch). KVPagePool passes its own movers; the pure-host
        # tier tests inject fakes — the state machine itself never
        # touches a device.
        self.host_pages = int(host_pages)
        if self.host_pages < 0:
            raise ValueError(f"host_pages={host_pages}: must be >= 0")
        # ---- snapshots of a recurrent state (ids 1..snapshots) ----
        self.snapshots = int(snapshots)
        if self.snapshots and self.host_pages:
            raise ValueError(
                "the host tier moves pages only: a demoted prefix would "
                "leave its snapshot behind (snapshots > 0 needs "
                "host_pages == 0)")
        self._free_snaps = list(range(self.snapshots, 0, -1))
        self.snapshots_taken = 0
        self.snapshot_hits = 0
        self.snapshots_evicted = 0  # left with their node, for any reason
        if self.host_pages and (d2h is None or h2d is None):
            raise ValueError("host_pages > 0 needs d2h and h2d callables")
        self.d2h = d2h
        self.h2d = h2d
        self.host_used = 0      # host-resident pages (pending included)
        self.demotions = 0
        self.promotions = 0
        self.demote_failures = 0
        self.promote_failures = 0
        self.host_evictions = 0  # host-LRU overflow kills (pages died)
        # ordered publisher: demotions publish host-ward in submission
        # order on ONE daemon thread (the async-checkpointing pattern);
        # _cv guards hostdata/gen/queue handoff between that thread and
        # the engine-lock holder. Structural trie mutation stays under
        # the ENGINE lock only (the pool's callers hold it).
        self._cv = locks.make_condition("prefix-cache")
        self._pending = collections.deque()
        self._inflight = 0
        self._publisher: Optional[threading.Thread] = None
        # depth-1 tier transitions for the router's tier-aware affinity:
        # (first-page chunk, "host"|"hbm"|None) — None means the prefix
        # died entirely (affinity entries pointing at it should drop)
        self.tier_events = collections.deque(maxlen=4096)

    def _chunk(self, prompt, i: int, ns=None):
        ps = self.page_size
        tup = tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])
        if ns is not None and i == 0:
            # namespace salt: KV depends on the LoRA adapter
            # the prompt was prefilled under, so cached prefixes must
            # never cross tenants — salting the FIRST edge partitions
            # the whole trie per adapter (every deeper edge hangs under
            # it). The salted first chunk is also the router's
            # adapter-aware affinity key (first_chunk()).
            return ("ns", ns) + tup
        return tup

    @staticmethod
    def first_chunk(tokens, ns=None):
        """The trie's first-edge key for ``tokens`` (one page worth of
        prompt) under adapter namespace ``ns`` — the fleet router's
        affinity hash, kept in one place so the two layers cannot
        drift."""
        tup = tuple(int(t) for t in tokens)
        return (("ns", ns) + tup) if ns is not None else tup

    def match(self, prompt, max_pages: int, ns=None) -> List[_TrieNode]:
        """Longest cached page-aligned prefix of ``prompt``, capped at
        ``max_pages``; returns the node path root-down (possibly empty).
        Does NOT take references or bump hit statistics — the caller
        commits with acquire()/note_admitted() only once admission is
        certain (a request that stays queued on pool pressure re-matches
        every tick and must leave refcounts AND counters untouched)."""
        self._tick += 1
        node, path = self.root, []
        limit = min(int(max_pages), len(prompt) // self.page_size)
        for i in range(limit):
            child = node.children.get(self._chunk(prompt, i, ns))
            if child is None:
                break
            if child.tier == "dead":
                # a migration failed on the publisher thread; the node
                # was only MARKED there (trie structure is engine-lock
                # territory) — reap it lazily here
                self._kill_subtree(child)
                break
            path.append(child)
            node = child
        for n in path:
            n.last_use = self._tick
        if self.snapshots:
            # a borrower resumes from a state: the match ends at the
            # deepest node that carries one, deeper pages are not leased
            while path and not path[-1].snap:
                path.pop()
        return path

    def note_admitted(self, matched_pages: int):
        """Commit one admission's lookup to the hit statistics — called
        exactly once per ADMITTED request, never for retried matches."""
        self.lookups += 1
        if matched_pages:
            self.hits += 1
            self.tokens_saved += matched_pages * self.page_size
            if self.snapshots:
                self.snapshot_hits += 1

    def acquire(self, nodes):
        for n in nodes:
            if n.tier != "hbm":  # the cross-tier refcount rule: only a
                #  resident page can be mounted — promote first
                raise AssertionError(
                    f"acquire on a {n.tier}-tier page: host-resident "
                    f"prefix pages must be promoted before mounting")
            n.ref += 1
            self._live_refs += 1
            if n.ref == 2:
                self._shared += 1

    def release(self, nodes):
        for n in nodes:
            n.ref -= 1
            self._live_refs -= 1
            if n.ref == 1:
                self._shared -= 1
            if n.ref < 0:  # accounting bug, not a recoverable state
                raise AssertionError(
                    f"prefix-cache refcount underflow on page {n.page}")

    def insert(self, prompt, matched, start: int,
               pages: List[int], ns=None) -> List[_TrieNode]:
        """Publish a finished prefill's full-prompt pages: ``pages[j]``
        holds chunk ``start + j`` of ``prompt``, appended under the
        ``matched`` path. Each created node starts at ref 1 (the
        publishing request still mounts it). Stops at the first chunk
        that already exists — the caller's duplicate page for it stays
        private (only possible when the match was capped below an
        existing deeper path)."""
        node = matched[-1] if matched else self.root
        created = []
        for j, page in enumerate(pages):
            chunk = self._chunk(prompt, start + j, ns)
            if chunk in node.children:
                break
            child = _TrieNode(chunk, page, node)
            child.ref = 1
            self._live_refs += 1
            child.last_use = self._tick
            node.children[chunk] = child
            node = child
            created.append(child)
            self.pages += 1
        return created

    # ---- snapshots --------------------------------------------------------

    def insert_snapshot(self, prompt, matched, start: int, pages: List[int],
                        snap: int, ns=None):
        """Publish a finished prefill of a model with recurrent-state ops:
        ``pages[j]`` holds chunk ``start + j`` of ``prompt`` and ``snap``
        the state after the LAST of them. Walks down from the ``matched``
        path: a chunk that is cached already is passed through (the
        caller's duplicate page stays private), a missing one gets the
        caller's page (ref 1, as ``insert``). The snapshot goes on the
        last node unless it has one. Returns (created nodes, whether the
        trie took ``snap``); an id it did not take is the caller's to
        hand back (``release_snapshot_id``)."""
        node = matched[-1] if matched else self.root
        created = []
        for j, page in enumerate(pages):
            chunk = self._chunk(prompt, start + j, ns)
            child = node.children.get(chunk)
            if child is None:
                child = _TrieNode(chunk, page, node)
                child.ref = 1
                self._live_refs += 1
                node.children[chunk] = child
                created.append(child)
                self.pages += 1
            child.last_use = self._tick
            node = child
        took = bool(pages) and not node.snap
        if took:
            node.snap = int(snap)
            self.snapshots_taken += 1
        return created, took

    def snapshot_id(self) -> int:
        """Pop a free snapshot id; 0 when every one is on a node."""
        return self._free_snaps.pop() if self._free_snaps else 0

    def release_snapshot_id(self, snap: int) -> None:
        if snap:
            self._free_snaps.append(int(snap))

    def evict_snapshot(self, protect=()) -> List[int]:
        """Pressure on the snapshot ids: the least recently used unmounted
        leaf outside ``protect`` leaves, with its snapshot and the
        ancestors it alone made reachable. Returns the pages freed
        (empty: every leaf is mounted or protected)."""
        keep = set(id(n) for n in protect)
        leaves = [n for n in self._iter_nodes()
                  if not n.children and n.ref == 0 and id(n) not in keep]
        if not leaves:
            return []
        self.evictions += 1
        return self._kill_reachable(min(leaves, key=lambda n: n.last_use),
                                    keep)

    def _kill_reachable(self, node, keep=()) -> List[int]:
        """``_kill_subtree(node)`` and, where the trie holds snapshots, the
        chain of ancestors left without child and snapshot (pages that no
        snapshot makes reachable any more), as far as they are unmounted
        and outside ``keep``."""
        parent = node.parent
        freed = self._kill_subtree(node)
        while (self.snapshots and parent is not None
               and parent is not self.root and not parent.children
               and not parent.snap and parent.ref == 0
               and id(parent) not in keep):
            node, parent = parent, parent.parent
            freed.extend(self._kill_subtree(node))
        return freed

    @property
    def snapshots_held(self) -> int:
        return self.snapshots - len(self._free_snaps)

    def _iter_nodes(self):
        stack = list(self.root.children.values())
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

    def cached_paths(self) -> List[Tuple[np.ndarray, object, int]]:
        """Every root-to-leaf cached prefix, hottest first, as
        ``(tokens, ns, last_use)`` — the evacuation manifest a
        preempted/retiring replica walks. Tokens are
        reconstructed from the edge chunks themselves (the first edge's
        ``("ns", ns)`` salt is peeled back into the namespace), so the
        caller can re-export each path with export_prefix_slab under the
        exact per-version/per-adapter key it was cached under. Leaves
        only: exporting a leaf path carries every interior page, and the
        importer dedupes shared prefixes. Dead (lost-host-copy) nodes
        prune their subtrees — there is nothing to evacuate below them."""
        out = []
        for first, child in self.root.children.items():
            if first and first[0] == "ns":
                ns, toks0 = first[1], first[2:]
            else:
                ns, toks0 = None, first
            stack = [(child, toks0)]
            while stack:
                node, toks = stack.pop()
                if node.tier == "dead":
                    continue
                kids = [(c.chunk, c) for c in node.children.values()
                        if c.tier != "dead"]
                if not kids:
                    out.append((np.asarray(toks, np.int32), ns,
                                node.last_use))
                    continue
                for chunk, c in kids:
                    stack.append((c, toks + chunk))
        out.sort(key=lambda e: -e[2])
        return out

    def evict(self, need: int, protect=(), pressure: bool = True) \
            -> List[int]:
        """Reclaim up to ``need`` HBM pages, oldest last_use first;
        returns the freed page ids. Without a host tier this evicts
        refcount-0 LEAVES and the page dies; with ``host_pages > 0`` and
        ``pressure=True`` the page DEMOTES instead — the node stays in
        the trie host-resident (eligible nodes are ref-0 with no HBM
        children, preserving the hbm*-then-host* path invariant) and the
        payload publishes host-ward asynchronously in order. ``protect``
        excludes a just-matched path the caller is about to acquire.
        Reclaiming a node may expose its parent — the sweep cascades.
        ``pressure=False`` (hot-swap flush, leak accounting) kills
        outright — host copies included, since both tiers hold KV that a
        weight swap staled — and stays out of the ``evictions``
        pool-pressure signal."""
        keep = set(id(n) for n in protect)
        demote = pressure and self.host_pages > 0

        def reclaimable(n):
            if n.ref != 0 or id(n) in keep or n.tier == "reaped":
                return False
            if not pressure:
                # flush kills outright — any tier, leaves only
                return not n.children
            if n.tier != "hbm":
                return False
            if demote:
                # demotion keeps the node: children only need to be
                # non-HBM so the hbm*-then-host* path invariant holds
                return all(c.tier != "hbm" for c in n.children.values())
            return not n.children

        heap = [(n.last_use, id(n), n) for n in self._iter_nodes()
                if reclaimable(n)]
        heapq.heapify(heap)
        freed: List[int] = []
        selected: List[_TrieNode] = []
        while heap and (len(freed) + len(selected) < need
                        or not pressure):
            _, _, n = heapq.heappop(heap)
            if not reclaimable(n):
                continue        # a cascade re-push raced a state change
            parent = n.parent
            if demote and n.tier == "hbm":
                if faultinject.active_plan().fire("d2h_fail", "migrate"):
                    # failed demotion: the page dies exactly as it did
                    # before a host tier existed
                    self.demote_failures += 1
                    freed.extend(self._kill_subtree(n))
                else:
                    # mark now (the cascade must see a non-HBM child);
                    # the ONE batched D2H snapshot happens below,
                    # before any freed page can be reused
                    n.tier = "host"
                    n.hostdata = None
                    n.gen += 1
                    self.pages -= 1
                    self.host_used += 1
                    self.demotions += 1
                    self._tier_event(n, "host")
                    selected.append(n)
                self.evictions += 1
            else:
                freed.extend(self._kill_reachable(n, keep))
                if pressure:
                    self.evictions += 1
            while parent is not None and parent.tier == "reaped":
                parent = parent.parent  # the chain left with its snapshot
            if parent is not None and parent is not self.root \
                    and reclaimable(parent):
                heapq.heappush(heap, (parent.last_use, id(parent), parent))
        # a failed-demotion kill (d2h_fail on a parent) may have reaped
        # an already-selected descendant — its page was freed by the
        # kill, so it must not reach the snapshot (a page -1 gather
        # would read junk and double-free)
        selected = [n for n in selected if n.tier == "host"]
        if selected:
            freed.extend(self._demote_sweep(selected))
            # host-LRU capacity is enforced per SWEEP (a mid-sweep
            # victim could be a selected-but-unsnapshot node, whose kill
            # would leak its pool page): after the snapshot every host
            # node is a legal victim
            self._make_host_room()
        return freed

    # ---- the HBM -> host tier state machine ------------------------------

    def _tier_event(self, node, tier):
        """Record a depth-1 tier transition for the router's tier-aware
        prefix affinity: the first-page chunk IS the affinity key."""
        if node.parent is self.root:
            self.tier_events.append((node.chunk, tier))

    def _kill_subtree(self, node) -> List[int]:
        """Remove ``node`` (and its now-unreachable descendants — all
        non-HBM by the path invariant when a migration kills an interior
        node) from the trie. Bumps every generation so late publishes
        abandon, returns the HBM pages freed."""
        if node.tier == "reaped":
            return []
        if node.parent is not None \
                and node.parent.children.get(node.chunk) is node:
            del node.parent.children[node.chunk]
        self._tier_event(node, None)
        freed: List[int] = []
        stack = [node]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            n.children = {}
            if n.ref:
                raise AssertionError(
                    f"killing a mounted prefix page (ref={n.ref})")
            if n.tier == "hbm":
                freed.append(n.page)
                self.pages -= 1
            elif n.tier in ("host", "dead"):
                self.host_used -= 1
                if n.page >= 0:
                    # selected-for-demotion but not yet snapshot: its
                    # pool page is still allocated — free it too
                    freed.append(n.page)
            if n.snap:
                self._free_snaps.append(n.snap)
                self.snapshots_evicted += 1
                n.snap = 0
            n.tier = "reaped"
            n.page = -1
            n.hostdata = None
            n.gen += 1      # abandon any in-flight migration publish
        with self._cv:
            self._cv.notify_all()   # wake promoters waiting on a corpse
        return freed

    def _demote_sweep(self, nodes) -> List[int]:
        """ONE batched D2H snapshot for a whole eviction sweep's
        demotions (per-page slicing was measurable host overhead on
        small hosts): the slices are enqueued BEFORE the freed pages can
        be reused (device programs execute in order: snapshot
        before donate), and the ordered publisher resolves
        them to pinned host memory off the hot path. Returns the freed
        HBM page ids."""
        pages = [n.page for n in nodes]
        handle = self.d2h(list(pages))
        gens = []
        for n in nodes:
            n.page = -1
            gens.append(n.gen)
        with self._cv:
            self._pending.append((list(nodes), gens, handle))
            self._inflight += len(nodes)
            self._cv.notify_all()
        self._ensure_publisher()
        return pages

    def _make_host_room(self):
        """LRU within the host tier: overflow evicts the oldest host
        LEAVES for real (host nodes' children are host by the
        invariant, so a leaf always exists while host_used > 0). ONE
        trie walk collects a whole sweep's victims — dead nodes (failed
        publishes awaiting reap: budget, no data) first, then oldest
        last_use — and the outer loop re-walks only when killing leaves
        exposed new ones. Nodes selected for demotion in the CURRENT
        sweep (page still >= 0, snapshot not yet taken) are never
        victims — killing one would leak its pool page."""
        while self.host_used > self.host_pages:
            cands = [n for n in self._iter_nodes()
                     if n.tier in ("host", "dead") and not n.children
                     and n.page < 0]
            if not cands:
                return
            cands.sort(key=lambda n: (0 if n.tier == "dead" else 1,
                                      n.last_use))
            for n in cands:
                if self.host_used <= self.host_pages:
                    break
                if n.tier == "reaped" or n.children:
                    continue
                self._kill_subtree(n)
                self.host_evictions += 1

    def promote(self, node, page) -> bool:
        """H2D one host-resident node into freshly allocated HBM
        ``page``; True on success (see promote_path)."""
        if node.tier == "hbm":
            return True
        return self.promote_path([node], [page]) == 1

    def promote_path(self, nodes, pages) -> int:
        """Promote host-resident ``nodes`` (a matched path's host tail,
        root-down) into ``pages``: per-node failure checks first —
        FF_FAULT ``h2d_fail@promote:<n>``, a publish that never landed —
        truncate the run and KILL the failed copy (the caller falls back
        to cold prefill past it: never a stall, never a corrupt page
        mounted); then ONE batched H2D writes the surviving prefix back
        bitwise. Returns the number promoted; unused pages are the
        caller's to reclaim."""
        ok_nodes, payloads = [], []
        for node in nodes:
            if node.tier != "host":
                break
            if faultinject.active_plan().fire("h2d_fail", "promote"):
                self.promote_failures += 1
                self._kill_subtree(node)
                break
            payload = self.host_payload(node)
            if payload is None:
                self.promote_failures += 1
                self._kill_subtree(node)
                break
            ok_nodes.append(node)
            payloads.append(payload)
        if not ok_nodes:
            return 0
        use = list(pages[:len(ok_nodes)])
        try:
            self.h2d(use, payloads)
        except Exception:   # noqa: BLE001 — any H2D loss falls back cold
            self.promote_failures += 1
            for node in ok_nodes:
                self._kill_subtree(node)
            return 0
        for node, page in zip(ok_nodes, use):
            node.page = int(page)
            node.tier = "hbm"
            node.hostdata = None
            node.gen += 1   # abandon any stale pending publish
            self.pages += 1
            self.host_used -= 1
            self.promotions += 1
            self._tier_event(node, "hbm")
        return len(ok_nodes)

    def host_payload(self, node, timeout: float = 60.0):
        """The node's host-tier payload, waiting (bounded) for an
        in-flight ordered publish; None if the node died or the publish
        never lands (the caller treats it as a promotion failure)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while node.tier == "host" and node.hostdata is None:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                self._cv.wait(left)
            return node.hostdata if node.tier == "host" else None

    def _ensure_publisher(self):
        if self._publisher is None or not self._publisher.is_alive():
            self._publisher = threading.Thread(
                target=self._publisher_main, daemon=True,
                name="ff-prefix-tier-publisher")
            self._publisher.start()

    def _publisher_main(self):
        """ONE background thread publishes demoted pages host-ward in
        submission order (the async-checkpointing ordered-publisher
        contract): resolve the D2H handle, then commit the payload ONLY
        if the node's generation still matches — an abandoned migration
        (the node was killed, flushed or re-promoted meanwhile) is
        dropped, never resurrected."""
        while True:
            with self._cv:
                while not self._pending:
                    self._cv.wait()
                nodes, gens, handle = self._pending.popleft()
            payloads, err = None, None
            try:
                payloads = handle()
            except Exception as e:  # noqa: BLE001 — a failed resolve is
                #   a failed demotion: the pages die, serving continues
                err = e
            with self._cv:
                self._inflight -= len(nodes)
                for i, (node, gen) in enumerate(zip(nodes, gens)):
                    if node.gen != gen or node.tier != "host":
                        continue    # abandoned migration: gen check
                    if err is not None:
                        # structural removal needs the engine lock —
                        # mark dead for lazy reaping by the next
                        # match/evict walk
                        node.tier = "dead"
                        node.hostdata = None
                        self.demote_failures += 1
                    else:
                        node.hostdata = payloads[i]
                self._cv.notify_all()
            if err is not None:
                fflogger.warning(
                    "prefix tier: D2H publish failed (%s) — %d pages "
                    "die as if untiered", err, len(nodes))

    def pending_migrations(self) -> int:
        with self._cv:
            return self._inflight

    def wait_migrations(self, timeout: float = 60.0) -> bool:
        """Quiesce the ordered publisher (drain/tests): True when every
        submitted demotion has published or abandoned."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._inflight > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return True

    def forget(self, prompt, ns=None) -> List[int]:
        """Kill the deepest unmounted, childless tail of ``prompt``'s
        cached path (any tier); returns freed HBM pages. The
        warm-the-import-writer helper: export, forget, re-import leaves
        the trie state unchanged with the writer program compiled."""
        path = self.match(prompt, len(prompt) // self.page_size, ns)
        freed: List[int] = []
        for n in reversed(path):
            if n.children or n.ref:
                break
            freed.extend(self._kill_subtree(n))
            if self.snapshots and n.parent is not self.root \
                    and n.parent.snap:
                break       # the rest of the path is another snapshot's
        return freed

    def flush_namespace(self, ns) -> List[int]:
        """Kill EVERY cached page under adapter namespace ``ns``, both
        tiers: the adapter's weights are being replaced, so KV computed
        under the old weights must never serve a prefix hit for the new
        ones (it would splice two weight versions into one stream).
        Refuses while any namespace page is mounted — impossible when
        the adapter itself is unpinned, since a mounted ns page always
        belongs to a live request holding the adapter. Returns the
        freed HBM pages."""
        roots = [c for c in self.root.children.values()
                 if isinstance(c.chunk, tuple) and len(c.chunk) >= 2
                 and c.chunk[0] == "ns" and c.chunk[1] == ns]
        for node in roots:
            stack = [node]
            while stack:
                n = stack.pop()
                if n.ref:
                    raise ValueError(
                        f"adapter namespace {ns!r} has a mounted cached "
                        f"page (ref={n.ref}): drain its requests before "
                        f"replacing the adapter")
                stack.extend(n.children.values())
        freed: List[int] = []
        for node in roots:
            freed.extend(self._kill_subtree(node))
        return freed

    def drain_tier_events(self) -> List:
        """Pop the recorded depth-1 tier transitions (router affinity
        feed)."""
        out = []
        while self.tier_events:
            out.append(self.tier_events.popleft())
        return out

    def live_refs(self) -> int:
        return self._live_refs

    def shared_pages(self) -> int:
        """Pages mounted by more than one live request right now."""
        return self._shared


class Lease:
    """What one holder has of the pool. ``reserve`` fills ``matched``
    (the cached prefix path, HBM-resident, no reference taken yet) and
    ``need`` (the fresh pages ``commit`` will pop). From ``commit`` on,
    ``nodes`` are the trie nodes whose refcount the holder holds (the
    matched path, then what it published), ``private`` the pages it owns
    outright, and ``pages`` its logical page list: matched pages, then
    fresh ones. ``hold`` tells a request's lease, which lives until
    ``release``, from a publisher's, which ends at ``publish``."""

    __slots__ = ("matched", "need", "hold", "nodes", "private", "pages",
                 "snap", "wants_snap")

    def __init__(self, matched, need: int, hold: bool,
                 wants_snap: bool = False):
        self.matched = matched
        self.need = need
        self.hold = hold
        self.nodes: List[_TrieNode] = []
        self.private: List[int] = []
        self.pages: List[int] = []
        # a model with recurrent-state ops: whether this prefill ends on a
        # page boundary (its final state IS the state after its last page:
        # ``reserve``), and from ``commit`` the snapshot row it writes
        # (0 = the scratch row: nothing will be published)
        self.wants_snap = wants_snap
        self.snap = 0

    @property
    def snap_from(self) -> int:
        """The snapshot row a hit resumes from (0 on a cold prefill)."""
        return self.matched[-1].snap if self.matched else 0


def op_keeps(op):
    """What of a sequence's rows attention op `op` keeps in the pool: None
    for all of them, else its window (ops/attention.py `kv_keep`)."""
    keep = getattr(op, "kv_keep", None)
    return keep() if keep is not None else None


def shared_page_groups(tables, shareable, cap: int):
    """Which live slots the paged kernel's shared-page form scores against
    one fetch of a page (ops/pallas_kernels.py `_paged_shared_kernel`), read
    off what a decode dispatch is handed and nothing else: `tables` (slots,
    width), each slot's page-table row; `shareable` (slots,), how many of
    its leading columns a slot could share: the whole pages of its prompt,
    which it attends in full and never writes (0: the slot is not live).
    -> [(member slots, pages)], at most `cap` members each and never one
    alone: every member's row holds the same pool pages in its first `pages`
    columns. A slot is in at most one group.

    Pages enter two rows only through the prefix trie (a hit's matched
    pages, by reference), so rows that agree, agree from column 0. The rows
    are sorted, which puts those with the longest common run side by side;
    a run of neighbours is one group as long as taking the next neighbour in
    saves no fewer page fetches than leaving it out (a slot that hit on a
    document's first pages alone does not drag a group of whole-document
    hits down to them), and a group over `cap` is split evenly: each part
    streams the pages once."""
    live = [int(s) for s in np.flatnonzero(np.asarray(shareable) > 0)]
    if cap < 2 or len(live) < 2:
        return []
    live.sort(key=lambda s: tables[s, :shareable[s]].tobytes())

    def common(a, b):
        n = int(min(shareable[a], shareable[b]))
        same = tables[a, :n] == tables[b, :n]
        return n if same.all() else int(same.argmin())

    run = [common(a, b) for a, b in zip(live, live[1:])] + [0]
    groups, i = [], 0
    while i < len(live) - 1:
        # a pair whose next neighbours share over twice as much: leave the
        # first of it alone
        if run[i] < 1 or 2 * run[i] < run[i + 1]:
            i += 1
            continue
        j, pages = i + 1, run[i]
        # live[i..j] share `pages` and save pages x (j - i) fetches
        while run[j] >= 1 \
                and min(pages, run[j]) * (j - i + 1) >= pages * (j - i):
            pages = min(pages, run[j])
            j += 1
        members = live[i:j + 1]
        parts = -(-len(members) // cap)
        for p in range(parts):
            part = members[p::parts]
            if len(part) > 1:
                groups.append((part, pages))
        i = j + 1
    return groups


class WindowPageGroup:
    """The pages of the attention ops that keep a window of ``window``
    positions: a RING of ``ring`` = ceil(window / page_size) + 1 pages a
    slot, the page of sequence positions [t * page_size, (t + 1) *
    page_size) in column ``t % ring`` of the slot's row of ``tables``.
    That is the bound: whatever the context length, a slot holds at most
    ``ring`` pages here, and the group's pool arrays are ``1 + slots *
    ring`` pages (page 0 the scratch page of idle slots), so seating never
    waits on this group and admission reserves by length for the global
    group alone.

    A slot takes pages as its context first reaches them (``seat`` for a
    prefilled prompt, ``reach`` before each decode dispatch) and, once it
    holds ``ring``, RECYCLES: the page whose rows have all left the window
    becomes the page of the next ``page_size`` positions. The column keeps
    its pool page through that (the device overwrites it row by row, and a
    row is overwritten ``ring * page_size`` >= ``window`` positions after
    it was written: only after it left the window), so a decode dispatch
    of several steps may be handed the table of its last step. ``release``
    returns everything with the slot. Invariant: ``free_pages + held_pages
    == num_pages - 1``."""

    def __init__(self, window: int, page_size: int, slots: int):
        self.window = int(window)
        self.page_size = int(page_size)
        self.ring = -(-self.window // self.page_size) + 1
        self.num_pages = 1 + int(slots) * self.ring
        self._free = list(range(self.num_pages - 1, 0, -1))
        self.tables = np.zeros((int(slots), self.ring), np.int32)
        self._held = [0] * int(slots)
        self._last = [-1] * int(slots)     # newest logical page a slot has
        self.recycled = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def held_pages(self) -> int:
        return sum(self._held)

    def held(self, slot: int) -> int:
        return self._held[slot]

    def seat(self, slot: int, length: int) -> None:
        """Give ``slot`` the pages a prefill of ``length`` positions
        leaves rows in: the page of the last position and the ``ring - 1``
        before it, as far as the sequence has them."""
        assert self._held[slot] == 0, f"window slot {slot} seated twice"
        last = (int(length) - 1) // self.page_size
        for t in range(max(0, last - self.ring + 1), last + 1):
            self.tables[slot, t % self.ring] = self._free.pop()
            self._held[slot] += 1
        self._last[slot] = last

    def reach(self, slot: int, position: int) -> None:
        """Make ``slot``'s ring hold the page of sequence ``position``
        (and of every position before it that a window still sees)."""
        last = int(position) // self.page_size
        while self._last[slot] < last:
            self._last[slot] += 1
            if self._held[slot] < self.ring:
                self.tables[slot, self._last[slot] % self.ring] = \
                    self._free.pop()
                self._held[slot] += 1
            else:
                self.recycled += 1

    def release(self, slot: int) -> None:
        row = self.tables[slot]
        self._free.extend(int(p) for p in row if p)
        row[:] = 0
        self._held[slot] = 0
        self._last[slot] = -1


class KVPagePool:
    """The one owner of the paged KV pool: the free list, the device
    arrays of the target (and draft) pool, the page movers and the trie.

    Page ids are popped from the low end (page 0 is the scratch page
    idle slots write), and every page is at any time exactly one of
    free, trie-owned or private to a lease: ``free_pages + trie pages +
    leased-private == num_pages - 1``. A lease's writes land in its
    fresh pages; a page enters the trie only through ``publish`` and is
    never written again (copy-on-write). ``gen`` / ``draft_gen`` are
    the Generators whose attention ops own the page format
    (``init_paged_cache``, ``export_page``, ``import_page``);
    ``page_import(build, *args)`` runs the one fixed-shape page writer
    through the engine's program table, so ``recompile_count`` and the
    retrace sentinel see it."""

    def __init__(self, gen, draft_gen, num_pages: int, page_size: int,
                 pages_per_slot: int, kv_dtype, prefix_cache: bool,
                 host_pages: int, page_import, slots: int = 0,
                 snapshots: int = 0):
        self.gen = gen
        self.slots = int(slots)
        self.draft_gen = draft_gen
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.pages_per_slot = int(pages_per_slot)
        self._page_import = page_import
        # beside the one table of the ops that keep everything: a ring of
        # pages a slot for each window size some op keeps
        self.window_groups: Dict[int, WindowPageGroup] = {
            w: WindowPageGroup(w, self.page_size, self.slots)
            for w in sorted({op_keeps(op) for op in gen.attn_ops} - {None})}
        if self.window_groups and draft_gen is not None:
            raise ValueError(
                "window layers keep a ring of pages a slot: no draft pool "
                "shares it (no draft model)")
        self.pool = self._init_arrays(gen, kv_dtype)
        # the draft pool mirrors the target pool's page GEOMETRY, page
        # IDS and storage dtype (its own KVH/Dh): one allocator, one page
        # table, one trie govern both — a shared prefix page id means
        # target AND draft prefix KV are both resident
        self.draft_pool = (self._init_arrays(draft_gen, kv_dtype)
                           if draft_gen is not None else None)
        self._free_pages = list(range(self.num_pages - 1, 0, -1))
        # host_pages > 0 gives the trie a pinned host-memory second tier
        # whose D2H/H2D are this pool's own movers
        state_ops = list(getattr(gen, "state_ops", ()))
        window_ops = [op for op in gen.attn_ops if op_keeps(op) is not None]
        snapshots = int(snapshots) if (
            prefix_cache and (state_ops or window_ops)) else 0
        self.prefix_cache = (RadixPrefixCache(
            self.page_size, host_pages=host_pages,
            d2h=self.d2h, h2d=self.h2d, snapshots=snapshots)
            if prefix_cache else None)
        # beside the slots' states: the snapshots the trie's nodes carry,
        # one row an id and row 0 the scratch row, in arrays of the ops'
        # own pool format: a recurrent op's state, and of a window layer
        # the pages of the window before the node's last position (ring - 1
        # pages an id: at a page-aligned match point that is all such a
        # layer knows). Written by the prefill programs only (a prefill
        # that ends on a page boundary), read by the hit prefills; None
        # for every engine without a prefix cache or with neither kind of op.
        self.snapshots = None
        if snapshots:
            repl = NamedSharding(gen.model.mesh, PartitionSpec())
            cdtype = gen._compute_dtype()
            self.snapshots = {
                op.name: jax.tree.map(
                    lambda a: jax.device_put(a, repl),
                    op.init_state_pool(snapshots + 1, cdtype))
                for op in state_ops}
            self.snapshots.update({
                op.name: jax.tree.map(
                    lambda a: jax.device_put(a, repl),
                    op.init_paged_cache(
                        (snapshots + 1)
                        * (self.window_groups[op_keeps(op)].ring - 1),
                        self.page_size, cdtype, kv_dtype=kv_dtype))
                for op in window_ops})

    def _init_arrays(self, gen, kv_dtype):
        # COMMITTED (replicated on the model's mesh) up front: an
        # uncommitted fresh pool has a different pjit signature
        # (UnspecifiedValue) than the committed arrays every program
        # RETURNS, so the second call to each warm program would silently
        # retrace and recompile it — a ~0.5 s stall in the serving loop
        # that the recompile counter could not see
        repl = NamedSharding(gen.model.mesh, PartitionSpec())
        cdtype = gen._compute_dtype()
        pool = {
            op.name: jax.tree.map(
                lambda a: jax.device_put(a, repl),
                op.init_paged_cache(self._op_pages(op), self.page_size,
                                    cdtype, kv_dtype=kv_dtype))
            for op in gen.attn_ops}
        # beside the pages: one recurrent state a slot for each op that
        # keeps one (no page table, no per-token bytes; the page movers
        # below walk the attention ops only)
        pool.update({
            op.name: jax.tree.map(lambda a: jax.device_put(a, repl),
                                  op.init_state_pool(self.slots, cdtype))
            for op in getattr(gen, "state_ops", ())})
        return pool

    def _op_pages(self, op) -> int:
        """Pages in `op`'s pool arrays: the pool's, or its window group's."""
        keep = op_keeps(op)
        return self.num_pages if keep is None \
            else self.window_groups[keep].num_pages

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    # ---- the window groups' rings ------------------------------------------

    def seat_windows(self, slot: int, length: int) -> None:
        for g in self.window_groups.values():
            g.seat(slot, length)

    def reach_windows(self, slot: int, position: int) -> None:
        for g in self.window_groups.values():
            g.reach(slot, position)

    def release_windows(self, slot: int) -> None:
        for g in self.window_groups.values():
            g.release(slot)

    def window_tables(self, slot=None) -> Dict[int, np.ndarray]:
        """{window: ring table}: every slot's (slots, ring), or one slot's
        (ring,) row; what a program of a model with window layers takes
        beside the global page table."""
        return {w: (g.tables if slot is None else g.tables[slot])
                for w, g in self.window_groups.items()}

    # ---- reserve / commit / publish / release ----------------------------

    def make_room(self, need: int, protect=()) -> bool:
        """Pool pressure: reclaim cold cached pages (LRU, refcount-0
        only; with a host tier they demote instead of dying) until
        ``need`` pages are free; ``protect`` is a just-matched path about
        to be mounted. False = still short."""
        short = need - len(self._free_pages)
        if short > 0 and self.prefix_cache is not None:
            self._free_pages.extend(
                self.prefix_cache.evict(short, protect=protect))
        return len(self._free_pages) >= need

    def reserve(self, prompt, ns, cap_pages: int, n_pages: int,
                hold: bool) -> Optional[Lease]:
        """Find room for ``n_pages`` logical pages of ``prompt`` under
        trie namespace ``ns``: the longest cached page-aligned prefix (at
        most ``cap_pages``) is shared, everything past it needs a fresh
        page, and so does every host-resident page of the match, to
        promote into. Short of pages, cold cached pages are evicted;
        then the host-tier tail of the match promotes root-down (parents
        first keeps the hbm*-then-host* invariant) through ONE batched
        H2D, a failed promotion truncating the match there — everything
        past it prefills cold. Nothing is held on return (no refcount
        moved, no page popped for the holder), so a lease dropped before
        ``commit`` costs nothing; None = pool pressure. A publisher
        (``hold=False``) whose full pages are all cached already gets
        ``need == 0`` back, and nothing was evicted or promoted."""
        trie = self.prefix_cache
        matched = (trie.match(prompt, cap_pages, ns=ns)
                   if trie is not None else [])
        if not hold and len(matched) >= len(prompt) // self.page_size:
            return Lease(matched, 0, hold)
        # where the trie holds snapshots, a prefill publishes only if its
        # last live row is the last row of a page: then the state it ends
        # with is the state after that path
        wants = bool(self.snapshots) and len(prompt) >= self.page_size \
            and len(prompt) % self.page_size == 0
        host = [n for n in matched if n.tier != "hbm"]
        if not self.make_room(n_pages - len(matched) + len(host), matched):
            return None
        if host:
            pages = [self._free_pages.pop() for _ in host]
            k = trie.promote_path(host, pages)
            self._free_pages.extend(pages[k:])
            matched = matched[:len(matched) - len(host) + k]
            if len(self._free_pages) < n_pages - len(matched):
                return None     # raced shortfall after a failed promotion
        return Lease(matched, n_pages - len(matched), hold, wants)

    def commit(self, lease: Lease) -> None:
        """Take what ``reserve`` found: pop the fresh pages and mount the
        matched path. Only a request's lease (``hold``) counts in the
        trie's hit statistics — once per admission, never per retry."""
        fresh = [self._free_pages.pop() for _ in range(lease.need)]
        if self.prefix_cache is not None:
            if lease.hold:
                self.prefix_cache.note_admitted(len(lease.matched))
            self.prefix_cache.acquire(lease.matched)
        lease.nodes = list(lease.matched)
        lease.private = fresh
        lease.pages = [n.page for n in lease.matched] + fresh
        if lease.wants_snap:
            # the matched path is mounted by now: pressure on the ids
            # cannot take the snapshot this prefill resumes from
            trie = self.prefix_cache
            lease.snap = trie.snapshot_id()
            if not lease.snap:
                self._free_pages.extend(trie.evict_snapshot())
                lease.snap = trie.snapshot_id()

    def publish(self, lease: Lease, prompt, ns, ok: bool) -> int:
        """A finished prefill offers ``prompt``'s FULL pages past the
        match for sharing; the adopted pages move from private to
        trie-owned (decref'd at release, freed only by eviction). A
        non-finite prefill (``ok`` False) publishes nothing: a NaN prompt
        cache must not infect later requests. A request's lease keeps
        its references; a publisher's ends here — its pages sit warm at
        refcount 0, exportable and evictable like any cached page, and
        what the trie did not adopt returns to the free list. Returns
        the pages published."""
        created = []
        trie = self.prefix_cache
        if trie is not None and trie.snapshots:
            # pages and the snapshot on the last of them, or nothing: no
            # page is published that no snapshot makes reachable
            took = False
            if ok and lease.snap:
                full = len(lease.matched)
                created, took = trie.insert_snapshot(
                    prompt, lease.matched, full,
                    lease.pages[full:len(prompt) // self.page_size],
                    lease.snap, ns=ns)
            if not took:
                trie.release_snapshot_id(lease.snap)
            lease.snap = 0
        elif ok and trie is not None:
            full = len(lease.matched)
            created = trie.insert(
                prompt, lease.matched, full,
                lease.pages[full:len(prompt) // self.page_size], ns=ns)
        if created:
            adopted = {n.page for n in created}
            lease.nodes.extend(created)
            lease.private = [p for p in lease.private if p not in adopted]
        if not lease.hold:
            self.release(lease)
        return len(created)

    def release(self, lease: Lease) -> None:
        """End a lease: trie-owned pages are DECREF'd — they stay cached,
        warm for the next hit, until the evictor needs them — and only
        the private pages (partial prompt page, bucket padding, decode
        appends) return to the free list."""
        if lease.nodes:
            self.prefix_cache.release(lease.nodes)
            lease.nodes = []
        self._free_pages.extend(lease.private)
        lease.private = []
        if lease.snap:      # committed and never published (a retired slot)
            self.prefix_cache.release_snapshot_id(lease.snap)
            lease.snap = 0

    def flush(self) -> int:
        """Evict EVERY refcount-0 cached page, both tiers, back to the
        free list; pages still mounted survive. Returns the HBM pages
        reclaimed."""
        freed = self.prefix_cache.evict(self.num_pages, pressure=False)
        self._free_pages.extend(freed)
        return len(freed)

    def flush_namespace(self, ns) -> None:
        """Drop everything cached under ``ns`` (RadixPrefixCache.
        flush_namespace: refuses while a page of it is mounted)."""
        if self.prefix_cache is not None:
            self._free_pages.extend(self.prefix_cache.flush_namespace(ns))

    def forget(self, prompt, ns=None) -> None:
        """Un-publish the unmounted tail of ``prompt``'s cached path."""
        self._free_pages.extend(self.prefix_cache.forget(prompt, ns))

    # ---- page movers (host tier + fleet handoff) --------------------------

    def d2h(self, pages):
        """Start the async D2H snapshot of a LIST of pool pages — ONE
        gather per pool array covers a whole demotion sweep or slab
        export; target AND draft pools (they share page ids), quantized
        scales included. Returns the resolver the ordered publisher (or
        a synchronous export) calls for the per-page payload list. The
        gathers are enqueued BEFORE any page can be reused, and device
        programs execute in order, so the HBM pages free immediately."""
        # FIXED gather width: eager jax ops compile per shape, so a
        # per-sweep-sized index would compile a fresh gather executable
        # every time the eviction need changes (~100 ms each on CPU —
        # measured as the whole tier overhead). Chunk to pages_per_slot
        # rows padded with scratch page 0; the pad payloads are dropped
        # at resolve.
        cap = self.pages_per_slot
        n = len(pages)
        chunks = []
        for i in range(0, n, cap):
            idx = np.zeros((cap,), np.int32)
            part = pages[i:i + cap]
            idx[:len(part)] = part
            chunks.append(idx)
        parts = []
        for idx in chunks:
            sub = {}
            for op in self.gen.attn_ops:
                sub[("t", op.name)] = op.export_page(
                    self.pool[op.name], idx)
            if self.draft_pool is not None:
                for op in self.draft_gen.attn_ops:
                    sub[("d", op.name)] = op.export_page(
                        self.draft_pool[op.name], idx)
            parts.append(sub)
        for sub in parts:
            for arrs in sub.values():
                for a in arrs.values():
                    try:
                        a.copy_to_host_async()
                    except (AttributeError, RuntimeError):
                        pass    # no async copy: resolve() blocks

        def resolve():
            out = []
            for ci, sub in enumerate(parts):
                host = {key: {name: np.asarray(a)
                              for name, a in arrs.items()}
                        for key, arrs in sub.items()}
                rows = min(cap, n - ci * cap)
                out.extend(
                    {key: {name: arr[i] for name, arr in arrs.items()}
                     for key, arrs in host.items()}
                    for i in range(rows))
            return out

        return resolve

    def h2d(self, pages, payloads):
        """Write migrated/handed-off page payloads back into the pools —
        ONE fixed-shape compiled writer serves EVERY promotion and
        handoff import: batches are padded to ``pages_per_slot`` rows
        with scratch page 0 (+ zero payload — the pool's designated
        garbage page absorbs the pad writes), so the program is
        count-independent and the tier/handoff hot paths compile nothing
        per page. Payload bytes land verbatim (scales ride along): the
        imported pages are BITWISE the donor's."""
        cap = self.pages_per_slot
        for i in range(0, len(pages), cap):
            self._h2d_chunk(pages[i:i + cap], payloads[i:i + cap])

    def _h2d_chunk(self, pages, payloads):
        have_draft = self.draft_pool is not None
        cap = self.pages_per_slot
        n = len(pages)
        idx = np.zeros((cap,), np.int32)
        idx[:n] = pages
        stacked = {
            key: {name: np.stack(
                [p[key][name] for p in payloads]
                + [np.zeros_like(payloads[0][key][name])] * (cap - n))
                for name in payloads[0][key]}
            for key in payloads[0]}

        def build():
            def kv_page_write(pool, dpool, payload, pages):
                out = {op.name: op.import_page(pool[op.name], pages,
                                               payload[("t", op.name)])
                       for op in self.gen.attn_ops}
                dout = dpool
                if have_draft:
                    dout = {op.name: op.import_page(
                        dpool[op.name], pages, payload[("d", op.name)])
                        for op in self.draft_gen.attn_ops}
                return out, dout

            return jax.jit(kv_page_write, donate_argnums=(0, 1))

        self.pool, dp = self._page_import(
            build, self.pool, self.draft_pool, stacked, idx)
        if have_draft:
            self.draft_pool = dp

    # ---- page slabs: what a prefill -> decode handoff moves ---------------

    def export_slab(self, prompt, ns, start_page: int = 0) -> Optional[Dict]:
        """Pages [start_page, last) of ``prompt``'s cached full-page
        prefix under ``ns`` as host bytes (ServingEngine.
        export_prefix_slab): host-tier pages straight from their pinned
        payload (no promotion), the HBM part in ONE batched D2H. None
        when the WHOLE prefix is not cached here."""
        if self.prefix_cache is None:
            return None
        last = len(prompt) // self.page_size
        if start_page < 0 or start_page >= last:
            if start_page == 0:
                return None     # last < 1: nothing page-aligned
            raise ValueError(
                f"start_page={start_page}: must be in [0, {last}) "
                f"for this prompt's {last} full prefix pages")
        path = self.prefix_cache.match(prompt, last, ns=ns)
        if len(path) < last:
            return None
        tail = path[start_page:]
        hbm = [n for n in tail if n.tier == "hbm"]
        hbm_payloads = self.d2h([n.page for n in hbm])() if hbm else []
        by_node = {id(n): p for n, p in zip(hbm, hbm_payloads)}
        payloads = []
        for node in tail:
            if node.tier == "host":
                payload = self.prefix_cache.host_payload(node)
                if payload is None:
                    return None
            else:
                payload = by_node[id(node)]
            payloads.append(payload)
        # the slab carries the exporter's SALTED namespace: an importer
        # on a different weight version files it under the exporter's
        # version key, so its own traffic can never hit cross-version KV
        return {"page_size": self.page_size,
                "tokens": prompt[:last * self.page_size].copy(),
                "ns": ns,
                "start_page": int(start_page),
                "payload": payloads}

    def import_slab(self, slab) -> int:
        """Scatter a peer's page slab into the pools through the one
        page writer and publish its chunks at refcount 0. The slab
        variant of reserve: pages already cached are skipped, the slab
        lands as far as the pool has room (a partial import keeps a
        valid prefix), and nothing is imported past a gap (it would
        cache a prefix whose middle was never written) or under a
        host-resident tail. Returns the pages written."""
        if self.prefix_cache is None:
            return 0
        if int(slab["page_size"]) != self.page_size:
            raise ValueError(
                f"slab page_size {slab['page_size']} != engine "
                f"page_size {self.page_size}: fleet replicas must "
                f"share the pool geometry")
        if not slab["payload"]:
            return 0
        p0 = slab["payload"][0]
        if any(k[0] == "d" for k in p0) != (self.draft_pool is not None):
            raise ValueError(
                "slab draft-pool payload mismatch: exporter and "
                "importer must agree on speculation")
        # the payload must match THIS pool's storage exactly:
        # import_page casts silently, so a dtype/geometry mismatch
        # (e.g. a bf16 slab into an int8 engine) would publish
        # saturating-cast garbage served as a prefix hit
        for op in self.gen.attn_ops:
            sub = p0.get(("t", op.name))
            if sub is None:
                raise ValueError(
                    f"slab payload missing attention op {op.name!r}:"
                    f" exporter and importer must run the same "
                    f"model")
            pool = self.pool[op.name]
            first = next(iter(pool))        # "k", or a latent op's "lat"
            pk = np.asarray(sub[first]) if first in sub else None
            if pk is None or pk.dtype != pool[first].dtype \
                    or pk.shape != pool[first].shape[1:]:
                raise ValueError(
                    f"slab payload for {op.name!r} is "
                    f"{getattr(pk, 'dtype', None)}"
                    f"{getattr(pk, 'shape', sorted(sub))} but this "
                    f"engine's pool stores "
                    f"{pool[first].dtype}{pool[first].shape[1:]}: fleet "
                    f"replicas must share kv_cache_dtype and pool "
                    f"geometry")
            if ("k_scale" in pool) != ("k_scale" in sub):
                raise ValueError(
                    f"slab scale presence mismatch for {op.name!r}: "
                    f"quantized and full-width pools cannot exchange"
                    f" pages")
        tokens = np.asarray(slab["tokens"], np.int32).reshape(-1)
        ns = slab.get("ns")
        sp = int(slab.get("start_page", 0))
        n = sp + len(slab["payload"])
        path = self.prefix_cache.match(tokens, n, ns=ns)
        # only extend under a fully HBM-resident prefix: fresh hbm nodes
        # below a host-tier tail would break the hbm*-then-host* path
        # invariant that promotion truncation and freed-page accounting
        # depend on. A host-resident tail means the prefix IS cached —
        # the next submit promotes it.
        if len(path) < sp or any(nd.tier != "hbm" for nd in path):
            return 0
        start = len(path)
        self.make_room(n - start, protect=path)
        take = min(n - start, len(self._free_pages))
        if take <= 0:
            return 0
        lease = Lease(path, take, hold=False)
        self.commit(lease)
        # a partial slab's payload list starts at page ``sp``
        self.h2d(lease.private, slab["payload"][start - sp:start - sp + take])
        return self.publish(lease, tokens, ns, ok=True)
