"""ctypes bridge to the native search core (csrc/sim.cc).

Builds the cost tables the C++ simulator consumes: per-op choice lists
(legal axis maps) with compute + grad-sync (and the part of it that holds
the compute stream) + per-device-memory costs and the device count each
choice spans, per-edge resharding cost matrices (forward and backward), the
reductions each edge causes (`CostModel.edge_held_time`, from the two ops'
raw maps) and tensor sizes (for placement transfers), and the annealer's
structured moves (tied groups, followers: `set_moves`). Compiles the
simulator on first use
(g++ through _native.build_native_lib — plain C ABI + ctypes).

Strategies evaluated here are (choice, place) pairs per op: the axis map
plus the contiguous aligned device block the op runs on (reference
ParallelConfig.device_ids, config.h:47-69).
"""

from __future__ import annotations

import ctypes
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from flexflow_tpu.ops.base import InputOp
from flexflow_tpu.parallel.pconfig import ParallelConfig

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "csrc",
                    "sim.cc")
_lib = None


def _load_lib():
    """Build (keyed by the hash of sim.cc) and load the native simulator.
    Raises OSError without a toolchain — search/driver.optimize_strategies
    decides, and logs, whether the Python annealer may stand in."""
    global _lib
    if _lib is not None:
        return _lib
    from flexflow_tpu._native import build_native_lib

    lib = ctypes.CDLL(build_native_lib(_SRC, "libffsim"))
    d, i32, i64 = (np.ctypeslib.ndpointer(dtype=np.float64, flags="C"),
                   np.ctypeslib.ndpointer(dtype=np.int32, flags="C"),
                   np.ctypeslib.ndpointer(dtype=np.int64, flags="C"))
    cd = ctypes.c_double
    tables = [ctypes.c_int, ctypes.c_int, ctypes.c_int,  # ops, edges, devices
              i64, d, d, d, d, i32,                      # op tables
              i32, i32, i64, d, d, d, i32]               # edge tables
    lib.ff_simulate.restype = cd
    lib.ff_simulate.argtypes = tables + [i32, i32, cd, cd, cd, cd]
    lib.ff_simulate_timeline.restype = cd
    lib.ff_simulate_timeline.argtypes = tables + [i32, i32, cd, cd, cd, cd,
                                                  d, d, d, d, d, d]
    lib.ff_mcmc.restype = cd
    lib.ff_mcmc.argtypes = tables + [i32, i32, cd, cd, cd, cd,
                                     ctypes.c_int,  # allow_place
                                     ctypes.c_int, i32, i32,  # tied groups
                                     i32, i64, i32,     # followers
                                     ctypes.c_int, cd, ctypes.c_uint64,
                                     i32, i32]
    _lib = lib
    return lib


class CompiledSearchProblem:
    """The graph + strategy space factorized into flat cost tables."""

    def __init__(self, model, cost, mesh_shape: Dict[str, int],
                 epp: bool = True, eap: bool = True):
        from flexflow_tpu.search.driver import (follow_sources,
                                                legal_axis_maps, tied_groups)

        self.ops = [op for op in model.ops if not isinstance(op, InputOp)]
        self.op_index = {op.name: i for i, op in enumerate(self.ops)}
        self.mesh_shape = mesh_shape
        self.cost = cost
        self.num_devices = 1
        for v in mesh_shape.values():
            self.num_devices *= v
        self.op_maps: List[List[dict]] = [
            legal_axis_maps(op, mesh_shape, epp, eap) for op in self.ops]

        # per-op cost tables
        offsets = [0]
        compute, sync, exposed, mem, ndev = [], [], [], [], []
        for op, maps in zip(self.ops, self.op_maps):
            for am in maps:
                compute.append(cost.op_compute_time(op, am))
                sync.append(cost.op_grad_sync_time(op, am))
                exposed.append(cost.op_exposed_sync_time(op, am))
                mem.append(cost.op_mem_bytes(op, am))
                parts = 1
                for ax, dd in am.items():
                    if dd is not None:
                        parts *= mesh_shape[ax]
                ndev.append(max(1, min(parts, self.num_devices)))
            offsets.append(len(compute))
        self.op_cost_offsets = np.asarray(offsets, np.int64)
        self.op_compute_costs = np.asarray(compute, np.float64)
        self.op_sync_costs = np.asarray(sync, np.float64)
        self.op_exposed_costs = np.asarray(exposed, np.float64)
        self.op_mem_bytes = np.asarray(mem, np.float64)
        self.op_ndev = np.asarray(ndev, np.int32)

        # edges (sorted by consumer index — required by the C scheduler)
        edges = []  # (src_idx, dst_idx, input_idx, tensor)
        for dst_idx, op in enumerate(self.ops):
            for input_idx, t in enumerate(op.inputs):
                if t.owner_op is None or isinstance(t.owner_op, InputOp):
                    continue
                src_idx = self.op_index[t.owner_op.name]
                edges.append((src_idx, dst_idx, input_idx, t))
        edges.sort(key=lambda x: x[1])
        self.edge_src = np.asarray([e[0] for e in edges], np.int32)
        self.edge_dst = np.asarray([e[1] for e in edges], np.int32)
        self.edge_bytes = np.asarray(
            [e[3].volume() * cost.dtype_bytes for e in edges], np.float64)
        # which tensor an edge carries: its reductions are paid once
        tensor_ids: Dict[int, int] = {}
        self.edge_tensor = np.asarray(
            [tensor_ids.setdefault(id(e[3]), len(tensor_ids)) for e in edges],
            np.int32)
        eoffsets = [0]
        ecosts: List[float] = []
        eheld: List[float] = []
        for src_idx, dst_idx, input_idx, t in edges:
            src_maps = self.op_maps[src_idx]
            dst_maps = self.op_maps[dst_idx]
            src_op = self.ops[src_idx]
            dst_op = self.ops[dst_idx]
            for pm in src_maps:
                # consumers see the producer's OUTPUT sharding (CONTRACT
                # axes deliver psum-replicated outputs)
                pm_out = src_op.output_axis_map(pm)
                for cm in dst_maps:
                    want = dst_op.input_axis_map(cm, input_idx)
                    ecosts.append(cost.edge_time(pm_out, want, t))
                    # the reductions the edge causes read the RAW maps
                    eheld.append(cost.edge_held_time(src_op, pm, dst_op, cm,
                                                     input_idx, t))
            eoffsets.append(len(ecosts))
        self.edge_cost_offsets = np.asarray(eoffsets, np.int64)
        self.edge_costs = np.asarray(ecosts, np.float64)
        self.edge_held_costs = np.asarray(eheld, np.float64)
        self.num_edges = len(edges)
        self.set_moves(tied_groups(model), follow_sources(model))

    def set_moves(self, groups, follows):
        """The annealer's structured moves as flat tables (sim.cc ff_mcmc):
        tied groups (lists of op names whose members hold one choice list)
        and, per follower, its producer and for each of the producer's
        choices the follower's own choice that equals what it delivers."""
        from flexflow_tpu.search.driver import follow_choice

        for g in groups:
            assert len({len(self.op_maps[self.op_index[n]]) for n in g}) == 1
        self.groups = [list(g) for g in groups]
        self.group_offsets = np.cumsum(
            [0] + [len(g) for g in groups]).astype(np.int32)
        self.group_members = np.asarray(
            [self.op_index[n] for g in groups for n in g], np.int32)
        src_arr, offsets, tbl = [], [0], []
        for i, op in enumerate(self.ops):
            src = self.op_index.get(follows.get(op.name), -1)
            src_arr.append(src)
            if src >= 0:
                tbl += [follow_choice(op, self.ops[src], m, self.op_maps[i])
                        for m in self.op_maps[src]]
            offsets.append(len(tbl))
        self.follow_src = np.asarray(src_arr, np.int32)
        self.follow_offsets = np.asarray(offsets, np.int64)
        self.follow_tbl = np.asarray(tbl or [-1], np.int32)

    def _table_args(self):
        return (len(self.ops), self.num_edges, self.num_devices,
                self.op_cost_offsets, self.op_compute_costs,
                self.op_sync_costs, self.op_exposed_costs, self.op_mem_bytes,
                self.op_ndev,
                self.edge_src, self.edge_dst, self.edge_cost_offsets,
                self.edge_costs, self.edge_bytes, self.edge_held_costs,
                self.edge_tensor)

    def _machine_args(self):
        from flexflow_tpu.search.cost_model import MEM_PENALTY_PER_BYTE

        m = self.cost.machine
        return (float(m.hbm_bytes), float(m.ici_bw), float(m.ici_latency),
                float(MEM_PENALTY_PER_BYTE))

    def _places_arr(self, places) -> np.ndarray:
        if places is None:
            return np.zeros(len(self.ops), np.int32)
        if isinstance(places, dict):
            return np.asarray([int(places.get(op.name, 0))
                               for op in self.ops], np.int32)
        return np.ascontiguousarray(places, np.int32)

    def choices_for(self, strategy: Dict[str, dict]) -> np.ndarray:
        out = np.zeros(len(self.ops), np.int32)
        for i, (op, maps) in enumerate(zip(self.ops, self.op_maps)):
            am = strategy.get(op.name, {})
            norm = {ax: d for ax, d in am.items() if d is not None}
            for j, m in enumerate(maps):
                if {ax: d for ax, d in m.items() if d is not None} == norm:
                    out[i] = j
                    break
            else:
                raise ValueError(
                    f"strategy for op {op.name!r} ({norm}) is not in its "
                    f"legal axis-map list — check divisibility against mesh "
                    f"{self.mesh_shape} and the enable-*-parallel flags")
        return out

    def simulate(self, choices: np.ndarray, places=None) -> float:
        lib = _load_lib()
        return lib.ff_simulate(
            *self._table_args(),
            np.ascontiguousarray(choices, np.int32),
            self._places_arr(places), *self._machine_args())

    def simulate_timeline(self, choices: np.ndarray, places=None):
        """Per-task schedule under `choices` (reference: simulator DOT export
        with start/end times, --taskgraph). Returns (total_seconds, rows)
        where rows = [{kind, name, start, finish, src, dst}]."""
        lib = _load_lib()
        n, ne = len(self.ops), self.num_edges
        cs, cf = np.zeros(n), np.zeros(n)
        ss, sf = np.zeros(n), np.zeros(n)
        ms, mf = np.zeros(max(ne, 1)), np.zeros(max(ne, 1))
        total = lib.ff_simulate_timeline(
            *self._table_args(),
            np.ascontiguousarray(choices, np.int32),
            self._places_arr(places), *self._machine_args(),
            cs, cf, ms, mf, ss, sf)
        rows = []
        for i, op in enumerate(self.ops):
            rows.append({"kind": "compute", "name": op.name,
                         "start": cs[i], "finish": cf[i]})
            if sf[i] > ss[i]:
                rows.append({"kind": "grad_sync", "name": op.name,
                             "start": ss[i], "finish": sf[i]})
        for e in range(ne):
            if mf[e] > ms[e]:
                rows.append({"kind": "comm",
                             "name": f"{self.ops[self.edge_src[e]].name}->"
                                     f"{self.ops[self.edge_dst[e]].name}",
                             "start": ms[e], "finish": mf[e],
                             "src": self.ops[self.edge_src[e]].name,
                             "dst": self.ops[self.edge_dst[e]].name})
        return total, rows

    def mcmc(self, init_choices: np.ndarray, budget: int, alpha: float,
             seed: int, init_places=None, restarts: int = 1,
             allow_place: bool = True
             ) -> Tuple[np.ndarray, np.ndarray, float]:
        """Run `restarts` independent annealing chains and keep the best.
        The reference runs one chain with periodic reset-to-best
        (model.cc:1673-1677); independent restarts cut the across-seed
        variance that grows with the choice space. Chains run concurrently
        (the C call releases the GIL), so K restarts cost ~1 chain of
        wall-clock; chain seeds are spaced by a large stride so different
        base seeds never share chains."""
        from concurrent.futures import ThreadPoolExecutor

        lib = _load_lib()
        init = np.ascontiguousarray(init_choices, np.int32)
        places = self._places_arr(init_places)
        K = max(1, restarts)

        def chain(k):
            c = np.zeros(len(self.ops), np.int32)
            p = np.zeros(len(self.ops), np.int32)
            cost = lib.ff_mcmc(
                *self._table_args(), init, places, *self._machine_args(),
                int(allow_place), len(self.groups), self.group_offsets,
                self.group_members, self.follow_src, self.follow_offsets,
                self.follow_tbl, budget, alpha, seed * 0x9E3779B1 + k, c, p)
            return c, p, cost

        if K == 1:
            return chain(0)
        with ThreadPoolExecutor(max_workers=min(K, 8)) as ex:
            results = list(ex.map(chain, range(K)))
        return min(results, key=lambda r: r[2])


_UNCACHEABLE = object()


def _machine_cache_key(machine):
    """Value identity for the machine in the search-table cache key. The
    machine parameters feed every table entry, so two cost models over
    different machines (e.g. the infinite-HBM no-penalty comparison)
    must not share cached tables. Never id()-based: addresses get
    reused. A dataclass repr carries class + every field by value; any
    machine whose repr (or an attribute's) is the default address form
    is _UNCACHEABLE — the caller bypasses the cache entirely (no stale
    tables on a recycled address, no unbounded never-matching inserts)."""
    if machine is None:
        return None
    r = repr(machine)
    if "object at 0x" not in r:
        return (type(machine).__qualname__, r)
    attrs = getattr(machine, "__dict__", None)
    if attrs is not None:
        items = tuple(sorted((k, repr(v)) for k, v in attrs.items()))
        if not any("object at 0x" in v for _, v in items):
            return (type(machine).__qualname__, items)
    return _UNCACHEABLE


def get_search_problem(model, cost, mesh_shape: Dict[str, int],
                       epp: bool = True, eap: bool = True
                       ) -> CompiledSearchProblem:
    """Cache CompiledSearchProblem per (graph, mesh, flags, measured?) on the
    model — the search pass and the --taskgraph export at compile share one
    cost-table build instead of enumerating the O(edges x choices^2) tables
    twice."""
    measured = getattr(cost, "measured", None)
    machine = getattr(cost, "machine", None)
    mkey = _machine_cache_key(machine)
    if mkey is _UNCACHEABLE:
        return CompiledSearchProblem(model, cost, mesh_shape, epp, eap)
    key = (tuple(op.name for op in model.ops),
           tuple(sorted(mesh_shape.items())), epp, eap,
           mkey,
           getattr(cost, "fsdp_axis", None),
           getattr(cost, "dtype_bytes", None),
           # content hash of the measured table: a refreshed or in-place
           # updated table must invalidate the cached cost tables (id() can
           # be reused by a new dict at the same address)
           hash(frozenset(measured.items())) if measured else None)
    cache = model.__dict__.setdefault("_csim_problem_cache", {})
    if key not in cache:
        cache[key] = CompiledSearchProblem(model, cost, mesh_shape, epp, eap)
    return cache[key]


def native_optimize(model, cost, mesh_shape: Dict[str, int], budget: int,
                    alpha: float, seed: int,
                    verbose: bool = False,
                    restarts: int = 4,
                    warm_start=None, seeds=None):
    """The native search: ``{op: ParallelConfig}``, with the seeds' prices,
    the start and the winner left on ``model._search_report``. ``seeds``
    ({name: strategy}, `driver.search_seeds`) are what
    `optimize_strategies` hands over; a caller that has none gets the same
    ones computed here (the tied groups and followers are the problem's
    own: `CompiledSearchProblem.set_moves`). The chains
    start from the cheapest seed, and every seed competes with the
    annealed winner (a two-tier machine's hierarchical candidate and a
    warm start among them, priced by the same C tables), so the result
    is never priced above any seed. A seed this mesh's legal maps do not
    hold (a stale warm start) is dropped, not fatal."""
    from flexflow_tpu.search.driver import search_seeds

    cfg = getattr(model, "config", None)
    epp = getattr(cfg, "enable_parameter_parallel", True)
    eap = getattr(cfg, "enable_attribute_parallel", True)
    prob = get_search_problem(model, cost, mesh_shape, epp, eap)
    if seeds is None:
        op_maps = {op.name: m for op, m in zip(prob.ops, prob.op_maps)}
        seeds = search_seeds(model, mesh_shape, cost, op_maps, warm_start,
                             epp, eap)
    choices, seed_costs = {}, {}
    for name, strat in seeds.items():
        try:
            choices[name] = prob.choices_for(strat)
        except ValueError:
            continue
        seed_costs[name] = prob.simulate(choices[name])
    started_from = min(seed_costs, key=seed_costs.get)
    init, init_cost = choices[started_from], seed_costs[started_from]
    dp_cost = seed_costs.get("data_parallel", init_cost)
    # FSDP shards every weight over the full fsdp mesh axis; a sub-mesh
    # placement cannot hold such a weight, so the annealer must not
    # propose device-block moves (compile would reject its own winner)
    allow_place = not getattr(cost, "fsdp_axis", "")
    best_c, best_p, best_cost = prob.mcmc(init, budget, alpha, seed,
                                          restarts=restarts,
                                          allow_place=allow_place)
    winner = "annealed"
    if init_cost <= best_cost:
        best_c, best_p, best_cost = (init, np.zeros(len(prob.ops), np.int32),
                                     init_cost)
        winner = started_from
    if verbose:
        print(f"[search/native] best {best_cost * 1e3:.3f} ms vs DP "
              f"{dp_cost * 1e3:.3f} ms "
              f"({dp_cost / max(best_cost, 1e-12):.2f}x), "
              f"{len(prob.ops)} ops, {prob.num_edges} edges, "
              f"{prob.num_devices} devices")
    out = {}
    for i, op in enumerate(prob.ops):
        am = prob.op_maps[i][int(best_c[i])]
        pc = ParallelConfig.from_axis_map(
            op.outputs[0].num_dims, mesh_shape, am)
        ndev = int(prob.op_ndev[prob.op_cost_offsets[i] + int(best_c[i])])
        start = int(best_p[i])
        pc.device_ids = tuple(range(start, start + ndev))
        out[op.name] = pc
    _snap_tied_blocks(model, out, prob.num_devices)
    model._search_report = {"seed_costs": seed_costs,
                            "started_from": started_from, "winner": winner}
    return out


def _snap_tied_blocks(model, out: Dict[str, ParallelConfig],
                      num_devices: int):
    """tie_weights PREFERENCE the annealer doesn't model: every op in a
    tie-connected component should share ONE device block. Since r5 the
    PlacementExecutor executes cross-block ties (per-step source-weight
    broadcast + gradient route-home), but the snapped strategy avoids
    that per-step transfer entirely, so the search still proposes only
    same-block tie components. Components (a source with several dests, a
    dest tied to several sources) are resolved together — a pairwise
    single pass is not a fixpoint: snapping pair 2 can re-break pair 1.
    Per component, pick the largest member block whose size every member's
    sharding degree divides; if none fits, the full mesh (block 0) —
    always valid. The simulated cost of the snapped strategy can differ
    from the annealer's estimate; correct-and-executable beats
    optimal-and-rejected."""
    tied = getattr(model, "_tied", None) or {}
    if not tied:
        return
    # union-find over tie edges
    parent: Dict[str, str] = {}

    def find(a):
        parent.setdefault(a, a)
        while parent[a] != a:
            parent[a] = parent[parent[a]]
            a = parent[a]
        return a

    for (dst_op, _), (src_op, _, _) in tied.items():
        if dst_op in out and src_op in out:
            parent[find(dst_op)] = find(src_op)
    comps: Dict[str, list] = {}
    for name in parent:
        comps.setdefault(find(name), []).append(name)

    def blk(pc):
        return ((min(pc.device_ids), len(pc.device_ids))
                if pc.device_ids else (0, num_devices))

    for members in comps.values():
        blocks = {blk(out[m]) for m in members}
        if len(blocks) <= 1:
            continue
        chosen = (0, num_devices)
        for cand in sorted(blocks, key=lambda b: -b[1]):
            if all(cand[1] % max(out[m].num_parts(), 1) == 0
                   for m in members):
                chosen = cand
                break
        ids = tuple(range(chosen[0], chosen[0] + chosen[1]))
        for m in members:
            out[m].device_ids = ids
