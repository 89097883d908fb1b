"""MCMC strategy search driver.

Reference: FFModel::optimize (model.cc:1663-1725) — simulated annealing over
per-op ParallelConfigs: accept a proposal if better, else with prob
exp(-alpha * diff), periodic reset-to-best every budget/100 iterations
(capped 1000). The reference starts from data parallelism and re-randomizes
one op's config per proposal (rewrite, model.cc:1652-1661). Here both are
wider, because on a mesh of more than one axis neither finds what the
simulator's own prices prefer (PERF.md, PR 36):

* **Where it starts** (`search_seeds`). Every seed is priced and the chains
  start from the cheapest; each competes with the annealed winner, so the
  result is never priced above any of them. The seeds: flat data parallelism
  over `data` (on a two-axis mesh it leaves the other axis replicated, holds
  every weight whole on every chip and LOSES: it is listed so that its price
  is on record); the uniform family (`uniform_seeds`: each mesh axis given
  one role for the whole graph: batch, sequence, the ops' parameter dim with
  CONTRACT on the matmul that consumes it, or none; all-batch, Megatron-style
  tensor parallelism and sequence parallelism are members, by role and not
  by model name); on a two-tier machine `hierarchical_strategy`; a warm
  start that carries over.
* **What a proposal rewrites**. One op, as in the reference (one in four:
  a strategy that treats an embedding-side or head-side layer differently
  stays reachable); or one TIED GROUP, the ops that play the same part in a
  repeated block (`tied_groups`: found from the graph, a decoder's four
  `ffn_gate`s), all to one map, and in two of three such moves every
  follower downstream (`follow_sources`: an elementwise op or norm with one
  producer shape) takes what its producer now delivers.

TPU version: proposals are mesh-expressible axis maps (each mesh axis is
assigned to one of the op's partitionable output dims or left replicated,
subject to divisibility) — the GSPMD-constrained SOAP space. The objective is
CostModel.iteration_time; when the C++ simulator library is built it replaces
the Python loop wholesale (flexflow_tpu/search/csim.py, csrc/sim.cc ff_mcmc:
the same seeds, groups and moves).

Where a collective is priced (PERF.md, PR 47): on the edge where it happens,
by what the two ends of that edge hold. An op's own time holds no psum; a
CONTRACT producer's (and a head-split attention's) partial sum is reduced on
the edge to its consumer, a reduce-scatter where the consumer shards the axis
on the dim the matmul produced and an all-reduce anywhere else (the chip
all-reduces and slices for a slice of the batch dim); a consumer whose
parameter dim is sharded over an axis hands back a partial input gradient,
reduce-scattered into a producer that shards the axis on the dim the consumer
contracts and all-reduced into any other (`CostModel.edge_held_time`, csim's
`edge_held_costs`); a tensor pays the largest of its edges' once. Only the
output nobody in the graph consumes (the head's, read by the loss) is reduced
inside its op.
"""

from __future__ import annotations

import math
import random
from typing import Dict, Optional

from flexflow_tpu.ops.base import InputOp
from flexflow_tpu.parallel.pconfig import ParallelConfig
from flexflow_tpu.search.cost_model import AxisMap, CostModel
from flexflow_tpu.search.machine import MachineModel


def legal_axis_maps(op, mesh_shape: Dict[str, int],
                    enable_parameter_parallel: bool = True,
                    enable_attribute_parallel: bool = True):
    """All axis maps for one op: each mesh axis -> None or a partitionable
    output dim whose size divides evenly.

    The two enable flags gate the proposal distribution the way the reference
    gates it (--enable-parameter-parallel, model.cc:2023 and linear.cu:1082;
    --enable-attribute-parallel for conv spatial dims, model.cc:2027 — minus
    the upstream bug where the latter sets the former)."""
    from flexflow_tpu.ffconst import OperatorType
    from flexflow_tpu.parallel.pconfig import CONTRACT, EXPERT, STAGE

    dims = list(op.partitionable_output_dims())
    out_shape = op.outputs[0].dims
    nd = len(out_shape)
    if not enable_parameter_parallel:
        weighted = {OperatorType.OP_LINEAR, OperatorType.OP_EMBEDDING,
                    OperatorType.OP_CONV2D, OperatorType.OP_MULTIHEAD_ATTENTION,
                    OperatorType.OP_BATCHNORM}  # channel dim shards scale/bias
        if op.op_type in weighted:
            param_dim = 1 if op.op_type in (
                OperatorType.OP_CONV2D, OperatorType.OP_BATCHNORM) else nd - 1
            dims = [d for d in dims if d != param_dim]
    if not enable_attribute_parallel and op.op_type in (
            OperatorType.OP_CONV2D, OperatorType.OP_POOL2D):
        dims = [d for d in dims if d not in (2, 3)]
    # CONTRACT (row-parallel) proposals, gated like parameter parallelism
    csize = op.contract_size() if enable_parameter_parallel else None
    # EXPERT (MoE expert-parallel) proposals, same gate: sharded weights
    esize = op.expert_parallel_size() if enable_parameter_parallel else None
    axes = [a for a in mesh_shape if mesh_shape[a] > 1]
    single_axis = set(op.single_axis_dims())
    maps = [{}]
    for ax in axes:
        new_maps = []
        size = mesh_shape[ax]
        for m in maps:
            new_maps.append({**m, ax: None})
            for d in dims:
                if d in single_axis and any(d2 == d for d2 in m.values()):
                    continue  # executor takes one mesh axis max for this dim
                deg = size
                for a2, d2 in m.items():
                    if d2 == d:
                        deg *= mesh_shape[a2]
                if d < len(out_shape) and out_shape[d] % deg == 0:
                    new_maps.append({**m, ax: d})
            if csize is not None:
                deg = size
                for a2, d2 in m.items():
                    if d2 == CONTRACT:
                        deg *= mesh_shape[a2]
                if csize % deg == 0:
                    new_maps.append({**m, ax: CONTRACT})
            if esize is not None:
                deg = size
                for a2, d2 in m.items():
                    if d2 == EXPERT:
                        deg *= mesh_shape[a2]
                if esize % deg == 0:
                    new_maps.append({**m, ax: EXPERT})
            # STAGE (pipeline-parallel) proposals: one mesh axis becomes the
            # ppermute ring the op's stacked layers pipeline over. Single
            # axis only — the GPipe/1F1B loop rotates around ONE named axis
            stages = op.pipeline_stages()
            if (stages and stages % size == 0 and size > 1
                    and not any(d2 == STAGE for d2 in m.values())):
                new_maps.append({**m, ax: STAGE})
        maps = new_maps
    return maps




def hierarchical_strategy(model, mesh_shape: Dict[str, int],
                          dcn_axes: Dict[str, int],
                          enable_parameter_parallel: bool = True,
                          enable_attribute_parallel: bool = True
                          ) -> Dict[str, AxisMap]:
    """First-class ICI/DCN candidate (ROADMAP item 4): place the
    once-per-step parallelism (data, STAGE) on the DCN-spanning axes and
    keep the per-layer-collective parallelism (CONTRACT/TP) inside ICI —
    the hierarchy the two-tier machine model prices but a flat proposal
    distribution only finds by luck. Per op the candidate is chosen from
    the op's LEGAL axis maps by a placement score, so the result always
    simulates, lints, and compiles. ``optimize_strategies`` seeds the
    anneal with it (and keeps it as a competing ``best``) whenever the
    machine model declares DCN axes."""
    from flexflow_tpu.parallel.pconfig import CONTRACT, STAGE

    dcn = {ax for ax, hosts in (dcn_axes or {}).items()
           if int(hosts) > 1 and mesh_shape.get(ax, 1) > 1}
    out: Dict[str, AxisMap] = {}
    for op in model.ops:
        if isinstance(op, InputOp):
            continue
        best, best_score = {}, float("-inf")
        for am in legal_axis_maps(op, mesh_shape,
                                  enable_parameter_parallel,
                                  enable_attribute_parallel):
            score = 0.0
            for ax, d in am.items():
                if d is None:
                    continue
                if ax in dcn:
                    # batch/stage across hosts: one grad sync / boundary
                    # hop per step. Anything else (CONTRACT psum, a
                    # sharded non-batch dim's halo/reshard) pays a
                    # per-layer collective at DCN bandwidth — the
                    # anti-pattern this candidate exists to avoid.
                    score += 2.0 if d in (0, STAGE) else -4.0
                else:
                    # spend ICI on the model dimensions first
                    score += (1.5 if d == CONTRACT
                              else 1.0 if d != 0 else 0.5)
            if score > best_score:
                best, best_score = am, score
        out[op.name] = {ax: d for ax, d in best.items() if d is not None}
    return out


ROLES = ("batch", "sequence", "parameter", None)


def _normalized(am: AxisMap) -> AxisMap:
    return {ax: d for ax, d in (am or {}).items() if d is not None}


def _role_dims(op):
    """(parameter dim, sequence dim) of an op's primary output, read off the
    op itself: the parameter dim is the one its weight contraction produces
    (`_contracted_output_dims`: a Linear's or an Embedding's channels, an
    attention's hidden, a convolution's out-channels), the sequence dim the
    first partitionable dim that is neither the sample dim nor that one (a
    decoder's positions, a convolution's rows). Either may be None."""
    nd = op.outputs[0].num_dims
    produced = [d % nd for d in op._contracted_output_dims]
    dims = op.partitionable_output_dims()
    pdim = next((d for d in produced if d in dims), None)
    sdim = next((d for d in dims if d != 0 and d not in produced), None)
    return pdim, sdim


def uniform_strategy(model, mesh_shape: Dict[str, int],
                     roles: Dict[str, Optional[str]],
                     op_maps: Dict[str, list]) -> Dict[str, AxisMap]:
    """ONE rule for every op: mesh axis -> role (`ROLES`). An axis on
    "batch" shards every op's sample dim, on "sequence" every op's sequence
    dim. On "parameter" it shards the parameter dim of each op that has one,
    or that op's contraction (CONTRACT) when its input already arrives
    sharded there over this axis (a column-parallel producer feeds a
    row-parallel consumer: the Megatron pair); an op with no parameter dim
    (elementwise, a norm) takes what its first producer delivers on the
    axis. Whatever the op's `legal_axis_maps` (``op_maps``) do not hold is
    dropped axis by axis, so the result always simulates and compiles."""
    from flexflow_tpu.parallel.pconfig import CONTRACT

    out: Dict[str, AxisMap] = {}
    delivered: Dict[str, AxisMap] = {}
    for op in model.ops:
        if isinstance(op, InputOp):
            continue
        pdim, sdim = _role_dims(op)
        src = next((t.owner_op for t in op.inputs if t.owner_op is not None
                    and not isinstance(t.owner_op, InputOp)), None)
        arrives = delivered.get(src.name, {}) if src is not None else {}
        want: AxisMap = {}
        for ax, role in roles.items():
            if role == "batch":
                want[ax] = 0
            elif role == "sequence":
                want[ax] = sdim
            elif role == "parameter":
                got = arrives.get(ax)
                if pdim is None:
                    want[ax] = got
                elif (got is not None and op.contract_size() is not None
                        and got == op.contract_input_dim(0)):
                    want[ax] = CONTRACT
                else:
                    want[ax] = pdim
        want = _normalized(want)
        legal = [_normalized(m) for m in op_maps[op.name]]
        for ax in reversed(list(want)):
            if want in legal:
                break
            del want[ax]
        out[op.name] = want
        delivered[op.name] = op.output_axis_map(want)
    return out


def uniform_seeds(model, mesh_shape: Dict[str, int],
                  op_maps: Dict[str, list]) -> Dict[str, Dict[str, AxisMap]]:
    """The family the annealer starts from: every assignment of the mesh's
    axes (size > 1) to a role, `uniform_strategy` of each, duplicates
    dropped. {name: strategy}; a name reads ``data=batch,model=parameter``.
    Two axes give 16 assignments (and about a dozen distinct strategies),
    three 64; past four axes the replicated role is left out."""
    import itertools

    axes = [ax for ax, n in mesh_shape.items() if n > 1]
    roles = ROLES if len(axes) <= 4 else ROLES[:-1]
    seeds: Dict[str, Dict[str, AxisMap]] = {}
    for combo in itertools.product(roles, repeat=len(axes)):
        strat = uniform_strategy(model, mesh_shape, dict(zip(axes, combo)),
                                 op_maps)
        if strat not in seeds.values():
            seeds[",".join(f"{ax}={r or 'none'}"
                           for ax, r in zip(axes, combo))] = strat
    return seeds


def tied_groups(model) -> list:
    """Ops that play the same part in a repeated block, as lists of op
    names in graph order (every op is in exactly one list; most of a
    non-repeating graph's lists hold one op). Found from the graph alone:
    an op's signature is its class, operator type, input, output and weight
    shapes and how far back each of its producers lies; a run of the op
    list in which a block of signatures repeats back to back (a decoder's
    layers) ties each position of the block across the repeats. The widest
    such run is taken first, then what lies left and right of it."""
    ops = [op for op in model.ops if not isinstance(op, InputOp)]
    index = {op.name: i for i, op in enumerate(ops)}
    sig = [(type(op).__name__, op.op_type,
            tuple(tuple(t.dims) for t in op.inputs),
            tuple(tuple(t.dims) for t in op.outputs),
            tuple(tuple(w.shape) for w in op.weight_specs()),
            tuple(i - index[t.owner_op.name] for t in op.inputs
                  if t.owner_op is not None and t.owner_op.name in index))
           for i, op in enumerate(ops)]
    groups = []

    def split(lo, hi):
        best = None  # (covered, start, period)
        for p in range(1, (hi - lo) // 2 + 1):
            run = 0
            for i in range(lo, hi - p):
                run = run + 1 if sig[i] == sig[i + p] else 0
                if run >= p:
                    covered = (run // p + 1) * p
                    if best is None or covered > best[0]:
                        best = (covered, i - run + 1, p)
        if best is None:
            groups.extend([ops[i].name] for i in range(lo, hi))
            return
        covered, start, p = best
        split(lo, start)
        for k in range(p):
            groups.append([ops[i].name
                           for i in range(start + k, start + covered, p)])
        split(start + covered, hi)

    split(0, len(ops))
    return groups


def follow_sources(model) -> Dict[str, str]:
    """{follower: producer}: an op with no parameter dim whose producers
    all deliver its own output shape (an elementwise op, a norm) may take
    its first producer's output map in the move that rewrites the
    producer."""
    out = {}
    for op in model.ops:
        if isinstance(op, InputOp) or _role_dims(op)[0] is not None:
            continue
        srcs = [t.owner_op for t in op.inputs if t.owner_op is not None
                and not isinstance(t.owner_op, InputOp)]
        if srcs and all(tuple(t.dims) == tuple(op.outputs[0].dims)
                        for t in op.inputs):
            out[op.name] = srcs[0].name
    return out


def follow_choice(op, src_op, src_map: AxisMap, maps: list) -> int:
    """Index in ``maps`` (the follower's legal maps) of the map that
    equals what ``src_op`` delivers under ``src_map``; -1 if none does."""
    want = _normalized(src_op.output_axis_map(src_map))
    return next((j for j, m in enumerate(maps) if _normalized(m) == want),
                -1)


def search_seeds(model, mesh_shape: Dict[str, int], cost, op_maps,
                 warm=None, epp: bool = True, eap: bool = True
                 ) -> Dict[str, Dict[str, AxisMap]]:
    """Every strategy the annealer may start from and must not lose to,
    by name: flat data parallelism over `data` (on a mesh of two axes it
    leaves the other replicated, holds every weight whole and loses; on a
    `data`-only mesh it is the family's batch member), the uniform family,
    on a two-tier machine the hierarchical ICI/DCN candidate, and a warm
    start that carries over."""
    seeds = {"data_parallel": data_parallel_strategy(model, mesh_shape)}
    for name, strat in uniform_seeds(model, mesh_shape, op_maps).items():
        if strat not in seeds.values():
            seeds[name] = strat
    if cost.machine.dcn_axes:
        seeds["hierarchical"] = hierarchical_strategy(
            model, mesh_shape, cost.machine.dcn_axes, epp, eap)
    if warm is not None:
        seeds["warm_start"] = warm
    return seeds


def data_parallel_strategy(model, mesh_shape: Dict[str, int]) -> Dict[str, AxisMap]:
    out = {}
    for op in model.ops:
        if isinstance(op, InputOp):
            continue
        am: AxisMap = {}
        if mesh_shape.get("data", 1) > 1 and op.outputs[0].num_dims > 0 \
                and op.outputs[0].dims[0] % mesh_shape["data"] == 0:
            am["data"] = 0
        out[op.name] = am
    return out


def warm_start_seed(model, mesh_shape: Dict[str, int],
                    warm_start, enable_parameter_parallel: bool = True,
                    enable_attribute_parallel: bool = True
                    ) -> Optional[Dict[str, AxisMap]]:
    """Normalize a saved strategy dict ({op_name: ParallelConfig}, e.g.
    searched at a DIFFERENT chip count) into a per-op axis-map seed legal
    on THIS mesh. Each saved map is restricted to the new mesh's axes and
    kept only when it matches one of the op's legal maps; illegal or
    missing maps fall back to data parallel. Returns None when nothing
    carries over — the elastic N->M transfer path (ISSUE 19d)."""
    if not warm_start:
        return None
    dp = data_parallel_strategy(model, mesh_shape)
    out: Dict[str, AxisMap] = {}
    carried = 0
    for op in model.ops:
        if isinstance(op, InputOp):
            continue
        pc = warm_start.get(op.name)
        am = None
        if pc is not None:
            saved = pc.axis_map if hasattr(pc, "axis_map") else pc
            if saved:
                cand = {ax: d for ax, d in saved.items()
                        if ax in mesh_shape and d is not None}
                # an empty restriction (the saved map used only axes this
                # mesh lacks) carries nothing — DP fallback, not replicated
                if cand:
                    legal = legal_axis_maps(op, mesh_shape,
                                            enable_parameter_parallel,
                                            enable_attribute_parallel)
                    norm = [{a: d for a, d in m.items() if d is not None}
                            for m in legal]
                    if cand in norm:
                        am = cand
                        carried += 1
        out[op.name] = am if am is not None else dp.get(op.name, {})
    return out if carried else None


def rank_mesh_candidates(model, candidates, strategies=None, measured=None):
    """Elastic-recovery helper (runtime/elastic.py): score candidate mesh
    shapes — factorizations of the SURVIVING device count over the saved
    axis names — by the cost model's iteration time under a re-partition
    of the saved strategy (each op keeps its saved axis map, restricted to
    the candidate's axes; ops without a usable saved map fall back to data
    parallel). Returns [(seconds, mesh_shape), ...] cheapest first; an
    infeasible candidate scores inf rather than raising, so the caller
    always gets a usable ranking. This is the "fast csim-ranked
    re-partition" path — a full re-search at the new count is
    ``research_strategies``. `measured` (a MeasuredTable, possibly
    cost-DB warm-started) prices every candidate from the same measured
    entries the original search used."""
    ops = [op for op in model.ops if not isinstance(op, InputOp)]
    scored = []
    for idx, mesh_shape in enumerate(candidates):
        try:
            cost = CostModel(model, mesh_shape, measured=measured)
            amaps: Dict[str, AxisMap] = {}
            dp = data_parallel_strategy(model, mesh_shape)
            for op in ops:
                pc = (strategies or {}).get(op.name)
                am = None
                if pc is not None and getattr(pc, "axis_map", None):
                    am = {ax: d for ax, d in pc.axis_map.items()
                          if ax in mesh_shape}
                amaps[op.name] = am if am else dp.get(op.name, {})
            scored.append((cost.iteration_time(amaps), idx, mesh_shape))
        except Exception:
            scored.append((float("inf"), idx, mesh_shape))
    scored.sort(key=lambda s: (s[0], s[1]))
    return [(t, shape) for t, _i, shape in scored]


def research_strategies(model, mesh_shape: Dict[str, int],
                        budget: int = 0,
                        warm_start=None) -> Dict[str, ParallelConfig]:
    """Re-run the strategy search at an explicit mesh — the elastic
    ``on_topology_change="research"`` entry point: the checkpointed
    strategy was searched for the OLD device count, and the paper's whole
    point is that the strategy is a searchable artifact of the machine,
    so a changed machine gets a fresh search. Budget defaults to the
    model's configured search_budget, else a small fixed sweep (the
    resumed job should start training again in seconds, not re-pay the
    original search). ``warm_start`` — the saved {op: ParallelConfig}
    from the N-chip job — seeds the M-chip anneal (ISSUE 19d), and the
    cost DB (when configured) supplies the measured entries, so the
    transfer re-measures zero already-keyed ops."""
    if budget <= 0:
        budget = getattr(model.config, "search_budget", 0) or 100
    return optimize_strategies(model, budget=budget,
                               alpha=getattr(model.config, "search_alpha",
                                             0.05),
                               mesh_shape=mesh_shape,
                               warm_start=warm_start)


def optimize_strategies(model, budget: int = 1000, alpha: float = 0.05,
                        mesh_shape: Optional[Dict[str, int]] = None,
                        machine: Optional[MachineModel] = None,
                        measured: Optional[Dict] = None,
                        seed: int = 0, verbose: bool = False,
                        use_native: bool = True,
                        warm_start=None) -> Dict[str, ParallelConfig]:
    """Run the search; returns {op_name: ParallelConfig} for the best found.
    ``warm_start`` ({op: ParallelConfig} from a previous search, possibly
    at a different chip count) becomes a competing seed after
    normalization against this mesh's legal maps."""
    mesh_shape = mesh_shape or model.config.mesh_shape
    cost = CostModel(model, mesh_shape, machine=machine, measured=measured)
    cfgflags = getattr(model, "config", None)
    epp = getattr(cfgflags, "enable_parameter_parallel", True)
    eap = getattr(cfgflags, "enable_attribute_parallel", True)
    warm = warm_start_seed(model, mesh_shape, warm_start, epp, eap)
    ops = [op for op in model.ops if not isinstance(op, InputOp)]
    # proposal distributions, precomputed once per op
    op_maps = {op.name: legal_axis_maps(op, mesh_shape, epp, eap) for op in ops}
    # the chains start from the CHEAPEST seed and `best` starts at that
    # seed's cost, so best-of-chain can only improve on it: the result is
    # never priced above any seed, however short or unlucky the chain
    seeds = search_seeds(model, mesh_shape, cost, op_maps, warm, epp, eap)
    groups = tied_groups(model)

    # which simulator priced the strategy rides the model (into
    # _search_summary) and the log: a search that quietly ran the Python
    # annealer is not the search the docs describe
    model._search_simulator = "python"
    out = None
    if use_native:
        from flexflow_tpu.logger import fflogger

        try:
            from flexflow_tpu.search.csim import native_optimize

            out = native_optimize(model, cost, mesh_shape, budget, alpha,
                                  seed, verbose=verbose, seeds=seeds)
            model._search_simulator = "native"
            fflogger.info("search: native C++ simulator ran (budget %d)",
                          budget)
        except (ImportError, OSError) as e:
            fflogger.warning(
                "search: native simulator unavailable (%s: %s) — running "
                "the Python annealer", type(e).__name__, e)
    if out is None:
        out = _python_anneal(model, cost, mesh_shape, ops, op_maps, seeds,
                             groups, budget, alpha, seed, verbose)
    model._search_report = _edge_report(cost, ops, out,
                                        model._search_report, groups)
    return out


def _python_anneal(model, cost, mesh_shape, ops, op_maps, seeds, groups,
                   budget, alpha, seed, verbose):
    """The annealer of `csrc/sim.cc` `ff_mcmc` in Python (no device-block
    moves: the Python objective is asked without placements): the same
    seeds, the same three kinds of proposal in the same shares, the same
    acceptance rule and reset-to-best."""
    rng = random.Random(seed)
    by_name = {op.name: op for op in ops}
    follows = follow_sources(model)
    seed_costs = {name: cost.iteration_time(s) for name, s in seeds.items()}
    started_from = min(seed_costs, key=seed_costs.get)
    current = dict(seeds[started_from])
    current_cost = seed_costs[started_from]
    best, best_cost = dict(current), current_cost
    reset_span = min(max(budget // 100, 1), 1000)  # reference model.cc:1673-1677

    for it in range(budget):
        if it % reset_span == 0 and it > 0:
            current, current_cost = dict(best), best_cost
        proposal = dict(current)
        if rng.randrange(4) == 0:
            op = rng.choice(ops)
            proposal[op.name] = rng.choice(op_maps[op.name])
        else:
            group = rng.choice(groups)
            follow = rng.randrange(3) != 0
            pick = rng.randrange(len(op_maps[group[0]]))
            moved = set(group)
            for name in group:
                proposal[name] = op_maps[name][pick]
            if follow:
                # in graph order, so a chain of followers moves as one
                for op in ops:
                    src = follows.get(op.name)
                    if src not in moved or op.name in moved:
                        continue
                    j = follow_choice(op, by_name[src], proposal[src],
                                      op_maps[op.name])
                    if j >= 0:
                        proposal[op.name] = op_maps[op.name][j]
                        moved.add(op.name)
        new_cost = cost.iteration_time(proposal)
        diff = new_cost - current_cost
        if diff < 0 or rng.random() < math.exp(-alpha * diff * 1e3):
            current, current_cost = proposal, new_cost
            if new_cost < best_cost:
                best, best_cost = dict(proposal), new_cost
        if verbose and it % max(budget // 10, 1) == 0:
            print(f"[search] iter {it}: current {current_cost * 1e3:.3f} ms, "
                  f"best {best_cost * 1e3:.3f} ms")

    if verbose:
        dp_cost = seed_costs["data_parallel"]
        print(f"[search] done: best {best_cost * 1e3:.3f} ms vs DP "
              f"{dp_cost * 1e3:.3f} ms ({dp_cost / max(best_cost, 1e-12):.2f}x)")

    out = {}
    for op in ops:
        am = best.get(op.name, {})
        out[op.name] = ParallelConfig.from_axis_map(
            op.outputs[0].num_dims, mesh_shape, am)
    model._search_report = {
        "seed_costs": seed_costs, "started_from": started_from,
        "winner": ("annealed" if best_cost < seed_costs[started_from]
                   else started_from)}
    return out


def _edge_report(cost, ops, out, report, groups):
    """The search's own account of its result, for `_search_summary`: the
    seeds' prices, where the chains started, what won, and how many of the
    graph's producer-consumer edges the result reshards (an edge counts
    when its reshard is priced above zero)."""
    edges = resharded = 0
    maps = {n: (pc.axis_map or {}) for n, pc in out.items()}
    for op in ops:
        for idx, t in enumerate(op.inputs):
            src = t.owner_op
            if src is None or isinstance(src, InputOp):
                continue
            edges += 1
            resharded += cost.edge_time(
                src.output_axis_map(maps.get(src.name, {})),
                op.input_axis_map(maps.get(op.name, {}), idx), t) > 0.0
    return {**report, "edges": edges, "resharded_edges": resharded,
            "tied_groups": len(groups)}


def optimize_strategies_multi(model, budget: int = 1000, alpha: float = 0.05,
                              mesh_shape: Optional[Dict[str, int]] = None,
                              machine: Optional[MachineModel] = None,
                              measured: Optional[Dict] = None,
                              seed: int = 0,
                              hbm_cap_bytes: Optional[float] = None,
                              warm_start=None, verbose: bool = False,
                              use_native: bool = True
                              ) -> Dict[str, ParallelConfig]:
    """Multi-objective search (ISSUE 19c): minimize step time SUBJECT TO a
    per-chip HBM cap. Runs the time-objective anneal, then — only if the
    winning strategy's footprint exceeds ``hbm_cap_bytes`` (default: the
    machine model's per-chip capacity) — greedily buys memory relief per
    op from ``cost_model.MEM_MODES`` (gradient remat, ZeRO-1/ZeRO-3
    optimizer/weight sharding, host offload), each priced by
    ``CostModel.mem_mode_time``, picking the (op, mode) upgrade with the
    best bytes-saved-per-second-added until under cap or out of relief.
    The chosen mode lands on each ``ParallelConfig.mem_mode`` so the
    executor (PR 9's real remat/ZeRO/offload modes) runs what the search
    priced, and fflint's footprint pass audits the same accounting.

    Stashes ``model._predicted_step_time`` (base + relief overhead) and
    ``model._search_summary`` for telemetry calibration
    (``cost_db.export_calibration``)."""
    from flexflow_tpu.search.cost_model import MEM_MODES

    mesh_shape = mesh_shape or model.config.mesh_shape
    cost = CostModel(model, mesh_shape, machine=machine, measured=measured)
    cap = (float(hbm_cap_bytes) if hbm_cap_bytes is not None
           else float(cost.machine.hbm_bytes))

    out = optimize_strategies(model, budget=budget, alpha=alpha,
                              mesh_shape=mesh_shape, machine=machine,
                              measured=measured, seed=seed, verbose=verbose,
                              use_native=use_native, warm_start=warm_start)
    ops = {op.name: op for op in model.ops if not isinstance(op, InputOp)}
    amaps = {n: (pc.axis_map or {}) for n, pc in out.items() if n in ops}
    base_time = cost.iteration_time(amaps)

    modes: Dict[str, str] = {n: "none" for n in amaps}

    def peak_bytes() -> float:
        return sum(cost.op_mem_bytes(ops[n], amaps[n], mem_mode=modes[n])
                   for n in amaps)

    while peak_bytes() > cap:
        # the upgrade with the best bytes-saved per second-added
        pick = None  # (ratio, name, mode)
        for n in amaps:
            cur_b = cost.op_mem_bytes(ops[n], amaps[n], mem_mode=modes[n])
            cur_t = cost.mem_mode_time(ops[n], amaps[n], modes[n])
            for mode in MEM_MODES:
                if mode in ("none", modes[n]):
                    continue
                saved = cur_b - cost.op_mem_bytes(ops[n], amaps[n],
                                                  mem_mode=mode)
                if saved <= 0:
                    continue
                dt = cost.mem_mode_time(ops[n], amaps[n], mode) - cur_t
                ratio = saved / max(dt, 1e-12)
                if pick is None or ratio > pick[0]:
                    pick = (ratio, n, mode)
        if pick is None:
            break  # no relief left: return over-cap, fflint will flag it
        _, n, mode = pick
        modes[n] = mode
        if verbose:
            print(f"[search] relief: {n} -> {mode} "
                  f"(peak {peak_bytes() / 1e9:.2f} GB, cap {cap / 1e9:.2f} GB)")

    for n, mode in modes.items():
        out[n].mem_mode = mode
    overhead = sum(cost.mem_mode_time(ops[n], amaps[n], modes[n])
                   for n in amaps)
    peak = peak_bytes()
    predicted = base_time + overhead
    model._predicted_step_time = predicted
    model._search_summary = {
        "simulator": model._search_simulator,
        "predicted_step_s": predicted,
        "base_step_s": base_time,
        "mem_overhead_s": overhead,
        "peak_hbm_bytes": peak,
        "hbm_cap_bytes": cap,
        "mem_modes": {n: m for n, m in modes.items() if m != "none"},
        "over_cap": peak > cap,
        **getattr(model, "_search_report", {}),
    }
    return out
