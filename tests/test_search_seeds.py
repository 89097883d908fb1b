"""The strategy search's seeds, tied moves and prices (ISSUE 36): the result
is never priced above a seed, repeated layers stay alike, memory counts a
replicated weight whole, a bf16 job is priced as one, and the two
simulators agree on the edge costs. ISSUE 47: a reduction is priced on the
edge where it happens, by what both ends hold. Graphs only: nothing
compiles, no device is used."""

import numpy as np
import pytest

from flexflow_tpu import ActiMode, AdamOptimizer, FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.ops.base import InputOp
from flexflow_tpu.parallel.pconfig import CONTRACT
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.csim import CompiledSearchProblem
from flexflow_tpu.search.driver import (follow_sources, legal_axis_maps,
                                        optimize_strategies,
                                        optimize_strategies_multi,
                                        search_seeds, tied_groups,
                                        uniform_seeds)

MESHES = {"data4": {"data": 4}, "data2_model2": {"data": 2, "model": 2}}


def _config(mesh, **kw):
    cfg = FFConfig(batch_size=8, mesh_shape=dict(mesh), **kw)
    cfg.enable_parameter_parallel = True
    return cfg


def build_mlp(mesh, **kw):
    ff = FFModel(_config(mesh, **kw))
    x = ff.create_tensor([8, 256], name="x")
    t = ff.dense(x, 1024, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 512, ActiMode.AC_MODE_RELU, name="fc2")
    ff.dense(t, 16, name="out")
    return ff


def build_cnn(mesh, **kw):
    ff = FFModel(_config(mesh, **kw))
    x = ff.create_tensor([8, 4, 16, 16], name="x")
    t = ff.conv2d(x, 8, 3, 3, 1, 1, 1, 1, ActiMode.AC_MODE_RELU, name="c1")
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0, name="p1")
    t = ff.flat(t, name="flat")
    ff.dense(t, 16, name="out")
    return ff


def build_llama(mesh, layers=3, **kw):
    ff = FFModel(_config(mesh, **kw))
    llama_lm(ff, 8, seq_len=32, hidden=64, layers=layers, heads=4, kv_heads=2,
             ffn_hidden=128, vocab_size=256)
    return ff


def build_cell():
    """The graph of `train-4k-search-4chip`: Mistral-7B's widths at depth 4,
    batch 4 x 4096, data 2 x model 2, bf16 under f32 masters and Adam."""
    cfg = FFConfig(batch_size=4, mesh_shape={"data": 2, "model": 2},
                   search_budget=2000, enable_parameter_parallel=True,
                   compute_dtype="bfloat16", master_dtype="float32")
    ff = FFModel(cfg)
    llama_lm(ff, 4, seq_len=4096, hidden=4096, layers=4, heads=32, kv_heads=8,
             ffn_hidden=14336, vocab_size=32768, rope_theta=1e6)
    ff.optimizer = AdamOptimizer(alpha=1e-4)
    return ff


@pytest.fixture(scope="module")
def cell():
    ff = build_cell()
    return ff, optimize_strategies_multi(ff, budget=2000)


def _ops(ff):
    return [op for op in ff.ops if not isinstance(op, InputOp)]


def _maps(ff, mesh):
    c = ff.config
    return {op.name: legal_axis_maps(op, mesh, c.enable_parameter_parallel,
                                     c.enable_attribute_parallel)
            for op in _ops(ff)}


def _total_mem(cost, ff, strategy):
    return sum(cost.op_mem_bytes(op, strategy[op.name]) for op in _ops(ff))


@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("build", [build_mlp, build_cnn, build_llama])
@pytest.mark.parametrize("native", [True, False])
def test_result_never_priced_above_a_seed(native, build, mesh):
    mesh = MESHES[mesh]
    ff = build(mesh)
    out = optimize_strategies(ff, budget=200, use_native=native, seed=3)
    assert ff._search_simulator == ("native" if native else "python")
    cost = CostModel(ff, mesh)
    places = {n: min(pc.device_ids) for n, pc in out.items() if pc.device_ids}
    got = cost.iteration_time({n: pc.axis_map or {} for n, pc in out.items()},
                              places)
    seeds = search_seeds(ff, mesh, cost, _maps(ff, mesh))
    assert "data_parallel" in seeds and len(seeds) >= 3
    report = ff._search_report
    assert set(report["seed_costs"]) == set(seeds)
    for name, strat in seeds.items():
        price = cost.iteration_time(strat)
        assert report["seed_costs"][name] == pytest.approx(price, rel=1e-9)
        assert got <= price * (1 + 1e-9), (name, got, price)
    assert report["started_from"] == min(report["seed_costs"],
                                         key=report["seed_costs"].get)
    assert report["winner"] in ("annealed", report["started_from"])
    assert 0 <= report["resharded_edges"] <= report["edges"]


def test_cell_search_keeps_the_four_layers_alike(cell):
    ff, best = cell
    s = ff._search_summary
    groups = tied_groups(ff)
    assert s["tied_groups"] == len(groups) == 14
    assert s["edges"] == 70
    assert s["resharded_edges"] <= s["edges"] // 4
    assert s["predicted_step_s"] <= min(s["seed_costs"].values())
    assert s["started_from"] == "data=batch,model=parameter"
    # ISSUE 47: the residual stream stays hidden-sharded over `model` (what
    # the chip runs fastest: PERF.md section 6, PR 47), every row-parallel
    # matmul and every attention reduce-scatters into it, the head keeps
    # the batch over `data`, and nothing is placed off block 0 (the step
    # stays ONE GSPMD program)
    maps = {n: {ax: d for ax, d in (pc.axis_map or {}).items()
                if d is not None} for n, pc in best.items()}
    for i in range(4):
        assert maps[f"res1_{i}"] == maps[f"res2_{i}"] \
            == {"data": 0, "model": 2}, (i, maps[f"res1_{i}"])
        assert maps[f"ffn_down_{i}"] == {"data": 0, "model": CONTRACT}
        assert maps[f"attn_{i}"] == {"data": 0, "model": 2}
    assert maps["lm_head"] == {"data": 0, "model": 2}
    assert {min(pc.device_ids) for pc in best.values()} == {0}
    assert s["seed_costs"]["data_parallel"] > 10 * s["predicted_step_s"], \
        "DP over one axis of two holds every weight whole: it must lose"
    assert not s["over_cap"] and s["peak_hbm_bytes"] < 16e9
    for group in groups:
        maps = {tuple(sorted((best[n].axis_map or {}).items(), key=str))
                for n in group}
        assert len(maps) == 1, (group, maps)
    # the same strategy in every run: the compiled step is read from the
    # compile cache
    again = optimize_strategies_multi(build_cell(), budget=2000)
    assert {n: pc.axis_map for n, pc in again.items()} \
        == {n: pc.axis_map for n, pc in best.items()}


def test_cell_memory_counts_a_replicated_weight_whole():
    ff = build_cell()
    mesh = ff.config.mesh_shape
    cost = CostModel(ff, mesh)
    assert (cost.dtype_bytes, cost.master_bytes, cost.opt_slots) == (2, 4, 2)
    seeds = uniform_seeds(ff, mesh, _maps(ff, mesh))
    all_batch = seeds["data=batch,model=batch"]
    megatron = seeds["data=batch,model=parameter"]
    assert megatron["ffn_down_2"] == {"data": 0, "model": CONTRACT}
    assert megatron["ffn_gated_2"] == megatron["ffn_gate_2"] \
        == {"data": 0, "model": 2}
    # 1.14 B parameters x 16 B whole on every chip, against half of it
    assert _total_mem(cost, ff, all_batch) > 16e9
    assert _total_mem(cost, ff, megatron) < 16e9
    state = sum(np.prod(w.shape) for op in _ops(ff)
                for w in op.weight_specs()) * 16.0
    acts = sum(t.volume() for op in _ops(ff) for t in op.outputs) * 2 / 4
    assert _total_mem(cost, ff, all_batch) == pytest.approx(state + acts)


@pytest.mark.parametrize("dtype,nbytes", [("bfloat16", 2), ("float32", 4)])
def test_prices_follow_the_compute_dtype(dtype, nbytes):
    mesh = MESHES["data2_model2"]
    ff = build_mlp(mesh, compute_dtype=dtype)
    cost = CostModel(ff, mesh)
    assert cost.dtype_bytes == nbytes
    assert CostModel(ff, mesh, dtype_bytes=4).dtype_bytes == 4
    op = ff.get_op_by_name("fc1")
    am = {"data": 0, "model": 1}
    io = (8 * 256 + 8 * 1024) * nbytes / 4
    m = cost.machine
    peak = m.peak_flops if nbytes == 2 else m.peak_flops_f32
    want = 3.0 * max(op.flops() / 4 / (peak * m.mxu_efficiency),
                     io / m.hbm_bw)
    assert cost.op_compute_time(op, am) == pytest.approx(want, rel=1e-12)
    # an edge moves the tensor at the compute dtype, forward and back
    t = op.outputs[0]
    fwd = cost.resharding_time({"data": 0, "model": 1}, {"data": 0}, t)
    assert fwd == pytest.approx(
        m.all_gather_time(t.volume() * nbytes / 4, 2, "model"), rel=1e-12)
    assert cost.edge_time({"data": 0, "model": 1}, {"data": 0}, t) \
        == pytest.approx(fwd + m.ici_latency, rel=1e-12)
    prob = CompiledSearchProblem(ff, cost, mesh)
    assert prob.edge_bytes[0] == t.volume() * nbytes


def test_f32_job_without_an_optimizer_counts_three_copies():
    mesh = MESHES["data4"]
    ff = build_mlp(mesh)
    cost = CostModel(ff, mesh)
    assert (cost.dtype_bytes, cost.master_bytes, cost.opt_slots) == (4, 4, 1)
    op = ff.get_op_by_name("fc1")
    assert cost.op_mem_bytes(op, {"data": 0}) \
        == op.weight_bytes() * 3 + op.output_bytes() / 4
    ff.optimizer = AdamOptimizer()
    assert CostModel(ff, mesh).opt_slots == 2


@pytest.mark.parametrize("mesh", list(MESHES))
def test_python_and_native_agree_on_the_edge_costs(mesh):
    mesh = MESHES[mesh]
    ff = build_llama(mesh, compute_dtype="bfloat16")
    cost = CostModel(ff, mesh)
    prob = CompiledSearchProblem(ff, cost, mesh)
    rs = np.random.RandomState(1)
    resharded = 0
    for _ in range(12):
        strategy = {op.name: prob.op_maps[i][rs.randint(len(prob.op_maps[i]))]
                    for i, op in enumerate(prob.ops)}
        a = prob.simulate(prob.choices_for(strategy))
        b = cost.iteration_time(strategy)
        assert a == pytest.approx(b, rel=1e-12)
        resharded += a > cost.iteration_time(
            search_seeds(ff, mesh, cost, _maps(ff, mesh))["data_parallel"])
    assert resharded, "random strategies reshard: the edges were priced"
    # every seed too: the family's parameter member holds CONTRACT producers
    # whose consumers keep a slice, and a head with no CONTRACT partner
    sliced = 0
    for name, strategy in search_seeds(ff, mesh, cost,
                                       _maps(ff, mesh)).items():
        assert prob.simulate(prob.choices_for(strategy)) == pytest.approx(
            cost.iteration_time(strategy), rel=1e-12), name
        sliced += any(
            CONTRACT in strategy[op.name].values() and any(
                strategy[c.name].get(ax) is not None
                for c in _ops(ff) if op.outputs[0] in c.inputs
                for ax, d in strategy[op.name].items() if d == CONTRACT)
            for op in _ops(ff))
    assert sliced, "no seed held a CONTRACT producer with a sliced consumer"
    # every table entry is the forward reshard plus its transpose, and
    # beside it the reductions the edge causes, from the RAW maps
    held = 0
    for e in range(prob.num_edges):
        src, dst = prob.ops[prob.edge_src[e]], prob.ops[prob.edge_dst[e]]
        off = prob.edge_cost_offsets[e]
        n_dst = len(prob.op_maps[prob.edge_dst[e]])
        idx, t = next((i, t) for i, t in enumerate(dst.inputs)
                      if t.owner_op is src)
        for i, pm in enumerate(prob.op_maps[prob.edge_src[e]]):
            for j, cm in enumerate(prob.op_maps[prob.edge_dst[e]]):
                p, c = src.output_axis_map(pm), dst.input_axis_map(cm, idx)
                if dst.inputs.count(t) == 1:
                    assert prob.edge_costs[off + i * n_dst + j] \
                        == pytest.approx(cost.resharding_time(p, c, t)
                                         + cost.resharding_time(c, p, t),
                                         rel=1e-12)
                    assert prob.edge_held_costs[off + i * n_dst + j] \
                        == pytest.approx(cost.edge_held_time(
                            src, pm, dst, cm, idx, t), rel=1e-12)
                held += prob.edge_held_costs[off + i * n_dst + j] > 0.0
    assert held, "no edge of the table holds a reduction"


def test_tied_groups_come_from_the_graph():
    mesh = MESHES["data2_model2"]
    ff = build_llama(mesh, layers=3)
    groups = tied_groups(ff)
    assert sorted(n for g in groups for n in g) \
        == sorted(op.name for op in _ops(ff))
    by_first = {g[0]: g for g in groups}
    assert by_first["ffn_gate_0"] == ["ffn_gate_0", "ffn_gate_1",
                                      "ffn_gate_2"]
    assert by_first["ln1_0"] == ["ln1_0", "ln1_1", "ln1_2"]
    assert by_first["tok_embed"] == ["tok_embed"]
    assert by_first["ln_f"] == ["ln_f"] and by_first["lm_head"] == ["lm_head"]
    assert len(groups) == 14
    # gate and up are one shape and one producer, and still two parts
    assert "ffn_up_0" not in by_first["ffn_gate_0"]
    follows = follow_sources(ff)
    assert follows["ffn_sig_1"] == "ffn_gate_1"
    assert follows["res1_1"] == "res2_0" and follows["ln2_1"] == "res1_1"
    assert "ffn_down_1" not in follows and "attn_1" not in follows
    # no block repeats in an MLP of three widths: every op stands alone
    assert [len(g) for g in tied_groups(build_mlp(mesh))] == [1, 1, 1]


def test_data_parallel_on_a_data_mesh_is_the_batch_seed():
    mesh = MESHES["data4"]
    ff = build_cnn(mesh)
    cost = CostModel(ff, mesh)
    seeds = search_seeds(ff, mesh, cost, _maps(ff, mesh))
    # `data=batch` is the flat data-parallel strategy itself: listed once
    assert "data=batch" not in seeds
    assert all(m == {"data": 0} for m in seeds["data_parallel"].values())
    out = optimize_strategies_multi(ff, budget=100)
    s = ff._search_summary
    for key in ("seed_costs", "started_from", "winner", "edges",
                "resharded_edges", "tied_groups"):
        assert key in s
    assert set(out) == {op.name for op in _ops(ff)}


# ---- ISSUE 47: a reduction is priced on its edge, by what both ends hold ----

def _edge(ff, src, dst):
    src, dst = ff.get_op_by_name(src), ff.get_op_by_name(dst)
    idx = next(i for i, t in enumerate(dst.inputs) if t.owner_op is src)
    return src, dst, idx, dst.inputs[idx]


@pytest.mark.parametrize("case", ["sliced", "sliced_on_batch", "whole",
                                  "column_from_whole", "column_from_sliced",
                                  "column_from_batch_sliced",
                                  "head_split_sliced", "head_split_whole",
                                  "batch_only"])
def test_a_reduction_is_priced_by_what_both_ends_hold(case):
    mesh = MESHES["data2_model2"]
    ff = build_llama(mesh, compute_dtype="bfloat16")
    cost = CostModel(ff, mesh)
    m = cost.machine
    dp, col, row = {"data": 0}, {"data": 0, "model": 2}, \
        {"data": 0, "model": CONTRACT}
    batch4 = {"data": 0, "model": 0}
    src, dst, src_map, dst_map = {
        # a row-parallel matmul's psum, by what the residual add keeps
        "sliced": ("ffn_down_0", "res2_0", row, col),
        # on the chip a slice of the batch dim is an all-reduce and a slice
        "sliced_on_batch": ("ffn_down_0", "res2_0", row, batch4),
        "whole": ("ffn_down_0", "res2_0", row, dp),
        # a column-parallel matmul's input gradient, by what the norm holds
        "column_from_whole": ("ln2_0", "ffn_gate_0", dp, col),
        "column_from_sliced": ("ln2_0", "ffn_gate_0", col, col),
        "column_from_batch_sliced": ("ln2_0", "ffn_gate_0", batch4, col),
        # a head split sums over heads in the output projection
        "head_split_sliced": ("attn_0", "res1_0", col, col),
        "head_split_whole": ("attn_0", "res1_0", col, dp),
        "batch_only": ("ffn_down_0", "res2_0", dp, dp),
    }[case]
    src, dst, idx, t = _edge(ff, src, dst)
    share = t.volume() * 2 / 2        # bf16, one `data` half of the tensor
    held = cost.edge_held_time(src, src_map, dst, dst_map, idx, t)
    edge = cost.edge_time(src.output_axis_map(src_map),
                          dst.input_axis_map(dst_map, idx), t)
    rs = m.reduce_scatter_time(share, 2, "model")
    ar = m.all_reduce_time(share, 2, "model")
    ag = m.all_gather_time(share / 2, 2, "model")
    want_held, want_edge = {
        # reduce-scatter forward, the gradient's all-gather back
        "sliced": (rs, m.ici_latency + ag),
        "sliced_on_batch": (ar, m.ici_latency + ag),
        "whole": (ar, 0.0),
        "column_from_whole": (ar, 0.0),
        # all-gather forward, the partial gradient's reduce-scatter back
        "column_from_sliced": (rs, ag + m.ici_latency),
        "column_from_batch_sliced": (ar, ag + m.ici_latency),
        "head_split_sliced": (rs, 0.0),
        "head_split_whole": (ar, ag + m.ici_latency),
        "batch_only": (0.0, 0.0),
    }[case]
    assert held == pytest.approx(want_held, rel=1e-12)
    assert edge == pytest.approx(want_edge, rel=1e-12)
    # nothing of the psum is left inside the op: its time is its roofline
    # and the optimizer's pass over what it holds
    if src_map is row:
        io = (src.inputs[0].volume() / 4 + src.outputs[0].volume() / 2) * 2
        assert cost.op_compute_time(src, row) == pytest.approx(
            3.0 * m.compute_time(src.flops() / 4, io, 2)
            + cost._state_pass_time(src, row), rel=1e-12)


def _mlp4(mesh):
    ff = FFModel(_config(mesh, compute_dtype="bfloat16"))
    x = ff.create_tensor([8, 256], name="x")
    t = ff.dense(x, 512, ActiMode.AC_MODE_RELU, name="fc0")
    t = ff.dense(t, 1024, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 512, name="fc2")
    return ff, t


def test_a_megatron_pair_costs_in_sum_what_it_cost_on_the_parent():
    """Column-parallel fc1 + row-parallel fc2 between whole tensors: one
    all-reduce forward (fc2 -> out) and one backward (fc0 -> fc1), where
    the parent booked two to fc2. The price is the parent's, to the digit
    (5.479840887937121e-05 at 55e419f)."""
    mesh = MESHES["data2_model2"]
    ff, t = _mlp4(mesh)
    ff.dense(t, 16, name="out")
    cost = CostModel(ff, mesh)
    pair = {"fc0": {"data": 0}, "fc1": {"data": 0, "model": 1},
            "fc2": {"data": 0, "model": CONTRACT}, "out": {"data": 0}}
    assert cost.iteration_time(pair) == pytest.approx(5.479840887937121e-05,
                                                      rel=1e-12)
    prob = CompiledSearchProblem(ff, cost, mesh)
    assert prob.simulate(prob.choices_for(pair)) == pytest.approx(
        cost.iteration_time(pair), rel=1e-12)
    ar = cost.machine.all_reduce_time(8 * 512 * 2 / 2, 2, "model")
    rows = {r["name"]: r["finish"] - r["start"] for r in
            prob.simulate_timeline(prob.choices_for(pair))[1]
            if r["kind"] == "comm"}
    assert rows == {"fc0->fc1": pytest.approx(ar), "fc2->out":
                    pytest.approx(ar)}
    # the first matmul of a graph has no gradient to hand back: a
    # column-parallel fc0 pays nothing on the edge from the input
    first = dict(pair, fc0={"data": 0, "model": 1},
                 fc1={"data": 0, "model": CONTRACT}, fc2={"data": 0})
    rows = [r for r in prob.simulate_timeline(prob.choices_for(first))[1]
            if r["kind"] == "comm"]
    assert [r["name"] for r in rows] == ["fc1->fc2"]


def test_a_tensor_pays_its_psum_once_however_many_consumers_it_has():
    mesh = MESHES["data2_model2"]
    ff, t = _mlp4(mesh)
    a = ff.relu(t, name="a")
    b = ff.sigmoid(t, name="b")
    ff.add(a, b, name="sum")
    cost = CostModel(ff, mesh)
    prob = CompiledSearchProblem(ff, cost, mesh)
    dp = {"data": 0}
    strat = {"fc0": dp, "fc1": {"data": 0, "model": 1},
             "fc2": {"data": 0, "model": CONTRACT}, "a": dp, "b": dp,
             "sum": dp}
    ar = cost.machine.all_reduce_time(8 * 512 * 2 / 2, 2, "model")
    total, rows = prob.simulate_timeline(prob.choices_for(strat))
    comm = {r["name"]: r["finish"] - r["start"] for r in rows
            if r["kind"] == "comm"}
    assert comm == {"fc0->fc1": pytest.approx(ar),
                    "fc2->a": pytest.approx(ar)}, "fc2->b paid again"
    assert total == pytest.approx(cost.iteration_time(strat), rel=1e-12)
    # a second consumer that keeps a slice where the first took the tensor
    # whole adds nothing either: the largest of the edges is what is paid
    strat["b"] = {"data": 0, "model": 1}
    total2, rows = prob.simulate_timeline(prob.choices_for(strat))
    assert total2 == pytest.approx(cost.iteration_time(strat), rel=1e-12)
    assert sum(r["finish"] - r["start"] for r in rows
               if r["kind"] == "comm" and r["name"] == "fc2->a") \
        == pytest.approx(ar)


def test_a_head_nobody_consumes_reduces_its_own_output():
    """A CONTRACT op whose output no op of the graph reads (the loss does)
    has no edge to carry its psum: it is reduced whole, in the op."""
    mesh = MESHES["data2_model2"]
    ff, t = _mlp4(mesh)
    cost = CostModel(ff, mesh)
    fc2, fc1 = ff.get_op_by_name("fc2"), ff.get_op_by_name("fc1")
    row = {"data": 0, "model": CONTRACT}
    ar = cost.machine.all_reduce_time(8 * 512 * 2 / 2, 2, "model")
    plain = 3.0 * cost.machine.compute_time(
        fc2.flops() / 4, (8 * 1024 / 4 + 8 * 512 / 2) * 2, 2)
    assert cost.op_compute_time(fc2, row) - cost._state_pass_time(fc2, row) \
        == pytest.approx(plain + ar, rel=1e-12)
    # fc1 is read by fc2: nothing of a psum in it
    assert cost.op_compute_time(fc1, {"data": 0, "model": CONTRACT}) \
        - cost._state_pass_time(fc1, {"data": 0, "model": CONTRACT}) \
        == pytest.approx(3.0 * cost.machine.compute_time(
            fc1.flops() / 4, (8 * 512 / 4 + 8 * 1024 / 2) * 2, 2), rel=1e-12)


def test_a_partnerless_sharded_head_is_not_priced_like_a_replicated_one():
    ff = build_cell()
    mesh = ff.config.mesh_shape
    cost = CostModel(ff, mesh)
    src, dst, idx, t = _edge(ff, "ln_f", "lm_head")
    dp = {"data": 0}
    assert cost.edge_held_time(src, dp, dst, dp, idx, t) == 0.0
    # vocabulary over `model`: dX = dY W^T is a partial sum over `model`
    half = t.volume() * 2 / 2
    assert cost.edge_held_time(src, dp, dst, {"data": 0, "model": 2}, idx,
                               t) == pytest.approx(
        cost.machine.all_reduce_time(half, 2, "model"), rel=1e-12)
    # vocabulary over both axes: an all-reduce over each (the norm shards
    # `data` on the batch dim, not on the dim the head contracts)
    both = cost.edge_held_time(src, dp, dst, {"data": 2, "model": 2}, idx, t)
    assert both == pytest.approx(
        cost.machine.all_reduce_time(2 * half, 2, "data")
        + cost.machine.all_reduce_time(2 * half, 2, "model"), rel=1e-12)
    # the embedding's input is token ids: no gradient, no reduction
    emb = ff.get_op_by_name("tok_embed")
    assert all(t.owner_op is None or isinstance(t.owner_op, InputOp)
               for t in emb.inputs)


PARENT_PRICES = {
    ("mlp", "data4"): {
        "data_parallel": 5.3204561489509626e-05,
        "data=sequence": 3.046849824287388e-07,
        "random0": 3.121635828251976e-05,
        "random1": 4.079475256894152e-05,
        "random2": 4.492747759779158e-05,
        "random3": 3.121635828251976e-05,
    },
    ("mlp", "data2_model2"): {
        "data_parallel": 5.0990196149750954e-05,
        "data=batch,model=batch": 0.00010175187856268035,
        "random0": 4.139599698528041e-05,
        "random1": 8.493722680866984e-05,
        "random2": 9.297430686608427e-05,
        "random3": 5.538285012991713e-05,
    },
    ("cnn", "data4"): {
        "data_parallel": 1.6416382024479583e-05,
        "data=sequence": 1.0683146966854282e-05,
        "data=none": 4.2104029304029306e-07,
        "random0": 2.1251688376663987e-05,
        "random1": 2.459286982935763e-05,
        "random2": 2.4035157687840612e-05,
        "random3": 1.0683146966854282e-05,
    },
    ("cnn", "data2_model2"): {
        "data_parallel": 8.625349414812828e-06,
        "data=batch,model=batch": 1.6934918609845435e-05,
        "data=batch,model=sequence": 1.1637791476815867e-05,
        "data=sequence,model=none": 6.845534173143929e-06,
        "random0": 2.289448476726525e-05,
        "random1": 2.1065393013490573e-05,
        "random2": 2.1872796926650586e-05,
        "random3": 2.0052878048780484e-05,
    },
    ("llama", "data4"): {
        "data_parallel": 0.00012901265290806745,
        "data=sequence": 0.000167593837577057,
        "data=none": 1.5005538461538457e-05,
        "random0": 0.00026484738747431427,
        "random1": 0.0002664690259983919,
        "random2": 0.0002600541917269721,
        "random3": 0.00028501108764406323,
    },
    ("llama", "data2_model2"): {
        "data_parallel": 7.45177936210131e-05,
        "data=batch,model=batch": 0.00013778143339587232,
        "data=batch,model=sequence": 0.0001480097632448851,
        "data=sequence,model=none": 9.137211185562401e-05,
        "random0": 0.0003037447554721701,
        "random1": 0.0003124627091932457,
        "random2": 0.00027601758027338504,
        "random3": 0.00033095931314214256,
    },
}


@pytest.mark.parametrize("build,mesh", sorted(PARENT_PRICES))
def test_strategies_without_a_sharded_parameter_price_as_on_the_parent(
        build, mesh):
    """No CONTRACT, no parameter dim on an axis: no reduction on any edge.
    Seeds that shard batch or sequence only, and random strategies drawn
    with parameter parallelism off, price to the digit as at 55e419f (the
    numbers were printed there by this loop)."""
    want = PARENT_PRICES[build, mesh]
    mesh = MESHES[mesh]
    ff = {"mlp": build_mlp, "cnn": build_cnn,
          "llama": build_llama}[build](mesh, compute_dtype="bfloat16")
    cost = CostModel(ff, mesh)
    seeds = search_seeds(ff, mesh, cost, _maps(ff, mesh))
    got = {name: cost.iteration_time(seeds[name]) for name in want
           if name in seeds}
    rs = np.random.RandomState(7)
    maps = {op.name: legal_axis_maps(op, mesh, False, True)
            for op in _ops(ff)}
    prob = CompiledSearchProblem(ff, cost, mesh, epp=False)
    for k in range(4):
        strat = {n: m[rs.randint(len(m))] for n, m in maps.items()}
        got[f"random{k}"] = cost.iteration_time(strat)
        assert prob.simulate(prob.choices_for(strat)) == pytest.approx(
            got[f"random{k}"], rel=1e-12)
    assert not prob.edge_held_costs.any()
    assert got == want
