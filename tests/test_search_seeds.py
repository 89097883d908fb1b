"""The strategy search's seeds, tied moves and prices (ISSUE 36): the result
is never priced above a seed, repeated layers stay alike, memory counts a
replicated weight whole, a bf16 job is priced as one, and the two
simulators agree on the edge costs. Graphs only: nothing compiles, no device
is used."""

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
    # every table entry is the forward reshard plus its transpose
    e = 0
    src, dst = prob.ops[prob.edge_src[e]], prob.ops[prob.edge_dst[e]]
    off = prob.edge_cost_offsets[e]
    n_dst = len(prob.op_maps[prob.edge_dst[e]])
    for i, pm in enumerate(prob.op_maps[prob.edge_src[e]]):
        for j, cm in enumerate(prob.op_maps[prob.edge_dst[e]]):
            p, c = src.output_axis_map(pm), dst.input_axis_map(cm, 0)
            t = dst.inputs[0]
            assert prob.edge_costs[off + i * n_dst + j] == pytest.approx(
                cost.resharding_time(p, c, t) + cost.resharding_time(c, p, t),
                rel=1e-12)


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
