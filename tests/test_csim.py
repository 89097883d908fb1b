"""Native (C++) search core tests: exact parity with the Python cost model,
determinism, memory penalty, DCN tiers, and placement-aware MCMC."""

import numpy as np
import pytest

from flexflow_tpu import ActiMode, FFConfig, FFModel
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.csim import CompiledSearchProblem, native_optimize
from flexflow_tpu.search.driver import data_parallel_strategy, legal_axis_maps
from flexflow_tpu.search.machine import MachineModel


def build_wide(mesh_shape, batch=64):
    cfg = FFConfig(batch_size=batch, mesh_shape=mesh_shape)
    cfg.enable_parameter_parallel = True
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 1024], name="x")
    t = ff.dense(x, 8192, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 8192, ActiMode.AC_MODE_RELU, name="fc2")
    t = ff.dense(t, 16, name="out")
    return ff


MESH = {"data": 4, "model": 2}


def build_decoder(mesh_shape):
    """Two decoder layers: row-parallel matmuls (CONTRACT) whose consumers
    (residual adds) may keep a slice, column-parallel ones with and without
    a CONTRACT partner, an attention whose head split sums over heads."""
    from flexflow_tpu.models.llama import llama_lm

    cfg = FFConfig(batch_size=8, mesh_shape=mesh_shape,
                   compute_dtype="bfloat16")
    cfg.enable_parameter_parallel = True
    ff = FFModel(cfg)
    llama_lm(ff, 8, seq_len=32, hidden=64, layers=2, heads=4, kv_heads=2,
             ffn_hidden=128, vocab_size=256)
    return ff


@pytest.mark.parametrize("build", [build_wide, build_decoder])
def test_native_matches_python_objective_on_random_strategies(build):
    """The C++ scheduler and CostModel.iteration_time are the same algorithm
    (VERDICT r1 weak #3): they must agree to float tolerance on random
    strategies, so the two objectives cannot drift silently. The decoder's
    random strategies hold CONTRACT producers with sliced consumers and
    parameter-sharded ops with no CONTRACT partner (ISSUE 47: their
    reductions are priced per edge and paid once per tensor)."""
    from flexflow_tpu.parallel.pconfig import CONTRACT

    ff = build(MESH)
    cost = CostModel(ff, MESH)
    prob = CompiledSearchProblem(ff, cost, MESH)
    rs = np.random.RandomState(0)
    ops = prob.ops
    sliced = reduced = 0
    for trial in range(20):
        strategy = {op.name: prob.op_maps[i][rs.randint(len(prob.op_maps[i]))]
                    for i, op in enumerate(ops)}
        c_native = prob.simulate(prob.choices_for(strategy))
        c_python = cost.iteration_time(strategy)
        assert c_native == pytest.approx(c_python, rel=1e-9), \
            f"trial {trial}: native {c_native} != python {c_python}"
        for e in range(prob.num_edges):
            src, dst = ops[prob.edge_src[e]], ops[prob.edge_dst[e]]
            pm, cm = strategy[src.name], strategy[dst.name]
            idx = next(i for i, t in enumerate(dst.inputs)
                       if t.owner_op is src)
            held = cost.edge_held_time(src, pm, dst, cm, idx,
                                       dst.inputs[idx])
            reduced += held > 0.0
            sliced += any(d == CONTRACT and dst.input_axis_map(cm, idx)
                          .get(ax) is not None for ax, d in pm.items())
    assert reduced, "no random strategy held a reduction on an edge"
    if build is build_decoder:
        assert sliced, "no CONTRACT producer met a consumer keeping a slice"


def test_native_matches_python_with_placement():
    ff = build_wide(MESH)
    cost = CostModel(ff, MESH)
    prob = CompiledSearchProblem(ff, cost, MESH)
    # shard fc1/fc2 4-way (half the mesh), placed on different blocks
    am4 = {"data": 0}
    strategy = {"fc1": am4, "fc2": am4, "out": am4}
    places = {"fc1": 0, "fc2": 4, "out": 0}
    c_native = prob.simulate(prob.choices_for(strategy), places)
    c_python = cost.iteration_time(strategy, places)
    assert c_native == pytest.approx(c_python, rel=1e-9)
    # a different placement must actually change the simulated time
    c_same = prob.simulate(prob.choices_for(strategy),
                           {"fc1": 0, "fc2": 0, "out": 0})
    assert c_native != pytest.approx(c_same, rel=1e-6)


def test_memory_penalty_rejects_oom_strategy():
    """An over-HBM strategy must cost more than a sharded one (reference
    simulator.cc:595-620: 1 ms/MB over capacity)."""
    mesh = {"data": 1, "model": 8}
    cfg = FFConfig(batch_size=8, mesh_shape=mesh)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 4096], name="x")
    ff.dense(x, 65536, name="big")  # 4096x65536 f32 = ~1 GiB weights x3
    machine = MachineModel(hbm_bytes=512e6)  # tiny HBM: replication OOMs
    cost = CostModel(ff, mesh, machine=machine)
    prob = CompiledSearchProblem(ff, cost, mesh)
    replicated = prob.simulate(prob.choices_for({"big": {}}))
    sharded = prob.simulate(prob.choices_for({"big": {"model": 1}}))
    assert sharded < replicated
    # the penalty term dominates: ~2.5 GB over 0.5 GB cap -> seconds
    assert replicated > 1.0
    # python objective agrees (same algorithm)
    assert replicated == pytest.approx(
        cost.iteration_time({"big": {}}), rel=1e-9)


def test_dcn_axis_prices_grad_sync_higher():
    """A {hosts:2, data:4} mesh prices a gradient all-reduce differently
    from {data:8} (reference simulator.cc:252-285 inter-node 3-hop model)."""
    ici = MachineModel()
    dcn = MachineModel(dcn_axes={"data": 2})
    nbytes = 64e6
    t_ici = ici.all_reduce_time(nbytes, 8, "data")
    t_dcn = dcn.all_reduce_time(nbytes, 8, "data")
    assert t_dcn > t_ici * 2, (t_dcn, t_ici)
    # and an axis not listed in dcn_axes is unaffected
    assert dcn.all_reduce_time(nbytes, 8, "model") == pytest.approx(t_ici)


def test_native_mcmc_deterministic_and_improves():
    ff = build_wide(MESH)
    cost = CostModel(ff, MESH)
    prob = CompiledSearchProblem(ff, cost, MESH)
    init = prob.choices_for(data_parallel_strategy(ff, MESH))
    dp_cost = prob.simulate(init)
    c1, p1, cost1 = prob.mcmc(init, 500, 0.05, seed=7)
    c2, p2, cost2 = prob.mcmc(init, 500, 0.05, seed=7)
    assert np.array_equal(c1, c2) and np.array_equal(p1, p2) and cost1 == cost2
    assert cost1 <= dp_cost


def test_native_optimize_end_to_end():
    ff = build_wide(MESH)
    cost = CostModel(ff, MESH)
    best = native_optimize(ff, cost, MESH, budget=500, alpha=0.05, seed=3)
    assert set(best) == {"fc1", "fc2", "out"}
    for name, pc in best.items():
        assert pc.num_parts() <= 8
        assert len(pc.device_ids) == pc.num_parts()
    # best strategy cost should not exceed DP
    am = {k: v.axis_map for k, v in best.items()}
    places = {k: (min(v.device_ids) if v.device_ids else 0)
              for k, v in best.items()}
    prob = CompiledSearchProblem(ff, cost, MESH)
    assert prob.simulate(prob.choices_for(am), places) <= \
        prob.simulate(prob.choices_for(data_parallel_strategy(ff, MESH))) \
        * 1.0001


def test_placement_search_beats_dp_on_branchy_graph():
    """Two fat parallel branches (InceptionV3-style): placing them on
    disjoint device blocks must simulate faster than running both
    full-mesh-serial, and the MCMC must find such a strategy (the SOAP 'O'
    axis, reference config.h:47-69 + model.cc:496-525). Parameter parallel
    is disabled — the reference's own default (model.cc:1935) — so sharding
    the weights away is not an option and placement is the winning move."""
    mesh = {"data": 4, "model": 2}
    cfg = FFConfig(batch_size=64, mesh_shape=mesh)
    ff = FFModel(cfg)
    x = ff.create_tensor([64, 1024], name="x")
    a = ff.dense(x, 4096, ActiMode.AC_MODE_RELU, name="branch_a1")
    a = ff.dense(a, 4096, name="branch_a2")
    b = ff.dense(x, 4096, ActiMode.AC_MODE_RELU, name="branch_b1")
    b = ff.dense(b, 4096, name="branch_b2")
    t = ff.concat([a, b], axis=1, name="join")
    ff.dense(t, 16, name="head")

    cost = CostModel(ff, mesh)
    prob = CompiledSearchProblem(ff, cost, mesh, epp=False)
    dp = data_parallel_strategy(ff, mesh)
    dp_cost = prob.simulate(prob.choices_for(dp))

    maps_a1 = legal_axis_maps(ff.get_op_by_name("branch_a1"), mesh,
                              enable_parameter_parallel=False)
    assert {"data": 0, "model": None} in maps_a1  # 4-way block is proposable
    best_c, best_p, best_cost = prob.mcmc(
        prob.choices_for(dp), 8000, 0.05, seed=1)
    assert best_cost < dp_cost * 0.5
    # the found strategy must be executable-aligned: every placement is a
    # legal aligned block
    blocks = {}
    for i, op in enumerate(prob.ops):
        ndev = int(prob.op_ndev[prob.op_cost_offsets[i] + best_c[i]])
        assert best_p[i] % max(ndev, 1) == 0
        blocks[op.name] = set(range(best_p[i], best_p[i] + ndev))
    # and some pair of opposite-branch ops runs on disjoint device blocks
    # (the op-parallel win: branches overlap in time)
    assert any(not (blocks[f"branch_a{i}"] & blocks[f"branch_b{j}"])
               for i in (1, 2) for j in (1, 2))


def test_timeline_matches_simulate_with_placement(tmp_path):
    from flexflow_tpu.runtime.profiler import export_sim_taskgraph

    dot = tmp_path / "g.dot"
    cfg = FFConfig(batch_size=32, mesh_shape={"data": 4, "model": 2},
                   taskgraph_file=str(dot))
    ff = FFModel(cfg)
    x = ff.create_tensor([32, 64], name="x")
    t = ff.dense(x, 256, ActiMode.AC_MODE_RELU, name="fc1")
    ff.dense(t, 64, name="fc2")
    ff.compile(optimizer=None)  # compile triggers the export
    text = dot.read_text()
    assert "simulated iteration:" in text
    assert '"fc1"' in text and '"fc2"' in text and "_sync" in text

    cost = CostModel(ff, cfg.mesh_shape)
    prob = CompiledSearchProblem(ff, cost, cfg.mesh_shape)
    strategy = {n: am for n, am in ff.executor._op_axis_maps.items()}
    ch = prob.choices_for(strategy)
    total_t, rows = prob.simulate_timeline(ch)
    assert abs(total_t - prob.simulate(ch)) < 1e-12
    assert any(r["kind"] == "compute" for r in rows)
    # schedule sanity: no task finishes after the total (memory penalty can
    # push the total above the last task, never below)
    assert all(r["finish"] <= total_t + 1e-12 for r in rows)
