"""CONTRACT (row-parallel / Megatron) sharding: numerics, persistence,
and cost-model semantics.

The reference expresses row parallelism as Linear's NDIM+1 replica dim +
backward2 reduction (linear.cu:171-192,774-835); the TPU re-design shards the
kernel's input-feature dim over a mesh axis (axis_map value CONTRACT) and
lets GSPMD insert the activation psum."""

import numpy as np
import pytest

from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType, MetricsType,
                          SGDOptimizer, SingleDataLoader)
from flexflow_tpu.parallel.pconfig import CONTRACT, ParallelConfig
from flexflow_tpu.parallel.strategy import (load_strategies_from_file,
                                            save_strategies_to_file)
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.driver import legal_axis_maps

MESH = {"data": 2, "model": 4}


def build(strategies):
    cfg = FFConfig(batch_size=16, mesh_shape=dict(MESH))
    cfg.strategies = dict(strategies)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 64], name="x")
    t = ff.dense(x, 128, ActiMode.AC_MODE_RELU, name="col")
    t = ff.dense(t, 64, name="row")
    ff.dense(t, 8, name="head")
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY])
    return ff


def megatron_strategy():
    return {
        "col": ParallelConfig.from_axis_map(2, MESH, {"data": 0, "model": 1}),
        "row": ParallelConfig.from_axis_map(
            2, MESH, {"data": 0, "model": CONTRACT}),
        "head": ParallelConfig.from_axis_map(2, MESH, {"data": 0}),
    }


def _train_losses(strategies, steps=3):
    ff = build(strategies)
    rs = np.random.RandomState(0)
    xd = rs.randn(16, 64).astype(np.float32)
    yd = rs.randint(0, 8, (16, 1)).astype(np.int32)
    SingleDataLoader(ff, ff.ops[0].outputs[0], xd)
    SingleDataLoader(ff, ff.label_tensor, yd)
    out = []
    for _ in range(steps):
        loss, _ = ff._run_train_step(ff._stage_batch())
        out.append(float(loss))
    return out


def test_megatron_pair_matches_dp_numerics():
    """col(column-parallel) -> row(CONTRACT) training must be numerically
    identical to pure DP: GSPMD's psum replaces the reference's backward2
    replica reduction."""
    dp = _train_losses({})
    meg = _train_losses(megatron_strategy())
    np.testing.assert_allclose(dp, meg, rtol=1e-4, atol=1e-5)


def test_contract_weight_sharding_applied():
    ff = build(megatron_strategy())
    sh = ff.executor.param_shardings()
    # row's kernel is sharded on its INPUT dim over 'model'
    assert sh["row"]["kernel"].spec[0] == "model"
    assert sh["row"]["kernel"].spec[1] is None
    # col's kernel is sharded on its OUTPUT dim
    assert sh["col"]["kernel"].spec[1] == "model"


def test_contract_round_trips_through_strategy_file(tmp_path):
    """The text schema carries the contract degree as a trailing dim entry
    (the reference's replica-dim convention); a degrees-only reload must
    resolve back to a CONTRACT axis map."""
    from flexflow_tpu.runtime.executor import resolve_axis_map

    path = str(tmp_path / "s.txt")
    strat = megatron_strategy()
    save_strategies_to_file(path, strat)
    loaded = load_strategies_from_file(path)
    pc = loaded["row"]
    assert pc.dims == (2, 1, 4)  # batch 2-way, out unsharded, contract 4-way
    am = resolve_axis_map(pc, MESH, ndims=2)
    assert am.get("model") == CONTRACT and am.get("data") == 0
    # and training under the reloaded strategy still matches DP
    reloaded_losses = _train_losses(loaded)
    np.testing.assert_allclose(_train_losses({}), reloaded_losses,
                               rtol=1e-4, atol=1e-5)


def test_cost_model_megatron_pair_has_free_edge():
    """The col->row edge needs NO resharding: col's output is sharded on its
    last dim over 'model', exactly what the CONTRACT consumer wants."""
    ff = build({})
    cost = CostModel(ff, MESH)
    col = ff.get_op_by_name("col")
    row = ff.get_op_by_name("row")
    pm = col.output_axis_map({"data": 0, "model": 1})
    want = row.input_axis_map({"data": 0, "model": CONTRACT}, 0)
    assert cost.resharding_time(pm, want, col.outputs[0]) == 0.0
    # whereas feeding a CONTRACT consumer from a replicated producer is not free
    pm_dp = col.output_axis_map({"data": 0})
    assert cost.resharding_time(pm_dp, want, col.outputs[0]) > 0.0


def test_contract_in_legal_axis_maps_and_sync_free():
    ff = build({})
    row = ff.get_op_by_name("row")
    maps = legal_axis_maps(row, MESH)
    assert {"data": 0, "model": CONTRACT} in maps
    # contract shards the kernel -> no grad all-reduce over 'model'
    cost = CostModel(ff, MESH)
    sync_contract = cost.op_grad_sync_time(row, {"data": 0, "model": CONTRACT})
    sync_dp = cost.op_grad_sync_time(row, {"data": 0, "model": 0})
    assert sync_contract < sync_dp
    # but the contract choice pays the activation psum in compute
    t_contract = cost.op_compute_time(row, {"data": 0, "model": CONTRACT})
    t_dp = cost.op_compute_time(row, {"data": 0, "model": 0})
    assert t_contract > 0 and t_dp > 0


def test_measured_table_distinguishes_contract_from_dp():
    """The measured-cost cache key must separate CONTRACT from plain DP:
    both have the same per-shard OUTPUT shape, but contract shards the
    inputs/weights. A collision would price row-parallel as the DP
    measurement and silently drop the psum term."""
    from flexflow_tpu.search.measure import choice_key

    ff = build({})
    row = ff.get_op_by_name("row")
    dp_key = choice_key("row", row.outputs[0].dims,
                        {"data": 0, "model": 0}, MESH)
    c_key = choice_key("row", row.outputs[0].dims,
                       {"data": 0, "model": CONTRACT}, MESH)
    assert dp_key != c_key
    # with a measured entry for the DP key only, the contract choice must
    # NOT reuse it (falls back to analytic + psum)
    cost = CostModel(ff, MESH, measured={dp_key: 1e-6})
    t_dp = cost.op_compute_time(row, {"data": 0, "model": 0})
    t_c = cost.op_compute_time(row, {"data": 0, "model": CONTRACT})
    # the measured shard time, plus the optimizer's pass over the weight
    # (no measurement of one op's forward and backward holds it)
    dp_map = {"data": 0, "model": 0}
    assert t_dp == 1e-6 + cost._state_pass_time(row, dp_map)
    assert t_c != t_dp
    # and a measured entry for the contract key is used but still pays psum
    base = 1e-6
    cost2 = CostModel(ff, MESH, measured={c_key: base})
    assert cost2.op_compute_time(row, {"data": 0, "model": CONTRACT}) > base


def test_conv_contract_matches_dp_numerics():
    """Conv2D row-parallel pair (c1 out-channel-sharded -> c2 CONTRACT on
    input channels) trains identically to DP."""
    def build(strategies):
        cfg = FFConfig(batch_size=8, mesh_shape=dict(MESH))
        cfg.strategies = dict(strategies)
        ff = FFModel(cfg)
        from flexflow_tpu.ffconst import ActiMode as AM
        x = ff.create_tensor([8, 8, 16, 16], name="x")
        t = ff.conv2d(x, 16, 3, 3, 1, 1, 1, 1, AM.AC_MODE_RELU, name="c1")
        t = ff.conv2d(t, 8, 3, 3, 1, 1, 1, 1, name="c2")
        t = ff.flat(t)
        ff.dense(t, 4, name="head")
        ff.compile(SGDOptimizer(lr=0.05),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        return ff

    meg = {
        "c1": ParallelConfig.from_axis_map(4, MESH, {"data": 0, "model": 1}),
        "c2": ParallelConfig.from_axis_map(
            4, MESH, {"data": 0, "model": CONTRACT}),
    }
    rs = np.random.RandomState(0)
    xd = rs.randn(16, 8, 16, 16).astype(np.float32)
    yd = rs.randint(0, 4, (16, 1)).astype(np.int32)
    out = {}
    for name, s in (("dp", {}), ("meg", meg)):
        ff = build(s)
        SingleDataLoader(ff, ff.ops[0].outputs[0], xd)
        SingleDataLoader(ff, ff.label_tensor, yd)
        ls = []
        for _ in range(3):
            loss, _ = ff._run_train_step(ff._stage_batch())
            ls.append(float(loss))
        out[name] = ls
    np.testing.assert_allclose(out["dp"], out["meg"], rtol=1e-4, atol=1e-5)
    # kernel sharded on its input-channel dim
    assert ff.params["c2"]["kernel"].sharding.spec[1] == "model"


def test_channel_sharded_batchnorm_matches_dp():
    """BN statistics reduce over N,H,W only, so sharding the channel dim
    (with scale/bias sharded alongside) must train identically to DP — this
    is what lets a channel-sharded conv feed BN without an all-gather."""
    def build(strategies):
        cfg = FFConfig(batch_size=8, mesh_shape=dict(MESH))
        cfg.strategies = dict(strategies)
        ff = FFModel(cfg)
        x = ff.create_tensor([8, 8, 16, 16], name="x")
        t = ff.conv2d(x, 16, 3, 3, 1, 1, 1, 1, name="c1")
        t = ff.batch_norm(t, relu=True, name="bn1")
        t = ff.conv2d(t, 8, 3, 3, 1, 1, 1, 1, name="c2")
        t = ff.flat(t)
        ff.dense(t, 4, name="head")
        ff.compile(SGDOptimizer(lr=0.05),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        return ff

    ch = {
        "c1": ParallelConfig.from_axis_map(4, MESH, {"data": 0, "model": 1}),
        "bn1": ParallelConfig.from_axis_map(4, MESH, {"data": 0, "model": 1}),
    }
    rs = np.random.RandomState(0)
    xd = rs.randn(16, 8, 16, 16).astype(np.float32)
    yd = rs.randint(0, 4, (16, 1)).astype(np.int32)
    out, states = {}, {}
    for name, s in (("dp", {}), ("chan", ch)):
        ff = build(s)
        SingleDataLoader(ff, ff.ops[0].outputs[0], xd)
        SingleDataLoader(ff, ff.label_tensor, yd)
        ls = []
        for _ in range(3):
            loss, _ = ff._run_train_step(ff._stage_batch())
            ls.append(float(loss))
        out[name] = ls
        states[name] = {k: np.asarray(v)
                        for k, v in ff.bn_state["bn1"].items()}
    np.testing.assert_allclose(out["dp"], out["chan"], rtol=1e-4, atol=1e-5)
    assert ff.params["bn1"]["scale"].sharding.spec[0] == "model"
    # running statistics (the eval-path state) must also match DP
    np.testing.assert_allclose(
        np.asarray(states["dp"]["mean"]), np.asarray(states["chan"]["mean"]),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(states["dp"]["var"]), np.asarray(states["chan"]["var"]),
        rtol=1e-5, atol=1e-6)


def test_contract_output_not_sharded():
    """CONTRACT axes never appear in the output PartitionSpec, and the
    per-shard output shape ignores them."""
    from flexflow_tpu.search.measure import shard_shape

    pc = ParallelConfig.from_axis_map(2, MESH, {"data": 0, "model": CONTRACT})
    spec = pc.to_partition_spec(2, ["data", "model"])
    assert spec[0] == "data" and spec[1] is None
    assert shard_shape((16, 64), {"data": 0, "model": CONTRACT}, MESH) \
        == (8, 64)
