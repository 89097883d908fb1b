"""FSDP / ZeRO-3 analog (FFConfig.fsdp_axis): weights + optimizer state
sharded over the data axis on top of any strategy sharding; GSPMD
all-gathers at use and reduce-scatters gradients. Numerics must be
IDENTICAL to the unsharded run — FSDP is a memory layout, not a model
change."""

import numpy as np
import pytest

from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          MetricsType)
from flexflow_tpu.parallel.pconfig import ParallelConfig

MESH = {"data": 4, "model": 2}


def _build(fsdp):
    cfg = FFConfig(batch_size=16, mesh_shape=dict(MESH),
                   fsdp_axis="data" if fsdp else "")
    # TP on the first dense: its kernel already shards out-dim on
    # 'model'; FSDP adds 'data' on the in-dim -> 2D-sharded weight
    cfg.strategies = {"d1": ParallelConfig.from_axis_map(
        2, MESH, {"data": 0, "model": 1})}
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 64], name="input")
    t = ff.dense(x, 128, name="d1")
    t = ff.relu(t, name="r1")
    t = ff.dense(t, 64, name="d2")
    t = ff.dense(t, 8, name="head")
    ff.compile(AdamOptimizer(alpha=0.01),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=t)
    return ff


def test_fsdp_shards_params_and_opt_state():
    ff = _build(True)
    k1 = ff.params["d1"]["kernel"]          # (64, 128), TP'd on 'model'
    assert "data" in str(k1.sharding.spec) and "model" in str(k1.sharding.spec)
    # 2D sharded: each device holds 1/8 of the array
    shard = k1.addressable_shards[0].data
    assert shard.size * 8 == k1.size, (shard.shape, k1.shape)
    k2 = ff.params["d2"]["kernel"]          # (128, 64), no strategy
    assert "data" in str(k2.sharding.spec)
    assert k2.addressable_shards[0].data.size * 4 == k2.size
    # optimizer state follows the param sharding
    m = ff.opt_state["m"]["d2"]["kernel"]
    assert m.addressable_shards[0].data.size * 4 == m.size


def test_fsdp_numerics_match_unsharded():
    rs = np.random.RandomState(0)
    batch = {"input": rs.randn(16, 64).astype(np.float32),
             "label": rs.randint(0, 8, (16, 1)).astype(np.int32)}
    ff_f, ff_r = _build(True), _build(False)
    for _ in range(3):
        lf, _ = ff_f._run_train_step(batch)
        lr, _ = ff_r._run_train_step(batch)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
    for op, ws in ff_r.params.items():
        for w, v in ws.items():
            np.testing.assert_allclose(
                np.asarray(ff_f.params[op][w]), np.asarray(v),
                atol=1e-5, rtol=1e-5, err_msg=f"{op}/{w}")
    # sharding survives the donated train step (stays FSDP across steps)
    assert "data" in str(ff_f.params["d2"]["kernel"].sharding.spec)


def test_cost_model_prices_fsdp():
    """Search-side FSDP awareness (time model): grad sync over the fsdp
    axis becomes a reduce-scatter (~half an all-reduce) plus 2 per-step
    weight all-gathers; memory counts a weight whole on a batch axis and
    1/axis-size of it (kernel and bias both divide here) once the axis is
    the fsdp axis, the activations unchanged."""
    from flexflow_tpu.search.cost_model import CostModel

    cfg = FFConfig(batch_size=16, mesh_shape=dict(MESH))
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 256], name="input")
    t = ff.dense(x, 1024, name="big")
    ff.dense(t, 8, name="head")
    dp = {"data": 0}
    plain = CostModel(ff, MESH)
    fsdp = CostModel(ff, MESH, fsdp_axis="data")
    op = ff.get_op_by_name("big")

    acts = 16 * 1024 * 4 / MESH["data"]
    state = (256 * 1024 + 1024) * 4 * 3     # weight, gradient, one moment
    assert plain.op_mem_bytes(op, dp) == state + acts
    assert fsdp.op_mem_bytes(op, dp) == state / MESH["data"] + acts

    s_plain, s_fsdp = (c.op_grad_sync_time(op, dp) for c in (plain, fsdp))
    assert s_fsdp != s_plain
    # reduce-scatter (0.5x all-reduce) + 2 gathers of the 1/4-resident
    # weight: strictly between half and double the plain all-reduce
    assert 0.5 * s_plain < s_fsdp < 2.0 * s_plain

    # a weight whose partition already uses the fsdp axis (TP on 'model'
    # with fsdp_axis='model') gets no FSDP terms at all
    tp = {"data": 0, "model": 1}
    both = CostModel(ff, MESH, fsdp_axis="model")
    np.testing.assert_allclose(both.op_grad_sync_time(op, tp),
                               plain.op_grad_sync_time(op, tp))

    # CostModel defaults fsdp_axis from the model's config
    cfg2 = FFConfig(batch_size=16, mesh_shape=dict(MESH), fsdp_axis="data")
    ff2 = FFModel(cfg2)
    x2 = ff2.create_tensor([16, 256], name="input")
    ff2.dense(x2, 1024, name="big")
    auto = CostModel(ff2, MESH)
    assert auto.fsdp_axis == "data"

    # explicit typo'd axis raises (config-derived absence is dropped)
    with pytest.raises(ValueError, match="not a mesh axis"):
        CostModel(ff, MESH, fsdp_axis="dat")

    # a weight with NO dim divisible by the fsdp axis is priced plain
    # (matches executor._with_fsdp's degrade-to-unsharded rule)
    cfg3 = FFConfig(batch_size=16, mesh_shape=dict(MESH))
    ff3 = FFModel(cfg3)
    x3 = ff3.create_tensor([16, 255], name="input")
    ff3.dense(x3, 1023, use_bias=False, name="odd")  # 255x1023: 4 | none
    odd = ff3.get_op_by_name("odd")
    np.testing.assert_allclose(
        CostModel(ff3, MESH, fsdp_axis="data").op_grad_sync_time(odd, dp),
        CostModel(ff3, MESH).op_grad_sync_time(odd, dp))


def test_fsdp_validation_and_indivisible_fallback():
    with pytest.raises(ValueError, match="not a mesh axis"):
        cfg = FFConfig(batch_size=8, mesh_shape={"data": 2},
                       fsdp_axis="zero")
        ff = FFModel(cfg)
        x = ff.create_tensor([8, 16], name="input")
        ff.dense(x, 4, name="d")
        ff.compile()
    # a weight with no divisible dim stays unsharded instead of failing
    cfg = FFConfig(batch_size=8, mesh_shape={"data": 8}, fsdp_axis="data")
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 6], name="input")
    ff.dense(x, 6, name="tiny")  # 6x6: nothing divides 8
    ff.compile()
    assert "data" not in str(ff.params["tiny"]["kernel"].sharding.spec)
