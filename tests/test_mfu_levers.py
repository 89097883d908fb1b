"""MFU levers (VERDICT r2 #4): bf16 master weights and the fused
residual-add + layernorm op. Numerics verified on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                          SGDOptimizer, SingleDataLoader)
from flexflow_tpu.models.transformer import build_encoder_classifier


def _train(master_dtype="float32", use_fused_ln=False, steps=3,
           compute="float32"):
    cfg = FFConfig(batch_size=4, mesh_shape={"data": 1}, seed=2,
                   compute_dtype=compute, master_dtype=master_dtype,
                   use_fused_ln=use_fused_ln)
    ff = FFModel(cfg)
    x, out = build_encoder_classifier(ff, 4, 32, 64, 2, 4)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=out)
    rs = np.random.RandomState(0)
    SingleDataLoader(ff, x, rs.randn(8, 32, 64).astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 16, (8, 1)).astype(np.int32))
    losses = []
    for _ in range(steps):
        loss, _ = ff._run_train_step(ff._stage_batch())
        losses.append(float(loss))
    return losses, ff


@pytest.mark.slow  # 21 s; bf16 master also pinned by fused_optimizer_scanned_training_bitwise
def test_bf16_master_weights_train_and_store_bf16():
    losses, ff = _train(master_dtype="bfloat16", compute="bfloat16")
    kernels = [v for op in ff.params.values() for k, v in op.items()
               if k == "kernel"]
    assert kernels and all(w.dtype == jnp.bfloat16 for w in kernels)
    assert losses[-1] < losses[0]  # training still converges
    # f32 math inside the update: trajectories track the f32-master run
    ref, _ = _train(master_dtype="float32", compute="bfloat16")
    np.testing.assert_allclose(losses, ref, rtol=0.08)


def test_fused_add_layernorm_matches_unfused_ops():
    """The fused op's two outputs equal add + layer_norm run separately
    (same weights), forward and gradient."""
    from flexflow_tpu.ops.pallas_kernels import fused_add_layernorm

    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(64, 128), jnp.float32)
    r = jnp.asarray(rs.randn(64, 128), jnp.float32)
    scale = jnp.asarray(rs.rand(128) + 0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(128), jnp.float32)

    def ref(x, r, scale, bias):
        s = x + r
        mean = jnp.mean(s, -1, keepdims=True)
        var = jnp.var(s, -1, keepdims=True)
        return s, (s - mean) * jax.lax.rsqrt(var + 1e-5) * scale + bias

    s1, y1 = fused_add_layernorm(x, r, scale, bias)
    s2, y2 = ref(x, r, scale, bias)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)

    def loss_f(f):
        def inner(x, r, scale, bias):
            s, y = f(x, r, scale, bias)
            return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(s))
        return inner

    g1 = jax.grad(loss_f(fused_add_layernorm), argnums=(0, 1, 2, 3))(
        x, r, scale, bias)
    g2 = jax.grad(loss_f(ref), argnums=(0, 1, 2, 3))(x, r, scale, bias)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.slow  # 14 s; fused-LN is opt-in (benched as a loss at h=1024), kernel parity test stays
def test_fused_ln_transformer_trains():
    losses, ff = _train(use_fused_ln=True)
    assert losses[-1] < losses[0]
    names = [op.name for op in ff.ops]
    assert any(n.startswith("res1_ln2") for n in names)
    # same norm-parameter count as the unfused graph: 2L+1
    _, ff_ref = _train(use_fused_ln=False, steps=1)
    n_norm_params = sum(1 for op in ff.params.values() for k in op
                       if k in ("scale",))
    n_ref = sum(1 for op in ff_ref.params.values() for k in op
                if k in ("scale",))
    assert n_norm_params == n_ref


@pytest.mark.slow  # 14 s; fused-LN is opt-in, kernel parity test stays
def test_fused_ln_shard_mapped_under_dp(monkeypatch):
    """Multi-chip fused LN: the Pallas kernel runs per-shard inside
    shard_map under a sharded strategy (GSPMD cannot partition a Mosaic
    custom call); losses must match the single-device fused run exactly."""
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")

    def losses(mesh):
        cfg = FFConfig(batch_size=8, mesh_shape=mesh, seed=4,
                       use_fused_ln=True)
        ff = FFModel(cfg)
        x, out = build_encoder_classifier(ff, 8, 32, 128, 1, 4)
        ff.compile(SGDOptimizer(lr=0.05),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY], final_tensor=out)
        rs = np.random.RandomState(0)
        SingleDataLoader(ff, x, rs.randn(16, 32, 128).astype(np.float32))
        SingleDataLoader(ff, ff.label_tensor,
                         rs.randint(0, 16, (16, 1)).astype(np.int32))
        return [float(ff._run_train_step(ff._stage_batch())[0])
                for _ in range(3)]

    np.testing.assert_allclose(losses({"data": 1}), losses({"data": 4}),
                               rtol=2e-4)


# ---- fused optimizer update (VERDICT r3 #4) --------------------------------


def _rand_tree(rs, dtype=np.float32):
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype(dtype))
    return {"a": {"kernel": mk(16, 8), "bias": mk(8)},
            "b": {"kernel": mk(8, 4), "bias": mk(4), "scale": mk(4)}}


@pytest.mark.parametrize("opt_kind,kwargs", [
    ("sgd", {}),
    ("sgd", {"momentum": 0.9, "nesterov": True, "weight_decay": 0.01}),
    ("adam", {"weight_decay": 0.01}),
])
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_fused_update_bitwise_matches_per_leaf(opt_kind, kwargs, dtype):
    """FusedUpdate flattens leaves into one vector per dtype bucket; the
    elementwise formula is unchanged, so results must be BIT-identical to
    the per-leaf update across steps (incl. bf16 master storage)."""
    from flexflow_tpu.runtime.optimizer import (AdamOptimizer, FusedUpdate,
                                                SGDOptimizer)

    mk = lambda: (SGDOptimizer(lr=0.1, **kwargs) if opt_kind == "sgd"
                  else AdamOptimizer(alpha=0.01, **kwargs))
    rs = np.random.RandomState(0)
    np_dtype = np.float32 if dtype == "bfloat16" else dtype
    params = _rand_tree(rs, np_dtype)
    if dtype == "bfloat16":
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)

    ref_opt, fused_opt = mk(), FusedUpdate(mk())
    p_ref, s_ref = params, ref_opt.init_state(params)
    p_fused, s_fused = params, fused_opt.init_state(params)
    for step in range(4):
        grads = _rand_tree(rs, np_dtype)
        if dtype == "bfloat16":
            grads = jax.tree.map(lambda a: a.astype(jnp.bfloat16), grads)
        p_ref, s_ref = jax.jit(ref_opt.update)(p_ref, grads, s_ref)
        p_fused, s_fused = jax.jit(fused_opt.update)(p_fused, grads, s_fused)
        for op in p_ref:
            for w in p_ref[op]:
                a, b = np.asarray(p_ref[op][w]), np.asarray(p_fused[op][w])
                assert a.dtype == b.dtype
                np.testing.assert_array_equal(a, b, err_msg=f"{op}/{w}@{step}")


def test_fused_optimizer_end_to_end_and_sharded_fallback():
    """FFConfig.fused_optimizer trains end-to-end (replicated weights) and
    falls back with a warning when the strategy shards a weight."""
    from flexflow_tpu.parallel.pconfig import ParallelConfig
    from flexflow_tpu.runtime.optimizer import FusedUpdate

    def build(mesh, strategies=None):
        cfg = FFConfig(batch_size=8, mesh_shape=mesh, seed=3,
                       fused_optimizer=True)
        if strategies:
            cfg.strategies.update(strategies)
        ff = FFModel(cfg)
        x = ff.create_tensor([8, 16], name="x")
        t = ff.dense(x, 32, name="fc1")
        ff.dense(t, 8, name="fc2")
        ff.compile(SGDOptimizer(lr=0.1),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        return ff

    rs = np.random.RandomState(0)
    ff = build({"data": 2})
    assert isinstance(ff.optimizer, FusedUpdate)
    SingleDataLoader(ff, ff.ops[0].outputs[0],
                     rs.randn(16, 16).astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 8, (16, 1)).astype(np.int32))
    ff.fit(epochs=2)

    # TP-sharded weight -> the shard-local fused update (VERDICT r4 #3:
    # the lever must not no-op exactly where it matters)
    from flexflow_tpu.runtime.optimizer import ShardedFusedUpdate

    tp = {"fc1": ParallelConfig.from_axis_map(
        2, {"data": 2, "model": 2}, {"data": 0, "model": 1})}
    ff2 = build({"data": 2, "model": 2}, tp)
    assert isinstance(ff2.optimizer, ShardedFusedUpdate)
    SingleDataLoader(ff2, ff2.ops[0].outputs[0],
                     rs.randn(16, 16).astype(np.float32))
    SingleDataLoader(ff2, ff2.label_tensor,
                     rs.randint(0, 8, (16, 1)).astype(np.int32))
    ff2.fit(epochs=2)  # trains end-to-end under TP


def _sharded_vs_per_leaf(mesh_shape, strategies=None, fsdp_axis="",
                         steps=4, master="float32"):
    """Train the same model with fused_optimizer on/off on a sharded mesh;
    return (losses_fused, losses_ref, params_fused, params_ref, opt_f)."""
    from flexflow_tpu.parallel.pconfig import ParallelConfig

    def build(fused):
        cfg = FFConfig(batch_size=8, mesh_shape=dict(mesh_shape), seed=5,
                       fused_optimizer=fused, master_dtype=master,
                       fsdp_axis=fsdp_axis)
        if strategies:
            cfg.strategies.update({k: ParallelConfig.from_axis_map(*v)
                                   for k, v in strategies.items()})
        from flexflow_tpu.ffconst import ActiMode

        ff = FFModel(cfg)
        x = ff.create_tensor([8, 16], name="x")
        t = ff.dense(x, 32, name="fc1", activation=ActiMode.AC_MODE_RELU)
        t = ff.dense(t, 32, name="fc2", activation=ActiMode.AC_MODE_RELU)
        ff.dense(t, 8, name="head")
        from flexflow_tpu import AdamOptimizer

        ff.compile(AdamOptimizer(alpha=0.01),
                   LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        rs = np.random.RandomState(1)
        SingleDataLoader(ff, x, rs.randn(16, 16).astype(np.float32))
        SingleDataLoader(ff, ff.label_tensor,
                         rs.randint(0, 8, (16, 1)).astype(np.int32))
        losses = [float(ff._run_train_step(ff._stage_batch())[0])
                  for _ in range(steps)]
        return losses, ff

    lf, ff_f = build(True)
    lr, ff_r = build(False)
    return lf, lr, ff_f, ff_r


@pytest.mark.parametrize("case", ["tp", "fsdp"])
def test_sharded_fused_update_bitwise_matches_per_leaf(case):
    """ShardedFusedUpdate (shard_map-local flatten) must be BIT-identical
    to the per-leaf update under TP and FSDP shardings — same elementwise
    formula, concat of local shards changes no values (VERDICT r4 #3)."""
    from flexflow_tpu.runtime.optimizer import ShardedFusedUpdate

    if case == "tp":
        strat = {"fc1": (2, {"data": 2, "model": 2}, {"data": 0, "model": 1}),
                 "fc2": (2, {"data": 2, "model": 2},
                         {"data": 0, "model": -2})}  # CONTRACT row-parallel
        lf, lr, ff_f, ff_r = _sharded_vs_per_leaf({"data": 2, "model": 2},
                                                  strat)
    else:
        lf, lr, ff_f, ff_r = _sharded_vs_per_leaf({"data": 4},
                                                  fsdp_axis="data")
    assert isinstance(ff_f.optimizer, ShardedFusedUpdate)
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lr))
    for op in ff_r.params:
        for w in ff_r.params[op]:
            a = np.asarray(ff_r.params[op][w])
            b = np.asarray(ff_f.params[op][w])
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b, err_msg=f"{op}/{w}")
    # per-device state bytes match the per-leaf layout: flat state is
    # sharded over ALL axes (each device persists only its slice)
    flat_m = ff_f.opt_state["m"]
    n_dev = ff_f.mesh.devices.size
    for dt, vec in flat_m.items():
        assert vec.addressable_shards[0].data.size * n_dev == vec.size, \
            f"flat state {dt} is not fully sharded"


def test_fused_grad_dtype_mismatch_buckets_by_param_dtype():
    """ADVICE r4: a grad leaf whose dtype differs from its param's must
    not misalign the dtype buckets (grads bucket by PARAM dtype) — and
    a full-precision f32 grad for a bf16 param is NOT rounded through
    bf16, so the result stays bit-identical to the per-leaf update."""
    from flexflow_tpu.runtime.optimizer import (AdamOptimizer, FusedUpdate)

    rs = np.random.RandomState(0)
    params = {"a": {"k": jnp.asarray(rs.randn(8, 4), jnp.float32)},
              "b": {"k": jnp.asarray(rs.randn(4), jnp.bfloat16)}}
    # grads dtypes SWAPPED vs params: independent bucketing would pair
    # a's grad with b's param (symmetric counts -> silent wrong pairing)
    grads = {"a": {"k": jnp.asarray(rs.randn(8, 4), jnp.bfloat16)},
             "b": {"k": jnp.asarray(rs.randn(4), jnp.float32)}}
    mk = lambda: AdamOptimizer(alpha=0.01, weight_decay=0.01)
    fused, ref = FusedUpdate(mk()), mk()
    pf, sf = params, fused.init_state(params)
    pr, sr = params, ref.init_state(params)
    for _ in range(3):
        pf, sf = jax.jit(fused.update)(pf, grads, sf)
        pr, sr = jax.jit(ref.update)(pr, grads, sr)
    for op in params:
        a, b = np.asarray(pr[op]["k"]), np.asarray(pf[op]["k"])
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b, err_msg=op)


@pytest.mark.parametrize("opt_kind", ["sgd", "adam"])
@pytest.mark.parametrize("master", ["float32", "bfloat16"])
def test_fused_optimizer_scanned_training_bitwise(opt_kind, master):
    """train_scanned + FusedUpdate: the
    scanned multi-step program with the fused update must be bit-identical
    to the per-leaf update — a break here would burn the TPU ablation
    window."""
    from flexflow_tpu import AdamOptimizer

    def run(fused):
        cfg = FFConfig(batch_size=8, mesh_shape={"data": 1}, seed=4,
                       fused_optimizer=fused, master_dtype=master)
        ff = FFModel(cfg)
        x = ff.create_tensor([8, 16], name="x")
        t = ff.dense(x, 32, name="fc1")
        ff.dense(t, 8, name="fc2")
        opt = (SGDOptimizer(lr=0.05) if opt_kind == "sgd"
               else AdamOptimizer(alpha=0.01))
        ff.compile(opt, LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_ACCURACY])
        rs = np.random.RandomState(0)
        SingleDataLoader(ff, x, rs.randn(32, 16).astype(np.float32))
        SingleDataLoader(ff, ff.label_tensor,
                         rs.randint(0, 8, (32, 1)).astype(np.int32))
        losses, _ = ff.train_scanned(6)
        return np.asarray(losses)

    np.testing.assert_array_equal(run(False), run(True))
