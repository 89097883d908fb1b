"""1F1B pipeline schedule + STAGE (pipeline-parallel) strategy search.

VERDICT r3 #5: pipeline as a schedule library (1F1B with the O(stages)
activation bound, parallel/pipeline.py) and as a search axis (STAGE
axis_map marker proposed by legal_axis_maps, priced by the cost model,
executed by TransformerPipelineStack under any mesh axis name).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.parallel.pipeline import pipeline_train_1f1b


def _mlp_stages(n, d, rs):
    return {"w": jnp.asarray(rs.randn(n, d, d).astype(np.float32) * 0.3),
            "b": jnp.asarray(rs.randn(n, d).astype(np.float32) * 0.1)}


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _loss_fn(y, lab, hp):
    return jnp.mean((y @ hp["wo"] - lab) ** 2)


def _serial_loss(stacked, hp, x, lab, n, m):
    xm = x.reshape(m, x.shape[0] // m, *x.shape[1:])
    lm = lab.reshape(m, lab.shape[0] // m, *lab.shape[1:])

    def one(j):
        h = xm[j]
        for i in range(n):
            h = _stage_fn({k: v[i] for k, v in stacked.items()}, h)
        return _loss_fn(h, lm[j], hp)

    return jnp.mean(jnp.stack([one(j) for j in range(m)]))


@pytest.mark.parametrize("n,m", [(4, 8), (4, 4), (2, 6)])
def test_1f1b_matches_serial_autodiff(n, m):
    """Loss, stage grads, head grads, and dx from the hand-scheduled 1F1B
    loop must equal autodiff through the serial model. Grads come back as
    microbatch SUMS (loss_fn returns per-microbatch means), so the serial
    mean-grad scales by m."""
    mb, d = 2, 16
    rs = np.random.RandomState(0)
    stacked = _mlp_stages(n, d, rs)
    head = {"wo": jnp.asarray(rs.randn(d, 4).astype(np.float32) * 0.3)}
    x = jnp.asarray(rs.randn(m * mb, d).astype(np.float32))
    lab = jnp.asarray(rs.randn(m * mb, 4).astype(np.float32))
    mesh = make_mesh({"pipe": n})

    loss, g, gh, dx = jax.jit(
        lambda sp, hp, xx, ll: pipeline_train_1f1b(
            _stage_fn, _loss_fn, sp, xx, ll, mesh,
            num_microbatches=m, head_params=hp))(stacked, head, x, lab)

    ref = jax.grad(_serial_loss, argnums=(0, 1, 2))(
        stacked, head, x, lab, n, m)
    ref_loss = _serial_loss(stacked, head, x, lab, n, m)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in stacked:
        np.testing.assert_allclose(np.asarray(g[k]),
                                   np.asarray(ref[0][k]) * m,
                                   rtol=1e-4, atol=1e-5, err_msg=k)
    np.testing.assert_allclose(np.asarray(gh["wo"]),
                               np.asarray(ref[1]["wo"]) * m,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(ref[2]) * m,
                               rtol=1e-4, atol=1e-5)


def test_1f1b_dp_pp_composition():
    """pipe=4 x data=2: each data slice pipelines its microbatch shard;
    grads psum over data, numerics equal the serial model."""
    n, m, mb, d = 4, 4, 4, 8
    rs = np.random.RandomState(1)
    stacked = _mlp_stages(n, d, rs)
    head = {"wo": jnp.asarray(rs.randn(d, 4).astype(np.float32) * 0.3)}
    x = jnp.asarray(rs.randn(m * mb, d).astype(np.float32))
    lab = jnp.asarray(rs.randn(m * mb, 4).astype(np.float32))
    mesh = make_mesh({"pipe": n, "data": 2})

    loss, g, gh, dx = pipeline_train_1f1b(
        _stage_fn, _loss_fn, stacked, x, lab, mesh,
        num_microbatches=m, head_params=head, data_axis="data")

    ref_loss = _serial_loss(stacked, head, x, lab, n, m)
    ref = jax.grad(_serial_loss, argnums=(0,))(stacked, head, x, lab, n, m)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k in stacked:
        np.testing.assert_allclose(np.asarray(g[k]),
                                   np.asarray(ref[0][k]) * m,
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def _deep_stack_model(mesh_shape, L=8, B=4, S=16, D=64, H=2):
    from flexflow_tpu import FFConfig, FFModel

    cfg = FFConfig(batch_size=B, mesh_shape=mesh_shape)
    ff = FFModel(cfg)
    xt = ff.create_tensor([B, S, D], name="x")
    t = ff.transformer_pipeline_stack(xt, L, H, name="stack")
    ff.dense(t, 8, name="head")
    return ff, xt


def test_legal_axis_maps_proposes_stage():
    from flexflow_tpu.parallel.pconfig import STAGE
    from flexflow_tpu.search.driver import legal_axis_maps

    mesh_shape = {"grid": 8}
    ff, _ = _deep_stack_model(mesh_shape)
    stack = next(op for op in ff.ops if op.name == "stack")
    maps = legal_axis_maps(stack, mesh_shape)
    assert {"grid": STAGE} in maps, maps
    # head (no stacked layers) must NOT get STAGE proposals
    head = next(op for op in ff.ops if op.name == "head")
    assert not any(d == STAGE for m in legal_axis_maps(head, mesh_shape)
                   for d in m.values())


def test_simulator_prices_pp_above_dp_for_deep_thin_model():
    """Deep stack, small batch: DP pays a full-weight grad all-reduce every
    step; PP shards the layers and pays only bubble + boundary p2p. The
    cost model must rank the pipe strategy faster — this is the 'search
    can discover PP' precondition, and the MCMC must then actually pick
    it."""
    from flexflow_tpu.parallel.pconfig import STAGE
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.driver import (data_parallel_strategy,
                                            optimize_strategies)

    mesh_shape = {"data": 8}
    # wide enough that the layers' compute counts: at S=16, D=128 a step is
    # latency alone and the all-replicated seed (no collective at all)
    # beats both, which the search rightly returns
    ff, _ = _deep_stack_model(mesh_shape, L=8, B=8, S=64, D=512)
    cost = CostModel(ff, mesh_shape)
    dp = data_parallel_strategy(ff, mesh_shape)
    pp = dict(dp)
    pp["stack"] = {"data": STAGE}
    t_dp = cost.iteration_time(dp)
    t_pp = cost.iteration_time(pp)
    assert t_pp < t_dp, f"PP {t_pp} not faster than DP {t_dp}"
    assert t_pp < cost.iteration_time({name: {} for name in dp})

    best = optimize_strategies(ff, budget=3000, mesh_shape=mesh_shape,
                               seed=0)
    assert any(d == STAGE
               for d in (best["stack"].axis_map or {}).values()), \
        f"search did not discover PP: {best['stack'].axis_map}"


def test_stage_priced_correctly_under_mesh_override():
    """Searching over a mesh_shape override whose axis is absent from the
    model config must still shard stage weights in weight_partition —
    otherwise grad-sync pricing charges PP candidates a full stacked-weight
    all-reduce and the search can never discover them."""
    from flexflow_tpu.parallel.pconfig import STAGE
    from flexflow_tpu.search.cost_model import CostModel

    ff, _ = _deep_stack_model({"data": 1}, L=8, B=8, S=16, D=128)
    override = {"grid": 8}
    stack = next(op for op in ff.ops if op.name == "stack")
    wp = stack.weight_partition({"grid": STAGE})
    assert wp["w1"][0] == "grid", wp["w1"]
    cost = CostModel(ff, override)
    assert cost.op_grad_sync_time(stack, {"grid": STAGE}) == 0.0
    t_pp = cost.iteration_time({"stack": {"grid": STAGE}, "head": {}})
    t_dp = cost.iteration_time({"stack": {"grid": 0}, "head": {}})
    assert t_pp < t_dp


def test_stack_executes_search_assigned_stage_axis():
    """A STAGE assignment on an arbitrary mesh axis name (not 'pipe') must
    activate the pipeline lowering, shard stage weights over that axis,
    and match the serial model's forward numerics."""
    from flexflow_tpu.parallel.pconfig import STAGE, ParallelConfig

    B, S, D, H, L = 4, 8, 32, 2, 8
    rs = np.random.RandomState(3)
    x = rs.randn(B, S, D).astype(np.float32)

    def build(mesh_shape, strategies=None):
        from flexflow_tpu import FFConfig, FFModel

        cfg = FFConfig(batch_size=B, mesh_shape=mesh_shape, seed=5)
        if strategies:
            cfg.strategies.update(strategies)
        ff = FFModel(cfg)
        xt = ff.create_tensor([B, S, D], name="x")
        t = ff.transformer_pipeline_stack(xt, L, H, name="stack")
        ff.compile(optimizer=None, final_tensor=t)
        return ff

    serial = build({"data": 1})
    y_serial = np.asarray(serial.predict({"x": x}))

    st = {"stack": ParallelConfig.from_axis_map(
        3, {"blocks": 4}, {"blocks": STAGE})}
    piped = build({"blocks": 4}, st)
    for k, v in serial.params["stack"].items():
        piped.set_weights("stack", k, np.asarray(v))
    y_piped = np.asarray(piped.predict({"x": x}))
    np.testing.assert_allclose(y_piped, y_serial, rtol=2e-4, atol=2e-5)

    # stage weights really shard over 'blocks'
    spec = piped.params["stack"]["w1"].sharding.spec
    assert spec[0] == "blocks", spec


def test_stage_strategy_file_round_trip(tmp_path):
    """A search-discovered PP strategy survives save -> load -> execute:
    the @axismap extension record persists STAGE (degrees alone cannot),
    and the loaded file drives the pipelined lowering via
    import_strategy_file."""
    from flexflow_tpu.parallel.pconfig import CONTRACT, STAGE, ParallelConfig
    from flexflow_tpu.parallel.strategy import (load_strategies_from_file,
                                                save_strategies_to_file)

    mesh = {"grid": 4, "data": 2}
    st = {
        "stack": ParallelConfig.from_axis_map(3, mesh,
                                              {"grid": STAGE, "data": 0}),
        "proj": ParallelConfig.from_axis_map(2, mesh,
                                             {"grid": CONTRACT, "data": 0}),
        "head": ParallelConfig.from_axis_map(2, mesh, {"data": 0}),
    }
    f = str(tmp_path / "pp_strategy.txt")
    save_strategies_to_file(f, st)
    back = load_strategies_from_file(f)
    for name in st:
        assert back[name].axis_map == st[name].axis_map, name
        assert back[name].dims == st[name].dims, name

    # execute through import_strategy_file: the stack must actually
    # pipeline over 'grid' (stage weights sharded on the loaded strategy)
    from flexflow_tpu import FFConfig, FFModel

    B, S, D, H, L = 8, 8, 32, 2, 8
    cfg = FFConfig(batch_size=B, mesh_shape=mesh, seed=5,
                   import_strategy_file=f)
    ff = FFModel(cfg)
    xt = ff.create_tensor([B, S, D], name="x")
    t = ff.transformer_pipeline_stack(xt, L, H, name="stack")
    ff.compile(optimizer=None, final_tensor=t)
    assert ff.params["stack"]["w1"].sharding.spec[0] == "grid"


def test_strategy_file_wrong_mesh_fails_clearly(tmp_path):
    """A file written on a differently-NAMED mesh must fail with the axis
    named, not deep inside JAX."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.pconfig import STAGE, ParallelConfig
    from flexflow_tpu.parallel.strategy import save_strategies_to_file

    f = str(tmp_path / "other_mesh.txt")
    save_strategies_to_file(f, {"stack": ParallelConfig.from_axis_map(
        3, {"grid": 4}, {"grid": STAGE})})
    cfg = FFConfig(batch_size=4, mesh_shape={"pipes": 4},
                   import_strategy_file=f)
    ff = FFModel(cfg)
    xt = ff.create_tensor([4, 8, 32], name="x")
    t = ff.transformer_pipeline_stack(xt, 8, 2, name="stack")
    with pytest.raises(ValueError, match="grid"):
        ff.compile(optimizer=None, final_tensor=t)


def test_1f1b_dead_ticks_cannot_poison_grads_with_nonfinite():
    """ADVICE r4 (medium): warm-up / drain ticks run stage_fn and loss_fn
    on zero-initialized garbage. A stage whose math divides by an
    input-dependent quantity yields inf/NaN there; with the old
    multiply-by-mask accumulation (0 * inf = NaN) one dead tick poisoned
    the grads of the whole step. The select-based mask must keep grads
    finite AND equal to serial autodiff."""
    n, m, mb, d = 4, 6, 2, 16
    rs = np.random.RandomState(7)
    stacked = _mlp_stages(n, d, rs)
    head = {"wo": jnp.asarray(rs.randn(d, 4).astype(np.float32) * 0.3)}
    x = jnp.asarray((rs.randn(m * mb, d) + 3.0).astype(np.float32))
    lab = jnp.asarray(rs.randn(m * mb, 4).astype(np.float32))
    mesh = make_mesh({"pipe": n})

    def bad_stage(p, h):
        # 1/sqrt(mean(h^2)): finite on real activations, inf/NaN on the
        # all-zero garbage that dead ticks carry
        return jnp.tanh(h @ p["w"] + p["b"]) / jnp.sqrt(jnp.mean(h * h))

    def serial(sp, hp, xx, ll):
        xm = xx.reshape(m, mb, d)
        lm = ll.reshape(m, mb, 4)

        def one(j):
            h = xm[j]
            for i in range(n):
                h = bad_stage({k: v[i] for k, v in sp.items()}, h)
            return _loss_fn(h, lm[j], hp)

        return jnp.mean(jnp.stack([one(j) for j in range(m)]))

    loss, g, gh, dx = jax.jit(
        lambda sp, hp, xx, ll: pipeline_train_1f1b(
            bad_stage, _loss_fn, sp, xx, ll, mesh,
            num_microbatches=m, head_params=hp))(stacked, head, x, lab)

    for name, arr in [("loss", loss), ("g.w", g["w"]), ("g.b", g["b"]),
                      ("gh.wo", gh["wo"]), ("dx", dx)]:
        assert bool(jnp.all(jnp.isfinite(arr))), \
            f"{name} contains non-finite values (dead-tick leak)"
    ref = jax.grad(serial, argnums=(0, 1))(stacked, head, x, lab)
    for k in stacked:
        np.testing.assert_allclose(np.asarray(g[k]),
                                   np.asarray(ref[0][k]) * m,
                                   rtol=1e-3, atol=1e-4, err_msg=k)
    np.testing.assert_allclose(np.asarray(gh["wo"]),
                               np.asarray(ref[1]["wo"]) * m,
                               rtol=1e-3, atol=1e-4)
