"""The power-retention op (ops/retention.py) at tiny sizes in float32 on the
CPU: the quadratic form of the layer equations (written out here, no state, no
chunks) against the chunked `forward`, the token-by-token step and the pool
update; five query heads share one KV head's state; the Pallas step
(interpreted) against the `jax.numpy` step, live and dead slots.

Every tolerance stands beside its reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops.retention import (PowerRetention, phi,
                                        retention_chunked,
                                        retention_state_update,
                                        retention_step)

EPS_N = 1e-5
# float32 sums of a few dozen products of order 1 in different orders (the
# quadratic form against chunks against steps): 1e-6 relative; the outputs
# are ratios of order 1. Measured 1e-6 to 1.3e-5 (a row whose weights nearly
# cancel loses digits in the ratio).
ATOL = 5e-5


def draws(b=2, s=20, kv=2, r=3, hd=16, seed=0):
    rs = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rs.normal(size=shape), jnp.float32)
    l = -jnp.asarray(rs.uniform(0.01, 0.3, size=(b, s, kv)), jnp.float32)
    return f(b, s, kv, r, hd), f(b, s, kv, hd), f(b, s, kv, hd), l


def quadratic(q, k, v, l, eps_n=EPS_N):
    """y_t,i = sum_{j <= t} w_tj v_j / (sum_j w_tj + eps_n), w_tj = (q_t,i .
    k_j / hd)^2 exp(G_t - G_j): the layer equations as they are written."""
    s, hd = q.shape[1], q.shape[-1]
    g = jnp.cumsum(l, axis=1).transpose(0, 2, 1)             # (B, KV, S)
    sc = jnp.einsum("btgrk,bjgk->bgrtj", q, k) / hd
    w = sc ** 2 * jnp.exp(g[:, :, None, :, None] - g[:, :, None, None, :])
    w = jnp.where(jnp.tril(jnp.ones((s, s), bool)), w, 0.0)
    return (jnp.einsum("bgrtj,bjgv->btgrv", w, v)
            / (w.sum(-1).transpose(0, 3, 1, 2) + eps_n)[..., None])


def zero_state(b, kv, hd):
    nd = hd // 2 + 1
    return jnp.zeros((b, kv, nd, hd, hd)), jnp.zeros((b, kv, nd, hd))


def test_phi_is_the_symmetric_square_by_diagonals():
    """phi(x) . phi(y) = (x . y / hd)^2 exactly (every unordered pair once
    with sqrt 2, the squares once, the half-way diagonal's pairs twice with
    1), in (hd / 2 + 1) x hd entries."""
    rs = np.random.default_rng(1)
    for hd in (2, 8, 16, 128):
        x, y = (jnp.asarray(rs.normal(size=(hd,)), jnp.float32)
                for _ in range(2))
        assert phi(x).shape == (hd // 2 + 1, hd)
        np.testing.assert_allclose(float((phi(x) * phi(y)).sum()),
                                   float((x @ y / hd) ** 2), rtol=2e-5)


@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_form_is_the_quadratic_form(chunk):
    """Chunks of 4 and 8 (20 rows: a ragged last chunk) and one chunk larger
    than the slab."""
    q, k, v, l = draws()
    want = quadratic(q, k, v, l)
    live = jnp.ones(q.shape[:2], bool)
    y, _, _ = retention_chunked(q, k, v, l, live, *zero_state(2, 2, 16),
                                chunk, EPS_N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=ATOL,
                               rtol=0)


def test_steps_are_the_quadratic_form_and_end_in_the_chunks_state():
    q, k, v, l = draws()
    want = quadratic(q, k, v, l)
    live = jnp.ones(q.shape[:2], bool)
    _, st_c, z_c = retention_chunked(q, k, v, l, live,
                                     *zero_state(2, 2, 16), 8, EPS_N)
    st, z = zero_state(2, 2, 16)
    for t in range(q.shape[1]):
        num, den, st, z = retention_step(st, z, jnp.exp(l[:, t]), q[:, t],
                                         k[:, t], v[:, t])
        np.testing.assert_allclose(
            np.asarray(num / (den + EPS_N)[..., None]),
            np.asarray(want[:, t]), atol=ATOL, rtol=0)
    # the state: sums of 20 products of order 1 / hd
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_c), atol=1e-6)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_c), atol=1e-6)


def test_rows_past_row_lengths_leave_the_state_alone():
    """A slab of 20 rows of which 13 and 20 are live, in two slabs of 12 and
    8 (`start`, `row_lengths`), ends in the state of the live rows alone, and
    resuming from a slab's state is the whole slab."""
    q, k, v, l = draws()
    lens = jnp.asarray([13, 20])
    live = jnp.arange(20)[None, :] < lens[:, None]
    lm = jnp.where(live[..., None], l, 0.0)
    _, st, z = retention_chunked(q, k, v, lm, live, *zero_state(2, 2, 16),
                                 8, EPS_N)
    for b, n in enumerate((13, 20)):
        _, st1, z1 = retention_chunked(
            q[b:b + 1, :n], k[b:b + 1, :n], v[b:b + 1, :n], l[b:b + 1, :n],
            jnp.ones((1, n), bool), *zero_state(1, 2, 16), 8, EPS_N)
        np.testing.assert_allclose(np.asarray(st[b]), np.asarray(st1[0]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(z[b]), np.asarray(z1[0]),
                                   atol=1e-6)
    y_a, st_a, z_a = retention_chunked(
        q[:, :12], k[:, :12], v[:, :12], lm[:, :12], live[:, :12],
        *zero_state(2, 2, 16), 8, EPS_N)
    y_b, st_b, z_b = retention_chunked(
        q[:, 12:], k[:, 12:], v[:, 12:], lm[:, 12:], live[:, 12:], st_a, z_a,
        8, EPS_N)
    np.testing.assert_allclose(np.asarray(st_b), np.asarray(st), atol=1e-6)
    want = quadratic(q, k, v, l)
    got = jnp.concatenate([y_a, y_b], axis=1)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(np.asarray(got[0, :13]),
                               np.asarray(want[0, :13]), atol=ATOL, rtol=0)


def test_the_query_heads_of_a_group_share_one_kv_heads_state():
    """R = 5 query heads a KV head: the state has KV entries, not H, and a
    group's five outputs are five read-outs of the SAME S and z."""
    q, k, v, l = draws(b=1, s=9, kv=2, r=5, hd=8, seed=3)
    st, z = zero_state(1, 2, 8)
    assert st.shape == (1, 2, 5, 8, 8)
    for t in range(9):
        num, den, st, z = retention_step(st, z, jnp.exp(l[:, t]), q[:, t],
                                         k[:, t], v[:, t])
    for i in range(5):
        one, d1, _, _ = retention_step(
            *(a for a in retention_chunked(
                q[:, :8, :, i:i + 1], k[:, :8], v[:, :8], l[:, :8],
                jnp.ones((1, 8), bool), *zero_state(1, 2, 8), 4,
                EPS_N)[1:]),
            jnp.exp(l[:, 8]), q[:, 8, :, i:i + 1], k[:, 8], v[:, 8])
        np.testing.assert_allclose(np.asarray(one[:, :, 0]),
                                   np.asarray(num[:, :, i]), atol=1e-6)
        np.testing.assert_allclose(np.asarray(d1[:, :, 0]),
                                   np.asarray(den[:, :, i]), atol=1e-6)


@pytest.mark.parametrize("live", [
    [True] * 5, [False, True, False, True, False], [False] * 5,
    [True, False, False, False, True]])
def test_pallas_step_matches_the_xla_oracle(live):
    """The Pallas state update (interpreted; phi formed in the kernel by
    rotations of one tile of rows) against XLA's loop over the live rows, at
    R = 5 query heads a KV head (the tile's eight rows: five q, k, v, the
    decay): live slots advance, dead slots keep every bit."""
    from flexflow_tpu.ops.pallas_kernels import retention_state_update_pallas

    slots, kv, r, hd = 5, 2, 5, 32
    nd = hd // 2 + 1
    rs = np.random.default_rng(9)
    f = lambda *shape: jnp.asarray(rs.normal(size=shape), jnp.float32)
    st, z = f(slots, kv, nd, hd, hd), f(slots, kv, nd, hd)
    q, k, v = f(slots, kv, r, hd), f(slots, kv, hd), f(slots, kv, hd)
    decay = jnp.asarray(rs.uniform(0.5, 1.0, (slots, kv)), jnp.float32)
    live = jnp.asarray(live)
    want = jax.jit(retention_state_update)(st, z, decay, q, k, v, live)
    got = jax.jit(retention_state_update_pallas)(st, z, decay, q, k, v, live)
    # sums of (hd / 2 + 1) x hd products of order 1 / hd
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=0)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(got[2])[dead],
                                  np.asarray(st)[dead])
    np.testing.assert_array_equal(np.asarray(got[3])[dead],
                                  np.asarray(z)[dead])
    assert not np.asarray(got[0])[dead].any()


def op_and_params(seed=5, chunk=8):
    ff = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}))
    x = ff.create_tensor([2, 24, 32], name="x")
    ff.power_retention(x, 6, 2, 8, rope_theta=100.0, chunk_size=chunk,
                       name="ret")
    op = ff.get_op_by_name("ret")
    rs = np.random.RandomState(seed)
    params = {w.name: jnp.asarray(rs.randn(*w.shape).astype(np.float32)
                                  * 0.3) for w in op.weight_specs()}
    params["gate_bias"] = jnp.asarray([1.5, 3.0], jnp.float32)
    params["q_norm"] = 1 + 0.3 * params["q_norm"]
    params["k_norm"] = 1 + 0.3 * params["k_norm"]
    return op, params, jnp.asarray(rs.randn(2, 24, 32).astype(np.float32))


def test_op_forward_scan_and_steps_agree():
    """`forward` at two chunk sizes, a scan in two slabs with a ragged
    `row_lengths`, the gather pass, and decode steps from the scan's state
    (each at its own position: the rotary) give one function."""
    op, params, x = op_and_params()
    assert isinstance(op, PowerRetention) and op.state_cache_protocol
    whole = op.forward(params, [x])[0]
    other, _, _ = op_and_params(chunk=5)
    np.testing.assert_allclose(np.asarray(other.forward(params, [x])[0]),
                               np.asarray(whole), atol=ATOL, rtol=0)
    lens = jnp.asarray([17, 24])
    st = op.init_state(2, jnp.float32)
    a, st = op.scan_forward(params, [x[:, :16]], st, 0, lens)
    b, st = op.scan_forward(params, [x[:, 16:]], st, 16, lens)
    got = jnp.concatenate([a, b], axis=1)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(whole[1]),
                               atol=ATOL, rtol=0)
    np.testing.assert_allclose(np.asarray(got[0, :17]),
                               np.asarray(whole[0, :17]), atol=ATOL, rtol=0)
    last, same = op.last_forward(params, [x[:, :1]], st)
    assert same is st
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(jnp.stack([whole[0, 16],
                                                     whole[1, 23]])),
                               atol=ATOL, rtol=0)
    # decode on from row 17 of sequence 0: positions 17, 18, 19
    st0 = {k: v[:1] for k, v in st.items()}
    for t in (17, 18, 19):
        out, st0 = op.step_forward(params, [x[:1, t:t + 1]], st0, t)
        np.testing.assert_allclose(np.asarray(out[0, 0]),
                                   np.asarray(whole[0, t]), atol=ATOL,
                                   rtol=0)


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_pool_step_advances_live_slots_in_place(impl):
    """`paged_step_forward` over a pool of three slots of which two are live
    (the Pallas impl falls back to XLA's loop at a head of 8 lanes: the
    kernel takes a head of 128; tests/test_brumby.py runs it through an
    engine at 128): the live slots' outputs and states are `step_forward`'s,
    the dead slot's state keeps every bit."""
    op, params, x = op_and_params()
    _, st = op.scan_forward(params, [x[:, :10]], op.init_state(2, jnp.float32,
                                                               out_last=False))
    pool = op.init_state_pool(3, jnp.float32)
    rs = np.random.RandomState(1)
    pool = {k: jnp.asarray(rs.randn(*v.shape).astype(np.float32))
            for k, v in pool.items()}
    keep = {k: np.asarray(v[1]) for k, v in pool.items()}
    pool = op.seat_state(pool, {k: v[:1] for k, v in st.items()}, 0)
    pool = op.seat_state(pool, {k: v[1:] for k, v in st.items()}, 2)
    xs = jnp.stack([x[0, 10:11], x[0, 3:4], x[1, 10:11]])
    live = jnp.asarray([True, False, True])
    out, new = op.paged_step_forward(params, [xs], pool, live, impl=impl,
                                     positions=jnp.asarray([10, 3, 10]))
    want, wst = op.step_forward(params, [x[:, 10:11]], st, 10)
    np.testing.assert_allclose(np.asarray(out)[[0, 2]], np.asarray(want),
                               atol=ATOL, rtol=0)
    for k in ("s", "z"):
        np.testing.assert_allclose(np.asarray(new[k])[[0, 2]],
                                   np.asarray(wst[k]), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(new[k][1]), keep[k])
    assert op.state_bytes_per_slot() == sum(
        int(np.prod(v.shape[1:])) * 4 for v in pool.values())
