"""The Mamba-2 op (ops/mamba.py) alone, at a tiny size in float32 on the CPU:
its forward against the plain reference's token-by-token recurrence
(tests/reference_nemotron_h.py `mamba`, the same text as
benchmark/reference/nemotron_h.py), its gradient by autodiff against the
reference's, and the state protocol the engine drives: a bucket's padding
never reaches the state, chunks of a prefill carry it, a decode step advances
it, and the pool's update touches live rows only. The recurrent state is held
(.., G, N, H / G x P) (ops/mamba.py says why); the equations and the
reference write (.., H, P, N), and `held` / `logical` here go between the two
without the op's help.

Every tolerance stands beside its reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_nemotron_h as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops.mamba import (Mamba2Mixer, mamba_state_update,
                                    ssd_chunked)

HIDDEN, HEADS, P, G, N = 48, 8, 16, 2, 16
# float32 op against the float32 reference: the chunked form sums a chunk's
# rows in another order than the recurrence (a matmul over the chunk against
# a running product), every product rounding to 2^-24; outputs are of order
# 1. Measured 4e-7; a chunk boundary handled wrongly (the carried state
# dropped or decayed twice) is an error of order 0.1.
ATOL = 2e-5


def held(h, g):
    """(.., H, P, N) as the equations write it -> the layout the op holds."""
    *lead, nh, p, n = np.shape(h)
    h = np.asarray(h).reshape(*lead, g, nh // g, p, n)
    return np.moveaxis(h, -1, -3).reshape(*lead, g, n, nh // g * p)


def logical(h, p):
    """The layout the op holds -> (.., H, P, N)."""
    *lead, g, n, q = np.shape(h)
    h = np.moveaxis(np.asarray(h).reshape(*lead, g, n, q // p, p), -3, -1)
    return h.reshape(*lead, g * (q // p), p, n)


def build(seq, chunk=16, batch=2, seed=1, p=P):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, seq, HIDDEN], name="x")
    y = ff.mamba2(x, HEADS, p, G, N, chunk_size=chunk, name="mamba")
    ff.compile(final_tensor=y)
    rs = np.random.RandomState(seed)
    for w in ("norm_w", "D"):
        v = ff.params["mamba"][w]
        ff.set_weights("mamba", w, (1 + 0.3 * rs.randn(*v.shape))
                       .astype(np.float32))
    return ff, ff.get_op_by_name("mamba")


def reference(params, x, rows=None, head_dim=P):
    """The reference's layer on x (S, D) without its pre-norm and residual:
    a unit norm scale with the row scaled back, and the input taken off.
    With `rows`, beside it the reference's state after that many rows: H
    (HEADS, head_dim, N) and the conv tail."""
    p = params
    # ref.mamba computes h + mamba(RMSNorm(h; norm)); feed it rows whose RMS
    # is 1 so that the norm with scale one is the identity up to eps
    out, h, tail = ref.mamba(
        x, jnp.ones((HIDDEN,)) * 1.0, p["w_in"], p["conv_w"], p["conv_b"],
        p["dt_bias"], p["A_log"], p["D"], p["norm_w"], p["w_out"],
        x.shape[0] if rows is None else rows, heads=HEADS,
        head_dim=head_dim, groups=G, state=N, eps=1e-5)
    return out - x if rows is None else (out - x, h, tail)


def unit_rows(rs, *shape):
    x = rs.randn(*shape).astype(np.float32)
    # rows of mean square 1 - eps: RMSNorm with a unit scale leaves them
    return x / np.sqrt((x * x).mean(-1, keepdims=True)) * np.sqrt(1 - 1e-5)


@pytest.mark.parametrize("seq,chunk", [(40, 16), (128, 128), (7, 16),
                                       (130, 128)])
def test_chunked_forward_is_the_token_by_token_recurrence(seq, chunk):
    """Across a chunk boundary and at lengths that are no multiple of the
    chunk: the op's forward (SSD) against the reference's scan."""
    ff, op = build(seq, chunk)
    x = unit_rows(np.random.RandomState(0), 2, seq, HIDDEN)
    got = np.asarray(op.forward(ff.params["mamba"], [jnp.asarray(x)])[0])
    for b in range(2):
        want = np.asarray(reference(ff.params["mamba"], jnp.asarray(x[b])))
        np.testing.assert_allclose(got[b], want, atol=ATOL, rtol=0)


def test_gradient_by_autodiff_is_the_references():
    ff, op = build(40)
    params = ff.params["mamba"]
    x = jnp.asarray(unit_rows(np.random.RandomState(3), 1, 40, HIDDEN))
    t = jnp.asarray(np.random.RandomState(4).randn(40, HIDDEN)
                    .astype(np.float32))

    def loss_op(p):
        return jnp.sum(op.forward(p, [x])[0][0] * t)

    def loss_ref(p):
        return jnp.sum(reference(p, x[0]) * t)

    got, want = jax.grad(loss_op)(params), jax.grad(loss_ref)(params)
    for w in want:
        scale = float(jnp.abs(want[w]).max())
        # a gradient sums over 40 rows what the forward rounds once
        np.testing.assert_allclose(np.asarray(got[w]), np.asarray(want[w]),
                                   atol=20 * ATOL * max(1.0, scale), rtol=0,
                                   err_msg=w)
        assert scale > 0, w


def test_padding_rows_never_reach_the_state_bit_for_bit():
    """A prompt of 21 rows in a bucket of 64: whatever lies behind it
    (garbage of order 1e3, other garbage, zeros), the seated state is the
    same BIT FOR BIT, and it is the state of the 21 rows alone (H after row
    20, the conv tail of rows 18..20) up to the rounding of a matmul of
    another height."""
    ff, op = build(64)
    p = ff.params["mamba"]
    rs = np.random.RandomState(5)
    x = rs.randn(1, 64, HIDDEN).astype(np.float32)
    lens = jnp.asarray([21], jnp.int32)
    seated = []
    for fill in (1e3 * rs.randn(1, 43, HIDDEN), -7.0 * rs.rand(1, 43, HIDDEN),
                 np.zeros((1, 43, HIDDEN))):
        x[:, 21:] = fill                            # the bucket's padding
        seated.append(op.scan_forward(p, [jnp.asarray(x)], op.init_state(1),
                                      0, lens)[1])
    for other in seated[1:]:
        for k in ("conv", "h", "out_last"):
            np.testing.assert_array_equal(np.asarray(seated[0][k]),
                                          np.asarray(other[k]))
    out, alone = op.scan_forward(p, [jnp.asarray(x[:, :21])],
                                 op.init_state(1))
    for k in ("conv", "h"):
        np.testing.assert_allclose(np.asarray(seated[0][k]),
                                   np.asarray(alone[k]), atol=ATOL, rtol=0)
    assert float(jnp.abs(alone["h"]).max()) > 0.01
    # and the kept output of the last live row is row 20's
    np.testing.assert_allclose(np.asarray(seated[0]["out_last"][0, 0]),
                               np.asarray(out[0, 20]), atol=ATOL, rtol=0)


@pytest.mark.parametrize("length", [9, 16, 37, 48])
def test_chunks_of_a_prefill_carry_the_state(length):
    """Three chunks of 16 rows, the prompt ending in any of them: the state
    after the last chunk and the kept last-row output are the one-pass
    prefill's (the chunked form's own rounding apart)."""
    ff, op = build(48)
    p = ff.params["mamba"]
    x = jnp.asarray(np.random.RandomState(6).randn(1, 48, HIDDEN)
                    .astype(np.float32))
    lens = jnp.asarray([length], jnp.int32)
    out1, one = op.scan_forward(p, [x], op.init_state(1), 0, lens)
    st = op.init_state(1)
    for c0 in (0, 16, 32):
        _, st = op.scan_forward(p, [x[:, c0:c0 + 16]], st, c0, lens)
    for k in ("conv", "h", "out_last"):
        np.testing.assert_allclose(np.asarray(st[k]), np.asarray(one[k]),
                                   atol=ATOL, rtol=0, err_msg=k)
    got, _ = op.last_forward(p, [x[:, :1]], st)
    np.testing.assert_allclose(np.asarray(got[0, 0]),
                               np.asarray(out1[0, length - 1]), atol=ATOL,
                               rtol=0)


def test_decode_steps_continue_the_prefill():
    """Prefill of 19 rows, then 11 one-token steps: the outputs of rows
    19..29 of one pass over all 30."""
    ff, op = build(30)
    p = ff.params["mamba"]
    x = jnp.asarray(np.random.RandomState(7).randn(2, 30, HIDDEN)
                    .astype(np.float32))
    want = np.asarray(op.forward(p, [x])[0])
    _, st = op.scan_forward(p, [x[:, :19]], op.init_state(2))
    for t in range(19, 30):
        out, st = op.step_forward(p, [x[:, t:t + 1]], st)
        np.testing.assert_allclose(np.asarray(out[:, 0]), want[:, t],
                                   atol=ATOL, rtol=0)


# (heads, P, groups, N): a tiny one whose group fills one 128-lane block, and
# the two cells' own (`ssm-latentmoe-chat-saturated` 8 groups of 16 heads,
# `hybrid-ssm-docqa-saturated` one group of 64)
POOL_SHAPES = {"tiny": (8, 32, 2, 16), "nemotron": (128, 64, 8, 128),
               "granite": (64, 64, 1, 128)}


@pytest.mark.parametrize("live", [
    [True, False, True, True, False], [False, False, True, False, False],
    [True] * 5, [False] * 5, [False, False, False, False, True]])
@pytest.mark.parametrize("shape", list(POOL_SHAPES))
@pytest.mark.parametrize("impl", ["loop", "pallas"])
def test_pool_update_reads_and_writes_live_rows_only(impl, shape, live):
    """XLA's loop over the live rows and the Pallas kernel (interpreted) on
    a pool in the held layout, at a tiny shape and at both cells': the
    recurrence's one step on live rows as the equations write it, the kernel
    the loop's equal, and not one bit of a dead row moves, wherever the dead
    rows lie."""
    from flexflow_tpu.ops.pallas_kernels import mamba_state_update_pallas

    heads, p, g, n = POOL_SHAPES[shape]
    rs = np.random.RandomState(8)
    h = rs.randn(5, heads, p, n).astype(np.float32)
    decay = rs.rand(5, heads).astype(np.float32)
    dtx = rs.randn(5, heads, p).astype(np.float32)
    bm = rs.randn(5, g, n).astype(np.float32)
    cm = rs.randn(5, g, n).astype(np.float32)
    args = tuple(map(jnp.asarray, (held(h, g), decay, dtx, bm, cm, live)))
    y, new = jax.jit(mamba_state_update)(*args)
    if impl == "pallas":
        y0, new0 = y, new
        y, new = jax.jit(mamba_state_update_pallas)(*args)
        # the same products; y's N terms summed in another order
        np.testing.assert_array_equal(np.asarray(new), np.asarray(new0))
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   atol=1e-5 * n / 16, rtol=0)
    assert new.shape == (5, g, n, heads // g * p) and y.shape == dtx.shape
    got = logical(new, p)
    bh, ch = (np.repeat(v, heads // g, axis=1) for v in (bm, cm))
    want = (decay[:, :, None, None] * h + dtx[..., None] * bh[:, :, None, :])
    for r in range(5):
        if live[r]:
            np.testing.assert_allclose(got[r], want[r], atol=1e-6, rtol=0)
            # a sum of N products of order 1
            np.testing.assert_allclose(
                np.asarray(y[r]), (want[r] * ch[r][:, None, :]).sum(-1),
                atol=1e-5 * n / 16, rtol=0)
        else:       # not one bit of a dead row moves
            np.testing.assert_array_equal(got[r], h[r])
            assert not np.asarray(y[r]).any()


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_a_seated_state_advanced_in_the_pool_is_the_recurrences(impl):
    """A prompt of 21 rows in a bucket of 32 prefilled by the chunked scan,
    seated into a pool whose other slots hold garbage (one of them live),
    advanced by six in-place decode steps (XLA's loop, the interpreted
    kernel: two groups of 128 lanes) and read back as the equations write it
    (`logical_state`): the reference's token-by-token state after 27 rows,
    and the six outputs are the reference's rows 21..26."""
    ff, op = build(32, batch=1, p=32)
    assert op._kernel_takes_layout()
    params = ff.params["mamba"]
    x = unit_rows(np.random.RandomState(11), 1, 32, HIDDEN)
    want_out, want_h, want_tail = reference(params, jnp.asarray(x[0, :27]),
                                            rows=27, head_dim=32)
    _, state = op.scan_forward(params, [jnp.asarray(x)], op.init_state(1), 0,
                               jnp.asarray([21], jnp.int32))
    pool = jax.tree.map(lambda v: jnp.full_like(v, 3.0),
                        op.init_state_pool(4, jnp.float32))
    pool = op.seat_state(pool, state, 2)
    live = jnp.asarray([False, True, True, False])
    step = jax.jit(lambda pool, u: op.paged_step_forward(
        params, [u], pool, live, impl=impl))
    for t in range(21, 27):
        u = jnp.zeros((4, 1, HIDDEN)).at[2, 0].set(x[0, t])
        out, pool = step(pool, u)
        np.testing.assert_allclose(np.asarray(out[2, 0]),
                                   np.asarray(want_out[t]), atol=ATOL, rtol=0)
    got = op.logical_state(jax.device_get(jax.tree.map(lambda v: v[2], pool)))
    assert got["h"].shape == (HEADS, 32, N)
    np.testing.assert_allclose(got["h"], np.asarray(want_h), atol=ATOL,
                               rtol=0)
    np.testing.assert_allclose(got["conv"], np.asarray(want_tail), atol=ATOL,
                               rtol=0)
    assert float(np.abs(got["h"]).max()) > 0.01
    # the dead slots kept their garbage to the bit
    for r in (0, 3):
        assert float(pool["h"][r].min()) == float(pool["h"][r].max()) == 3.0


def test_ssd_with_an_entering_state_and_dead_rows():
    """`ssd_chunked` from a non-zero state, with dt = 0 rows in the middle
    of a chunk: they neither decay nor feed the state."""
    rs = np.random.RandomState(9)
    x = jnp.asarray(rs.randn(1, 20, HEADS, P).astype(np.float32))
    dt = jnp.asarray(rs.rand(1, 20, HEADS).astype(np.float32) * 0.1)
    dt = dt.at[:, 12:].set(0.0)
    a = -jnp.asarray(rs.rand(HEADS).astype(np.float32) * 4 - 0.5) - 1.0
    bm = jnp.asarray(rs.randn(1, 20, G, N).astype(np.float32))
    cm = jnp.asarray(rs.randn(1, 20, G, N).astype(np.float32))
    h0 = rs.randn(1, HEADS, P, N).astype(np.float32)
    y, h = ssd_chunked(x, dt, a, bm, cm, jnp.asarray(held(h0, G)), 8)
    hs = h0[0]
    for t in range(12):
        d = np.exp(np.asarray(dt[0, t]) * np.asarray(a))
        bh = np.repeat(np.asarray(bm[0, t]), HEADS // G, axis=0)
        ch = np.repeat(np.asarray(cm[0, t]), HEADS // G, axis=0)
        hs = d[:, None, None] * hs + (np.asarray(dt[0, t])[:, None]
                                      * np.asarray(x[0, t]))[:, :, None] \
            * bh[:, None, :]
        np.testing.assert_allclose(np.asarray(y[0, t]),
                                   (hs * ch[:, None, :]).sum(-1), atol=1e-4,
                                   rtol=0)
    np.testing.assert_allclose(logical(h[0], P), hs, atol=1e-4, rtol=0)


def test_op_states_its_state_and_its_cost():
    ff, op = build(16)
    assert isinstance(op, Mamba2Mixer) and op.state_cache_protocol
    pool = op.init_state_pool(3, jnp.bfloat16)
    # N along the sublanes, a group's (head, p) along the lanes; the bytes
    # a slot are what (HEADS, P, N) float32 takes
    assert pool["h"].shape == (3, G, N, HEADS // G * P) \
        and pool["h"].dtype == jnp.float32
    assert pool["h"][0].nbytes == 4 * HEADS * P * N
    assert pool["conv"].shape == (3, 3, HEADS * P + 2 * G * N) \
        and pool["conv"].dtype == jnp.bfloat16
    assert op.state_bytes_per_slot(jnp.bfloat16) == sum(
        int(a.nbytes) for a in jax.tree.leaves(pool)) // 3
    assert op.flops() > 2 * 2 * 16 * HIDDEN * (2 * HEADS * P)
    assert op.partitionable_output_dims() == [0]
    st = op.init_state(1)
    seated = op.seat_state(pool, jax.tree.map(jnp.ones_like, st), 1)
    assert float(seated["h"][1].min()) == 1 and not seated["h"][0].any()
    assert not hasattr(op, "reset_state")       # seating overwrites a slot
    # the logical view is the inverse of the held layout, for a slot's
    # arrays and for a batch's
    for lead in ((), (2,)):
        eq = np.random.RandomState(2).randn(*lead, HEADS, P, N)
        view = op.logical_state({"h": held(eq, G), "conv": None})
        np.testing.assert_array_equal(view["h"], eq)
        assert view["conv"] is None
    # the kernel takes a group of whole 128-lane blocks; this op's is 64
    assert not op._kernel_takes_layout()
