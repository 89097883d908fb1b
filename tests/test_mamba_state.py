"""The Mamba-2 op (ops/mamba.py) alone, at a tiny size in float32 on the CPU:
its forward against the plain reference's token-by-token recurrence
(tests/reference_nemotron_h.py `mamba`, the same text as
benchmark/reference/nemotron_h.py), its gradient by autodiff against the
reference's, and the state protocol the engine drives: a bucket's padding
never reaches the state, chunks of a prefill carry it, a decode step advances
it, and the pool's update touches live rows only.

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


def build(seq, chunk=16, batch=2, seed=1):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, seq, HIDDEN], name="x")
    y = ff.mamba2(x, HEADS, P, G, N, chunk_size=chunk, name="mamba")
    ff.compile(final_tensor=y)
    rs = np.random.RandomState(seed)
    for w in ("norm_w", "D"):
        v = ff.params["mamba"][w]
        ff.set_weights("mamba", w, (1 + 0.3 * rs.randn(*v.shape))
                       .astype(np.float32))
    return ff, ff.get_op_by_name("mamba")


def reference(params, x):
    """The reference's layer on x (S, D) without its pre-norm and residual:
    a unit norm scale with the row scaled back, and the input taken off."""
    p = params
    # ref.mamba computes h + mamba(RMSNorm(h; norm)); feed it rows whose RMS
    # is 1 so that the norm with scale one is the identity up to eps
    rms = jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)
    out = ref.mamba(x, jnp.ones((HIDDEN,)) * 1.0, p["w_in"], p["conv_w"],
                    p["conv_b"], p["dt_bias"], p["A_log"], p["D"],
                    p["norm_w"], p["w_out"], x.shape[0], heads=HEADS,
                    head_dim=P, groups=G, state=N, eps=1e-5)[0]
    del rms
    return out - x


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


@pytest.mark.parametrize("live", [
    [True, False, True, True, False], [False, False, True, False, False],
    [True] * 5, [False] * 5, [False, False, False, False, True]])
@pytest.mark.parametrize("impl", ["loop", "pallas"])
def test_pool_update_reads_and_writes_live_rows_only(impl, live):
    """XLA's loop over the live rows and the Pallas kernel (interpreted; the
    state's columns at the lanes' 128): the recurrence's one step on live
    rows, and not one bit of a dead row moves, wherever the dead rows lie."""
    from flexflow_tpu.ops.pallas_kernels import mamba_state_update_pallas

    update = mamba_state_update if impl == "loop" \
        else mamba_state_update_pallas
    N = 128 if impl == "pallas" else 16
    rs = np.random.RandomState(8)
    h = jnp.asarray(rs.randn(5, HEADS, P, N).astype(np.float32))
    decay = jnp.asarray(rs.rand(5, HEADS).astype(np.float32))
    dtx = jnp.asarray(rs.randn(5, HEADS, P).astype(np.float32))
    bm = jnp.asarray(rs.randn(5, G, N).astype(np.float32))
    cm = jnp.asarray(rs.randn(5, G, N).astype(np.float32))
    live = jnp.asarray(live)
    y, new = jax.jit(update)(h, decay, dtx, bm, cm, live)
    bh, ch = (np.repeat(np.asarray(v), HEADS // G, axis=1) for v in (bm, cm))
    want = (np.asarray(decay)[:, :, None, None] * np.asarray(h)
            + np.asarray(dtx)[..., None] * bh[:, :, None, :])
    for r in range(5):
        if bool(live[r]):
            np.testing.assert_allclose(np.asarray(new[r]), want[r],
                                       atol=1e-6, rtol=0)
            # a sum of N products of order 1
            np.testing.assert_allclose(
                np.asarray(y[r]), (want[r] * ch[r][:, None, :]).sum(-1),
                atol=1e-5 * N / 16, rtol=0)
        else:       # not one bit of a dead row moves
            np.testing.assert_array_equal(np.asarray(new[r]),
                                          np.asarray(h[r]))
            assert not np.asarray(y[r]).any()


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
    h0 = jnp.asarray(rs.randn(1, HEADS, P, N).astype(np.float32))
    y, h = ssd_chunked(x, dt, a, bm, cm, h0, 8)
    hs = np.asarray(h0[0])
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
    np.testing.assert_allclose(np.asarray(h[0]), hs, atol=1e-4, rtol=0)


def test_op_states_its_state_and_its_cost():
    ff, op = build(16)
    assert isinstance(op, Mamba2Mixer) and op.state_cache_protocol
    pool = op.init_state_pool(3, jnp.bfloat16)
    assert pool["h"].shape == (3, HEADS, P, N) \
        and pool["h"].dtype == jnp.float32
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
