"""The expert-stream kernel (ops/pallas_kernels.py `moe_expert_stream_pallas`)
and the rule that picks it (ops/moe.py `dropless_lowering`), on the CPU in
interpret mode (tests/conftest.py sets FF_PALLAS_INTERPRET=1), in float32.

The kernel is held to the plain loop of tests/reference_olmoe.py (`route`
and `expert`: every expert on every row under a dense gate matrix) and to
the grouped `ragged_dot` lowering of the same op; the two lowerings share
one routing, so their counts and aux value are equal, not close. A host
without a TPU resolves every call to `grouped`; the tests that want the
kernel say the backend is a TPU (`streamed` below), which is the one fact
of the rule a test has to steer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmoe as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops import moe as moe_mod
from flexflow_tpu.ops import pallas_kernels as pk

D, F, E = 128, 128, 8
# float32 kernel against the float32 reference loop: both round every
# product to 2^-24 relative and sum the experts in another order; outputs
# are of order 0.1. Measured 2e-7. bf16 operands would land near 1e-3.
ATOL = 2e-6


def moe_op(n, k=3, renormalize=False, expert="swiglu", d=D, f=F):
    ff = FFModel(FFConfig(batch_size=n, mesh_shape={"data": 1}, seed=1))
    x = ff.create_tensor([n, d], name="x")
    out = ff.moe(x, num_experts=E, hidden_dim=f, k=k, capacity_factor=None,
                 expert=expert, renormalize=renormalize, name="moe")
    ff.compile(final_tensor=out)
    return ff.params["moe"], ff.get_op_by_name("moe")


@pytest.fixture
def streamed(monkeypatch):
    """This process's backend reads as a TPU to the lowering rule."""
    monkeypatch.setattr(moe_mod, "_backend", lambda: "tpu")


def run(op, p, x, mask=None, training=False):
    routing, took = [], []
    y, aux = op.forward(p, [jnp.asarray(x)], training=training,
                        row_mask=None if mask is None else jnp.asarray(mask),
                        routing=routing, lowerings=took)
    return (np.asarray(y), float(aux), [int(v) for v in routing[0]],
            took[0])


def reference_loop(p, x, k, renormalize, mask=None):
    """reference_olmoe's experts on rows already normed: its `route` with a
    unit scale and eps 0 would norm them again, so the gates are taken from
    its formula directly and its `expert` does the rest."""
    with jax.default_matmul_precision("highest"):
        gates = jax.nn.softmax(jnp.asarray(x) @ p["router"], axis=-1)
    top_p, top_e = jax.lax.top_k(gates, k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    dense = jnp.zeros_like(gates).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_p)
    if mask is not None:
        dense = dense * jnp.asarray(mask)[:, None]
    return np.asarray(sum(
        ref.expert(jnp.asarray(x), dense[:, e], p["w_gate"][e],
                   p["w_up"][e], p["w_down"][e]) for e in range(E)))


@pytest.mark.parametrize("renormalize", [True, False])
@pytest.mark.parametrize("n", [1, 8, 32, 128])
def test_streamed_matches_reference_loop_and_grouped(streamed, n,
                                                     renormalize):
    p, op = moe_op(n, renormalize=renormalize)
    x = np.random.RandomState(n).randn(n, D).astype(np.float32)
    y, aux, counts, took = run(op, p, x)
    assert took == "streamed"
    np.testing.assert_allclose(y, reference_loop(p, x, op.k, renormalize),
                               atol=ATOL, rtol=0)
    y_g, aux_g, counts_g, took_g = run(op, p, x, training=True)
    assert took_g == "grouped"
    np.testing.assert_allclose(y, y_g, atol=ATOL, rtol=0)
    assert counts == counts_g and counts[0] == n * op.k
    assert aux == aux_g


MASKS = {
    "all_live": np.ones(32, bool),
    "one_live": np.arange(32) == 17,
    "none_live": np.zeros(32, bool),
    "free_slots_between": np.arange(32) % 3 == 0,
}


@pytest.mark.parametrize("case", sorted(MASKS))
def test_streamed_dead_rows_route_nowhere(streamed, case):
    mask = MASKS[case]
    p, op = moe_op(32)
    x = np.random.RandomState(7).randn(32, D).astype(np.float32)
    y, aux, counts, took = run(op, p, x, mask)
    assert took == "streamed"
    assert np.all(y[~mask] == 0)
    np.testing.assert_allclose(
        y, reference_loop(p, x, op.k, False, mask), atol=ATOL, rtol=0)
    y_g, aux_g, counts_g, _ = run(op, p, x, mask, training=True)
    np.testing.assert_allclose(y, y_g, atol=ATOL, rtol=0)
    assert counts == counts_g and aux == aux_g
    assert counts[0] == int(mask.sum()) * op.k
    if case == "none_live":
        assert counts == [0, 0]
    if case == "one_live":
        assert counts == [op.k, op.k]


def test_one_expert_hit_by_every_row(streamed):
    """Every row's top-1 is expert 5: one expert streams, hit count 1."""
    p, op = moe_op(32, k=1)
    x = np.random.RandomState(2).randn(32, D).astype(np.float32)
    x[:, 0] = 6.0
    p = dict(p, router=p["router"].at[0, 5].set(4.0))
    y, _, counts, took = run(op, p, x)
    assert took == "streamed" and counts == [32, 1]
    np.testing.assert_allclose(y, reference_loop(p, x, 1, False),
                               atol=ATOL, rtol=0)


def test_row_output_is_bitwise_its_own(streamed):
    """A row's result does not change by one bit with what the other rows
    hold (they change which experts are hit and where in the stream this
    row's experts come), and a dead neighbour changes nothing either."""
    p, op = moe_op(32)
    rs = np.random.RandomState(3)
    x = rs.randn(32, D).astype(np.float32)
    base = run(op, p, x)[0]
    x2 = x.copy()
    x2[8:] = 3 * rs.randn(24, D)
    other = run(op, p, x2)[0]
    assert np.array_equal(other[:8], base[:8])
    assert not np.array_equal(other[8:], base[8:])
    mask = np.arange(32) < 8
    assert np.array_equal(run(op, p, x2, mask)[0][:8], base[:8])


def test_nan_in_one_row_reaches_no_other_row(streamed):
    """The engine's per-slot isfinite rests on this: the poisoned row comes
    out non-finite, every other row bitwise as without the poison (rows
    that did not choose an expert are dropped by select, never multiplied
    by a zero gate)."""
    p, op = moe_op(32)
    x = np.random.RandomState(4).randn(32, D).astype(np.float32)
    clean = run(op, p, x)[0]
    x[11] = np.nan
    y = run(op, p, x)[0]
    assert np.isnan(y[11]).all()
    keep = np.arange(32) != 11
    assert np.isfinite(y[keep]).all()
    assert np.array_equal(y[keep], clean[keep])


def plain_stream(x, gates, wg, wu, wd):
    y = np.zeros(x.shape, np.float64)
    for e in range(wg.shape[0]):
        a = x.astype(np.float64) @ wg[e]
        h = a / (1 + np.exp(-a)) * (x.astype(np.float64) @ wu[e])
        sel = gates[:, e] != pk.MOE_NOT_CHOSEN
        y[sel] += gates[sel, e, None] * (h @ wd[e])[sel]
    return y


# (D, F, dtype, experts): what the shapes make of the stream
SHAPES = {
    # the whole width in one chunk: a copy takes the expert's full matrices
    "whole_width": (128, 256, jnp.float32, 8),
    # two chunks an expert (float32 doubles the bytes), D not a multiple of
    # 1024: column slices of w_gate / w_up, row slices of w_down
    "two_chunks_d1536": (1536, 1024, jnp.float32, 4),
    # bf16 operands, D = 2560: the whole width again, rows padded to 16
    "bf16_d2560": (2560, 256, jnp.bfloat16, 4),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("n_hit", [0, 1, 3])
def test_kernel_alone_over_chunkings_and_widths(shape, n_hit):
    """The kernel alone, rows not a multiple of the tile, the last expert
    never hit; no hit expert at all gives zeros and copies nothing."""
    d, f, dtype, experts = SHAPES[shape]
    assert pk.moe_stream_chunk(d, f, dtype) == (
        512 if shape == "two_chunks_d1536" else f)
    rs = np.random.RandomState(len(shape) + n_hit)
    n = 13
    cast = lambda a: np.asarray(jnp.asarray(a, dtype).astype(jnp.float32))
    x = cast(rs.randn(n, d))
    wg, wu = (cast(rs.randn(experts, d, f) / np.sqrt(d)) for _ in range(2))
    wd = cast(rs.randn(experts, f, d) / np.sqrt(f))
    gates = np.full((n, experts), pk.MOE_NOT_CHOSEN, np.float32)
    hit = np.sort(rs.choice(experts - 1, n_hit, replace=False))
    for r in range(n if n_hit else 0):
        # every hit expert by some row, two experts a row where there are
        mine = {hit[r % n_hit], hit[(r + 1) % n_hit]}
        gates[r, sorted(mine)] = rs.rand(len(mine))
    sizes = (gates != pk.MOE_NOT_CHOSEN).sum(0).astype(np.int32)
    assert (sizes > 0).sum() == n_hit and not sizes[-1]
    got = np.asarray(pk.moe_expert_stream_pallas(
        jnp.asarray(x, dtype), jnp.asarray(gates), jnp.asarray(sizes),
        *(jnp.asarray(w, dtype) for w in (wg, wu, wd))).astype(jnp.float32))
    # float32: the rounding of sums in another order; bf16: h and the
    # output round to 2^-9 of values of order 1
    np.testing.assert_allclose(got, plain_stream(x, gates, wg, wu, wd),
                               atol=5e-6 if dtype == jnp.float32 else 2e-2,
                               rtol=0)
    if n_hit == 0:
        assert np.all(got == 0)


def test_stream_chunk_follows_the_vmem_budget():
    # OLMoE in bf16: the whole width, two buffers a matrix (24 MiB)
    assert pk.moe_stream_chunk(2048, 1024, jnp.bfloat16) == 1024
    # twice the bytes an element: half the width
    assert pk.moe_stream_chunk(2048, 1024, jnp.float32) == 512
    assert pk.moe_stream_chunk(128, 128, jnp.float32) == 128
    # Mixtral's 4096 x 14336: a 128-multiple that divides F and fits twice
    chunk = pk.moe_stream_chunk(4096, 14336, jnp.bfloat16)
    assert 14336 % chunk == 0 and chunk % 128 == 0
    assert 2 * 3 * 4096 * chunk * 2 <= pk._MOE_BUFFER_BUDGET
    # hidden sizes between the multiples of 1024 have a chunk like any
    for d in (1536, 2560, 3584):
        assert pk.moe_stream_chunk(d, 1024, jnp.bfloat16) in (512, 1024)
    # sizes off the lanes: no chunk, the op keeps ragged_dot
    assert pk.moe_stream_chunk(2048, 1000, jnp.bfloat16) is None
    assert pk.moe_stream_chunk(100, 1024, jnp.bfloat16) is None


@pytest.mark.parametrize("n", [1, 32])
def test_streamed_op_at_a_hidden_size_off_1024(streamed, n):
    """The whole op at D = 1536 (two chunks an expert in float32): what the
    rule sends to the kernel, the kernel can build, and it agrees with the
    reference loop and the grouped lowering."""
    p, op = moe_op(n, d=1536, f=1024)
    x = np.random.RandomState(n).randn(n, 1536).astype(np.float32)
    y, aux, counts, took = run(op, p, x)
    assert took == "streamed"
    np.testing.assert_allclose(y, reference_loop(p, x, op.k, False),
                               atol=4 * ATOL, rtol=0)
    y_g, aux_g, counts_g, took_g = run(op, p, x, training=True)
    assert took_g == "grouped" and counts == counts_g and aux == aux_g
    np.testing.assert_allclose(y, y_g, atol=4 * ATOL, rtol=0)


RULE = dict(backend="tpu", expert="swiglu", training=False, devices=1,
            n_tokens=32, dim=2048, hidden_dim=1024, dtype=jnp.bfloat16)


@pytest.mark.parametrize("change,want", [
    ({}, "streamed"),                            # a decode step, 32 slots
    ({"n_tokens": 1}, "streamed"),               # the gather-last pass
    ({"n_tokens": 128}, "streamed"),             # the 128-token bucket
    ({"n_tokens": 129}, "grouped"),
    ({"n_tokens": 2048}, "grouped"),             # the longest bucket
    ({"training": True}, "grouped"),             # fit(): no VJP
    ({"expert": "gelu"}, "grouped"),
    ({"backend": "cpu"}, "grouped"),
    ({"backend": "gpu"}, "grouped"),
    ({"devices": 4}, "grouped"),                 # GSPMD owns a mesh
    ({"hidden_dim": 1000}, "grouped"),           # off the lanes
    ({"dim": 1536}, "streamed"),                 # between multiples of 1024
    ({"dim": 2560}, "streamed"),
    ({"dim": 3584, "n_tokens": 128}, "streamed"),
    ({"dim": 3584, "n_tokens": 129}, "grouped"),
    ({"dtype": jnp.float32}, "streamed"),
])
def test_lowering_rule_reads_static_facts_only(change, want):
    assert moe_mod.dropless_lowering(**{**RULE, **change}) == want


def test_op_resolves_by_call_shape_and_host():
    """The same op, a decode-shaped and a 2048-token call: on this TPU-less
    host both keep ragged_dot; told the backend is a TPU, the small call
    streams and the large one, a training call and a gelu op do not."""
    p, op = moe_op(32)
    assert jax.default_backend() != "tpu"
    assert op.lowering(32, False, jnp.float32) == "grouped"
    assert op.lowering(2048, False, jnp.float32) == "grouped"
    assert run(op, p, np.zeros((32, D), np.float32))[3] == "grouped"


def test_op_resolves_by_call_shape_on_a_tpu(streamed):
    p, op = moe_op(32)
    assert op.lowering(32, False, jnp.float32) == "streamed"
    assert op.lowering(2048, False, jnp.float32) == "grouped"
    assert op.lowering(32, True, jnp.float32) == "grouped"
    x = np.random.RandomState(0).randn(2048, D).astype(np.float32)
    y, _, counts, took = run(op, p, x)
    assert took == "grouped" and counts[0] == 2048 * op.k
    # the first 32 rows through the other lowering: the same numbers
    y32, _, _, took = run(op, p, x[:32])
    assert took == "streamed"
    np.testing.assert_allclose(y32, y[:32], atol=ATOL, rtol=0)
    _, gelu = moe_op(32, expert="gelu")
    assert gelu.lowering(32, False, jnp.float32) == "grouped"


def test_gradient_keeps_ragged_dot_on_a_tpu(streamed):
    """jax.grad through a training call takes the grouped lowering whatever
    the backend: the kernel has no VJP and is never asked for one."""
    p, op = moe_op(8)
    x = jnp.asarray(np.random.RandomState(1).randn(8, D), jnp.float32)
    took = []
    g = jax.grad(lambda w: jnp.sum(op.forward(
        dict(p, w_up=w), [x], training=True, lowerings=took)[0] ** 2))(
            p["w_up"])
    assert took == ["grouped"] and np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).max()) > 0
