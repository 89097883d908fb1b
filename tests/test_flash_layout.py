"""The flash kernels' layout rule (ops/pallas_kernels.py `_lane_heads`): with
head widths that are whole numbers of 128 lanes the three kernels read q, k,
v, o, dO and write o, dq, dk, dv as (B, S, H * d), where the projections
leave them, through their index maps; latent attention hands its key in two
parts (a head's [cKV W_UK] and the one rotary key a token) and no 192-wide
array exists; other widths keep the transposed copy around the same kernels.

Every case has B = 2, H = 4 and inputs that differ by head and batch row, so
an index map that reaches the wrong head's tile fails the comparison with a
plain masked softmax. Interpret mode (tests/conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_kanana2 as ref
import flexflow_tpu as fft
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.kanana2 import kanana2_lm
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.pallas_kernels import (flash_attention,
                                             flash_attention_window)

B, H = 2, 4
# name: (d_1, d_2 of a second part or 0, d_v, sq, sk)
CASES = {"lanes_128_128": (128, 0, 128, 128, 128),
         "mla_parts_apart": (128, 64, 128, 128, 128),
         "copy_64": (64, 0, 64, 128, 128),
         "copy_192_undivided": (192, 0, 128, 128, 128),
         "parts_joined_small": (32, 16, 32, 128, 128),
         "lanes_sq_lt_sk": (128, 0, 128, 128, 256),
         "mla_sq_lt_sk": (128, 64, 128, 128, 256)}


def _inputs(d1, d2, dv, sq, sk, seed=0):
    rs = np.random.RandomState(seed)

    def arr(*shape):
        return jnp.asarray(rs.randn(*shape), jnp.float32)

    q, k = arr(B, sq, H, d1), arr(B, sk, H, d1)
    v, g = arr(B, sk, H, dv), arr(B, sq, H, dv)
    if not d2:
        return (q, k, v), g
    return (q, arr(B, sq, H, d2), k, arr(B, sk, d2), v), g


def _flash(args, causal=True):
    if len(args) == 3:
        return flash_attention(*args, causal, None)
    q, q2, k, k2, v = args
    return flash_attention((q, q2), (k, k2), v, causal, None)


def _joined(args):
    """(q, k, v) with a second part concatenated, the one k2 for every
    head."""
    if len(args) == 3:
        return args
    q, q2, k, k2, v = args
    k2 = jnp.broadcast_to(k2[:, :, None, :], k.shape[:3] + k2.shape[-1:])
    return (jnp.concatenate([q, q2], -1), jnp.concatenate([k, k2], -1), v)


def _plain(args, causal=True, window=None, sink=None):
    """Masked softmax by einsum; bottom-right aligned where sq < sk."""
    q, k, v = _joined(args)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    sq, sk = logits.shape[-2:]
    row = jnp.arange(sq)[:, None] + sk - sq
    col = jnp.arange(sk)[None, :]
    seen = col <= row if causal else jnp.ones((sq, sk), bool)
    if window is not None:
        seen = seen & (col > row - window)
    logits = jnp.where(seen, logits, -jnp.inf)
    if sink is not None:
        logits = jnp.concatenate([logits, jnp.broadcast_to(
            sink[None, :, None, None], logits.shape[:3] + (1,))], -1)
    p = jax.nn.softmax(logits, axis=-1)[..., :sk]
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("what", ["forward", "grad"])
def test_flash_attention_matches_a_plain_masked_softmax(case, what):
    """float32 both ways: the kernels round their products in another order
    than the einsum (measured 2e-6 at most; a wrong head's tile reads O(1)
    off)."""
    args, g = _inputs(*CASES[case])
    if what == "forward":
        got, want = _flash(args), _plain(args)
        assert got.shape == want.shape == g.shape
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
        return
    n = tuple(range(len(args)))
    got = jax.grad(lambda *a: jnp.vdot(_flash(a), g), argnums=n)(*args)
    want = jax.grad(lambda *a: jnp.vdot(_plain(a), g), argnums=n)(*args)
    for a, b_, x in zip(got, want, args):
        assert a.shape == x.shape
        np.testing.assert_allclose(a, b_, atol=2e-5, rtol=0)


def test_non_causal_lanes_forward_and_grad():
    args, g = _inputs(128, 0, 128, 128, 256, seed=3)
    np.testing.assert_allclose(_flash(args, False), _plain(args, False),
                               atol=1e-5, rtol=0)
    got = jax.grad(lambda *a: jnp.vdot(_flash(a, False), g),
                   argnums=(0, 1, 2))(*args)
    want = jax.grad(lambda *a: jnp.vdot(_plain(a, False), g),
                    argnums=(0, 1, 2))(*args)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=2e-5, rtol=0)


@pytest.mark.parametrize("case", ["lanes_128_128", "mla_parts_apart",
                                  "copy_64"])
def test_several_tiles_a_head(case, monkeypatch):
    """Four 64-row tiles each way a head: the index maps' sequence block
    beside their head (the diagonal's clamps walk it in all three
    kernels)."""
    monkeypatch.setattr(pk, "_OUTER_BLOCK", 64)
    d1, d2, dv, _, _ = CASES[case]
    args, g = _inputs(d1, d2, dv, 256, 256, seed=7)
    n = tuple(range(len(args)))
    np.testing.assert_allclose(_flash(args), _plain(args), atol=1e-5, rtol=0)
    got = jax.grad(lambda *a: jnp.vdot(_flash(a), g), argnums=n)(*args)
    want = jax.grad(lambda *a: jnp.vdot(_plain(a), g), argnums=n)(*args)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=3e-5, rtol=0)


@pytest.mark.parametrize("d", [128, 64], ids=["lanes", "copy_64"])
@pytest.mark.parametrize("kv_heads", [2, 1])
@pytest.mark.parametrize("tiles", ["one_tile", "four_tiles"])
def test_grouped_query_heads_read_their_groups_key_head(d, kv_heads, tiles,
                                                        monkeypatch):
    """k and v with fewer heads than q (grouped-query attention) are never
    repeated: query head h reads key head h // rep through the index maps,
    and dk, dv come back at the keys' own width, the group's query heads
    accumulated inside the dkv kernel (over several q tiles a head where
    the sequence has them)."""
    if tiles == "four_tiles":
        monkeypatch.setattr(pk, "_OUTER_BLOCK", 64)
    rs = np.random.RandomState(kv_heads)
    sq, sk = 256, 256 if d == 128 else 512
    q, g = (jnp.asarray(rs.randn(B, sq, H, d), jnp.float32) for _ in "qg")
    k, v = (jnp.asarray(rs.randn(B, sk, kv_heads, d), jnp.float32)
            for _ in "kv")

    def plain(q, k, v):
        rep = H // kv_heads
        return _plain((q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)))

    np.testing.assert_allclose(_flash((q, k, v)), plain(q, k, v), atol=1e-5,
                               rtol=0)
    got = jax.grad(lambda *a: jnp.vdot(_flash(a), g), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: jnp.vdot(plain(*a), g), argnums=(0, 1, 2))(
        q, k, v)
    for a, b_, x in zip(got, want, (q, k, v)):
        assert a.shape == x.shape
        np.testing.assert_allclose(a, b_, atol=3e-5, rtol=0)
    if d == 128:
        transposed, _, kernels = _layout_ops(
            jax.grad(lambda *a: jnp.vdot(_flash(a), g), argnums=(0, 1, 2)),
            q, k, v)
        assert len(kernels) == 3 and transposed == []


@pytest.mark.parametrize("d,dv", [(128, 128), (192, 128), (64, 64)],
                         ids=["lanes", "copy_192", "copy_64"])
@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("kv_heads", [H, 2])
def test_window_and_sink_forward(d, dv, window, kv_heads):
    """The forward kernel alone with a window's lower edge and a sink a
    head (the sink is read by grid row: batch x head); with fewer key heads
    a query head reads its group's through the index maps, on the lane path
    and on the copied one."""
    (q, k, v), _ = _inputs(d, 0, dv, 128, 256, seed=5)
    k, v = k[:, :, :kv_heads], v[:, :, :kv_heads]
    rep = H // kv_heads
    sink = jnp.asarray([0.5, -1.0, 2.0, 0.0], jnp.float32)
    got = flash_attention_window(q, k, v, window, None, sink=sink)
    want = _plain((q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)),
                  window=window, sink=sink)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("window,sink", [(0, None), (48, 0.5)],
                         ids=["causal", "window_sink"])
@pytest.mark.parametrize("d", [128, 64], ids=["lanes", "copy_64"])
def test_grouped_query_op_on_flash_matches_xla(d, window, sink, monkeypatch):
    """`MultiHeadAttention.forward` with 4 query heads on 2 key heads: the
    flash path (k and v reach the kernels as projected, 2 heads wide)
    against XLA's masked attention on the same weights."""
    rs = np.random.RandomState(11)
    x = rs.randn(B, 128, 32).astype(np.float32)

    def run():
        ff = FFModel(FFConfig(batch_size=B, mesh_shape={"data": 1}, seed=3))
        xt = ff.create_tensor([B, 128, 32], name="x")
        out = ff.multihead_attention(xt, xt, xt, 32, H, kdim=H * d,
                                     vdim=H * d, num_kv_heads=2, causal=True,
                                     rope=True, window=window, sink=sink,
                                     name="gqa")
        ff.compile(optimizer=None, final_tensor=out)
        return np.asarray(ff.predict({"x": x}))

    monkeypatch.delenv("FF_FORCE_FLASH_ATTENTION", raising=False)
    want = run()
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    np.testing.assert_allclose(run(), want, rtol=2e-4, atol=2e-5)


# ---- what the traced program holds ------------------------------------------


def _eqns(jaxpr):
    """Every equation outside the Pallas kernels' own bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _layout_ops(fn, *args):
    """(shapes of 4-D operands transposed, widths of concatenations'
    results, names of the pallas_calls) of fn's jaxpr."""
    transposed, joined, kernels = [], [], []
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        name = eqn.primitive.name
        if name == "transpose" and eqn.invars[0].aval.ndim == 4:
            transposed.append(eqn.invars[0].aval.shape)
        elif name == "concatenate":
            joined.append(eqn.outvars[0].aval.shape[-1])
        elif name == "pallas_call":
            kernels.append(eqn.params["name"])
    return transposed, joined, kernels


def _fwd_and_grad(args, g):
    n = tuple(range(len(args)))
    return (lambda *a: _flash(a),
            jax.grad(lambda *a: jnp.vdot(_flash(a), g), argnums=n))


@pytest.mark.parametrize("case", ["lanes_128_128", "mla_parts_apart"])
def test_lane_path_traces_no_transposed_copy_and_no_wide_key(case):
    args, g = _inputs(*CASES[case])
    fwd, grad = _fwd_and_grad(args, g)
    for fn, want in ((fwd, ["flash_attention_fwd"]),
                     (grad, ["flash_attention_fwd", "flash_attention_bwd_dq",
                             "flash_attention_bwd_dkv"])):
        transposed, joined, kernels = _layout_ops(fn, *args)
        assert kernels == want      # each kernel once, for all heads
        assert transposed == [], transposed
        assert 192 not in joined and not joined, joined


@pytest.mark.parametrize("case", ["copy_64", "copy_192_undivided",
                                  "parts_joined_small"])
def test_other_widths_keep_the_transposed_copy(case):
    """The rule, pinned: a head the lanes do not divide is copied to
    (B * H, S, d) around the same three kernels (q, k, v in; o out; q, k,
    v, dO in and dq, dk, dv out of the backward)."""
    args, g = _inputs(*CASES[case])
    fwd, grad = _fwd_and_grad(args, g)
    transposed, _, kernels = _layout_ops(fwd, *args)
    assert kernels == ["flash_attention_fwd"] and len(transposed) == 4
    transposed, _, kernels = _layout_ops(grad, *args)
    assert kernels == ["flash_attention_fwd", "flash_attention_bwd_dq",
                       "flash_attention_bwd_dkv"]
    assert len(transposed) == 4 + 4 + 3


def test_rule_reads_the_widths_alone():
    assert pk._lane_heads(128, 128) and pk._lane_heads(256, 128)
    assert not pk._lane_heads(192, 128) and not pk._lane_heads(128, 64)
    assert not pk._lane_heads(64, 64)


# ---- latent attention at the published widths -------------------------------

SEQ = 128
SIZES = dict(num_hidden_layers=1, first_k_dense_replace=1, rms_norm_eps=1e-6,
             rope_theta=1e6, qk_nope_head_dim=128, qk_rope_head_dim=64,
             kv_lora_rank=32, num_experts_per_tok=1,
             routed_scaling_factor=1.0, norm_topk_prob=True,
             experts_held=(0, 1))
WEIGHTS = ("w_q", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo")


@pytest.fixture(scope="module")
def mla():
    """One layer of Kanana-2's attention at its head widths (128 + 64 / 128,
    4 heads), the rest tiny."""
    cfg = FFConfig(batch_size=B, mesh_shape={"data": 1}, seed=5)
    ff = FFModel(cfg)
    _, logits = kanana2_lm(
        ff, B, seq_len=SEQ, hidden=64, layers=1, heads=H, kv_lora_rank=32,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        dense_layers=1, ffn_hidden=32, num_experts=2, experts_per_token=1,
        expert_hidden=8, shared_experts=1, vocab_size=64)
    ff.compile(fft.AdamOptimizer(alpha=1e-3),
               fft.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [fft.MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    rs = np.random.RandomState(2)
    params = dict(ff.params["attn_0"])
    params["kv_norm"] = jnp.asarray(1 + 0.3 * rs.randn(32), jnp.float32)
    h = jnp.asarray(rs.randn(B, SEQ, 64), jnp.float32)
    g = jnp.asarray(rs.randn(B, SEQ, 64), jnp.float32)
    return ff.get_op_by_name("attn_0"), params, h, g


def _reference(params, h):
    """tests/reference_kanana2.py's attention a sequence, its RMSNorm's
    scale one."""
    z = dict(ref._sizes(SIZES))
    one = jnp.ones((h.shape[-1],), jnp.float32)
    return jnp.stack([ref.attention(x, one, params, z) for x in h])


def _normed(h):
    return ref.rms_norm(h, 1.0, SIZES["rms_norm_eps"])


def test_latent_attention_forward_and_grads_against_the_reference(
        mla, monkeypatch):
    """`LatentAttention.forward` on the flash kernels (parts apart, every
    operand (B, S, H * d)) against the plain reference, in float32: output
    within 2e-5 of its largest entry, each weight's gradient within 3e-5 of
    its own (tests/test_kanana2.py's GRAD_TOL: two orders of rounding)."""
    op, params, h, g = mla
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    assert op._takes_flash(SEQ)

    def program(p):
        return op.forward(p, [_normed(h)])[0]

    got, want = program(params), _reference(params, h)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    got = jax.grad(lambda p: jnp.vdot(program(p), g))(params)
    want = jax.grad(lambda p: jnp.vdot(_reference(p, h), g))(params)
    for w in WEIGHTS:
        a, b_ = np.asarray(got[w]), np.asarray(want[w])
        np.testing.assert_allclose(a, b_, rtol=0,
                                   atol=3e-5 * np.abs(b_).max(), err_msg=w)


def test_latent_attention_traces_no_192_wide_array(mla, monkeypatch):
    op, params, h, g = mla
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")

    def program(p, a):
        return op.forward(p, [a])[0]

    grad = jax.grad(lambda p, a: jnp.vdot(program(p, a), g))
    for fn, n in ((program, 1), (grad, 3)):
        jaxpr = jax.make_jaxpr(fn)(params, h)
        transposed, joined, kernels = _layout_ops(fn, params, h)
        assert len(kernels) == n and transposed == [], (kernels, transposed)
        # the rotary halves are joined (64 wide); nothing is 192 wide
        assert set(joined) <= {64}, joined
        shapes = {v.aval.shape for e in _eqns(jaxpr.jaxpr)
                  for v in e.outvars if hasattr(v.aval, "shape")}
        assert not [s for s in shapes if s and s[-1] == 192
                    and len(s) == 4 and s[1] == SEQ], shapes
