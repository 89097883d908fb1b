"""LSTM/GRU golden tests vs torch + pallas flash attention (interpret mode)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu import FFConfig, FFModel


def test_lstm_matches_torch():
    torch = pytest.importorskip("torch")
    B, S, D, H = 2, 6, 8, 12
    rs = np.random.RandomState(0)
    x = rs.randn(B, S, D).astype(np.float32)

    cfg = FFConfig(batch_size=B, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    xt = ff.create_tensor([B, S, D], name="x")
    out = ff.lstm(xt, H, name="lstm")
    ff.compile(optimizer=None, final_tensor=out)

    ref = torch.nn.LSTM(D, H, batch_first=True)
    # torch gate order: i, f, g, o — same as ours
    wx = ref.weight_ih_l0.detach().numpy().T  # (D, 4H)
    wh = ref.weight_hh_l0.detach().numpy().T
    bias = (ref.bias_ih_l0 + ref.bias_hh_l0).detach().numpy()
    ff.set_weights("lstm", "wx", wx)
    ff.set_weights("lstm", "wh", wh)
    ff.set_weights("lstm", "bias", bias)

    got = np.asarray(ff.predict({"x": x}))
    with torch.no_grad():
        want, _ = ref(torch.from_numpy(x))
    np.testing.assert_allclose(got, want.numpy(), rtol=1e-4, atol=1e-5)


def test_gru_matches_torch():
    torch = pytest.importorskip("torch")
    B, S, D, H = 2, 5, 8, 10
    rs = np.random.RandomState(1)
    x = rs.randn(B, S, D).astype(np.float32)

    cfg = FFConfig(batch_size=B, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    xt = ff.create_tensor([B, S, D], name="x")
    out = ff.gru(xt, H, name="gru")
    ff.compile(optimizer=None, final_tensor=out)

    ref = torch.nn.GRU(D, H, batch_first=True)
    ff.set_weights("gru", "wx", ref.weight_ih_l0.detach().numpy().T)
    ff.set_weights("gru", "wh", ref.weight_hh_l0.detach().numpy().T)
    # torch keeps separate ih/hh biases; our cell folds ih bias into xg and
    # applies hh bias inside the recurrence only via wh @ h (hn term differs) —
    # set hh bias to zero in the reference for an exact comparison
    with torch.no_grad():
        ref.bias_hh_l0.zero_()
    ff.set_weights("gru", "bias", ref.bias_ih_l0.detach().numpy())

    got = np.asarray(ff.predict({"x": x}))
    with torch.no_grad():
        want, _ = ref(torch.from_numpy(x))
    np.testing.assert_allclose(got, want.numpy(), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_dense(causal):
    from flexflow_tpu.ops.pallas_kernels import flash_attention

    B, S, H, D = 2, 128, 4, 16
    rs = np.random.RandomState(2)
    q = rs.randn(B, S, H, D).astype(np.float32)
    k = rs.randn(B, S, H, D).astype(np.float32)
    v = rs.randn(B, S, H, D).astype(np.float32)

    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        mask = np.tril(np.ones((S, S), bool))
        s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, v)

    got = np.asarray(flash_attention(jnp.asarray(q), jnp.asarray(k),
                                     jnp.asarray(v), causal))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal,shape", [
    (False, (2, 128, 2, 16)),
    (True, (2, 128, 2, 16)),
    (True, (1, 256, 4, 64)),
])
def test_flash_attention_grads_match_dense(causal, shape):
    """The hand-written dq/dk/dv Pallas kernels must match autodiff through
    a dense reference — finite-and-nonzero alone would not catch a sign,
    scale, or masking regression."""
    from flexflow_tpu.ops.pallas_kernels import flash_attention

    B, S, H, D = shape
    rs = np.random.RandomState(3)
    q, k, v, g = (jnp.asarray(rs.randn(B, S, H, D).astype(np.float32))
                  for _ in range(4))
    scale = 1.0 / np.sqrt(D)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = jnp.tril(jnp.ones((S, S), bool))
            s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", p, v)

    gf = jax.grad(lambda *a: jnp.vdot(flash_attention(*a, causal, scale), g),
                  (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.vdot(dense(*a), g), (0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("causal,sq,sk", [
    (False, 64, 192),   # plain cross-attention
    (True, 64, 192),    # causal cross: diagonal offset sk-sq=128
    (True, 128, 384),
    (True, 96, 136),    # offset 40 not a block multiple (blocks degrade to 8)
    (True, 1, 128),     # single-query decode shape
])
def test_flash_cross_attention_matches_dense(causal, sq, sk):
    """sq != sk on the flash path (VERDICT r3 #6): the causal mask carries
    the bottom-right diagonal offset k_pos <= q_pos + (sk - sq), matching
    the einsum path's tril(k=sk-sq) — fwd AND all three grads (the
    dead-tile index-map clamps shift with the offset too; a clamp bug
    shows up as a wrong, not crashing, gradient)."""
    from flexflow_tpu.ops.pallas_kernels import flash_attention

    B, H, D = 2, 2, 16
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(B, sq, H, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, sk, H, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, sk, H, D).astype(np.float32))
    g = jnp.asarray(rs.randn(B, sq, H, D).astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", p, v)

    got = np.asarray(flash_attention(q, k, v, causal, scale))
    np.testing.assert_allclose(got, np.asarray(dense(q, k, v)), rtol=2e-4,
                               atol=2e-5)

    gf = jax.grad(lambda *a: jnp.vdot(flash_attention(*a, causal, scale), g),
                  (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.vdot(dense(*a), g), (0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_flash_causal_rejects_more_queries_than_keys():
    from flexflow_tpu.ops.pallas_kernels import flash_attention

    q = jnp.zeros((1, 128, 2, 16), jnp.float32)
    kv = jnp.zeros((1, 64, 2, 16), jnp.float32)
    with pytest.raises(AssertionError, match="sq <= sk"):
        flash_attention(q, kv, kv, True, 0.25)


def test_mha_causal_cross_attention_flash_matches_einsum(monkeypatch):
    """Model-level: a decoder-style MHA (causal, kv longer than q — the
    reference Transformer app shape, attention.cu:533-570) runs the flash
    path and matches the einsum mask convention. SK must satisfy
    _flash_ok's 128-divisibility gate or the comparison silently becomes
    einsum-vs-einsum — asserted below."""
    B, SQ, SK, D, H = 2, 64, 256, 32, 4
    rs = np.random.RandomState(13)
    xq = rs.randn(B, SQ, D).astype(np.float32)
    xkv = rs.randn(B, SK, D).astype(np.float32)

    def run():
        cfg = FFConfig(batch_size=B, mesh_shape={"data": 1}, seed=11)
        ff = FFModel(cfg)
        qt = ff.create_tensor([B, SQ, D], name="q")
        kvt = ff.create_tensor([B, SK, D], name="kv")
        out = ff.multihead_attention(qt, kvt, kvt, D, H, causal=True,
                                     name="xmha")
        ff.compile(optimizer=None, final_tensor=out)
        op = next(o for o in ff.ops if o.name == "xmha")
        return np.asarray(ff.predict({"q": xq, "kv": xkv})), op

    monkeypatch.delenv("FF_FORCE_FLASH_ATTENTION", raising=False)
    y_einsum, _ = run()
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    y_flash, op = run()
    assert op._flash_ok(jnp.zeros((B, SQ, H, D // H)),
                        jnp.zeros((B, SK, H, D // H))), \
        "shape no longer takes the flash path — comparison is vacuous"
    np.testing.assert_allclose(y_flash, y_einsum, rtol=2e-4, atol=2e-5)


def test_mha_flash_path_matches_einsum(monkeypatch):
    """Model-level equivalence: MultiHeadAttention with the Pallas flash
    kernel forced on (interpret mode on CPU) vs the einsum softmax path."""
    B, S, D, H = 2, 128, 32, 4
    rs = np.random.RandomState(7)
    x = rs.randn(B, S, D).astype(np.float32)

    def run():
        cfg = FFConfig(batch_size=B, mesh_shape={"data": 1}, seed=11)
        ff = FFModel(cfg)
        xt = ff.create_tensor([B, S, D], name="x")
        out = ff.multihead_attention(xt, xt, xt, D, H, causal=True,
                                     name="mha")
        ff.compile(optimizer=None, final_tensor=out)
        return np.asarray(ff.predict({"x": x}))

    monkeypatch.delenv("FF_FORCE_FLASH_ATTENTION", raising=False)
    y_einsum = run()
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    y_flash = run()
    np.testing.assert_allclose(y_flash, y_einsum, rtol=2e-4, atol=2e-5)


def test_flash_bwd_dlse_term():
    """The dlse slot of flash_attention_bwd_pallas (lse cotangent folded
    into delta) must match autodiff of the dense logsumexp: grad of
    sum(w * lse(q,k)) via the kernel equals the dense reference."""
    from flexflow_tpu.ops.pallas_kernels import (flash_attention_bwd_pallas,
                                                 flash_attention_fwd_pallas)

    B, S, H, D = 1, 64, 2, 16
    rs = np.random.RandomState(9)
    q, k, v = (jnp.asarray(rs.randn(B, S, H, D).astype(np.float32))
               for _ in range(3))
    w = jnp.asarray(rs.randn(B, H, S).astype(np.float32))
    scale = 1.0 / np.sqrt(D)

    o, lse8 = flash_attention_fwd_pallas(q, k, v, False, scale)
    # cotangents: do = 0, dlse = w  ->  dq/dk from the lse path only
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, o, lse8, jnp.zeros_like(q), False, scale, dlse=w)

    def dense_lse(q, k):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        return jnp.sum(w * jax.scipy.special.logsumexp(s, axis=-1))

    gd_q, gd_k = jax.grad(dense_lse, (0, 1))(q, k)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(gd_q), rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(gd_k), rtol=2e-4,
                               atol=2e-5)
    assert np.abs(np.asarray(dv)).max() == 0  # lse has no v dependence


def test_cold_fallback_is_static_heuristic():
    from flexflow_tpu.ops.pallas_kernels import _pick_block, _resolve_blocks

    assert _resolve_blocks(640, 640, None, None) \
        == (_pick_block(640, 512), _pick_block(640, 512)) == (128, 128)
    # a side the caller pins is degraded to a divisor, the other is free
    assert _resolve_blocks(640, 640, 640, 128) == (640, 128)
    assert _resolve_blocks(640, 1024, 256, None) == (128, 1024)


def test_static_rule_is_legal_for_every_admitted_sequence():
    """The static rule alone picks the tiles: for every sequence
    `flash_eligible` admits to the TPU (128 to 8192 in steps of 128) the
    outer tile divides the sequence and is whole lane tiles (what a
    (1, 1, block_q) row of lse needs), and the rows a step takes at a time
    divide the tile and are whole lane tiles too."""
    from flexflow_tpu.ops.pallas_kernels import (_OUTER_BLOCK, _chunk_rows,
                                                 _pick_block,
                                                 _resolve_blocks,
                                                 flash_tile_counts)

    for seq in range(128, 8192 + 1, 128):
        bq, bk = _resolve_blocks(seq, seq, None, None)
        assert bq == bk == _pick_block(seq, _OUTER_BLOCK)
        assert seq % bq == 0 and bq % 128 == 0 and bq <= _OUTER_BLOCK
        chunk = _chunk_rows(bq)
        assert bq % chunk == 0 and chunk % 128 == 0
        counts = flash_tile_counts(seq, seq, bq, bk, 0, True)
        assert counts["masked"] == seq // bq    # the diagonal's own tiles
    assert _pick_block(4096, _OUTER_BLOCK) == 1024
    assert _pick_block(1536, _OUTER_BLOCK) == 512
    assert _pick_block(640, _OUTER_BLOCK) == 128
