"""Kanana-2-30B-A3B's decoder through the normal path (models/kanana2.py ->
compile(Adam, cross-entropy) -> fit() / predict()) against the plain
reference (tests/reference_kanana2.py, the same text as
benchmark/reference/kanana2.py), at a tiny size that keeps every ratio (4
heads of 32 + 16 / 32, latent 32, 16 experts top-4 of which 4 are held, two
shared experts), in float32 on the CPU; and what the training path forced,
each alone: the flash kernels at key and value widths that differ
(ops/pallas_kernels.py, interpret mode), the latent attention without query
compression or indexer (ops/mla.py), the held share under a gradient and the
step's routing counts (ops/moe.py, runtime/executor.py).

Every tolerance stands beside its reason.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_kanana2 as ref
import flexflow_tpu as fft
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.deepseek_v32 import deepseek_v32_lm
from flexflow_tpu.models.kanana2 import kanana2_lm
from flexflow_tpu.ops import moe as moe_mod
from flexflow_tpu.ops.mla import LatentAttention
from flexflow_tpu.ops.moe import MoE
from flexflow_tpu.ops.pallas_kernels import (flash_attention,
                                             flash_attention_bwd_pallas,
                                             flash_attention_fwd_pallas)
from flexflow_tpu.runtime import telemetry
from flexflow_tpu.runtime.initializer import init_weight

VOCAB, SEQ, HELD = 256, 128, (4, 4)
SIZES = dict(num_hidden_layers=3, first_k_dense_replace=1, rms_norm_eps=1e-6,
             rope_theta=1e6, qk_nope_head_dim=32, qk_rope_head_dim=16,
             kv_lora_rank=32, num_experts_per_tok=4,
             routed_scaling_factor=2.448, norm_topk_prob=True,
             experts_held=HELD)
CHECKED = [("attn_0", w) for w in ("w_q", "w_dkv", "kv_norm", "w_uk", "w_uv",
                                   "wo")] \
    + [("moe_1", w) for w in ("router", "w_gate", "w_down", "shared_up")] \
    + [("ln2_2", "scale"), ("tok_embed", "kernel")]

# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative in different orders (flash or blocked against a full masked
# row, sorted groups against a dense gate matrix). Measured: loss equal to
# the last digit printed, gradients within 2.1e-6 of each weight's largest
# entry; bf16 compute lands near 1e-2.
LOSS_RTOL, GRAD_TOL = 2e-6, 3e-5


def build(batch=2, seq=SEQ, seed=3, held=HELD, optimizer=None):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    tokens, logits = kanana2_lm(
        ff, batch, seq_len=seq, hidden=64, layers=3, heads=4,
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, dense_layers=1, ffn_hidden=96, num_experts=16,
        experts_per_token=4, expert_hidden=24, shared_experts=2,
        experts_held=held, score_bias_std=0.1, vocab_size=VOCAB)
    ff.compile(optimizer or fft.AdamOptimizer(alpha=1e-3),
               fft.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [fft.MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    # norm scales initialise to one, where a missing or misplaced one would
    # pass: spread them
    rs = np.random.RandomState(seed)
    for op, ws in ff.params.items():
        for w, v in ws.items():
            if w in ("scale", "kv_norm"):
                ff.set_weights(op, w, (1 + 0.3 * rs.randn(*v.shape))
                               .astype(np.float32))
    return ff, tokens


def batches(n=2, seq=SEQ, seed=0):
    toks = np.random.default_rng(seed).integers(0, VOCAB, (n, seq + 1),
                                                dtype=np.int32)
    return toks[:, :-1], toks[:, 1:, None]


def program_loss_and_grads(ff, x, y):
    """The loss and its gradient through the executor's own training graph
    (`apply_graph(training=True)`: the held share's compact rows, the step's
    routing counts), no optimizer."""
    from flexflow_tpu.runtime.loss import compute_loss

    final = ff.get_op_by_name("lm_head").outputs[0]
    inp = next(op for op in ff.ops if op.name == "input").outputs[0]

    def loss(p):
        vals, _ = ff.executor.apply_graph(p, {}, {inp: jnp.asarray(x)},
                                          training=True, rng=None)
        return compute_loss(ff.loss_type, vals[final], jnp.asarray(y))

    return jax.value_and_grad(loss)(ff.params)


@pytest.fixture(scope="module")
def compared():
    """One program and one reference pass for the cases below."""
    ff, _ = build()
    x, y = batches()
    got_loss, got = program_loss_and_grads(ff, x, y)
    trace = {}
    want_loss, want = ref.mean_loss_and_grads(ff.params, x, y[..., 0],
                                              CHECKED, SIZES, trace=trace)
    return dict(ff=ff, x=x, got_loss=float(got_loss), got=got,
                want_loss=want_loss, want=want, trace=trace)


def test_graph_is_latent_attention_without_indexer_and_held_experts():
    ff, _ = build()
    attn, moe = ff.get_op_by_name("attn_1"), ff.get_op_by_name("moe_1")
    assert isinstance(attn, LatentAttention)
    assert attn.q_lora_rank is None and not attn.indexed
    assert list(ff.params["attn_1"]) == ["w_q", "w_dkv", "kv_norm", "w_uk",
                                         "w_uv", "wo"]
    assert ff.params["attn_1"]["w_q"].shape == (64, 4, 48)
    assert (moe.scoring, moe.n_group, moe.k) == ("sigmoid", 1, 4)
    assert moe.shared_hidden_dim == 48 and moe.routed_scaling == 2.448
    assert ff.params["moe_1"]["router"].shape == (64, 16)
    assert ff.params["moe_1"]["w_gate"].shape == (4, 64, 24)
    assert "moe_0" not in ff.params and "ffn_gate_0" in ff.params


def test_step_loss_matches_reference(compared):
    assert compared["got_loss"] == pytest.approx(compared["want_loss"],
                                                 rel=LOSS_RTOL)
    assert abs(compared["want_loss"] - np.log(VOCAB)) < 0.5


@pytest.mark.parametrize("op,w", CHECKED, ids=[f"{o}.{w}" for o, w in CHECKED])
def test_gradient_matches_reference(compared, op, w):
    got = np.asarray(compared["got"][op][w])
    want = np.asarray(compared["want"][op][w])
    assert np.abs(want).max() > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=GRAD_TOL * np.abs(want).max())


def test_selection_bias_has_zero_gradient(compared):
    for i in (1, 2):
        assert not np.asarray(compared["got"][f"moe_{i}"]["score_bias"]).any()


def test_predict_logits_match_reference(compared):
    ff, x = compared["ff"], compared["x"]
    got = np.asarray(ff.predict({"input": x}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, x[b], SIZES))
        # logits of order 1, float32 against float32: measured 1.3e-6
        np.testing.assert_allclose(got[b], want, atol=5e-5, rtol=0)


def test_program_routes_as_the_reference_does(compared):
    """In float32 the program's top-k sets are the reference's (a bf16
    residual stream flips near-ties: the benchmark's check counts those)."""
    ff, x = compared["ff"], compared["x"]
    ln2 = ff.get_op_by_name("ln2_1").outputs[0]
    m = jax.jit(ff.executor.make_forward([ln2]))(
        ff.params, ff.bn_state, ff.executor.shard_batch({"input": x}))[0]
    mine = np.sort(np.asarray(ff.get_op_by_name("moe_1")._route(
        ff.params["moe_1"], m[0])[2]), -1)
    theirs = np.sort(np.asarray(compared["trace"]["experts"][0][0]), -1)
    assert (mine == theirs).all()


@pytest.mark.parametrize("path", ["blocked", "flash"])
def test_fit_trains_and_leaves_the_selection_bias_alone(path, monkeypatch):
    """fit() with Adam through either attention core: the loss falls, and
    `score_bias` (which reaches the top-k's indices only) is bit for bit
    what it was while its neighbours moved."""
    if path == "flash":
        monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    ff, tokens = build()
    assert ff.get_op_by_name("attn_0")._takes_flash(SEQ) == (path == "flash")
    x, y = batches(8)
    fft.SingleDataLoader(ff, tokens, x)
    fft.SingleDataLoader(ff, ff.label_tensor, y)
    before = {i: np.asarray(ff.params[f"moe_{i}"]["score_bias"]).copy()
              for i in (1, 2)}
    router = np.asarray(ff.params["moe_1"]["router"]).copy()
    ff.fit(epochs=1, verbose=False)
    first = float(ff._last_loss)
    ff.fit(epochs=2, verbose=False)
    assert float(ff._last_loss) < first
    for i, b in before.items():
        assert np.array_equal(b, np.asarray(
            ff.params[f"moe_{i}"]["score_bias"]))
        assert not np.asarray(ff.opt_state["m"][f"moe_{i}"]["score_bias"]) \
            .any()
    assert not np.array_equal(router, np.asarray(ff.params["moe_1"]["router"]))


def test_flash_and_blocked_cores_give_the_same_gradients(monkeypatch):
    ff, _ = build()
    x, y = batches()
    l0, g0 = program_loss_and_grads(ff, x, y)
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    l1, g1 = program_loss_and_grads(ff, x, y)
    assert float(l0) == pytest.approx(float(l1), rel=LOSS_RTOL)
    for w in ("w_q", "w_dkv", "w_uk", "w_uv", "wo"):
        a, b = np.asarray(g0["attn_0"][w]), np.asarray(g1["attn_0"][w])
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=GRAD_TOL * np.abs(a).max())


def test_step_counts_reach_the_breakdown_and_the_spans():
    """The held assignments, the held experts hit and the most rows an
    expert got, summed over the two expert layers, per step."""
    ff, tokens = build()
    x, y = batches(4)
    fft.SingleDataLoader(ff, tokens, x)
    fft.SingleDataLoader(ff, ff.label_tensor, y)
    ff.fit(epochs=1, verbose=False)
    bd = ff.last_step_breakdown
    n = 2 * SEQ                         # tokens a step
    assert bd["moe_steps"] == 2
    assert 0 < bd["moe_assignments_total"] <= 2 * 2 * n * 4
    assert 0 < bd["moe_experts_hit_total"] <= 2 * 2 * 4
    assert bd["moe_assignments_total"] / (2 * 2 * 4) \
        <= bd["moe_rows_max"] <= n
    spans = telemetry.tracer().events(name="train_step")[-2:]
    assert sum(s["args"]["moe_assignments_total"] for s in spans) \
        == bd["moe_assignments_total"]
    assert max(s["args"]["moe_rows_max"] for s in spans) \
        == bd["moe_rows_max"]


# ---- the flash kernels at key and value widths that differ ------------------


def _qkv(b, s, h, dqk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, s, h, dqk)),
            jax.random.normal(ks[1], (b, s, h, dqk)),
            jax.random.normal(ks[2], (b, s, h, dv)),
            jax.random.normal(ks[3], (b, s, h, dv)))


def _oracle(q, k, v, scale):
    """Causal attention by a float32 einsum; with more keys than queries row
    q sees k <= q + sk - sq."""
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k) * scale
    sq, sk = logits.shape[-2:]
    mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
    p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqs,bshv->bqhv", p, v)


FLASH_SHAPES = {"128x128": (256, 128, 128), "64x128": (256, 64, 128),
                # 384 is no multiple of 256: the tile degrades to 128
                "256_of_384": (384, 256, 256)}


@pytest.fixture(scope="module", params=sorted(FLASH_SHAPES))
def flash_case(request):
    s, bq, bk = FLASH_SHAPES[request.param]
    b, h, dqk, dv = 2, 2, 48, 32
    q, k, v, do = _qkv(b, s, h, dqk, dv)
    scale = dqk ** -0.5
    o, lse = flash_attention_fwd_pallas(q, k, v, True, scale, bq, bk)
    got = dict(zip(("dq", "dk", "dv"), flash_attention_bwd_pallas(
        q, k, v, o, lse, do, True, scale, bq, bk)))
    got["forward"] = o
    want_o, vjp = jax.vjp(lambda q, k, v: _oracle(q, k, v, scale), q, k, v)
    want = dict(zip(("dq", "dk", "dv"), vjp(do)))
    want["forward"] = want_o
    return got, want


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
def test_flash_kernels_take_key_and_value_widths_apart(flash_case, what):
    got, want = flash_case
    assert got[what].shape == want[what].shape
    # float32 tiles against a float32 einsum, values of order 1: measured
    # 2e-6
    np.testing.assert_allclose(got[what], want[what], atol=3e-5, rtol=0)


# ---- the classes of a causal tile: dead, interior, crossed by the diagonal ---


# name: (queries, keys, pinned block or None for the static default). 640 is
# no multiple of 512: tiles of 128, whole-tile chunks. 1024 under tiles of
# 512 walks chunks of 256, and its diagonal tiles leave their dead part out;
# 1024_of_1280 has tiles of 512 x 256, which the diagonal crosses anywhere.
WALKED = {"640": (640, 640, None), "640_of_1280": (640, 1280, None),
          "1024": (1024, 1024, 512), "1024_of_1536": (1024, 1536, 512),
          "1024_of_1280": (1024, 1280, 512)}
WIDTHS = {"128x128": (128, 128), "192x128": (192, 128)}


@pytest.fixture(scope="module", params=[(n, w) for n in sorted(WALKED)
                                        for w in sorted(WIDTHS)],
                ids=lambda p: f"{p[0]}-{p[1]}")
def walked_case(request):
    from flexflow_tpu.ops.pallas_kernels import (_resolve_blocks,
                                                 flash_tile_counts)

    (sq, sk, block), (dqk, dv) = (WALKED[request.param[0]],
                                  WIDTHS[request.param[1]])
    b, h = 1, 1
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, sq, h, dqk))
    k = jax.random.normal(ks[1], (b, sk, h, dqk))
    v = jax.random.normal(ks[2], (b, sk, h, dv))
    do = jax.random.normal(ks[3], (b, sq, h, dv))
    scale = dqk ** -0.5
    bq, bk = _resolve_blocks(sq, sk, block, block)
    counts = flash_tile_counts(sq, sk, bq, bk, sk - sq, True)
    # every class of tile is there to be walked
    assert min(counts["live"] - counts["masked"], counts["masked"],
               counts["dead"]) >= 1, counts

    o, lse = flash_attention_fwd_pallas(q, k, v, True, scale, block, block)
    got = dict(zip(("dq", "dk", "dv"), flash_attention_bwd_pallas(
        q, k, v, o, lse, do, True, scale, block, block)))
    got["forward"] = o
    out, none = flash_attention_fwd_pallas(q, k, v, True, scale, block,
                                           block, need_lse=False)
    assert none is None
    got["forward_without_lse"] = out
    want_o, vjp = jax.vjp(lambda q, k, v: _oracle(q, k, v, scale),
                          q, k, v)
    want = dict(zip(("dq", "dk", "dv"), vjp(do)))
    want["forward"] = want["forward_without_lse"] = want_o
    return got, want


@pytest.mark.parametrize("what", ["forward", "forward_without_lse", "dq",
                                  "dk", "dv"])
def test_flash_kernels_walk_every_class_of_tile(walked_case, what):
    got, want = walked_case
    assert got[what].shape == want[what].shape
    # float32 tiles against a float32 einsum, values of order 1, rows of up
    # to 1536 keys: measured 2e-6
    np.testing.assert_allclose(got[what], want[what], atol=5e-5, rtol=0)


@pytest.mark.parametrize("causal", [False, True])
def test_only_a_causal_kernel_builds_a_mask(causal):
    """The non-causal kernels are traced without a mask (no iota anywhere in
    the forward's or the backward's jaxpr) and without a branch on the
    tile's class; the causal ones hold both."""
    q, k, v, do = _qkv(1, 256, 1, 48, 32)
    scale = 48 ** -0.5

    def both(q, k, v, do):
        o, lse = flash_attention_fwd_pallas(q, k, v, causal, scale)
        return flash_attention_bwd_pallas(q, k, v, o, lse, do, causal, scale)

    text = str(jax.make_jaxpr(both)(q, k, v, do))
    assert text.count("pallas_call") == 3
    assert ("iota" in text) == causal


@pytest.mark.parametrize("sq,sk,bq,bk,want", [
    (4096, 4096, 512, 512, (36, 8, 28)),    # the issue's reading
    (4096, 4096, 1024, 1024, (10, 4, 6)),   # the static default's tiles
    (4096, 4096, 256, 256, (136, 16, 120)),
    (1024, 1280, 512, 256, (8, 4, 2)),
    (640, 1280, 128, 128, (40, 5, 10)),
    (96, 136, 8, 8, (138, 12, 66)),
])
def test_tile_counts_match_the_mask(sq, sk, bq, bk, want):
    """`flash_tile_counts` against a brute-force count over the mask itself:
    a tile is live where any of its elements is visible, masked where some
    but not all are."""
    from flexflow_tpu.ops.pallas_kernels import flash_tile_counts

    mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
    tiles = mask.reshape(sq // bq, bq, sk // bk, bk).transpose(0, 2, 1, 3)
    live = tiles.any(axis=(2, 3))
    brute = {"live": int(live.sum()),
             "masked": int((live & ~tiles.all(axis=(2, 3))).sum()),
             "dead": int((~live).sum())}
    assert flash_tile_counts(sq, sk, bq, bk, sk - sq, True) == brute
    assert tuple(brute[c] for c in ("live", "masked", "dead")) == want
    assert flash_tile_counts(sq, sk, bq, bk, sk - sq, False) == {
        "live": live.size, "masked": 0, "dead": 0}


def test_flash_attention_vjp_at_widths_apart():
    q, k, v, do = _qkv(1, 128, 2, 48, 32, seed=1)
    scale = 48 ** -0.5
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, True, scale) * do),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_oracle(*a, scale) * do),
                    argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=0)


def test_mismatched_multihead_attention_takes_flash_on_the_tpu_rule(
        monkeypatch):
    """kdim != vdim no longer refuses the kernels: under the TPU rule (here
    forced, interpret mode) the op's dense path is `flash_attention`, and
    its output is the einsum path's."""
    from flexflow_tpu.ops import pallas_kernels

    ff = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}))
    x = ff.create_tensor([2, 128, 64], name="x")
    ff.multihead_attention(x, x, x, 64, 2, kdim=48, vdim=32, causal=True,
                           bias=False, name="attn")
    op = ff.get_op_by_name("attn")
    assert op.qk_head_dim != op.v_head_dim
    params = {s.name: init_weight(s, jax.random.PRNGKey(i), dtype=np.float32)
              for i, s in enumerate(op.weight_specs())}
    a = jax.random.normal(jax.random.PRNGKey(9), (2, 128, 64))
    want = op.forward(params, [a, a, a])[0]
    calls = []
    real = pallas_kernels.flash_attention
    monkeypatch.setattr(pallas_kernels, "flash_attention",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    assert op._flash_ok(jnp.zeros((2, 128, 2, 48)),
                        jnp.zeros((2, 128, 2, 48)))
    got = op.forward(params, [a, a, a])[0]
    assert calls
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)


# ---- the latent attention's two optional parts -------------------------------


def _latent(q_lora_rank, index_topk):
    ff = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}))
    x = ff.create_tensor([2, 64, 64], name="x")
    idx = (4, 32) if index_topk else (None, None)
    ff.latent_attention(x, 64, 4, q_lora_rank, 32, 32, 16, 32, *idx,
                        index_topk, name="attn")
    return ff.get_op_by_name("attn")


def test_no_query_compression_and_no_indexer_build_no_such_weight():
    names = [s.name for s in _latent(None, None).weight_specs()]
    assert names == ["w_q", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo"]
    full = [s.name for s in _latent(48, 16).weight_specs()]
    assert full[:3] == ["w_dq", "q_norm", "w_uq"] and "w_q" not in full
    assert full[-5:] == ["w_iq", "w_ik", "ik_norm_scale", "ik_norm_bias",
                         "w_iw"]
    mixed = [s.name for s in _latent(48, None).weight_specs()]
    assert mixed == full[:8]
    with pytest.raises(ValueError, match="compressed query"):
        _latent(None, 16)


def test_deepseek_v32_graph_is_what_it_was():
    """`tests/test_deepseek_v32.py`'s model built after this op learned to
    leave parts out: names, order, shapes and seeded values of an
    attention's weights and the predict logits, as the parent commit printed
    them."""
    ff = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=3))
    _, logits = deepseek_v32_lm(
        ff, 2, seq_len=96, hidden=64, layers=3, heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, index_n_heads=4, index_head_dim=32, index_topk=16,
        dense_layers=1, ffn_hidden=128, num_experts=16, experts_per_token=4,
        expert_hidden=32, n_group=4, topk_group=2, experts_held=(4, 4),
        score_bias_std=0.05, vocab_size=128)
    ff.compile(final_tensor=logits)
    was = {"w_dq": ((64, 48), 357.6085), "q_norm": ((48,), 48.0),
           "w_uq": ((48, 4, 48), 724.7401), "w_dkv": ((64, 48), 354.6131),
           "kv_norm": ((32,), 32.0), "w_uk": ((32, 4, 32), 397.0052),
           "w_uv": ((32, 4, 32), 396.0791), "wo": ((4, 32, 64), 721.8038),
           "w_iq": ((48, 4, 32), 563.0332), "w_ik": ((64, 32), 257.6655),
           "ik_norm_scale": ((32,), 32.0), "ik_norm_bias": ((32,), 0.0),
           "w_iw": ((64, 4), 36.1958)}
    now = {w: (tuple(v.shape),
               round(float(np.abs(np.asarray(v, np.float64)).sum()), 4))
           for w, v in ff.params["attn_1"].items()}
    assert list(now) == list(was) and now == was
    toks = np.random.RandomState(0).randint(1, 128, (2, 96)).astype(np.int32)
    out = np.asarray(ff.predict({"input": toks}), np.float64)
    assert float(np.abs(out).sum()) == pytest.approx(16353.158, abs=2e-3)
    np.testing.assert_allclose(
        out[1, -1, :4], [-0.21993, 1.10307, 0.64815, -0.77445], atol=1e-5)


def test_generate_and_the_paged_pool_serve_the_layer_without_an_indexer():
    """`generate()` (the contiguous latent cache) serves the layer, and so
    does the serving engine: a latent attention without an indexer gets a
    pool of `lat` alone (refused until PR 53) and the dense core reads it."""
    ff, _ = build(batch=1, seq=32)
    prompt = np.random.RandomState(1).randint(1, VOCAB, (1, 8)) \
        .astype(np.int32)
    out = np.asarray(ff.generate(prompt, max_new_tokens=4))
    full = out[0, :12]
    want = np.asarray(ref.forward(ff.params, full, SIZES))
    # each emitted token is the reference's argmax up to float32 rounding
    for t in range(8, 12):
        assert want[t - 1].max() - want[t - 1, full[t]] < 1e-4
    attn = ff.get_op_by_name("attn_0")
    pool = attn.init_paged_cache(4, 8, jnp.float32)
    assert set(pool) == {"lat"} and pool["lat"].shape == (4, 8, attn.lat_width)
    eng = ff.make_serving_engine(max_seq_len=32, serve_slots=2,
                                 kv_page_size=8)
    req = eng.run([prompt[0]], max_new_tokens=4)[0]
    assert req.state == "done" and list(req.tokens) == list(full[8:12])


# ---- one chip's share of an expert layer, under a gradient -------------------


def moe_op(held=None, n=96, d=32, f=16, seed=0, experts=32):
    ff = FFModel(FFConfig(batch_size=n, mesh_shape={"data": 1}))
    x = ff.create_tensor([n, d], name="x")
    op = MoE(ff, "moe", [x], experts, f, 4, None, expert="swiglu",
             scoring="sigmoid", score_bias=0.1, routed_scaling=2.448,
             shared_hidden_dim=2 * f, experts_held=held)
    params = {s.name: init_weight(s, jax.random.PRNGKey(seed + i),
                                  dtype=np.float32)
              for i, s in enumerate(op.weight_specs())}
    xv = jax.random.normal(jax.random.PRNGKey(seed + 99), (n, d))
    return op, params, xv


def _share(p, first, count):
    return {n: (v[first:first + count] if n in MoE._EXPERT_WEIGHTS else v)
            for n, v in p.items()}


def test_eight_shares_sum_to_the_uncut_layer():
    """The guide's share test at this configuration's split: eight layers
    holding 4 of 32 experts each, under training=True (the compact rows),
    the shared experts counted once, give the uncut layer's output."""
    whole, p, x = moe_op()
    full = whole.forward(p, [x], training=True)[0]
    shared = whole._shared_expert(p, x)
    routed = jnp.zeros_like(full)
    for first in range(0, 32, 4):
        part, _, _ = moe_op(held=(first, 4))
        sizes = []
        routed = routed + part.forward(_share(p, first, 4), [x],
                                       training=True,
                                       group_sizes=sizes)[0] - shared
        assert sizes[0].shape == (4,)
    np.testing.assert_allclose(routed + shared, full, atol=2e-5, rtol=0)


@pytest.mark.parametrize("first", range(0, 32, 4))
def test_a_share_has_the_uncut_layers_gradients_of_its_experts(first):
    """Under one upstream gradient the held experts' weight gradients of a
    share are the uncut layer's gradients of those experts."""
    whole, p, x = moe_op()
    ct = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def pulled(op, params):
        return jnp.sum(op.forward(params, [x], training=True)[0] * ct)

    want = jax.grad(lambda q: pulled(whole, q))(p)
    part, _, _ = moe_op(held=(first, 4))
    got = jax.grad(lambda q: pulled(part, q))(_share(p, first, 4))
    for w in ("w_gate", "w_up", "w_down"):
        ref_g = np.asarray(want[w][first:first + 4])
        np.testing.assert_allclose(got[w], ref_g, rtol=0,
                                   atol=GRAD_TOL * np.abs(ref_g).max())
    assert not np.asarray(got["score_bias"]).any()


def _only(p, first, count):
    """The uncut layer's weights with every expert outside first .. first +
    count - 1 silenced (`w_down` zero): the layer that holds every expert
    (the all-rows form, never `_held_passes`) then gives that share's
    output, and its gradients of the share's own weights."""
    keep = (jnp.arange(p["w_down"].shape[0]) >= first) \
        & (jnp.arange(p["w_down"].shape[0]) < first + count)
    return {**p, "w_down": p["w_down"] * keep[:, None, None]}


@pytest.mark.parametrize("slack,passes", [(2.0, "one pass"),
                                          (0.25, "several passes")])
def test_held_share_under_training_equals_the_all_rows_form(monkeypatch,
                                                            slack, passes):
    """training=True works on `held_rows_cap` sorted rows a pass; a step
    whose held assignments outnumber the cap (slack 0.25) takes more passes
    and drops nothing: both give the output and the gradients of the uncut
    layer with the other experts silenced, whose all-rows form passes
    through no `_held_passes` (a forward-only call of the share itself
    does, since PR 41)."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    monkeypatch.setattr(moe_mod, "HELD_ROWS_SLACK", slack)
    op, _, x = moe_op(held=(8, 4))
    whole, uncut, _ = moe_op()
    p = _share(uncut, 8, 4)
    cap = moe_mod.held_rows_cap(96, 4, 4, 32)
    sizes = []

    def pulled(params, layer, training):
        out = layer.forward(params, [x], training=training,
                            group_sizes=sizes)[0]
        return jnp.sum(out * jnp.cos(out)), out

    (_, want), uncut_g = jax.value_and_grad(pulled, has_aux=True)(
        _only(uncut, 8, 4), whole, False)
    want_g = _share(uncut_g, 8, 4)
    (_, got), got_g = jax.value_and_grad(pulled, has_aux=True)(p, op, True)
    assert cap < 96 * 4 and sizes[0].shape == (32,)
    assert (int(sizes[1].sum()) <= cap) == (passes == "one pass")
    assert int(sizes[1].sum()) > 2 * cap or passes == "one pass"
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    for w, g in want_g.items():
        np.testing.assert_allclose(
            got_g[w], g, rtol=0,
            atol=GRAD_TOL * max(float(np.abs(g).max()), 1e-30))


def test_rows_past_the_last_group_never_reach_a_gradient(monkeypatch):
    """What a grouped matmul leaves in rows past its last group is
    unspecified, and on the chip it was NaN (PERF.md section 6, PR 32): with
    every such row poisoned, the held share's output and gradients are what
    they were."""
    op, _, x = moe_op(held=(8, 4))
    p = _share(moe_op()[1], 8, 4)

    def pulled(params):
        return jnp.sum(jnp.sin(op.forward(params, [x], training=True)[0]))

    want, want_g = jax.value_and_grad(pulled)(p)
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, sizes):
        live = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]
        return jnp.where(live, real(lhs, rhs, sizes), jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got, got_g = jax.value_and_grad(pulled)(p)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for w, g in want_g.items():
        assert np.isfinite(np.asarray(got_g[w])).all(), w
        np.testing.assert_allclose(
            got_g[w], g, rtol=0,
            atol=GRAD_TOL * max(float(np.abs(g).max()), 1e-30))


def test_reference_copy_is_the_benchmarks():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "reference_kanana2.py")) as a, open(
            os.path.join(here, "..", "benchmark", "reference",
                         "kanana2.py")) as b:
        assert a.read() == b.read()
