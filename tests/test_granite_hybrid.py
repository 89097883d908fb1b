"""Granite 4.0-H through the normal path (models/granite_hybrid.py ->
compile() -> predict / generate / make_serving_engine) against the plain
reference (tests/reference_granite_hybrid.py, the same text as
benchmark/reference/granite_hybrid.py), at a tiny size in float32 on the CPU:
every layer a Mamba-2 mixer or attention without rotary under a softmax scale
that is NOT 1 / sqrt(head size), then a SwiGLU MLP as one op, scaled
residuals, a scaled embedding, a tied head, scaled logits. The snapshot of
the recurrent state under the prefix cache is tests/test_state_snapshots.py.

Logits are compared, never tokens. Every tolerance stands beside its reason.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_granite_hybrid as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.granite_hybrid import (LAYER_TYPES_MICRO,
                                                granite_hybrid_lm)
from flexflow_tpu.ops.dense import GatedMLP
from flexflow_tpu.ops.mamba import Mamba2Mixer, mamba_state_update

VOCAB, SEQ = 97, 40
LAYERS = ("mamba", "attention", "mamba", "mamba")
# 0.05 where the convention 1 / sqrt(16) is 0.25: a path that kept the
# convention is five times off in its logits' scale
ATTN_MULT = 0.05
SIZES = dict(layer_types=LAYERS, rms_norm_eps=1e-5, mamba_n_heads=8,
             mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
             embedding_multiplier=12.0, residual_multiplier=0.22,
             attention_multiplier=ATTN_MULT, logits_scaling=8.0)
# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative, in different orders (chunked scan against the recurrence),
# and the logits are of order 1 after the division by 8. Measured 1.2e-7;
# bf16 compute lands near 1e-2.
LOGIT_ATOL = 2e-5


def build(batch=2, seq=SEQ, seed=3, layers=LAYERS, mult=ATTN_MULT,
          state_size=16):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = granite_hybrid_lm(
        ff, batch, seq_len=seq, hidden=64, layer_types=layers, heads=4,
        kv_heads=2, mamba_heads=8, mamba_head_dim=16, n_groups=1,
        state_size=state_size, chunk_size=16, ffn_hidden=96,
        vocab_size=VOCAB, attention_multiplier=mult)
    ff.compile(final_tensor=logits)
    # scales initialise to one, where a missing or misplaced scale would
    # pass: spread them
    rs = np.random.RandomState(seed)
    for op, ws in ff.params.items():
        for w, v in ws.items():
            if w in ("scale", "norm_w", "D"):
                ff.set_weights(op, w, (1 + 0.3 * rs.randn(*v.shape))
                               .astype(np.float32))
    return ff


@pytest.fixture(scope="module")
def ff():
    return build()


def margins(ff, req, sizes=SIZES):
    full = np.asarray(req.output)
    logits = np.asarray(ref.forward(ff.params, full, sizes))
    p = req.prompt.size
    rows = logits[p - 1:full.size - 1]
    return rows.max(-1) - rows[np.arange(rows.shape[0]), full[p:]]


def prompts(lengths, seed=10):
    return [np.random.RandomState(seed + i).randint(1, VOCAB, (n,))
            .astype(np.int32) for i, n in enumerate(lengths)]


def test_graph_is_a_mixer_and_an_mlp_a_layer_with_a_tied_head(ff):
    names = {op.name for op in ff.ops}
    for i, kind in enumerate(LAYERS):
        mixer = f"mamba_{i}" if kind == "mamba" else f"attn_{i}"
        assert {f"norm1_{i}", mixer, f"mix_scale_{i}", f"res1_{i}",
                f"norm2_{i}", f"mlp_{i}", f"mlp_scale_{i}",
                f"res2_{i}"} <= names
    assert isinstance(ff.get_op_by_name("mamba_0"), Mamba2Mixer)
    assert isinstance(ff.get_op_by_name("mlp_2"), GatedMLP)
    attn = ff.get_op_by_name("attn_1")
    assert not attn.rope and attn.num_kv_heads == 2
    assert attn.softmax_scale == ATTN_MULT != 1 / math.sqrt(16)
    # the head is the embedding: one stored matrix
    assert "kernel" not in ff.params.get("lm_head", {})
    assert ff.params["tok_embed"]["kernel"].shape == (VOCAB, 64)
    assert set(ff.params["mlp_0"]) == {"w_in", "w_out"}
    assert ff.params["mlp_0"]["w_in"].shape == (64, 192)
    assert LAYER_TYPES_MICRO.count("attention") == 4
    assert [i for i, k in enumerate(LAYER_TYPES_MICRO)
            if k == "attention"] == [5, 15, 25, 35]
    with pytest.raises(ValueError, match="layer_types"):
        granite_hybrid_lm(FFModel(FFConfig(batch_size=1)), 1,
                          layer_types=("mamba", "moe"))


def test_predict_logits_match_reference(ff):
    toks = np.random.RandomState(0).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, toks[b], SIZES))
        np.testing.assert_allclose(got[b], want, atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("key,value", [
    ("attention_multiplier", 0.25), ("residual_multiplier", 1.0),
    ("embedding_multiplier", 1.0), ("logits_scaling", 1.0)])
def test_the_reference_tells_each_multiplier_apart(ff, key, value):
    """Each of the four scalars at the value a plain decoder has: the
    reference then reads far from the program, so none of them is a factor
    the comparison cannot see."""
    toks = np.random.RandomState(1).randint(1, VOCAB, (SEQ,)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": np.stack([toks, toks])}))[0]
    wrong = np.asarray(ref.forward(ff.params, toks, {**SIZES, key: value}))
    assert np.abs(got - wrong).max() > 100 * LOGIT_ATOL


def test_generate_scores_match_reference(ff):
    """Prefill + decode through the contiguous caches (`prefill_forward`,
    `decode_forward` under the stated softmax scale; the recurrent state
    stepping beside the attention's rows)."""
    prompt = np.random.RandomState(2).randint(1, VOCAB, (2, 7)) \
        .astype(np.int32)
    out, scores = ff.generate(prompt, max_new_tokens=9, return_scores=True)
    for b in range(2):
        logp = jax.nn.log_softmax(ref.forward(ff.params, out[b], SIZES))
        want = [float(logp[6 + j, out[b, 7 + j]]) for j in range(9)]
        np.testing.assert_allclose(scores[b], want, atol=2 * LOGIT_ATOL,
                                   rtol=0)


@pytest.mark.parametrize("kw", [
    {}, {"prefill_chunk": 8}, {"paged_attention_impl": "pallas"}],
    ids=["whole", "chunked", "pallas"])
def test_engine_cold_prefill_and_decode_are_the_full_forward(kw):
    """Cold prefill (whole or in chunks: `chunk_forward`, `query_forward`),
    the seat, ten in-place decode steps through the page-gather einsum or the
    interpreted kernels (the paged kernel at heads of 16 under the stated
    scale; the state kernel at ONE group, which needs a state of 128
    columns): every emitted token within rounding of the reference's maximum,
    through the tied head."""
    n = 128 if kw.get("paged_attention_impl") == "pallas" else 16
    ff = build(batch=1, state_size=n)
    sizes = {**SIZES, "mamba_d_state": n}
    eng = ff.make_serving_engine(serve_slots=3, kv_page_size=8, kv_pages=40,
                                 max_seq_len=64, prefix_cache=False,
                                 decode_chunk=2, **kw)
    reqs = eng.run(prompts([5, 9, 13, 21, 7]), max_new_tokens=10)
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == 10
        assert margins(ff, r, sizes).max() <= 2 * LOGIT_ATOL
    assert eng.kv.snapshots is None
    assert eng.stats()["state_snapshot_pool_bytes"] == 0


@pytest.mark.parametrize("path", ["forward", "prefill", "chunk", "decode"])
def test_softmax_scale_none_is_the_convention_bit_for_bit(path):
    """`softmax_scale=None` computes 1 / sqrt(head size) as every path wrote
    it out before: the same float, so the same program, and an op given that
    number explicitly computes the same bits on every contiguous path."""
    def op_of(scale):
        ff = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
        x = ff.create_tensor([1, 12, 32], name="x")
        ff.multihead_attention(x, x, x, 32, 4, causal=True, bias=False,
                               num_kv_heads=2, rope=True,
                               softmax_scale=scale, name="attn")
        return ff.get_op_by_name("attn")

    a, b, c = op_of(None), op_of(1.0 / math.sqrt(8)), op_of(0.07)
    assert a.softmax_scale == b.softmax_scale == 1.0 / math.sqrt(8)
    rs = np.random.RandomState(5)
    params = {k: jnp.asarray(rs.randn(*w.shape).astype(np.float32) * 0.3)
              for k, w in ((w.name, w) for w in a.weight_specs())}
    x = jnp.asarray(rs.randn(1, 12, 32).astype(np.float32))

    def run(op):
        if path == "forward":
            return op.forward(params, [x, x, x])[0]
        cache = op.init_cache(1, 16, jnp.float32)
        if path == "prefill":
            return op.prefill_forward(params, [x, x, x], cache)[0]
        out, cache = op.chunk_forward(params, [x[:, :8]] * 3, cache, 0)
        if path == "chunk":
            return op.chunk_forward(params, [x[:, 8:]] * 3, cache, 8)[0]
        return op.decode_forward(params, [x[:, 8:9]] * 3, cache, 8)[0]

    np.testing.assert_array_equal(np.asarray(run(a)), np.asarray(run(b)))
    assert np.abs(np.asarray(run(a)) - np.asarray(run(c))).max() > 1e-3


def test_a_graph_traces_what_it_traced_without_the_argument():
    """A graph that never names `softmax_scale` lowers to the same program
    text as one that passes None."""
    def text(**kw):
        ff = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}, seed=0))
        toks = ff.create_tensor([1, 8], name="input",
                                dtype=__import__("flexflow_tpu").DataType
                                .DT_INT32)
        t = ff.embedding(toks, 31, 16, name="tok_embed")
        t = ff.multihead_attention(t, t, t, 16, 2, causal=True, bias=False,
                                   name="attn_0", **kw)
        ff.compile(final_tensor=t)
        fwd = jax.jit(ff.executor.make_forward([t]))
        return fwd.lower(ff.params, ff.bn_state, ff.executor.shard_batch(
            {"input": np.zeros((1, 8), np.int32)})).as_text()

    assert text() == text(softmax_scale=None)
    assert text() != text(softmax_scale=0.1)


def test_gated_mlp_is_gate_times_up_through_one_in_projection():
    ff = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}))
    x = ff.create_tensor([2, 5, 16], name="x")
    ff.gated_mlp(x, 24, name="mlp")
    op = ff.get_op_by_name("mlp")
    rs = np.random.RandomState(6)
    w_in = rs.randn(16, 48).astype(np.float32)
    w_out = rs.randn(24, 16).astype(np.float32)
    xv = rs.randn(2, 5, 16).astype(np.float32)
    got = np.asarray(op.forward({"w_in": jnp.asarray(w_in),
                                 "w_out": jnp.asarray(w_out)},
                                [jnp.asarray(xv)])[0])
    g, u = (xv @ w_in)[..., :24], (xv @ w_in)[..., 24:]
    want = (g / (1 + np.exp(-g)) * u) @ w_out
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    assert op.flops() == 6 * 10 * 16 * 24


@pytest.mark.parametrize("live", [
    [True, False, True, True], [False, False, False, False], [True] * 4])
def test_state_kernel_at_one_group_matches_the_xla_oracle(live):
    """The Pallas state update (interpreted) at G = 1, where every head of a
    slot reads the SAME B and C row (the layout it had run at was 8 groups of
    16 heads), against XLA's loop over the live rows; the pool in the held
    layout, (slots, 1, N, heads x P)."""
    from flexflow_tpu.ops.pallas_kernels import mamba_state_update_pallas

    slots, heads, p, n = 4, 8, 16, 128
    rs = np.random.RandomState(9)
    h = jnp.asarray(rs.randn(slots, 1, n, heads * p).astype(np.float32))
    decay = jnp.asarray(rs.rand(slots, heads).astype(np.float32))
    dtx = jnp.asarray(rs.randn(slots, heads, p).astype(np.float32))
    bm = jnp.asarray(rs.randn(slots, 1, n).astype(np.float32))
    cm = jnp.asarray(rs.randn(slots, 1, n).astype(np.float32))
    live = jnp.asarray(live)
    y0, h0 = jax.jit(mamba_state_update)(h, decay, dtx, bm, cm, live)
    y1, h1 = jax.jit(mamba_state_update_pallas)(h, decay, dtx, bm, cm, live)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), atol=1e-6,
                               rtol=0)
    # a sum of 128 products of order 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-4,
                               rtol=0)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(h1)[dead], np.asarray(h)[dead])


def test_heads_of_a_part_of_a_lane_tile_share_a_row_of_the_pool():
    """Eight KV heads of 16 (the chip's case is 8 of 64): the pool's rows
    hold 128 lanes = all eight heads side by side (`pool_pack`), the paged
    kernel (interpreted) tells them apart by the zeros in the query rows,
    and both paged impls, the prefill write of both impls and the hit
    prefill's gather give the reference's tokens."""
    cfg = FFConfig(batch_size=1, mesh_shape={"data": 1}, seed=5)
    ff = FFModel(cfg)
    layers = ("mamba", "attention", "attention")
    _, logits = granite_hybrid_lm(
        ff, 1, seq_len=SEQ, hidden=128, layer_types=layers, heads=8,
        kv_heads=8, mamba_heads=8, mamba_head_dim=16, n_groups=1,
        state_size=128, chunk_size=16, ffn_hidden=96, vocab_size=VOCAB,
        attention_multiplier=0.4)
    ff.compile(final_tensor=logits)
    sizes = {**SIZES, "layer_types": layers, "mamba_d_state": 128,
             "attention_multiplier": 0.4}
    assert ff.get_op_by_name("attn_1").pool_pack() == 8
    assert ff.get_op_by_name("attn_1").pool_pack(quantized=True) == 1
    doc = prompts([24], seed=3)[0]
    for impl in ("einsum", "pallas"):
        eng = ff.make_serving_engine(
            serve_slots=3, kv_page_size=8, kv_pages=40, max_seq_len=64,
            prefix_cache=True, state_snapshots=2, decode_chunk=2,
            paged_attention_impl=impl)
        assert eng.kv.pool["attn_1"]["k"].shape == (40, 8, 1, 128)
        assert eng.prefill_into_cache(doc) == 3
        reqs = eng.run([np.concatenate([doc, p]) for p in prompts([3, 6])]
                       + prompts([5, 13]), max_new_tokens=8)
        assert [r.prefix_tokens for r in reqs] == [24, 24, 0, 0]
        for r in reqs:
            assert margins(ff, r, sizes).max() <= 2 * LOGIT_ATOL
        # a page of 8 tokens x 1 row: the kernel's turn takes a block of as
        # many pages as the table is wide, and fetches no page past a slot's
        # last live one (the pages past its last whole block go one a turn).
        # The two hits hold the document's 3 pages together: counted once a
        # step in what must be read, fetched once for both by the kernel
        # (one group), once each by the einsum's gather
        st = eng.stats()
        assert st["paged_turn_pages"] == (8 if impl == "pallas" else 1)
        assert st["kv_attended_bytes"] > st["kv_read_bytes"] > 0
        assert st["kv_streamed_bytes"] == st[
            "kv_read_bytes" if impl == "pallas" else "kv_attended_bytes"]
        assert (st["shared_groups"] > 0) == (impl == "pallas")


def test_a_chunk_loop_is_the_unrolled_chunks(ff):
    """`prefill_chunk_loop`: the cold prefill's chunks as one loop body with
    a traced start (the attention ops attend the whole cache under the
    causal rule, the state ops carry their state) against the reference,
    for prompts that end in the first, a middle and the last chunk."""
    one = build(batch=1)
    eng = one.make_serving_engine(
        serve_slots=2, kv_page_size=8, kv_pages=40, max_seq_len=64,
        prefix_cache=True, state_snapshots=2, decode_chunk=2,
        prefill_chunk=8, prefill_chunk_loop=True, decode_buckets=[16, 32])
    # ONE program, the largest bucket's, serves both buckets
    reqs = eng.run(prompts([5, 16, 21, 32]), max_new_tokens=6)
    assert [r.bucket for r in reqs] == [16, 16, 32, 32]
    for r in reqs:
        assert margins(one, r).max() <= 2 * LOGIT_ATOL
    assert [k for k in eng._programs if k[0] == "prefill"] \
        == [("prefill", 32, 4, 8)]
    # the whole-page prompts published pages and a snapshot each
    assert eng.stats()["state_snapshots_taken"] == 2
    with pytest.raises(ValueError, match="prefill_chunk > 0"):
        one.make_serving_engine(prefill_chunk_loop=True, prefix_cache=False)
