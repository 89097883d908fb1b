"""MiMo-V2-Flash through the normal path (models/mimo_v2.py -> compile() ->
predict / make_serving_engine) against the plain reference
(tests/reference_mimo_v2.py, the same text as
benchmark/reference/mimo_v2.py), at a tiny size in float32 on the CPU: window
layers with a sink and 2 KV heads beside global layers with 1, keys of 24 and
values of 16, rotary over a head's first 8 entries with a base a kind of
layer, a value scale, a dense first layer and sigmoid-routed experts with no
shared one; both kinds of page in the serving engine; a prefix hit that
resumes a window layer from one page on the trie's node; the flash forward's
sink; and the planted faults the comparison has to see.

Logits are compared, never tokens. Every tolerance stands beside its reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_mimo_v2 as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.mimo_v2 import hybrid_layer_pattern, mimo_v2_lm
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.attention import MultiHeadAttention

VOCAB, SEQ, WINDOW, PAGE = 97, 64, 8, 8
# the published pattern's kinds at a depth the CPU compiles quickly: a
# leading dense global layer, two window layers, a global expert layer
PATTERN = [0, 1, 1, 0]
FREQ = [0, 1, 1, 1]
EXPERTS, TOP_K = 16, 3
MODEL = dict(hidden=64, layers=4, heads=4, kv_heads=1, swa_kv_heads=2,
             head_dim=24, v_head_dim=16, rope_dim=8, hybrid_pattern=PATTERN,
             moe_layer_freq=FREQ, sliding_window=WINDOW, ffn_hidden=96,
             num_experts=EXPERTS, experts_per_token=TOP_K, expert_hidden=48,
             score_bias_std=0.05, vocab_size=VOCAB)
SIZES = dict(num_hidden_layers=4, hybrid_layer_pattern=PATTERN,
             moe_layer_freq=FREQ, layernorm_epsilon=1e-5, head_dim=24,
             partial_rotary_factor=0.334, rope_theta=5e6, swa_rope_theta=1e4,
             sliding_window=WINDOW, attention_value_scale=0.707,
             add_swa_attention_sink_bias=True,
             add_full_attention_sink_bias=False, num_experts_per_tok=TOP_K,
             norm_topk_prob=True)
# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative, in different orders (grouped experts against a dense loop,
# a masked softmax over the window's slice against one over all keys with the
# sink as a column), and the logits are of order 3. Measured 5e-6; every
# planted fault below lands past 5e-3.
LOGIT_ATOL = 5e-5
# an emitted token is the reference's argmax up to the same rounding
MARGIN_ATOL = 1e-4


def build(batch=2, seq=SEQ, seed=3, **over):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = mimo_v2_lm(ff, batch, seq_len=seq, **{**MODEL, **over})
    ff.compile(final_tensor=logits)
    # scales initialise to one, where a missing or misplaced scale would
    # pass: spread them
    rs = np.random.RandomState(seed)
    for op, ws in ff.params.items():
        if "scale" in ws:
            ff.set_weights(op, "scale", (1 + 0.3 * rs.randn(
                *ws["scale"].shape)).astype(np.float32))
    return ff


@pytest.fixture(scope="module")
def ff():
    return build()


def tokens(seed=0, batch=2, seq=SEQ):
    return np.random.RandomState(seed).randint(1, VOCAB, (batch, seq)) \
        .astype(np.int32)


def prompts(lengths, seed=10):
    return [np.random.RandomState(seed + i).randint(1, VOCAB, (n,))
            .astype(np.int32) for i, n in enumerate(lengths)]


def margins(params, req):
    """How far below the reference's maximum logit each emitted token's
    reference logit lies, the reference scoring prompt + emitted tokens in
    one pass."""
    full = np.asarray(req.output)
    p = req.prompt.size
    rows = np.asarray(ref.forward(params, full, SIZES,
                                  rows=(p - 1, full.size - 1)))
    return rows.max(-1) - rows[np.arange(rows.shape[0]), full[p:]]


def predict_error(ff, params=None, seed=0):
    toks = tokens(seed)
    got = np.asarray(ff.predict({"input": toks}))
    return max(np.abs(got[b] - np.asarray(ref.forward(
        ff.params if params is None else params, toks[b], SIZES))).max()
        for b in range(2))


def test_graph_says_each_layers_kind(ff):
    assert hybrid_layer_pattern(12) == [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0]
    assert ref.rope_dim_of(SIZES) == 8
    assert ref.rope_dim_of({"partial_rotary_factor": 0.334,
                            "head_dim": 192}) == 64
    names = {op.name for op in ff.ops}
    for i, p in enumerate(PATTERN):
        assert (f"attn_window_{i}" if p else f"attn_global_{i}") in names
        assert ("ffn_gate_0" if i == 0 else f"moe_{i}") in names
    win, glob = (ff.get_op_by_name(n)
                 for n in ("attn_window_1", "attn_global_3"))
    assert (win.window, win.num_kv_heads, win.rope_theta, win.sink) \
        == (WINDOW, 2, 1e4, 1.0)
    assert (glob.window, glob.num_kv_heads, glob.rope_theta, glob.sink) \
        == (0, 1, 5e6, None)
    for op in (win, glob):
        assert (op.qk_head_dim, op.v_head_dim, op.rope_dim, op.value_scale,
                op.qk_norm) == (24, 16, 8, 0.707, False)
    assert ff.params["attn_window_1"]["sink"].shape == (4,)
    assert "sink" not in ff.params["attn_global_3"]
    assert np.abs(np.asarray(ff.params["attn_window_1"]["sink"])).max() > 0.1
    assert ff.params["attn_window_1"]["wk"].shape == (64, 2, 24)
    assert ff.params["attn_global_0"]["wv"].shape == (64, 1, 16)
    moe = ff.get_op_by_name("moe_1")
    assert (moe.scoring, moe.k, moe.routed_scaling, moe.shared_hidden_dim) \
        == ("sigmoid", TOP_K, 1.0, 0)
    # a token's bytes differ by the kind of layer: (24 + 16) x 2 a KV head
    assert win.cache_bytes_per_token() == 2 * glob.cache_bytes_per_token() \
        == 2 * 80


def test_predict_logits_match_reference_over_eight_windows(ff):
    assert SEQ >= 6 * WINDOW
    assert predict_error(ff) < LOGIT_ATOL


# ---- the comparison sees the planted faults ------------------------------
# each a program built otherwise and given the sound model's weights, held to
# the reference of the sound configuration

FAULTS = {
    "sink_off": dict(swa_sink=None),
    "sink_on_global_layers": dict(full_sink=1.0),
    "rotary_over_all_entries": dict(rope_dim=0),
    "bases_swapped": dict(rope_theta=1e4, swa_rope_theta=5e6),
    "value_scale_left_out": dict(value_scale=1.0),
    "window_layers_read_one_kv_head": dict(swa_kv_heads=1),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_predict_with_a_planted_fault_fails_the_comparison(ff, fault):
    bad = build(**FAULTS[fault])
    for op, ws in ff.params.items():
        for w, v in ws.items():
            have = bad.params[op].get(w)
            if have is None:
                continue
            v = np.asarray(v)
            if have.shape != v.shape:   # fewer KV heads: the leading ones
                v = v[tuple(slice(0, n) for n in have.shape)]
            bad.set_weights(op, w, v)
    if fault == "sink_on_global_layers":
        for i, p in enumerate(PATTERN):
            if not p:   # as large as a window layer's
                bad.set_weights(f"attn_global_{i}", "sink",
                                np.asarray(ff.params["attn_window_1"]["sink"]))
    assert predict_error(bad, params=ff.params, seed=2) > 100 * LOGIT_ATOL


# ---- both kinds of page in the engine -------------------------------------


@pytest.mark.parametrize("kw", [
    dict(paged_attention_impl="einsum", prefill_chunk=16),
    dict(paged_attention_impl="pallas", prefill_chunk=16),
    dict(paged_attention_impl="einsum", prefill_chunk=0),
], ids=["einsum-chunk16", "pallas-chunk16", "einsum-whole"])
def test_engine_prefill_and_decode_are_the_full_forward(ff, kw):
    """Prefill, then decode through the paged pool of both kinds of layer
    (keys of 24 beside values of 16; 1 KV head a global page, 2 a window
    page; the sink in the window layers' decode), against the reference's
    one forward pass over prompt + emitted tokens; 30 emitted tokens wrap a
    window layer's two pages of 8 twice."""
    eng = ff.make_serving_engine(serve_slots=3, kv_page_size=PAGE,
                                 max_seq_len=128, prefix_cache=False,
                                 **{"decode_chunk": 4, **kw})
    pool = eng.kv.pool
    assert pool["attn_window_1"]["k"].shape[1:] == (PAGE, 2, 24)
    assert pool["attn_window_1"]["v"].shape[1:] == (PAGE, 2, 16)
    assert pool["attn_global_3"]["k"].shape[1:] == (PAGE, 1, 24)
    reqs = [eng.submit(p, max_new_tokens=30)
            for p in prompts([23, 24, 40, 7])]
    while eng.pending():
        eng.step()
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == 30
        assert margins(ff.params, r).max() < MARGIN_ATOL
    assert eng.stats()["kv_window_pages_recycled"] > 0


# ---- a prefix hit over window layers --------------------------------------

ENGINE = dict(serve_slots=2, kv_page_size=PAGE, max_seq_len=160,
              decode_chunk=4, prefill_chunk=16)


def hit_and_cold(ff, impl="einsum", questions=(5, 7, 8)):
    doc = prompts([64], seed=50)[0]
    cold = ff.make_serving_engine(prefix_cache=False,
                                  paged_attention_impl=impl, **ENGINE)
    warm = ff.make_serving_engine(prefix_cache=True, state_snapshots=3,
                                  paged_attention_impl=impl, **ENGINE)
    assert warm.prefill_into_cache(doc) == 64 // PAGE
    out = []
    for q in prompts(questions, seed=60):
        p = np.concatenate([doc, q])
        a, b = (e.submit(p, max_new_tokens=20) for e in (cold, warm))
        for e in (cold, warm):
            while e.pending():
                e.step()
        out.append((a, b))
    return warm, out


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_a_prefix_hit_resumes_a_window_layer_from_one_page(ff, impl):
    """A document of 8 pages is published once: its two global layers' pages
    and ONE page a window layer on the trie's last node. Questions hit it,
    prefill their tail from the match point and decode 20 tokens (the ring
    wraps) exactly as a cold prefill of the same tokens does."""
    # the interpreted kernel is slow: one question there
    warm, pairs = hit_and_cold(
        ff, impl, questions=(5, 7, 8) if impl == "einsum" else (8,))
    snaps = warm.kv.snapshots
    assert sorted(snaps) == ["attn_window_1", "attn_window_2"]
    # ids 1..3 and the scratch row, one page each
    assert snaps["attn_window_1"]["k"].shape == (4, PAGE, 2, 24)
    for cold, hit in pairs:
        assert hit.prefix_tokens == 64 and cold.prefix_tokens == 0
        assert hit.tokens == cold.tokens
        assert margins(ff.params, hit).max() < MARGIN_ATOL
    st = warm.stats()
    assert st["state_snapshot_hits"] == len(pairs)
    # the document's, and the 72-token prompt's (it ends on a page edge)
    assert st["state_snapshots_taken"] == 2
    assert st["state_snapshot_pool_bytes"] == 2 * 4 * PAGE * 2 * 40 * 4
    warm.drain()
    warm.flush_prefix_cache()
    st = warm.stats()
    assert st["free_pages"] == warm.num_pages - 1
    assert st["state_snapshots_held"] == 0


def test_a_hit_that_seats_the_page_before_the_match_points_fails(
        monkeypatch, ff):
    seed = MultiHeadAttention.seed_window_cache

    def one_page_early(self, contiguous, snaps, snap, p0):
        return seed(self, contiguous, snaps, snap, p0 - PAGE)

    monkeypatch.setattr(MultiHeadAttention, "seed_window_cache",
                        one_page_early)
    _, pairs = hit_and_cold(ff, questions=(5,))
    assert margins(ff.params, pairs[0][1]).max() > 100 * MARGIN_ATOL


def test_a_match_without_a_snapshot_prefills_cold(ff):
    """Two snapshot ids, three documents: the first document's snapshot is
    evicted with its pages, and a question of it prefills cold and right."""
    warm = ff.make_serving_engine(prefix_cache=True, state_snapshots=2,
                                  **ENGINE)
    docs = prompts([32, 32, 32], seed=80)
    for d in docs:
        assert warm.prefill_into_cache(d) == 4
    st = warm.stats()
    assert (st["state_snapshots_held"], st["state_snapshots_evicted"]) \
        == (2, 1)
    reqs = [warm.submit(np.concatenate([d, q]), max_new_tokens=6)
            for d, q in zip(docs[::2], prompts([5, 5], seed=90))]
    while warm.pending():
        warm.step()
    assert [r.prefix_tokens for r in reqs] == [0, 32]
    for r in reqs:
        assert margins(ff.params, r).max() < MARGIN_ATOL


# ---- one chip's share of the experts --------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips each hold one of the sixteen experts: the shares'
    routed parts sum to the layer that holds them all; there is no shared
    expert to count once."""
    from flexflow_tpu.ops.moe import MoE

    def moe_op(held=None):
        m = FFModel(FFConfig(batch_size=16, mesh_shape={"data": 1}))
        x = m.create_tensor([16, 64], name="x")
        return MoE(m, "moe", [x], EXPERTS, 48, TOP_K, None, expert="swiglu",
                   scoring="sigmoid", score_bias=0.05, experts_held=held)

    whole = moe_op()
    rs = np.random.RandomState(0)
    p = {w.name: jnp.asarray(rs.randn(*w.shape) * (
        0.5 if w.name == "score_bias" else w.shape[-2] ** -0.5),
        jnp.float32) for w in whole.weight_specs()}
    x = jnp.asarray(rs.randn(16, 64), jnp.float32)
    want = np.asarray(whole.forward(p, [x])[0])
    total = np.zeros_like(want)
    for e in range(EXPERTS):
        pe = {n: (v[e:e + 1] if n in MoE._EXPERT_WEIGHTS else v)
              for n, v in p.items()}
        total += np.asarray(moe_op(held=(e, 1)).forward(pe, [x])[0])
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=0)
    assert np.abs(want).max() > 0.1


def test_reference_runs_one_chips_share(ff):
    """The held share through the model and the reference alike."""
    held = build(experts_held=(4, 8))
    sizes = dict(SIZES, experts_held=[4, 8])
    toks = tokens(5)
    got = np.asarray(held.predict({"input": toks}))
    want = np.asarray(ref.forward(held.params, toks[0], sizes))
    assert held.params["moe_1"]["w_gate"].shape[0] == 8
    assert np.abs(got[0] - want).max() < LOGIT_ATOL
    everything = np.asarray(ref.forward(held.params, toks[0], dict(
        sizes, experts_held=[0, 8])))
    assert np.abs(everything - want).max() > 100 * LOGIT_ATOL


# ---- the flash forward's sink ---------------------------------------------


def dense_sink(q, k, v, window, scale, sink):
    sq, sk = q.shape[1], k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    at = (sk - sq + jnp.arange(sq))[:, None]
    cols = jnp.arange(sk)[None, :]
    seen = cols <= at
    if window:
        seen = seen & (cols > at - window)
    logits = jnp.concatenate(
        [jnp.where(seen, logits, -jnp.inf), jnp.broadcast_to(
            sink[None, :, None, None], logits.shape[:-1] + (1,))], axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)[..., :-1]
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("sq, sk, block, window", [
    (64, 64, 16, 20), (64, 64, 16, None), (32, 96, 16, 5),
    (256, 256, 128, 128)],
    ids=["window", "global", "chunk-against-prefix", "lane-tiles"])
def test_flash_forward_with_a_sink_against_the_dense_softmax(sq, sk, block,
                                                             window):
    rs = np.random.RandomState(sq + sk)
    q, k = (jnp.asarray(rs.randn(2, s, 3, 24), jnp.float32)
            for s in (sq, sk))
    v = jnp.asarray(rs.randn(2, sk, 3, 16), jnp.float32)
    sink = jnp.asarray(2 * rs.randn(3), jnp.float32)
    out, _ = pallas_kernels.flash_attention_fwd_pallas(
        q, k, v, True, 24 ** -0.5, block_q=block, block_k=block,
        need_lse=False, window=window, sink=sink)
    want = dense_sink(q, k, v, window, 24 ** -0.5, sink)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=0)
    # and the sink is not nothing
    assert np.abs(want - dense_sink(q, k, v, window, 24 ** -0.5,
                                    sink - 30)).max() > 1e-2
