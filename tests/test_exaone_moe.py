"""K-EXAONE through the normal path (models/exaone_moe.py -> compile() ->
predict / generate / make_serving_engine) against the plain reference
(tests/reference_exaone_moe.py, the same text as
benchmark/reference/exaone_moe.py), at a tiny size in float32 on the CPU:
window layers (rotary, the last 8 keys) beside a global one (no rotary, every
key), a QK norm per head, a dense first layer and sigmoid-routed experts
after it; the window layers' ring of pages beside the global table in the
serving engine; the flash forward's lower edge; and the planted faults the
comparison has to see.

Logits are compared, never tokens: with random weights the largest logit
changes on rounding. Every tolerance stands beside its reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_exaone_moe as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.exaone_moe import exaone_moe_lm
from flexflow_tpu.ops import pallas_kernels
from flexflow_tpu.ops.attention import MultiHeadAttention

VOCAB, SEQ, WINDOW, PAGE = 97, 64, 8, 8
LAYER_TYPES = ["sliding_attention"] * 3 + ["full_attention",
                                           "sliding_attention"]
WINDOWS = [WINDOW, WINDOW, WINDOW, 0, WINDOW]
MLP_TYPES = ["dense"] + ["sparse"] * 4
EXPERTS, TOP_K = 8, 3
SIZES = dict(num_hidden_layers=5, layer_types=LAYER_TYPES,
             sliding_windows=WINDOWS, mlp_layer_types=MLP_TYPES,
             rms_norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
             num_experts_per_tok=TOP_K, routed_scaling_factor=2.5,
             norm_topk_prob=True)
# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative, in different orders (grouped experts against a dense loop,
# a masked softmax over the window's slice against one over all keys), and
# the logits are of order 3. Measured 4.5e-6; every planted fault below
# lands past 1e-2, bf16 compute near 3e-2.
LOGIT_ATOL = 5e-5
# an emitted token is the reference's argmax up to the same rounding
MARGIN_ATOL = 1e-4


def build(batch=2, seq=SEQ, seed=3, held=None, flash_chunks=True):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = exaone_moe_lm(
        ff, batch, seq_len=seq, hidden=64, layers=5, heads=4, kv_heads=2,
        head_dim=16, layer_types=LAYER_TYPES, sliding_windows=WINDOWS,
        mlp_layer_types=MLP_TYPES, ffn_hidden=96, num_experts=EXPERTS,
        experts_per_token=TOP_K, expert_hidden=48, experts_held=held,
        score_bias_std=0.05, vocab_size=VOCAB, flash_chunks=flash_chunks)
    ff.compile(final_tensor=logits)
    # scales initialise to one, where a missing or misplaced scale would
    # pass: spread them
    rs = np.random.RandomState(seed)
    for op, ws in ff.params.items():
        for w, v in ws.items():
            if w in ("scale", "q_norm", "k_norm"):
                ff.set_weights(op, w, (1 + 0.3 * rs.randn(*v.shape))
                               .astype(np.float32))
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


def margins(ff, req, sizes=SIZES):
    """How far below the reference's maximum logit each emitted token's
    reference logit lies, the reference scoring prompt + emitted tokens in
    one pass."""
    full = np.asarray(req.output)
    p = req.prompt.size
    rows = np.asarray(ref.forward(ff.params, full, sizes,
                                  rows=(p - 1, full.size - 1)))
    return rows.max(-1) - rows[np.arange(rows.shape[0]), full[p:]]


def predict_error(ff, seed=0):
    toks = tokens(seed)
    got = np.asarray(ff.predict({"input": toks}))
    return max(np.abs(got[b] - np.asarray(
        ref.forward(ff.params, toks[b], SIZES))).max() for b in range(2))


def test_graph_says_each_layers_kind(ff):
    names = {op.name for op in ff.ops}
    for i, w in enumerate(WINDOWS):
        assert (f"attn_window_{i}" if w else f"attn_global_{i}") in names
        assert ("ffn_gate_0" if i == 0 else f"moe_{i}") in names
    win, glob = (ff.get_op_by_name(n)
                 for n in ("attn_window_0", "attn_global_3"))
    assert (win.window, win.rope, win.kv_keep()) == (WINDOW, True, WINDOW)
    assert (glob.window, glob.rope, glob.kv_keep()) == (0, False, None)
    assert win.qk_norm == glob.qk_norm == "head"
    assert ff.params["attn_window_0"]["q_norm"].shape == (16,)
    assert ff.params["attn_window_0"]["wq"].shape == (64, 4, 16)
    moe = ff.get_op_by_name("moe_1")
    assert (moe.scoring, moe.k, moe.routed_scaling) == ("sigmoid", TOP_K, 2.5)
    assert "score_bias" in ff.params["moe_1"]
    with pytest.raises(ValueError, match="layer 0"):
        exaone_moe_lm(FFModel(FFConfig(batch_size=1)), 1, layers=1,
                      layer_types=["full_attention"], sliding_windows=[8],
                      mlp_layer_types=["dense"])
    with pytest.raises(ValueError, match="qk_norm"):
        m = FFModel(FFConfig(batch_size=1))
        x = m.create_tensor([1, 8, 16], name="x")
        m.multihead_attention(x, x, x, 16, 2, causal=True, qk_norm="rows")
    with pytest.raises(ValueError, match="window"):
        m = FFModel(FFConfig(batch_size=1))
        x = m.create_tensor([1, 8, 16], name="x")
        m.multihead_attention(x, x, x, 16, 2, causal=False, window=4)


def test_a_window_layer_is_priced_and_cached_as_one(ff):
    win, glob = (ff.get_op_by_name(n)
                 for n in ("attn_window_0", "attn_global_3"))
    b, s, h, d = 2, SEQ, 4, 16
    proj = 2 * b * s * 64 * (64 + 2 * 32 + 64)
    assert glob.flops() == proj + 2 * b * h * s * s * 2 * d
    assert win.flops() == proj + 2 * b * h * s * WINDOW * 2 * d
    assert win.cache_bytes_per_token() == glob.cache_bytes_per_token() \
        == 2 * 2 * 16 * 2
    assert [win.cache_tokens_kept(n) for n in (3, 8, 500)] == [3, 8, 8]
    assert glob.cache_tokens_kept(500) == 500


def test_predict_logits_match_reference_over_eight_windows(ff):
    assert SEQ >= 6 * WINDOW
    assert predict_error(ff) < LOGIT_ATOL


def test_generate_decodes_inside_the_window(ff):
    """generate()'s contiguous cache: ragged prompts leave a pad between the
    prompt and the emitted tokens, and the window is counted in sequence
    positions across it."""
    ps = prompts([21, 13], seed=70)
    padded = np.zeros((2, 21), np.int32)
    for i, p in enumerate(ps):
        padded[i, :p.size] = p
    out = np.asarray(ff.generate(padded, 20,
                                 prompt_lengths=np.asarray([21, 13])))
    for i, p in enumerate(ps):
        full = np.concatenate([p, out[i, 21:]])
        rows = np.asarray(ref.forward(ff.params, full, SIZES))[p.size - 1:-1]
        gap = rows.max(-1) - rows[np.arange(rows.shape[0]), full[p.size:]]
        assert gap.max() < MARGIN_ATOL


@pytest.mark.parametrize("kw", [
    dict(paged_attention_impl="einsum", prefill_chunk=16),
    dict(paged_attention_impl="pallas", prefill_chunk=16),
    dict(paged_attention_impl="einsum", prefill_chunk=0),
    dict(paged_attention_impl="pallas", prefill_chunk=24, decode_chunk=5),
], ids=["einsum-chunk16", "pallas-chunk16", "einsum-whole",
        "pallas-chunk24-k5"])
def test_engine_prefill_and_decode_are_the_full_forward(ff, kw):
    """Prefill, then decode through the paged pool, against the reference's
    one forward pass over prompt + emitted tokens. Prompts end inside a
    page (23), on its edge (24) and one past it (25); 40 is prefilled in
    chunks whose edges fall inside windows (chunks of 16 or 24, windows of
    8 across them); 30 emitted tokens wrap a window layer's two pages of 8
    twice; three slots of different lengths decode in one dispatch."""
    eng = ff.make_serving_engine(serve_slots=3, kv_page_size=PAGE,
                                 max_seq_len=128, prefix_cache=False,
                                 **{"decode_chunk": 4, **kw})
    reqs = [eng.submit(p, max_new_tokens=30)
            for p in prompts([23, 24, 25, 40, 7])]
    held = []
    while eng.pending():
        eng.step()
        held.append(eng.stats()["kv_pages_held_window"])
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == 30
        assert margins(ff, r).max() < MARGIN_ATOL
    st = eng.stats()
    # never more than the ring a live slot; everything back at the end
    assert max(held) <= 3 * st["kv_window_ring_pages"] == 6
    assert st["kv_pages_held_window"] == st["kv_pages_held_global"] == 0
    assert st["kv_window_pages_recycled"] > 0
    assert all(g.free_pages == g.num_pages - 1
               for g in eng.kv.window_groups.values())


def test_a_window_layers_pool_is_its_ring_whatever_the_context(ff):
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PAGE,
                                 max_seq_len=320, prefix_cache=False,
                                 decode_chunk=4)
    pool = eng.kv.pool
    assert pool["attn_window_0"]["k"].shape[0] == 1 + 2 * 2
    assert pool["attn_global_3"]["k"].shape[0] == eng.num_pages > 5
    long, short = (eng.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts([150, 5], seed=20), (60, 2)))
    seen = []
    while eng.pending():
        eng.step()
        group = eng.kv.window_groups[WINDOW]
        seen.append((group.held(long.slot) if long.slot >= 0 else 0,
                     eng.stats()["kv_pages_held_global"]))
    assert max(h for h, _ in seen) == 2
    # the global table holds the long request's bucket and outputs by then
    assert max(g for _, g in seen) >= (150 + 60) // PAGE
    assert margins(ff, long).max() < MARGIN_ATOL
    assert margins(ff, short).max() < MARGIN_ATOL


def test_engine_refuses_what_a_ring_of_pages_cannot_do(ff):
    kw = dict(serve_slots=2, kv_page_size=PAGE, max_seq_len=48)
    with pytest.raises(ValueError, match="host_kv_pages must be 0"):
        ff.make_serving_engine(prefix_cache=True, host_kv_pages=8, **kw)
    with pytest.raises(ValueError, match="at least one snapshot"):
        ff.make_serving_engine(prefix_cache=True, state_snapshots=0, **kw)
    with pytest.raises(ValueError, match="speculate_k must be 0"):
        ff.make_serving_engine(prefix_cache=False, draft_model=ff,
                               speculate_k=2, **kw)
    with pytest.raises(ValueError, match="prefill_interleave_chunks"):
        ff.make_serving_engine(prefix_cache=False, prefill_chunk=8,
                               prefill_interleave_chunks=1, **kw)
    eng = ff.make_serving_engine(prefix_cache=False, **kw)
    p = prompts([16])[0]
    for call in (lambda: eng.export_prefix_slab(p),
                 lambda: eng.import_prefix_slab({})):
        with pytest.raises(NotImplementedError, match="ring of pages"):
            call()
    with pytest.raises(RuntimeError, match="needs the radix prefix cache"):
        eng.prefill_into_cache(p)
    op = ff.get_op_by_name("attn_window_0")
    with pytest.raises(NotImplementedError, match="verification"):
        op.paged_verify_forward({}, [None] * 3, {}, None, None, None, None,
                                None)


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_a_prefix_hit_over_window_layers_is_a_cold_prefill(ff, impl):
    """What the engine refused until PR 46: `prefix_cache=True` over window
    layers. A document of 8 pages is published once (its global layer's
    pages and ONE page a window layer on the trie's last node); questions
    hit it, prefill their tail from the match point and decode 20 tokens
    (the rings wrap) token for token as a cold prefill of the same prompt
    does, and as the reference's full forward says."""
    kw = dict(serve_slots=2, kv_page_size=PAGE, max_seq_len=160,
              decode_chunk=4, prefill_chunk=16, paged_attention_impl=impl)
    doc = prompts([64], seed=50)[0]
    cold = ff.make_serving_engine(prefix_cache=False, **kw)
    warm = ff.make_serving_engine(prefix_cache=True, state_snapshots=3, **kw)
    assert warm.prefill_into_cache(doc) == 64 // PAGE
    assert sorted(warm.kv.snapshots) == [
        f"attn_window_{i}" for i, w in enumerate(WINDOWS) if w]
    for q in prompts([5, 8] if impl == "einsum" else [7], seed=60):
        p = np.concatenate([doc, q])
        a, b = (e.submit(p, max_new_tokens=20) for e in (cold, warm))
        for e in (cold, warm):
            while e.pending():
                e.step()
        assert (a.prefix_tokens, b.prefix_tokens) == (0, 64)
        assert a.tokens == b.tokens
        assert margins(ff, b).max() < MARGIN_ATOL
    st = warm.stats()
    assert st["state_snapshot_hits"] == st["prefix_lookups"] >= 1
    warm.drain()
    warm.flush_prefix_cache()
    st = warm.stats()
    assert st["free_pages"] == warm.num_pages - 1
    assert st["state_snapshots_held"] == 0


def test_decode_dispatch_says_what_each_kind_of_layer_read(ff):
    from flexflow_tpu.runtime import telemetry

    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PAGE,
                                 max_seq_len=64, prefix_cache=False,
                                 decode_chunk=2)
    eng.run(prompts([20]), max_new_tokens=3)
    dec = [e for e in telemetry.tracer().events(name="decode_dispatch")
           if "context_tokens_window" in e["args"]][-1]["args"]
    # one live slot, two steps at sequence positions 20 and 21
    assert dec["context_tokens_global"] == 21 + 22
    assert dec["context_tokens_window"] == 2 * WINDOW
    assert dec["program"] == "decode_k2"


def moe_op(held=None, e=8):
    """The expert layer's op alone, with the family's router (no groups),
    and seeded weights for the layer that holds every expert."""
    from flexflow_tpu.ops.moe import MoE

    m = FFModel(FFConfig(batch_size=16, mesh_shape={"data": 1}))
    x = m.create_tensor([16, 64], name="x")
    op = MoE(m, "moe", [x], e, 48, TOP_K, None, expert="swiglu",
             scoring="sigmoid", score_bias=0.05, routed_scaling=2.5,
             shared_hidden_dim=48, experts_held=held)
    return op


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips each hold one of the eight experts: the eight shares'
    routed parts plus the shared expert counted ONCE sum to the layer that
    holds them all (the guide's share test)."""
    from flexflow_tpu.ops.moe import MoE

    whole = moe_op()
    rs = np.random.RandomState(0)
    p = {w.name: jnp.asarray(rs.randn(*w.shape) * (
        0.5 if w.name == "score_bias" else w.shape[-2] ** -0.5),
        jnp.float32) for w in whole.weight_specs()}
    x = jnp.asarray(rs.randn(16, 64), jnp.float32)
    want = np.asarray(whole.forward(p, [x])[0])
    shared = np.asarray(whole._shared_expert(p, x))
    total = shared.copy()
    for e in range(8):
        share = moe_op(held=(e, 1))
        pe = {n: (v[e:e + 1] if n in MoE._EXPERT_WEIGHTS else v)
              for n, v in p.items()}
        assert pe["w_gate"].shape == share.weight_specs()[1].shape
        total += np.asarray(share.forward(pe, [x])[0]) - shared
    np.testing.assert_allclose(total, want, atol=5e-5, rtol=0)
    assert np.abs(total - shared).max() > 0.1


# ---- the comparison sees the planted faults ------------------------------


def _window(ff, size):
    def plant(monkeypatch):
        for i, w in enumerate(WINDOWS):
            if w:
                monkeypatch.setattr(ff.get_op_by_name(f"attn_window_{i}"),
                                    "window", size)
    return plant


def _rope_on_global(ff):
    return lambda mp: mp.setattr(ff.get_op_by_name("attn_global_3"), "rope",
                                 True)


def _norm_over_all_heads(ff):
    def plant(monkeypatch):
        for op in ff.ops:
            if isinstance(op, MultiHeadAttention):
                monkeypatch.setattr(
                    op, "_head_rms_norm",
                    lambda xh, scale, op=op: op._whole_rms_norm(
                        xh, jnp.tile(scale, xh.shape[-2])))
    return plant


def _gates_from_biased_scores(ff):
    def plant(monkeypatch):
        from flexflow_tpu.ops.moe import MoE

        route = MoE._route

        def biased(self, params, t):
            scores, _, top_e = route(self, params, t)
            g = jnp.take_along_axis(
                scores + params["score_bias"].astype(jnp.float32), top_e, -1)
            return scores, 2.5 * g / g.sum(-1, keepdims=True), top_e

        monkeypatch.setattr(MoE, "_route", biased)
    return plant


FAULTS = {"window_off": lambda ff: _window(ff, 0),
          "window_plus_one": lambda ff: _window(ff, WINDOW + 1),
          "rope_on_global": _rope_on_global,
          "qk_norm_over_all_heads": _norm_over_all_heads,
          "gates_from_s_plus_b": _gates_from_biased_scores}


@pytest.fixture
def retraced(ff):
    """The module's model, its predict traced anew inside the test (with
    the fault planted) and once more after it (without)."""
    ff._predict_fn = None
    yield ff
    ff._predict_fn = None


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_predict_with_a_planted_fault_fails_the_comparison(
        monkeypatch, retraced, fault):
    FAULTS[fault](retraced)(monkeypatch)
    assert predict_error(retraced, seed=2) > 100 * LOGIT_ATOL


@pytest.mark.parametrize("fault", ["window_off", "window_plus_one"])
def test_engine_with_a_planted_window_fault_fails_the_margins(
        monkeypatch, ff, fault):
    FAULTS[fault](ff)(monkeypatch)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PAGE,
                                 max_seq_len=128, prefix_cache=False,
                                 prefill_chunk=16, decode_chunk=4)
    reqs = [eng.submit(p, max_new_tokens=30) for p in prompts([40, 23])]
    while eng.pending():
        eng.step()
    assert max(margins(ff, r).max() for r in reqs) > 100 * MARGIN_ATOL


# ---- the flash forward's lower edge --------------------------------------


def dense_window(q, k, v, window, scale):
    sq, sk = q.shape[1], k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    at = (sk - sq + jnp.arange(sq))[:, None]
    cols = jnp.arange(sk)[None, :]
    seen = (cols <= at) & (cols > at - window)
    probs = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


FLASH_CASES = {
    # (sq, sk, block_q, block_k, window)
    "edge-crosses-tiles": (64, 64, 16, 16, 20),
    "edge-on-a-tile-edge": (64, 64, 16, 16, 17),
    "window-is-a-tile": (64, 64, 16, 16, 16),
    "whole-tiles-dead": (64, 64, 16, 16, 8),
    "chunk-against-prefix": (32, 96, 16, 16, 5),
    "wide-keys": (64, 128, 16, 32, 40),
    "window-past-the-sequence": (64, 64, 32, 16, 100),
}


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_forward_with_a_lower_edge_matches_the_dense_mask(case):
    sq, sk, bq, bk, window = FLASH_CASES[case]
    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(2, s, 2, 16), jnp.float32)
               for s in (sq, sk, sk))
    got, _ = pallas_kernels.flash_attention_fwd_pallas(
        q, k, v, True, 0.25, bq, bk, need_lse=False, window=window)
    np.testing.assert_allclose(got, dense_window(q, k, v, window, 0.25),
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_tile_counts_agree_with_the_mask_and_the_grid(case):
    """The counts against a brute-force count over the mask itself, and the
    grid's steps against the tiles that can be live: a window layer has no
    step for a tile wholly below its window."""
    sq, sk, bq, bk, window = FLASH_CASES[case]
    at = (sk - sq + np.arange(sq))[:, None]
    cols = np.arange(sk)[None, :]
    seen = (cols <= at) & (cols > at - window)
    tiles = seen.reshape(sq // bq, bq, sk // bk, bk).transpose(0, 2, 1, 3)
    live = tiles.any(axis=(2, 3))
    whole = tiles.all(axis=(2, 3))
    counts = pallas_kernels.flash_tile_counts(sq, sk, bq, bk, sk - sq, True,
                                              window)
    assert counts["live"] == live.sum()
    assert counts["masked"] == (live & ~whole).sum()
    assert counts["dead"] == (~live).sum()
    per_row = max(int(np.ptp(r.nonzero()[0])) + 1 for r in live)
    assert counts["steps"] == (sq // bq) * per_row
    plain = pallas_kernels.flash_tile_counts(sq, sk, bq, bk, sk - sq, True)
    assert "steps" not in plain and plain["live"] >= counts["live"]


def test_predict_and_chunked_prefill_through_the_flash_lower_edge(
        monkeypatch):
    """The op's own route to the kernel (interpret mode): tiles of 16 under
    windows of 8, in predict and in the engine's prefill chunks (a chunk of
    32 against a prefix of 32 on the global layer, against the 16 keys
    before it on a window layer)."""
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    monkeypatch.setattr(pallas_kernels, "_WINDOW_BLOCK", 16)
    monkeypatch.setattr(pallas_kernels, "_OUTER_BLOCK", 16)
    calls = []
    fwd = pallas_kernels.flash_attention_fwd_pallas
    monkeypatch.setattr(
        pallas_kernels, "flash_attention_fwd_pallas",
        lambda q, k, *a, **kw: calls.append(
            (q.shape[1], k.shape[1], kw.get("window"))) or fwd(q, k, *a, **kw))
    m = build(seed=6)       # a model of its own: nothing traced without flash
    assert predict_error(m, seed=3) < LOGIT_ATOL
    assert (SEQ, SEQ, WINDOW) in calls and (SEQ, SEQ, None) in calls
    del calls[:]
    eng = m.make_serving_engine(serve_slots=2, kv_page_size=PAGE,
                                max_seq_len=128, prefix_cache=False,
                                prefill_chunk=32, decode_chunk=4,
                                decode_buckets=[64])
    req = eng.submit(prompts([50])[0], max_new_tokens=12)
    while eng.pending():
        eng.step()
    assert margins(m, req).max() < MARGIN_ATOL
    assert {(32, 32, WINDOW), (32, 48, WINDOW), (32, 32, None),
            (32, 64, None)} <= set(calls)


def test_a_window_under_a_gradient_is_the_masked_attention():
    """fit() takes XLA's masked attention for a window layer (the flash
    backward kernels carry no window): its gradient is the dense mask's."""
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=1)
    m = FFModel(cfg)
    x = m.create_tensor([2, 24, 32], name="x")
    y = m.multihead_attention(x, x, x, 32, 4, causal=True, bias=False,
                              num_kv_heads=2, rope=True, window=5,
                              name="attn")
    m.compile(final_tensor=y)
    op, p = m.get_op_by_name("attn"), m.params["attn"]
    xs = jnp.asarray(np.random.RandomState(2).randn(2, 24, 32), jnp.float32)

    def dense(p, xs):
        q, k, v = op._project_qkv(p, xs, xs, xs)
        k, v = op._broadcast_kv(k, v)
        ctx = dense_window(q, k, v, 5, 8 ** -0.5)
        return jnp.sum(jnp.sin(op._out_proj(p, ctx)))

    def program(p, xs):
        return jnp.sum(jnp.sin(op.forward(p, [xs] * 3, training=True)[0]))

    want, got = jax.grad(dense)(p, xs), jax.grad(program)(p, xs)
    for name in want:
        np.testing.assert_allclose(got[name], want[name], atol=1e-5, rtol=0)
    assert float(jnp.abs(got["wk"]).max()) > 1e-3
