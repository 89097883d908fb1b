"""Brumby through the normal path (models/brumby.py -> compile() -> predict /
generate / make_serving_engine) against the plain reference
(tests/reference_brumby.py, the same text as benchmark/reference/brumby.py:
the quadratic form, no state), at a tiny size in float32 on the CPU: every
mixer a power-retention layer, so the graph has NO attention op and the
serving engine's page pool holds no row: pages only key the trie, the slots'
states and their snapshots are what is held.

Logits and states are compared, never tokens. Every tolerance stands beside
its reason.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_brumby as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.brumby import brumby_lm
from flexflow_tpu.ops.dense import GatedMLP
from flexflow_tpu.ops.retention import PowerRetention

VOCAB, SEQ, PS = 97, 40, 8
SIZES = dict(num_hidden_layers=3, rope_theta=1e4, rms_norm_eps=1e-6,
             retention_norm_eps=1e-5)
# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative, in different orders (chunks and a state against the
# quadratic form), and the logits are of order 1. Measured 2e-6; bf16
# compute lands near 1e-2.
LOGIT_ATOL = 4e-5
CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs",
    "brumby-14b-base-serve.json")


def build(batch=1, seq=SEQ, seed=3, heads=6, kv_heads=2, head_dim=16,
          decay_floor=(1e-2, 2e-1)):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = brumby_lm(
        ff, batch, seq_len=seq, hidden=64, layers=3, heads=heads,
        kv_heads=kv_heads, head_dim=head_dim, ffn_hidden=96,
        vocab_size=VOCAB, rope_theta=1e4, chunk_size=16,
        decay_floor=decay_floor)
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


def engine(ff, **kw):
    args = dict(serve_slots=2, kv_page_size=PS, kv_pages=48, max_seq_len=96,
                prefix_cache=True, state_snapshots=3, decode_chunk=2)
    args.update(kw)
    return ff.make_serving_engine(**args)


def margins(ff, req):
    full = np.asarray(req.output)
    logits = np.asarray(ref.forward(ff.params, full, SIZES))
    p = req.prompt.size
    rows = logits[p - 1:full.size - 1]
    return rows.max(-1) - rows[np.arange(rows.shape[0]), full[p:]]


def tokens(n, seed):
    return np.random.RandomState(seed).randint(1, VOCAB, (n,)) \
        .astype(np.int32)


def serve(eng, prompt, new=6, read_after=2):
    """The request run to its end, and the slot's state while it is still
    seated (after `read_after` emitted tokens at the earliest)."""
    req = eng.submit(prompt, new)
    state = None
    while eng.pending():
        eng.step()
        if state is None and req.slot >= 0 \
                and len(req.tokens) >= read_after:
            state = (eng.slot_state(req.slot), len(req.tokens))
    return req, state


def rel(got, want):
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(np.asarray(got, np.float32) - want)
                 / np.linalg.norm(want))


def test_graph_is_a_retention_layer_and_an_mlp_a_layer(ff):
    names = {op.name for op in ff.ops}
    for i in range(3):
        assert {f"norm1_{i}", f"retention_{i}", f"res1_{i}", f"norm2_{i}",
                f"mlp_{i}", f"res2_{i}"} <= names
    op = ff.get_op_by_name("retention_1")
    assert isinstance(op, PowerRetention)
    assert (op.num_heads, op.num_kv_heads, op.group) == (6, 2, 3)
    assert isinstance(ff.get_op_by_name("mlp_2"), GatedMLP)
    assert set(ff.params["retention_0"]) == {
        "wq", "wk", "wv", "wg", "gate_bias", "q_norm", "k_norm", "wo"}
    # an untied head beside the embedding
    assert ff.params["lm_head"]["kernel"].shape == (64, VOCAB)
    assert ff.params["tok_embed"]["kernel"].shape == (VOCAB, 64)
    # no op keeps a per-token row
    assert not any(getattr(o, "kv_cache_protocol", False) for o in ff.ops)
    # the published shape: 8 KV heads of 128 under 40 query heads hold
    # 8 x 65 x 128 x (128 + 1) float32 a sequence and layer
    big = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
    x = big.create_tensor([1, 8, 5120], name="x")
    big.power_retention(x, 40, 8, 128, name="r")
    assert big.get_op_by_name("r").state_bytes_per_slot() \
        == 4 * 8 * 65 * 128 * 129 == 34_344_960


def test_predict_logits_match_reference():
    ff = build(batch=2)
    toks = np.random.RandomState(0).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, toks[b], SIZES))
        np.testing.assert_allclose(got[b], want, atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("change", [
    {"retention_norm_eps": 0.0}, {"rope_theta": 1e6},
    {"rms_norm_eps": 1e-2}])
def test_the_reference_tells_each_assumed_number_apart(change):
    """eps_n dropped, another rotary base, another norm epsilon: the
    reference then reads far from the program, so none of them is a number
    the comparison cannot see. (eps_n 1e-5 weighs against sums of squared
    scores of order 1e-2 at the first rows of a sequence.)"""
    ff = build(batch=2)
    toks = np.random.RandomState(1).randint(1, VOCAB, (SEQ,)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": np.stack([toks, toks])}))[0]
    wrong = np.asarray(ref.forward(ff.params, toks, {**SIZES, **change}))
    assert np.abs(got - wrong).max() > 5 * LOGIT_ATOL


def test_generate_scores_match_reference():
    """Prefill + decode through the contiguous state (`scan_forward`, then
    `step_forward` at each token's own position)."""
    ff = build(batch=2)
    prompt = np.random.RandomState(2).randint(1, VOCAB, (2, 7)) \
        .astype(np.int32)
    out, scores = ff.generate(prompt, max_new_tokens=9, return_scores=True)
    for b in range(2):
        logp = jax.nn.log_softmax(ref.forward(ff.params, out[b], SIZES))
        want = [float(logp[6 + j, out[b, 7 + j]]) for j in range(9)]
        np.testing.assert_allclose(scores[b], want, atol=2 * LOGIT_ATOL,
                                   rtol=0)


@pytest.mark.parametrize("kw", [
    {}, {"prefill_chunk": 8},
    {"prefill_chunk": 8, "prefill_chunk_loop": True,
     "decode_buckets": [16, 32]}],
    ids=["whole", "chunked", "loop"])
def test_engine_cold_prefill_and_decode_are_the_full_forward(ff, kw):
    """An engine over a graph with NO attention op: cold prefill (whole, in
    unrolled chunks, as one loop body), the seat, ten in-place decode steps:
    every emitted token within rounding of the reference's maximum."""
    eng = engine(ff, serve_slots=3, prefix_cache=False, **kw)
    reqs = eng.run([tokens(n, 10 + i) for i, n in
                    enumerate([5, 9, 13, 21, 7])], max_new_tokens=10)
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == 10
        assert margins(ff, r).max() <= 2 * LOGIT_ATOL
    assert eng.kv.snapshots is None


def test_engine_runs_the_kernel_at_a_head_of_128():
    """The Pallas step (interpreted) inside the decode program: a head of 128
    lanes, 5 query heads a KV head (the published group)."""
    ff = build(heads=5, kv_heads=1, head_dim=128)
    assert ff.get_op_by_name("retention_0")._kernel_takes_layout()
    eng = engine(ff, prefix_cache=False, paged_attention_impl="pallas")
    reqs = eng.run([tokens(5, 1), tokens(11, 2), tokens(8, 3)],
                   max_new_tokens=6)
    for r in reqs:
        assert r.state == "done"
        assert margins(ff, r).max() <= 2 * LOGIT_ATOL


def test_a_hit_through_a_snapshot_is_the_request_served_cold(ff):
    """The same request cold (prefix_cache=False) and through its document's
    snapshot + a tail: the emitted tokens lie within rounding of the
    reference's maximum either way, the state the slot holds after the same
    tokens is the same state, and it is the reference's weighted sum over
    those tokens. Tolerance: all float32; the hit's scan starts at the
    document's end, so its chunks fall elsewhere than the cold prefill's
    (sums in another order), nothing more: 1e-5 relative."""
    doc, q = tokens(5 * PS, 1), tokens(5, 2)
    prompt = np.concatenate([doc, q])
    cold, (cold_state, n_cold) = serve(engine(ff, prefix_cache=False),
                                       prompt)
    eng = engine(ff)
    assert eng.prefill_into_cache(doc) == 5
    hit, (hit_state, n_hit) = serve(eng, prompt)
    assert hit.prefix_tokens == doc.size and cold.prefix_tokens == 0
    assert n_cold == n_hit
    assert margins(ff, hit).max() <= 2 * LOGIT_ATOL
    assert margins(ff, cold).max() <= 2 * LOGIT_ATOL
    seen = np.concatenate([prompt, hit.tokens[:n_hit - 1]])
    want = {}
    ref.forward(ff.params, np.concatenate([seen, np.zeros(8, np.int32)]),
                SIZES, states=want, rows=seen.size)
    for op in ("retention_0", "retention_2"):
        for k in ("s", "z"):
            assert rel(hit_state[op][k], cold_state[op][k]) < 1e-5
            assert rel(hit_state[op][k], want[op][k]) < 1e-5
    st = eng.stats()
    assert st["state_snapshot_hits"] == 1
    assert st["state_snapshots_taken"] == 1


def test_a_state_only_engine_builds_serves_evicts_and_reports(ff):
    """No op keeps a row: the page pool has no array behind it (0 bytes, 0
    bytes a token), the trie keys snapshots by page-aligned prefixes, a
    snapshot leaves when its room is needed, and `stats()` says what fills
    memory. What a state refuses stays refused."""
    eng = engine(ff, state_snapshots=2)
    op = ff.get_op_by_name("retention_0")
    per_slot = 3 * op.state_bytes_per_slot()
    st = eng.stats()
    assert st["kv_pool_bytes"] == 0 and st["kv_bytes_per_token"] == 0
    assert st["tokens_per_pool_gb"] == 0
    assert st["kv_capacity_vs_bf16"] == 1.0
    assert st["state_bytes_per_slot"] == per_slot
    assert st["state_pool_bytes"] == 2 * per_slot
    # two snapshots and the scratch row
    assert st["state_snapshot_pool_bytes"] == 3 * per_slot
    assert set(eng.kv.pool) == {"retention_0", "retention_1", "retention_2"}
    docs = [tokens(3 * PS, 20 + i) for i in range(3)]
    for d in docs:
        assert eng.prefill_into_cache(d) == 3
    st = eng.stats()
    assert st["state_snapshots_taken"] == 3
    assert st["state_snapshots_evicted"] == 1
    assert st["state_snapshots_held"] == 2
    # the two that stayed are hit, the evicted one prefills cold
    reqs = eng.run([np.concatenate([d, tokens(4, 30 + i)])
                    for i, d in enumerate(docs)], max_new_tokens=5)
    assert sorted(r.prefix_tokens for r in reqs) == [0, 3 * PS, 3 * PS]
    for r in reqs:
        assert margins(ff, r).max() <= 2 * LOGIT_ATOL
    st = eng.stats()
    assert st["prefix_refs_live"] == 0
    assert st["free_pages"] + st["kv_pages_cached"] == eng.num_pages - 1
    with pytest.raises(ValueError, match="host_kv_pages must be 0"):
        engine(ff, host_kv_pages=4)
    with pytest.raises(ValueError, match="speculate_k"):
        engine(ff, speculate_k=2, draft_model=ff)
    with pytest.raises(NotImplementedError, match="recurrent"):
        eng.export_prefix_slab(docs[1])


def test_a_graph_with_nothing_to_cache_says_so():
    cfg = FFConfig(batch_size=1, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    from flexflow_tpu import DataType
    toks = ff.create_tensor([1, 8], dtype=DataType.DT_INT32, name="input")
    t = ff.embedding(toks, VOCAB, 16, name="tok_embed")
    logits = ff.dense(t, VOCAB, use_bias=False, name="lm_head")
    ff.compile(final_tensor=logits)
    with pytest.raises(ValueError, match="neither an attention op"):
        ff.make_serving_engine(serve_slots=1, kv_page_size=8, max_seq_len=16)


def held_in_bf16(ff):
    """Every retention op's state rounded to bfloat16 wherever it is written
    (the seat, every decode step): the nearest precision below the float32
    the configuration states."""
    def rounded(fn):
        def call(*a, **kw):
            out = fn(*a, **kw)
            pool = out[1] if isinstance(out, tuple) else out
            low = {k: v.astype(jnp.bfloat16).astype(v.dtype)
                   for k, v in pool.items()}
            return (out[0], low) if isinstance(out, tuple) else low
        return call

    for op in ff.ops:
        if isinstance(op, PowerRetention):
            op.seat_state = rounded(op.seat_state)
            op.paged_step_forward = rounded(op.paged_step_forward)


@pytest.mark.parametrize("low", [False, True], ids=["float32", "bfloat16"])
def test_a_bf16_state_fails_the_configurations_tolerance(low):
    """The configuration's `state_rel_rms` (set on the chip between the bf16
    PROGRAM's reading and a bf16-held state's): at this size in float32 a
    state held in float32 reads far below it and one held in bfloat16 above
    it, after a hit and 60 decode steps in place on a decay of 1e-3 to 1e-2
    a token (the configuration's draw)."""
    with open(CONFIG) as f:
        tol = json.load(f)["tolerances"]["state_rel_rms"]
    ff = build(decay_floor=(1e-3, 1e-2))
    if low:
        held_in_bf16(ff)
    eng = engine(ff, max_seq_len=128)
    doc, q = tokens(4 * PS, 4), tokens(6, 5)
    assert eng.prefill_into_cache(doc) == 4
    req, (state, n) = serve(eng, np.concatenate([doc, q]), new=62,
                            read_after=60)
    assert req.prefix_tokens == doc.size
    seen = np.concatenate([doc, q, req.tokens[:n - 1]])
    want = {}
    ref.forward(ff.params, seen, SIZES, states=want, rows=seen.size)
    worst = max(rel(state[op]["s"], want[op]["s"])
                for op in ("retention_0", "retention_2"))
    assert (worst > tol) if low else (worst < tol / 20), (worst, tol)
