"""Nemotron-H through the normal path (models/nemotron_h.py -> compile() ->
predict / generate / make_serving_engine) against the plain reference
(tests/reference_nemotron_h.py, the same text as
benchmark/reference/nemotron_h.py), at a tiny size in float32 on the CPU: a
pattern of Mamba-2 mixers, attention without rotary and relu^2 experts in a
latent, one mixer a layer; the recurrent state beside the page pool in the
serving engine; and the dropless MoE op's new forms alone (`expert="relu2"`,
`latent_dim`, a held share whose parts add up), with the SwiGLU op's outputs
held to what the parent commit computed.

Logits are compared, never tokens: with random weights the largest logit
changes on rounding. Every tolerance stands beside its reason.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flexflow_tpu.ops.moe as moe_mod
import reference_nemotron_h as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.nemotron_h import nemotron_h_lm
from flexflow_tpu.ops.mamba import Mamba2Mixer

VOCAB, SEQ, PATTERN = 97, 40, "ME*MEM"
EXPERTS, TOP_K = 8, 3
SIZES = dict(hybrid_override_pattern=PATTERN, norm_eps=1e-5,
             mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
             ssm_state_size=16, num_experts_per_tok=TOP_K,
             norm_topk_prob=True, routed_scaling_factor=5.0,
             rope_theta=10000.0)
# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative, in different orders (chunked scan against the recurrence,
# grouped experts against a dense loop), and the logits are of order 4.
# Measured 4e-6; bf16 compute lands near 3e-2 and fails by three orders.
LOGIT_ATOL = 5e-5


def build(batch=2, seq=SEQ, seed=3, held=None, pattern=PATTERN, rope=False):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = nemotron_h_lm(
        ff, batch, seq_len=seq, hidden=64, pattern=pattern, heads=4,
        kv_heads=2, mamba_heads=8, mamba_head_dim=16, n_groups=2,
        state_size=16, chunk_size=16, num_experts=EXPERTS,
        experts_per_token=TOP_K, expert_hidden=48, latent_dim=32,
        shared_hidden=40, routed_scaling=5.0, experts_held=held,
        score_bias_std=0.05, vocab_size=VOCAB, rope=rope)
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
    """How far below the reference's maximum logit each emitted token's
    reference logit lies, the reference scoring prompt + emitted tokens in
    one pass."""
    full = np.asarray(req.output)
    logits = np.asarray(ref.forward(ff.params, full, sizes))
    p = req.prompt.size
    rows = logits[p - 1:full.size - 1]
    return rows.max(-1) - rows[np.arange(rows.shape[0]), full[p:]]


def prompts(lengths, seed=10):
    return [np.random.RandomState(seed + i).randint(1, VOCAB, (n,))
            .astype(np.int32) for i, n in enumerate(lengths)]


def test_graph_is_one_mixer_a_layer_under_one_norm(ff):
    names = [op.name for op in ff.ops]
    for i, c in enumerate(PATTERN):
        want = {"M": f"mamba_{i}", "*": f"attn_{i}", "E": f"moe_{i}"}[c]
        assert {f"norm_{i}", want, f"res_{i}"} <= set(names)
    assert not any(n.startswith(("ln1_", "ln2_", "ffn_")) for n in names)
    assert isinstance(ff.get_op_by_name("mamba_0"), Mamba2Mixer)
    attn, moe = ff.get_op_by_name("attn_2"), ff.get_op_by_name("moe_1")
    assert not attn.rope and attn.num_kv_heads == 2
    assert (moe.expert, moe.latent_dim, moe.scoring, moe.k) == (
        "relu2", 32, "sigmoid", TOP_K)
    assert set(ff.params["moe_1"]) == {
        "router", "score_bias", "w_up", "w_down", "w_latent_in",
        "w_latent_out", "shared_up", "shared_down"}
    assert ff.params["moe_1"]["w_up"].shape == (EXPERTS, 32, 48)
    assert ff.params["moe_1"]["shared_up"].shape == (64, 40)
    with pytest.raises(ValueError, match="pattern"):
        nemotron_h_lm(FFModel(FFConfig(batch_size=1)), 1, pattern="MX")


def test_predict_logits_match_reference(ff):
    toks = np.random.RandomState(0).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, toks[b], SIZES))
        np.testing.assert_allclose(got[b], want, atol=LOGIT_ATOL, rtol=0)


def test_rotary_is_one_argument_and_the_reference_tells_it_apart(ff):
    """The family's code applies no rotary; `rope=True` is the one line that
    changes it, in the program and the reference alike, and the two do not
    pass for each other."""
    toks = np.random.RandomState(1).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))[0]
    wrong = np.asarray(ref.forward(ff.params, toks[0],
                                   {**SIZES, "attention_rope": True}))
    assert np.abs(got - wrong).max() > 100 * LOGIT_ATOL
    roped = build(rope=True)
    got = np.asarray(roped.predict({"input": toks}))[0]
    want = np.asarray(ref.forward(roped.params, toks[0],
                                  {**SIZES, "attention_rope": True}))
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_generate_scores_match_reference(ff):
    """Prefill + decode through the contiguous caches (the recurrent state
    stepping beside the attention's rows): the model's log-probability of
    each emitted token against the reference's full forward."""
    prompt = np.random.RandomState(2).randint(1, VOCAB, (2, 7)) \
        .astype(np.int32)
    out, scores = ff.generate(prompt, max_new_tokens=9, return_scores=True)
    for b in range(2):
        logp = jax.nn.log_softmax(ref.forward(ff.params, out[b], SIZES))
        want = [float(logp[6 + j, out[b, 7 + j]]) for j in range(9)]
        np.testing.assert_allclose(scores[b], want, atol=2 * LOGIT_ATOL,
                                   rtol=0)


def test_ragged_generate_keeps_padding_out_of_the_state(ff):
    prompt = np.random.RandomState(4).randint(1, VOCAB, (2, 12)) \
        .astype(np.int32)
    out, scores = ff.generate(prompt, max_new_tokens=5, return_scores=True,
                              prompt_lengths=[12, 5])
    seq = np.concatenate([prompt[1, :5], out[1, 12:]])
    logp = jax.nn.log_softmax(ref.forward(ff.params, seq, SIZES))
    want = [float(logp[4 + j, seq[5 + j]]) for j in range(5)]
    np.testing.assert_allclose(scores[1], want, atol=2 * LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("kw", [
    {}, {"prefill_chunk": 8},
    {"prefill_chunk": 8, "prefill_interleave_chunks": 1}],
    ids=["whole", "chunked", "interleaved"])
def test_engine_prefill_and_decode_are_the_full_forward(ff, kw):
    """Prefill (whole, in chunks that carry the state, or in chunks
    interleaved with other slots' decode steps) -> the seated state -> ten
    in-place updates, five requests over three slots so that slots are
    reused: every emitted token is within rounding of the reference's
    maximum at its position, the reference scoring prompt + emitted tokens
    in ONE pass."""
    eng = ff.make_serving_engine(serve_slots=3, kv_page_size=8, kv_pages=40,
                                 max_seq_len=64, prefix_cache=False,
                                 decode_chunk=2, **kw)
    reqs = eng.run(prompts([5, 9, 13, 21, 7]), max_new_tokens=10)
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == 10
        # a token that is not the reference's argmax lies within the two
        # logits' rounding of it
        assert margins(ff, r).max() <= 2 * LOGIT_ATOL
    st = eng.stats()
    assert st["state_pool_bytes"] == 3 * st["state_bytes_per_slot"] > 0
    assert st["state_slots_live"] == 0
    assert st["kv_pool_bytes"] == 40 * 8 * st["kv_bytes_per_token"]


def test_a_new_request_never_reads_what_the_slot_held_before(ff):
    """A released slot keeps its last state (nothing zeroes it: seating
    overwrites the whole slot, and decode touches live slots only). With
    every slot's state turned to garbage between two requests the second
    still emits what a fresh engine emits, and its seated and advanced state
    is the reference's (`slot_state` against `forward(states=, rows=)`)."""
    kw = dict(serve_slots=2, kv_page_size=8, kv_pages=24, max_seq_len=48,
              prefix_cache=False, decode_chunk=2)
    first, second = prompts([11, 14], seed=7)
    want = ff.make_serving_engine(**kw).run([second], max_new_tokens=7)[0]
    eng = ff.make_serving_engine(**kw)
    r = eng.submit(first, 6)
    eng.step()                                  # admitted: prefill seated it
    assert eng.stats()["state_slots_live"] == 1
    assert np.abs(eng.slot_state(r.slot)["mamba_0"]["h"]).max() > 0
    while eng.pending():
        eng.step()
    assert eng.stats()["state_slots_live"] == 0
    assert ("state_reset",) not in eng._registered
    for op in ("mamba_0", "mamba_3", "mamba_5"):
        eng.kv.pool[op] = jax.tree.map(lambda v: jnp.full_like(v, 1e4),
                                       eng.kv.pool[op])
    r = eng.submit(second, 7)
    while len(r.tokens) < 5:
        eng.step()
    # the state has read the prompt and every emitted token but the last
    seq = np.concatenate([second, r.tokens[:-1]]).astype(np.int32)
    got = eng.slot_state(r.slot)
    ref_state = {}
    ref.forward(ff.params, np.pad(seq, (0, 6)), SIZES, states=ref_state,
                rows=seq.size)
    assert sorted(got) == sorted(ref_state) == ["mamba_0", "mamba_3",
                                                "mamba_5"]
    for op, st in ref_state.items():
        for k in ("h", "conv"):
            err = np.linalg.norm(np.asarray(got[op][k], np.float32)
                                 - np.asarray(st[k]))
            assert err <= 2e-3 * np.linalg.norm(np.asarray(st[k])), (op, k)
    while eng.pending():
        eng.step()
    assert r.tokens == want.tokens


def test_one_slots_state_in_anothers_place_fails_the_check(ff):
    """The control of the benchmark's check (b): a request decoding from a
    neighbour's recurrent state emits tokens far below the reference's
    maximum."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8, kv_pages=24,
                                 max_seq_len=48, prefix_cache=False,
                                 decode_chunk=2)
    a, b = (eng.submit(p, 12) for p in prompts([9, 17], seed=40))
    eng.step()
    pool = eng.kv.pool
    for name in ("mamba_0", "mamba_3", "mamba_5"):
        pool[name] = jax.tree.map(lambda v: v[::-1], pool[name])
    while eng.pending():
        eng.step()
    assert max(margins(ff, a).max(), margins(ff, b).max()) > 1e3 * LOGIT_ATOL


def test_engine_refuses_what_a_recurrent_state_cannot_do(ff):
    kw = dict(serve_slots=2, kv_page_size=8, kv_pages=24, max_seq_len=48)
    # a prefix cache is no longer refused (tests/test_state_snapshots.py:
    # a hit resumes from a snapshot); its host tier still is
    with pytest.raises(ValueError, match="host_kv_pages must be 0"):
        ff.make_serving_engine(prefix_cache=True, host_kv_pages=8, **kw)
    with pytest.raises(ValueError, match="speculate_k must be 0"):
        ff.make_serving_engine(prefix_cache=False, draft_model=ff,
                               speculate_k=2, **kw)
    eng = ff.make_serving_engine(prefix_cache=False, **kw)
    p = prompts([16])[0]
    for call in (lambda: eng.export_prefix_slab(p),
                 lambda: eng.import_prefix_slab({})):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()
    with pytest.raises(RuntimeError, match="needs the radix prefix cache"):
        eng.prefill_into_cache(p)
    assert eng.flush_prefix_cache() == 0        # the generator's call


def test_decode_dispatch_and_prefill_spans_carry_the_state_counts(ff):
    from flexflow_tpu.runtime import telemetry

    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8, kv_pages=24,
                                 max_seq_len=48, prefix_cache=False,
                                 decode_chunk=2)
    eng.run(prompts([9]), max_new_tokens=4)
    ev = telemetry.tracer().events
    pre = [e for e in ev(name="prefill") if "scan_rows" in e["args"]][-1]
    assert pre["args"]["scan_rows"] == 16 * 3       # bucket x M layers
    dec = [e for e in ev(name="decode_dispatch")
           if "state_bytes" in e["args"]][-1]
    assert dec["args"]["state_bytes"] == (
        2 * 2 * 1 * eng.stats()["state_bytes_per_slot"])


# ---- the dropless MoE op's new forms, alone ------------------------------


def moe_layer(held=None, expert="relu2", latent=32, hidden=128, width=128,
              seed=5, **kw):
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=seed)
    m = FFModel(cfg)
    x = m.create_tensor([2, 8, hidden], name="x")
    y = m.moe(x, num_experts=8, hidden_dim=width, k=3, capacity_factor=None,
              expert=expert, scoring="sigmoid", score_bias=0.05,
              routed_scaling=5.0, shared_hidden_dim=64, latent_dim=latent,
              experts_held=held, name="moe", **kw)
    m.compile(final_tensor=y)
    return m, m.get_op_by_name("moe")


def moe_reference(p, x, first=0, count=8):
    """The reference's E layer on rows x (S, D) of mean square 1 (its
    pre-norm, with a unit scale and eps 0, leaves them), without the
    residual; `count` 0 gives the shared expert alone."""
    x = jnp.asarray(x)
    u, lat, gates, _ = ref.route(
        x, jnp.ones((x.shape[-1],)), p["router"], p["score_bias"],
        p["w_latent_in"], top_k=3, renormalize=True, scaling=5.0, eps=0.0)
    r = jnp.zeros_like(lat)
    for e in range(count):
        r = r + ref.expert(lat, gates[:, first + e], p["w_up"][e],
                           p["w_down"][e])
    return ref.moe_out(jnp.zeros_like(u), u, r, p["w_latent_out"],
                       p["shared_up"], p["shared_down"])


def unit_rows(seed, *shape):
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return x / np.sqrt((x * x).mean(-1, keepdims=True))


@pytest.mark.parametrize("backend,took", [("cpu", "grouped"),
                                          ("tpu", "streamed")])
def test_relu2_latent_op_matches_reference_in_both_lowerings(
        monkeypatch, backend, took):
    """`expert="relu2"` + `latent_dim` on the one dropless op: grouped
    (`ragged_dot`) and streamed (the two-matrix expert-stream kernel,
    interpreted), against the reference's dense loop over experts."""
    monkeypatch.setattr(moe_mod, "_backend", lambda: backend)
    m, op = moe_layer(latent=128)       # the kernel wants the lanes' 128
    x = unit_rows(0, 2, 8, 128)
    lowerings = []
    got = np.asarray(op.forward(m.params["moe"], [jnp.asarray(x)],
                                lowerings=lowerings)[0])
    assert lowerings == [took]
    want = np.asarray(moe_reference(m.params["moe"], x.reshape(16, 128)))
    # outputs of order 1; the streamed kernel sums a row's experts in f32
    np.testing.assert_allclose(got.reshape(16, 128), want, atol=2e-5, rtol=0)
    assert np.abs(want).max() > 0.1


def test_streamed_relu2_counts_and_masks_like_the_grouped(monkeypatch):
    m, op = moe_layer(latent=128, held=(2, 4))
    x = jnp.asarray(unit_rows(1, 2, 8, 128))
    mask = jnp.asarray(np.random.RandomState(2).rand(2, 8) > 0.3)
    outs, counts = {}, {}
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(moe_mod, "_backend", lambda b=backend: b)
        routing = []
        outs[backend] = np.asarray(op.forward(
            m.params["moe"], [x], row_mask=mask, routing=routing)[0])
        counts[backend] = np.asarray(routing[0])
    np.testing.assert_array_equal(counts["cpu"], counts["tpu"])
    np.testing.assert_allclose(outs["cpu"], outs["tpu"], atol=2e-5, rtol=0)
    assert counts["cpu"][0] > 0


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips each hold one of the eight experts: the eight shares'
    routed parts (each through its own copy of W_up) plus the shared expert
    counted ONCE sum to the layer that holds them all."""
    whole, op = moe_layer()
    x = unit_rows(3, 2, 8, 128)
    want = np.asarray(op.forward(whole.params["moe"], [jnp.asarray(x)])[0])
    rows = x.reshape(16, 128)
    shared = np.asarray(moe_reference(whole.params["moe"], rows, count=0))
    total = shared.copy()
    for e in range(8):
        share, sop = moe_layer(held=(e, 1))
        p = dict(whole.params["moe"])
        p["w_up"], p["w_down"] = p["w_up"][e:e + 1], p["w_down"][e:e + 1]
        assert {k: v.shape for k, v in p.items()} == {
            k: v.shape for k, v in share.params["moe"].items()}
        got = np.asarray(sop.forward(p, [jnp.asarray(x)])[0]).reshape(16, 128)
        np.testing.assert_allclose(
            got, np.asarray(moe_reference(p, rows, e, 1)), atol=2e-5, rtol=0)
        total += got - shared
    np.testing.assert_allclose(total, want.reshape(16, 128), atol=5e-5,
                               rtol=0)
    assert np.abs(total - shared).max() > 0.1


def test_new_forms_belong_to_the_dropless_op():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    m = FFModel(cfg)
    x = m.create_tensor([2, 8, 32], name="x")
    for kw in (dict(expert="relu2"), dict(expert="swiglu", latent_dim=16)):
        with pytest.raises(ValueError, match="dropless"):
            m.moe(x, num_experts=4, hidden_dim=16, **kw)
    with pytest.raises(ValueError, match="relu2"):
        m.moe(x, num_experts=4, hidden_dim=16, expert="geglu")
    op_flops = moe_layer()[1].flops()
    assert op_flops == int(2 * 2 * (16 * 3 * 32 * 128 + 16 * 128 * 64)
                           + 4 * 16 * 128 * 32)


# What the PARENT commit (42116e1) computes for a seeded SwiGLU layer, plain
# and with the router's DeepSeek form and a held share, grouped and streamed
# (interpreted): sha256 of the float32 output's bytes. The two-matrix form
# went into the same op and the same kernel; these must not move.
SWIGLU_GOLDEN = {
    (None, "cpu"): "11486741b546ceab", (None, "tpu"): "d3e93b62099ea7f3",
    ((2, 4), "cpu"): "1b41da5dea2d96a6", ((2, 4), "tpu"): "a2ac89a22bc58395",
}


@pytest.mark.parametrize("held,backend", sorted(SWIGLU_GOLDEN, key=str))
def test_swiglu_outputs_are_the_parents_bit_for_bit(monkeypatch, held,
                                                    backend):
    monkeypatch.setattr(moe_mod, "_backend", lambda: backend)
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=5)
    m = FFModel(cfg)
    x = m.create_tensor([2, 8, 128], name="x")
    kw = dict(scoring="sigmoid", score_bias=0.05, routed_scaling=2.5,
              shared_hidden_dim=64, experts_held=held) if held else {}
    y = m.moe(x, num_experts=8, hidden_dim=128, k=2, capacity_factor=None,
              expert="swiglu", renormalize=bool(held), name="moe", **kw)
    m.compile(final_tensor=y)
    op = m.get_op_by_name("moe")
    xs = jnp.asarray(np.random.RandomState(0).randn(2, 8, 128)
                     .astype(np.float32))
    out = np.asarray(jax.jit(lambda p, v: op.forward(p, [v])[0])(
        m.params["moe"], xs))
    assert hashlib.sha256(out.tobytes()).hexdigest()[:16] == \
        SWIGLU_GOLDEN[(held, backend)]
