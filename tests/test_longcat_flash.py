"""LongCat-Flash's decoder through the normal path (models/longcat_flash.py ->
compile() -> predict / fit / make_serving_engine) against the plain reference
(tests/reference_longcat_flash.py, the same text as
benchmark/reference/longcat_flash.py), at a tiny size that keeps every ratio
(2 double layers, 4 heads, latent 32 + rope 16, both LoRA scales, 16 experts
+ 8 zero-computation experts, top-4 of the 24, gates times 6, 4 held), in
float32 on the CPU; and the mechanisms it forced, each alone: the
zero-computation experts of the dropless op (ops/moe.py) and the prefill tail
trimmed by dependency (runtime/generation.py).

Logits are compared, never tokens: with random weights the largest logit
changes on rounding. Every tolerance stands beside its reason.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_longcat_flash as ref
from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                          SGDOptimizer, SingleDataLoader)
from flexflow_tpu.models.longcat_flash import longcat_flash_lm
from flexflow_tpu.ops import moe as moe_mod
from flexflow_tpu.ops.moe import MoE
from flexflow_tpu.runtime.generation import Generator

VOCAB, SEQ = 128, 64
E, Z, K, HELD = 16, 8, 4, (4, 4)
SIZES = dict(num_layers=2, hidden_size=64, rms_norm_eps=1e-5, rope_theta=1e7,
             q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=32,
             qk_rope_head_dim=16, mla_scale_q_lora=True,
             mla_scale_kv_lora=True, ffn_hidden_size=128, moe_topk=K,
             routed_scaling_factor=6.0, router_experts=E, zero_expert_num=Z,
             experts_held=HELD)

# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative in different orders (absorbed against expanded, grouped
# against dense), logits of order 1. Measured 2e-6; bf16 compute lands near
# 1e-2.
LOGIT_ATOL = 5e-5


def build(batch=2, seq=SEQ, seed=3, held=HELD, optimizer=None, layers=2):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    ff.token_tensor, logits = longcat_flash_lm(
        ff, batch, seq_len=seq, hidden=64, layers=layers, heads=4,
        q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, ffn_hidden=128, num_experts=E,
        zero_experts=Z, experts_per_token=K, expert_hidden=32,
        experts_held=held, score_bias_std=0.02, vocab_size=VOCAB)
    if optimizer is None:
        ff.compile(final_tensor=logits)
    else:
        ff.compile(optimizer, LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                   [MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
                   final_tensor=logits)
    # norm scales initialise to one, where a missing or misplaced one would
    # pass: spread them
    rs = np.random.RandomState(seed)
    for op, ws in ff.params.items():
        for w, v in ws.items():
            if w in ("scale", "q_norm", "kv_norm"):
                ff.set_weights(op, w, (1 + 0.3 * rs.randn(*v.shape))
                               .astype(np.float32))
    return ff


@pytest.fixture(scope="module")
def ff():
    return build()


def margins(ff, req, n):
    """How far below the reference's maximum each emitted token's reference
    logit lies, over one full pass of prompt + emitted tokens."""
    full = np.asarray(req.output, np.int32)
    assert full.size == req.prompt.size + n
    rows = np.asarray(ref.forward(ff.params, full, SIZES,
                                  rows=(req.prompt.size - 1, full.size - 1)))
    return rows.max(axis=-1) - rows[np.arange(n), full[req.prompt.size:]]


def test_graph_is_the_double_block_with_a_shortcut(ff):
    names = [op.name for op in ff.ops]
    for l in range(2):
        for n in (f"attn_{l}_0", f"attn_{l}_1", f"ffn_{l}_0", f"ffn_{l}_1",
                  f"moe_{l}", f"res_moe_{l}"):
            assert n in names
        # the expert op stands where the equations put it, and its one
        # consumer closes the layer
        assert names.index(f"ln_post_{l}_0") < names.index(f"moe_{l}") \
            < names.index(f"ffn_{l}_0") < names.index(f"attn_{l}_1") \
            < names.index(f"res_moe_{l}")
        moe = ff.get_op_by_name(f"moe_{l}")
        assert moe.inputs[0] is ff.get_op_by_name(f"ln_post_{l}_0").outputs[0]
        assert moe.outputs[0] in ff.get_op_by_name(f"res_moe_{l}").inputs
    attn, moe = ff.get_op_by_name("attn_1_1"), ff.get_op_by_name("moe_1")
    assert not attn.indexed and attn.q_lora_scale == 2.0 \
        and attn.kv_lora_scale == pytest.approx(2 ** 0.5)
    assert (moe.scoring, moe.router_f32, moe.renormalize, moe.routed_scaling,
            moe.zero_experts, moe.router_width, moe.k) \
        == ("softmax", True, False, 6.0, Z, E + Z, K)
    assert ff.params["moe_1"]["router"].shape == (64, E + Z)
    assert ff.params["moe_1"]["score_bias"].shape == (E + Z,)
    assert ff.params["moe_1"]["w_gate"].shape == (HELD[1], 64, 32)
    assert ff.params["ffn_0_1"]["w_in"].shape == (64, 256)


def test_predict_logits_match_reference(ff):
    toks = np.random.RandomState(0).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, toks[b], SIZES))
        np.testing.assert_allclose(got[b], want, atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("rows", [None, (20, 40), (63, 64)])
def test_reference_does_not_depend_on_its_blocks_or_rows(ff, monkeypatch,
                                                         rows):
    """Spans of query rows with a key bound each, blocks of 16 rows, and a
    last layer whose expert layer, second attention and second feed-forward
    compute the asked rows' blocks only: the same logits."""
    toks = np.random.RandomState(5).randint(1, VOCAB, (SEQ,)).astype(np.int32)
    want = np.asarray(ref.forward(ff.params, toks, SIZES))
    for name, value in (("QUERY_BLOCK", 16), ("KEY_BLOCK", 32),
                        ("ROW_BLOCK", 32), ("HEAD_BLOCK", 2)):
        monkeypatch.setattr(ref, name, value)
    trace = {}
    got = np.asarray(ref.forward(ff.params, toks, SIZES, rows=rows,
                                 trace=trace))
    lo, hi = rows or (0, SEQ)
    np.testing.assert_allclose(got, want[lo:hi], atol=1e-5, rtol=0)
    assert [sum(len(t) for t in trace["experts"][l]) for l in range(2)] \
        == [SEQ, -(-hi // 16) * 16 - lo // 16 * 16]


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_serving_engine_emits_the_reference_argmax(ff, impl):
    """Cold prefill (whole, and in chunks of 32 whose non-final chunks skip
    the tail), the latent pool and paged decode against the reference's full
    pass, ragged rows side by side; `pallas` runs the dense core (interpret
    mode), `einsum` its oracle."""
    eng = ff.make_serving_engine(serve_slots=4, kv_page_size=8,
                                 max_seq_len=160, prefill_chunk=32,
                                 decode_chunk=4, prefix_cache=False,
                                 paged_attention_impl=impl)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, VOCAB, (n,)).astype(np.int32)
               for n in (40, 8, 70)]
    reqs = eng.run(prompts, max_new_tokens=10)
    assert [r.state for r in reqs] == ["done"] * 3
    for r in reqs:
        # both sides hold the logit to LOGIT_ATOL: a near-tie flips within
        # twice that
        assert margins(ff, r, 10).max() <= 2 * LOGIT_ATOL
    st = eng.stats()
    # page bytes: lat 128 x f32, 4 attentions
    assert st["kv_bytes_per_token"] == 128 * 4 * 4
    # counted on the device over the live rows of the decode dispatches:
    # every live row picks K columns a layer, the real picks that land on a
    # held expert are the assignments
    assert st["moe_zero_picks"] > 0 and st["moe_real_picks"] > 0
    assert (st["moe_zero_picks"] + st["moe_real_picks"]) % K == 0
    assert st["moe_held_picks"] == st["moe_assignments"] \
        <= st["moe_real_picks"]


def test_prefix_hit_prefill_matches_cold_prefill(ff):
    """The same 88-token prompt cold, then again as a hit of its 10 full
    pages (the tail's 8 rows against the gathered latent pages): the same
    tokens, each on the reference's maximum; the decode span carries the
    distinct live pages."""
    from flexflow_tpu.runtime import telemetry

    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=160, decode_chunk=4,
                                 prefix_cache=True)
    prompt = np.random.RandomState(9).randint(1, VOCAB, (88,)) \
        .astype(np.int32)
    cold = eng.run([prompt], max_new_tokens=12)[0]
    since = len(telemetry.tracer().events())
    a, b = eng.run([prompt, prompt], max_new_tokens=12)
    spans = [e for e in telemetry.tracer().events()[since:]
             if e["pid"] == eng._tm_track]
    st = eng.stats()
    assert st["prefix_hits"] == 2 and a.prefix_tokens == 80
    assert a.tokens == cold.tokens == b.tokens
    assert margins(ff, a, 12).max() <= 2 * LOGIT_ATOL
    both = [s["args"] for s in spans if s["name"] == "decode_dispatch"
            and s["args"]["slots"] == 2]
    assert both
    for at in both:
        # two slots on one document: its 10 whole pages are held twice and
        # counted once a step; every other page up to a slot's frontier
        # (its prompt's last page, its bucket's padding, the page it
        # writes) is its own
        page_bytes = 8 * st["kv_bytes_per_token"]
        assert at["live_pages_distinct"] * page_bytes == at["kv_read_bytes"]
        assert at["kv_attended_bytes"] - at["kv_read_bytes"] \
            == at["k"] * 10 * page_bytes
    rec = [s["args"] for s in spans if s["name"] == "record_tokens"]
    assert rec
    assert all({"zero_picks", "real_picks", "held_picks"} <= set(r)
               for r in rec)


def test_prefill_tail_is_trimmed_by_dependency(ff):
    """The last layer's expert op stands BEFORE the last attention op in
    `model.ops` and nothing cached reads it: it is tail. A plain decoder's
    tail is what it was, the ops past the last cached one."""
    gen = Generator(ff)
    names = {op.name for op in gen._tail_ops}
    assert names == {"moe_1", "res_attn_1_1", "ln_post_1_1", "ffn_1_1",
                     "res_ffn_1_1", "res_moe_1", "ln_f", "lm_head"}
    assert "moe_0" not in names
    # a `last_only` prefill runs the last layer's experts on the last row,
    # a `skip_tail` chunk on none
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        1, VOCAB, (1, 32)).astype(np.int32))
    caches = gen.init_caches(1, 32, jnp.float32)
    # traced, not run: the walks' shapes and what they collect
    rows, took = [], []
    logits = jax.eval_shape(lambda p: gen._walk(
        p, ff.bn_state, tokens, caches, None, last_only=True,
        expert_rows=rows, routing=[])[0], ff.params)
    assert logits.shape == (1, 1, VOCAB)
    # moe_0 on all 32 rows, moe_1 on ONE (at this size a share's cap is all
    # N k rows, a static count)
    assert rows == [32 * K, K]
    jax.eval_shape(lambda p: gen._walk(
        p, ff.bn_state, tokens[:, :16], caches, None, chunk_start=0,
        skip_tail=True, lowerings=took)[1], ff.params)
    assert len(took) == 1                       # moe_0 alone ran
    from flexflow_tpu.models.llama import llama_lm

    plain = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
    _, lg = llama_lm(plain, 1, seq_len=16, hidden=32, layers=2, heads=2,
                     vocab_size=64)
    plain.compile(final_tensor=lg)
    g2 = Generator(plain)
    last = max(i for i, op in enumerate(plain.ops) if op in g2.attn_ops)
    assert g2._tail_ops == set(plain.ops[last + 1:])


# ---- the router and the zero-computation experts alone -----------------------

N, D, F = 96, 32, 16


def moe_op(held=None, n=N, zero=Z, experts=E, k=K, bias=0.05):
    ff = FFModel(FFConfig(batch_size=n, mesh_shape={"data": 1}))
    x = ff.create_tensor([n, D], name="x")
    return MoE(ff, "moe", [x], experts, F, k, None, expert="swiglu",
               renormalize=False, scoring="softmax", score_bias=bias,
               routed_scaling=6.0, experts_held=held, zero_experts=zero,
               router_f32=True)


def weights(op, seed=0, router_gain=1.0):
    rs = np.random.RandomState(seed)
    p = {w.name: jnp.asarray(rs.randn(*w.shape) * (
        0.05 if w.name == "score_bias" else w.shape[-2] ** -0.5),
        jnp.float32) for w in op.weight_specs()}
    p["router"] = p["router"] * router_gain
    return p


def share(p, first, count):
    return {n: (v[first:first + count] if n in MoE._EXPERT_WEIGHTS else v)
            for n, v in p.items()}


def test_router_selects_from_p_plus_b_and_gates_from_p():
    """p = softmax over all E + Z columns in float32; the top-k runs on
    p + b; the gates are 6 p of the chosen columns, never from p + b and
    never renormalised."""
    op = moe_op()
    p = weights(op, router_gain=3.0)
    # a bias that moves the selection: column 20 (a zero expert) always in
    p["score_bias"] = p["score_bias"].at[20].set(1.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (N, D))
    scores, top_g, top_e = op._route(p, x)
    want = jax.nn.softmax(jnp.dot(x, p["router"],
                                  precision=jax.lax.Precision.HIGHEST), -1)
    np.testing.assert_allclose(scores, want, atol=1e-6)
    assert scores.shape == (N, E + Z) and top_e.shape == (N, K)
    sel = np.asarray(want + p["score_bias"])
    np.testing.assert_array_equal(
        np.sort(np.asarray(top_e), -1),
        np.sort(np.argsort(-sel, -1)[:, :K], -1))
    assert (np.asarray(top_e) == 20).any(axis=-1).all()
    np.testing.assert_allclose(
        top_g, 6.0 * np.take_along_axis(np.asarray(want), np.asarray(top_e),
                                        -1), rtol=1e-6)
    # not renormalised: the gates of a row do not sum to 6
    assert np.abs(np.asarray(top_g).sum(-1) - 6.0).min() > 1e-3


def test_a_token_with_no_real_pick_and_one_with_all_real():
    """Biased so that rows pick zero-computation columns only (output = the
    gates' sum times the row, no expert row anywhere) or real experts only
    (no identity term)."""
    op = moe_op()
    p = weights(op)
    x = jax.random.normal(jax.random.PRNGKey(2), (N, D))
    for zero_only in (True, False):
        bias = jnp.where((jnp.arange(E + Z) >= E) == zero_only, 5.0, 0.0)
        routing, sizes = [], []
        y = op.forward({**p, "score_bias": bias}, [x], routing=routing,
                       group_sizes=sizes)[0]
        scores, top_g, top_e = op._route({**p, "score_bias": bias}, x)
        assert ((np.asarray(top_e) >= E) == zero_only).all()
        held, hit, zero, real = (int(v) for v in routing[0])
        if zero_only:
            assert (held, hit, zero, real) == (0, 0, N * K, 0)
            np.testing.assert_allclose(
                y, jnp.sum(top_g, -1, keepdims=True) * x, atol=1e-5)
        else:
            assert (held, zero, real) == (N * K, 0, N * K)
            assert int(sizes[0].sum()) == N * K
            # what the uncut op without zero columns gives under the same
            # gates: the real experts alone
            dense = sum(
                jnp.where((top_e == e).any(-1, keepdims=True),
                          jnp.sum(jnp.where(top_e == e, top_g, 0), -1,
                                  keepdims=True), 0.0)
                * ((jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e]))
                   @ p["w_down"][e]) for e in range(E))
            np.testing.assert_allclose(y, dense, atol=2e-5)


@pytest.mark.parametrize("training", [False, True])
def test_the_shares_add_up(training):
    """The guide's share test: the four shares' held-expert parts (their
    identity term taken out) plus the identity term counted ONCE equal the
    uncut reference's whole expert layer."""
    whole = moe_op()
    p = weights(whole, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(4), (N, D))
    sizes = dict(SIZES, experts_held=(0, E))
    want = np.asarray(ref.expert_layer(x, p, sizes))
    identity = np.asarray(ref.expert_layer(
        x, share(p, 0, 0), dict(SIZES, experts_held=(0, 0))))
    assert np.abs(identity).max() > 1e-2
    total = np.zeros_like(want)
    for first in range(0, E, 4):
        part = moe_op(held=(first, 4))
        mine = np.asarray(part.forward(share(p, first, 4), [x],
                                       training=training)[0])
        # the share against the reference given the same share
        np.testing.assert_allclose(
            mine, ref.expert_layer(x, share(p, first, 4),
                                   dict(SIZES, experts_held=(first, 4))),
            atol=2e-5, rtol=0)
        total += mine - identity
    np.testing.assert_allclose(total + identity, want, atol=5e-5, rtol=0)
    # and the op that holds every expert IS the whole layer
    np.testing.assert_allclose(whole.forward(p, [x], training=training)[0],
                               want, atol=2e-5, rtol=0)


def test_held_share_with_zero_experts_drops_no_token_past_the_slack(
        monkeypatch):
    """A routing skewed past HELD_ROWS_SLACK of the even share (which is
    N k held / (E + Z)): the grouped passes take as many as the held rows
    need, dead rows give 0 and count nowhere."""
    monkeypatch.setattr(moe_mod, "HELD_ROWS_TILE", 8)
    part, whole = moe_op(held=HELD), moe_op()
    p = weights(whole, seed=5)
    # every row picks the held experts 4..7 first
    p["score_bias"] = jnp.where((jnp.arange(E + Z) >= 4)
                                & (jnp.arange(E + Z) < 7), 5.0, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (N, D))
    mask = jnp.arange(N) < 80
    cap = moe_mod.held_rows_cap(N, K, HELD[1], E + Z)
    assert cap == moe_mod.HELD_ROWS_TILE * -(-int(2.0 * N * K * 4 / (E + Z))
                                             // 8) < N * K
    routing, given = [], []
    got = part.forward(share(p, *HELD), [x], row_mask=mask, routing=routing,
                       expert_rows=given)[0]
    held, hit, zero, real = (int(v) for v in routing[0])
    assert held >= 3 * 80 > cap and zero + real == 80 * K
    assert int(given[0]) == cap * -(-held // cap) > cap
    want = ref.expert_layer(x, share(p, *HELD),
                            dict(SIZES, experts_held=HELD))
    np.testing.assert_allclose(got[:80], want[:80], atol=2e-5, rtol=0)
    assert not np.asarray(got[80:]).any()


def test_flops_and_cap_count_real_experts_over_the_routers_width():
    with_zero, without = moe_op(held=HELD), moe_op(held=HELD, zero=0)
    assert with_zero.flops() * (E + Z) == without.flops() * E
    assert moe_mod.held_rows_cap(4096, 12, 16, 768) \
        == 256 * -(-int(2.0 * 4096 * 12 * 16 / 768) // 256)
    with pytest.raises(ValueError, match="zero_experts"):
        MoE(with_zero.model, "bad", with_zero.inputs, E, F, K, 1.25,
            zero_experts=4)


def test_olmoe_softmax_router_is_the_compute_dtype_matmul_it_was():
    """`router_f32` is LongCat's; the default softmax router (OLMoE,
    `moe-chat-steady`) still multiplies in the compute dtype: in bf16 its
    scores are the bf16 product's, not the float32 one's."""
    ff = FFModel(FFConfig(batch_size=N, mesh_shape={"data": 1}))
    x = ff.create_tensor([N, D], name="x")
    op = MoE(ff, "moe", [x], E, F, K, None, expert="swiglu",
             renormalize=False)
    assert not op.router_f32 and op.router_width == E
    p = {"router": jax.random.normal(jax.random.PRNGKey(0), (D, E))}
    t = jax.random.normal(jax.random.PRNGKey(1), (N, D)).astype(jnp.bfloat16)
    scores = op._route(p, t)[0]
    want = jax.nn.softmax((t @ p["router"].astype(jnp.bfloat16))
                          .astype(jnp.float32), -1)
    np.testing.assert_array_equal(scores, want)


# ---- fit() -------------------------------------------------------------------

def test_fit_takes_a_step_and_the_identity_term_has_its_gradient():
    """One `fit()` step through the shortcut and the zero-computation
    experts: finite loss, weights moved; and the identity term's gradient
    with respect to the op's input and the router against finite
    differences of the reference's expert layer."""
    ff = build(batch=2, seq=16, optimizer=SGDOptimizer(lr=0.05), layers=1)
    rs = np.random.RandomState(0)
    x = rs.randint(1, VOCAB, (4, 16)).astype(np.int32)
    y = rs.randint(1, VOCAB, (4, 16)).astype(np.int32)
    before = np.asarray(ff.params["moe_0"]["router"]).copy()
    SingleDataLoader(ff, ff.token_tensor, x)
    SingleDataLoader(ff, ff.label_tensor, y)
    ff.fit(epochs=1, verbose=False)
    assert np.isfinite(float(ff._last_loss))
    after = np.asarray(ff.params["moe_0"]["router"])
    assert np.isfinite(after).all() and np.abs(after - before).max() > 0

    op = moe_op(held=(0, 0 + 4), n=8)
    p = weights(op, seed=7)
    xs = jax.random.normal(jax.random.PRNGKey(8), (8, D))
    sizes = dict(SIZES, experts_held=(0, 4))

    def program(router, xs):
        return jnp.sum(jnp.sin(op.forward({**p, "router": router}, [xs],
                                          training=True)[0]))

    def reference(router, xs):
        return jnp.sum(jnp.sin(ref.expert_layer(
            xs, {**p, "router": router}, sizes)))

    got = jax.grad(program, argnums=(0, 1))(p["router"], xs)
    want = jax.grad(reference, argnums=(0, 1))(p["router"], xs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, rtol=1e-4)
    # the zero columns of the router get gradient: the identity term's
    assert np.abs(np.asarray(got[0])[:, E:]).max() > 1e-4


def test_reference_copy_is_the_benchmarks():
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "reference_longcat_flash.py")) as a, \
            open(os.path.join(here, "..", "benchmark", "reference",
                              "longcat_flash.py")) as b:
        assert a.read() == b.read()


def test_the_search_prices_the_shortcut_graph():
    """`compile()` with a search budget over a data x model mesh: the
    shortcut is one more long edge of the graph, priced without error, and
    the searched program still gives the reference's logits."""
    cfg = FFConfig(batch_size=4, mesh_shape={"data": 2, "model": 2}, seed=3,
                   search_budget=20)
    ff = FFModel(cfg)
    _, logits = longcat_flash_lm(
        ff, 4, seq_len=32, hidden=64, layers=1, heads=4, q_lora_rank=16,
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, ffn_hidden=128, num_experts=E, zero_experts=Z,
        experts_per_token=K, expert_hidden=32, experts_held=HELD,
        score_bias_std=0.02, vocab_size=VOCAB)
    ff.compile(final_tensor=logits)
    assert ff._search_summary["predicted_step_s"] > 0
    toks = np.random.RandomState(0).randint(1, VOCAB, (4, 32)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    want = np.asarray(ref.forward(ff.params, toks[0],
                                  dict(SIZES, num_layers=1)))
    np.testing.assert_allclose(got[0], want, atol=LOGIT_ATOL, rtol=0)
