"""OLMoE through the normal path (models/olmoe.py -> compile() -> predict /
fit's gradient / make_serving_engine) against the plain reference
(tests/reference_olmoe.py, the same text as benchmark/reference/olmoe.py),
at a tiny size in float32 on the CPU; and the two mechanisms it forced,
each alone: the dropless MoE op (ops/moe.py, `capacity_factor=None`) and
QK-norm (ops/attention.py, `qk_norm=True`).

Logits are compared, never tokens: with random weights the largest logit
changes on rounding. Every tolerance stands beside its reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_olmoe as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.models.olmoe import olmoe_lm
from flexflow_tpu.ops.attention import MultiHeadAttention, _apply_rope
from flexflow_tpu.runtime import telemetry

VOCAB = 97
SEQ = 32
LAYERS, EXPERTS, TOP_K = 2, 8, 2
SIZES = dict(num_hidden_layers=LAYERS, rope_theta=10000.0,
             rms_norm_eps=1e-5, num_experts_per_tok=TOP_K,
             norm_topk_prob=False)

# float32 program against the float32 reference: both round every matmul
# to 2^-24 relative, in different orders (grouped against dense, fused
# against plain), and the logits are of order 4. Measured 3e-6; bf16
# compute (2^-8 per product) lands near 3e-2 and fails by three orders.
LOGIT_ATOL = 5e-5


def build(batch=2, seq=SEQ, seed=3, expert_hidden=64):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = olmoe_lm(ff, batch, seq_len=seq, hidden=128, layers=LAYERS,
                         heads=4, kv_heads=4, num_experts=EXPERTS,
                         experts_per_token=TOP_K,
                         expert_hidden=expert_hidden, vocab_size=VOCAB)
    ff.compile(final_tensor=logits)
    # norm scales initialise to one, where a missing or misplaced scale
    # would pass: spread them
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


def test_graph_is_llama_block_with_the_two_changes(ff):
    names = {op.name for op in ff.ops}
    assert {"attn_0", "ln1_0", "ln2_0", "moe_0", "moe_1", "ln_f",
            "lm_head"} <= names
    assert not any(n.startswith("ffn_") for n in names)
    moe, attn = ff.get_op_by_name("moe_1"), ff.get_op_by_name("attn_1")
    assert moe.dropless and moe.capacity is None
    assert (moe.expert, moe.renormalize, moe.k) == ("swiglu", False, TOP_K)
    assert set(ff.params["moe_1"]) == {"router", "w_gate", "w_up", "w_down"}
    assert ff.params["moe_1"]["w_down"].shape == (EXPERTS, 64, 128)
    assert attn.qk_norm and attn.eps == 1e-5
    assert ff.params["attn_1"]["q_norm"].shape == (128,)
    assert ff.get_op_by_name("ln2_1").eps == 1e-5


def test_predict_logits_match_reference(ff):
    toks = np.random.RandomState(0).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, toks[b], SIZES))
        np.testing.assert_allclose(got[b], want, atol=LOGIT_ATOL, rtol=0)


def test_reference_gates_are_not_renormalised(ff):
    """The error a wrong gate weight makes is of order 1, far outside the
    tolerance: the reference with renormalised gates must NOT match."""
    toks = np.random.RandomState(1).randint(1, VOCAB, (SEQ,)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": np.stack([toks, toks])}))[0]
    wrong = np.asarray(ref.forward(ff.params, toks,
                                   {**SIZES, "norm_topk_prob": True}))
    assert np.abs(got - wrong).max() > 100 * LOGIT_ATOL


def test_generate_scores_match_reference(ff):
    """Prefill + decode through the contiguous KV cache: the model's
    log-probability of each emitted token against the reference's full
    forward over prompt + emitted tokens."""
    prompt = np.random.RandomState(2).randint(1, VOCAB, (2, 7)) \
        .astype(np.int32)
    out, scores = ff.generate(prompt, max_new_tokens=9, return_scores=True)
    for b in range(2):
        logp = jax.nn.log_softmax(ref.forward(ff.params, out[b], SIZES))
        want = [float(logp[6 + j, out[b, 7 + j]]) for j in range(9)]
        # a log-probability carries the logit's error twice (the logit and
        # the normaliser)
        np.testing.assert_allclose(scores[b], want, atol=2 * LOGIT_ATOL,
                                   rtol=0)


@pytest.fixture(scope="module")
def served(ff):
    """Three requests through a 4-slot engine (so a free slot sits beside
    live ones in every decode dispatch), page size 8, the 40-token prompt
    prefilled in two chunks of 32, 24 tokens decoded in chunks of 4."""
    telemetry.set_enabled(True)
    since = len(telemetry.tracer().events())
    eng = ff.make_serving_engine(serve_slots=4, kv_page_size=8,
                                 max_seq_len=128, prefill_chunk=32,
                                 decode_chunk=4, prefix_cache=False)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, VOCAB, (n,)).astype(np.int32)
               for n in (40, 11, 23)]
    reqs = eng.run(prompts, max_new_tokens=24)
    events = [e for e in telemetry.tracer().events()[since:]
              if e["pid"] == eng._tm_track]
    return eng, reqs, events


def test_serving_engine_emits_the_reference_argmax(ff, served):
    """Prefill (whole and chunked), the paged cache and decode against the
    reference's full forward, at the level of logits: every emitted token's
    reference logit lies within the tolerance of that position's maximum
    (a token read from a wrong page, position or mask is a draw from the
    whole distribution, whose spread is of order 1)."""
    _, reqs, _ = served
    assert [r.state for r in reqs] == ["done"] * 3
    for r in reqs:
        full = np.asarray(r.output, np.int32)
        assert full.size == r.prompt.size + 24
        logits = np.asarray(ref.forward(ff.params, full, SIZES))
        rows = logits[r.prompt.size - 1:full.size - 1]
        margin = rows.max(axis=-1) - rows[np.arange(24), full[r.prompt.size:]]
        # both sides hold the logit to LOGIT_ATOL, so a near-tie can flip
        # within twice that
        assert margin.max() <= 2 * LOGIT_ATOL, margin.max()


def test_interleaved_admission_stays_on_the_reference(ff):
    """Chunk-interleaved admission (two chunks of 32 spread over ticks, a
    neighbour decoding between them) gives its chunks no live-row mask, so
    a prompt's padding rows do route there: wasted work, but a row's
    output depends on no other row, so the emitted tokens still sit on the
    reference's maximum."""
    eng = ff.make_serving_engine(serve_slots=4, kv_page_size=8,
                                 max_seq_len=128, prefill_chunk=32,
                                 prefill_interleave_chunks=1,
                                 decode_chunk=4, prefix_cache=False)
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, VOCAB, (n,)).astype(np.int32) for n in (13, 40)]
    reqs = eng.run(prompts, max_new_tokens=8)
    assert [r.state for r in reqs] == ["done"] * 2
    assert eng.stats()["prefill_chunks_interleaved"] >= 2
    for r in reqs:
        full = np.asarray(r.output, np.int32)
        logits = np.asarray(ref.forward(ff.params, full, SIZES))
        rows = logits[r.prompt.size - 1:full.size - 1]
        margin = rows.max(axis=-1) - rows[np.arange(8), full[r.prompt.size:]]
        assert margin.max() <= 2 * LOGIT_ATOL, margin.max()


def test_serving_routing_counters_count_live_rows_only(served):
    """The decode program counts its routing on the device: a dispatch of
    s live slots and n steps makes exactly s x n x top_k x layers
    assignments (the free slot routes nowhere), and hits at most that many
    experts; the prefill span carries its own count."""
    eng, _, events = served
    ends = {e["name"]: [] for e in events}
    for e in events:
        ends[e["name"]].append(e.get("args", {}))
    disp, rec = ends["decode_dispatch"], ends["record_tokens"]
    assert len(disp) == len(rec) > 0
    total = hit = 0
    for d, r in zip(disp, rec):
        want = d["slots"] * d["k"] * TOP_K * LAYERS
        assert r["assignments"] == want
        assert d["k"] * LAYERS <= r["experts_hit"] <= min(
            want, d["k"] * LAYERS * EXPERTS)
        total += want
        hit += r["experts_hit"]
    st = eng.stats()
    assert (st["moe_assignments"], st["moe_experts_hit"]) == (total, hit)
    # a cold whole-prompt prefill narrows to the last position after the
    # last attention op, so the last layer's experts see one row; the
    # chunked one runs its last layer only in the one-row gather pass
    by_len = {a["prompt_tokens"]: a["assignments"] for a in ends["prefill"]}
    assert by_len[11] == (11 * (LAYERS - 1) + 1) * TOP_K
    assert by_len[40] == (40 * (LAYERS - 1) + LAYERS) * TOP_K


def test_streamed_dispatches_count_what_the_programs_took(monkeypatch):
    """With the backend read as a TPU (the kernel itself interprets here)
    the decode program (4 rows) and the 16- and 32-token prefills take the
    expert-stream kernel in every MoE call; the 256-token prefill runs its
    first layer over 256 rows through ragged_dot (its last layer sees one
    row and streams), so its dispatch is not a streamed one. The spans say
    it per dispatch, stats() sums it, and the tokens are still the
    reference's argmax."""
    from flexflow_tpu.ops import moe as moe_mod

    monkeypatch.setattr(moe_mod, "_backend", lambda: "tpu")
    wide = build(expert_hidden=128)     # a width the kernel can tile
    telemetry.set_enabled(True)
    since = len(telemetry.tracer().events())
    eng = wide.make_serving_engine(serve_slots=4, kv_page_size=8,
                                   max_seq_len=320, decode_chunk=4,
                                   prefix_cache=False)
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, VOCAB, (n,)).astype(np.int32)
               for n in (11, 150, 23)]
    reqs = eng.run(prompts, max_new_tokens=8)
    assert [r.state for r in reqs] == ["done"] * 3
    events = [e for e in telemetry.tracer().events()[since:]
              if e["pid"] == eng._tm_track]
    by_len = {e["args"]["prompt_tokens"]: e["args"]["moe_streamed"]
              for e in events if e["name"] == "prefill"}
    assert by_len == {11: 1, 23: 1, 150: 0}
    disp = [e["args"] for e in events if e["name"] == "decode_dispatch"]
    assert disp and all(d["moe_streamed"] == 1 for d in disp)
    assert eng.stats()["moe_streamed_dispatches"] == len(disp) + 2
    # each program's own list, filled as it was traced: two layers a walk,
    # the 256-token prefill's first layer over 256 rows, its last over one
    assert eng._moe_took == {
        ("decode", 4): ["streamed"] * 2,
        ("prefill", 16, 2, 0): ["streamed"] * 2,
        ("prefill", 32, 4, 0): ["streamed"] * 2,
        ("prefill", 256, 32, 0): ["grouped", "streamed"]}
    for r in reqs:
        full = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        logits = np.asarray(ref.forward(wide.params, full, SIZES))
        rows = logits[r.prompt.size - 1:-1]
        margin = rows.max(axis=-1) - rows[np.arange(8), full[r.prompt.size:]]
        assert margin.max() <= 2 * LOGIT_ATOL, margin.max()


def test_grouped_dispatches_say_so_on_this_host(served):
    """The same engine on a TPU-less host: every program keeps ragged_dot,
    the spans say 0 and nothing is counted."""
    eng, _, events = served
    took = [e["args"]["moe_streamed"] for e in events
            if e["name"] in ("decode_dispatch", "prefill")]
    assert took and not any(took)
    assert eng.stats()["moe_streamed_dispatches"] == 0


def test_dense_model_programs_carry_no_routing_output():
    """A model without a dropless MoE op traces the serve programs it
    traced before: three outputs, no counter moved."""
    cfg = FFConfig(batch_size=1, mesh_shape={"data": 1})
    dense = FFModel(cfg)
    _, logits = llama_lm(dense, 1, seq_len=16, hidden=32, layers=1, heads=2,
                         vocab_size=VOCAB)
    dense.compile(final_tensor=logits)
    eng = dense.make_serving_engine(serve_slots=2, kv_page_size=8,
                                    max_seq_len=32)
    assert eng.gen.dropless_moe_ops == []
    eng.run([np.arange(1, 6, dtype=np.int32)], max_new_tokens=3)
    out = jax.eval_shape(
        eng._build_decode(2), eng.gen._params(), dense.bn_state, eng.kv.pool,
        eng.page_tables, eng.last_tok, *eng._slot_decode_state()[:2],
        eng.row_len, eng.prompt_pad, eng._slot_decode_state()[2],
        eng.poison, eng.temps, eng.top_ps, eng.top_ks, eng.seeds,
        eng.emitted.copy(), None, None)
    assert len(out) == 3
    st = eng.stats()
    assert st["moe_assignments"] == st["moe_experts_hit"] == 0
    assert st["moe_streamed_dispatches"] == 0 and eng._moe_took == {}


WRT = [("moe_0", "router"), ("moe_1", "w_gate"), ("moe_1", "w_up"),
       ("moe_1", "w_down"), ("attn_0", "q_norm"), ("attn_1", "k_norm")]


def test_gradient_through_dropless_op_matches_reference(ff):
    """jax.grad of the mean next-token cross-entropy through the program's
    forward (the dropless op's sort, grouped matmuls and unsort; QK-norm)
    against the reference's, for the router, one expert's three matrices
    and the QK-norm scales."""
    rs = np.random.RandomState(7)
    toks = rs.randint(1, VOCAB, (2, SEQ)).astype(np.int32)
    labels = rs.randint(0, VOCAB, (2, SEQ)).astype(np.int32)
    fwd = ff.executor.make_forward([ff._final_tensor])

    def xent(logits, y):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    def program_loss(params):
        return xent(fwd(params, ff.bn_state, {"input": toks})[0], labels)

    def reference_loss(params):
        return xent(jnp.stack([ref.forward(params, toks[b], SIZES)
                               for b in range(2)]), labels)

    params = jax.tree.map(jnp.asarray, ff.params)
    got = jax.grad(program_loss)(params)
    want = jax.grad(reference_loss)(params)
    for op, w in WRT:
        g, r = np.asarray(got[op][w]), np.asarray(want[op][w])
        if w.startswith("w_"):      # ONE expert: the most loaded
            e = int(np.abs(r).sum(axis=(1, 2)).argmax())
            g, r = g[e], r[e]
        assert np.abs(r).max() > 0
        # float32 both sides, sums of 64 rows' products in another order:
        # relative to the gradient's largest entry. bf16 would give 1e-2.
        np.testing.assert_allclose(g, r, rtol=0,
                                   atol=2e-4 * np.abs(r).max(),
                                   err_msg=f"{op}/{w}")


def test_olmoe_lm_trains_through_fit():
    """fit() runs the same dropless lowering (and folds the auxiliary
    load-balancing loss into the training loss): a constant successor
    mapping is learnable in a few epochs on a data=2 mesh."""
    from flexflow_tpu import (LossType, MetricsType, SGDOptimizer,
                              SingleDataLoader)

    vocab, seq, batch = 32, 8, 8
    ff = FFModel(FFConfig(batch_size=batch, epochs=12,
                          mesh_shape={"data": 2}))
    tokens, logits = olmoe_lm(ff, batch, seq_len=seq, hidden=32, layers=1,
                              heads=2, kv_heads=2, num_experts=4,
                              experts_per_token=2, expert_hidden=32,
                              vocab_size=vocab)
    ff.compile(SGDOptimizer(lr=0.5),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY,
                MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    router0 = np.asarray(ff.params["moe_0"]["router"]).copy()
    x = np.random.RandomState(0).randint(0, vocab, (64, seq)) \
        .astype(np.int32)
    SingleDataLoader(ff, tokens, x)
    SingleDataLoader(ff, ff.label_tensor,
                     ((x + 1) % vocab)[..., None].astype(np.int32))
    perf = ff.fit(verbose=False)
    assert perf.accuracy > 0.9, perf.accuracy
    # the router trained (gates and the auxiliary loss reach it)
    assert np.abs(np.asarray(ff.params["moe_0"]["router"])
                  - router0).max() > 1e-4


# ---- the dropless op alone -------------------------------------------------

def moe_model(expert, renormalize, n=12, d=16, f=24, e=6, k=3):
    cfg = FFConfig(batch_size=n, mesh_shape={"data": 1}, seed=1)
    ff = FFModel(cfg)
    x = ff.create_tensor([n, d], name="x")
    out = ff.moe(x, num_experts=e, hidden_dim=f, k=k, capacity_factor=None,
                 expert=expert, renormalize=renormalize, name="moe")
    ff.compile(final_tensor=out)
    return ff, ff.get_op_by_name("moe")


def plain_moe(p, x, k, expert, renormalize):
    """Every expert on every row, float64 numpy."""
    p = {n: np.asarray(v, np.float64) for n, v in p.items()}
    x = np.asarray(x, np.float64)
    logits = x @ p["router"]
    gates = np.exp(logits - logits.max(-1, keepdims=True))
    gates /= gates.sum(-1, keepdims=True)
    top = np.argsort(-gates, axis=-1, kind="stable")[:, :k]
    y = np.zeros_like(x)
    for n in range(x.shape[0]):
        g = gates[n, top[n]]
        if renormalize:
            g = g / g.sum()
        for w, e in zip(g, top[n]):
            if expert == "swiglu":
                a = x[n] @ p["w_gate"][e]
                h = a / (1 + np.exp(-a)) * (x[n] @ p["w_up"][e])
                y[n] += w * (h @ p["w_down"][e])
            else:
                a = x[n] @ p["w_in"][e]
                h = np.asarray(jax.nn.gelu(jnp.asarray(a, jnp.float32)),
                               np.float64)
                y[n] += w * (h @ p["w_out"][e])
    return y


@pytest.mark.parametrize("renormalize", [True, False])
@pytest.mark.parametrize("expert", ["gelu", "swiglu"])
def test_dropless_op_matches_plain_loop(expert, renormalize):
    ff, op = moe_model(expert, renormalize)
    x = np.random.RandomState(0).randn(12, 16).astype(np.float32)
    y, aux = op.forward(ff.params["moe"], [jnp.asarray(x)])
    want = plain_moe(ff.params["moe"], x, op.k, expert, renormalize)
    # float32 against float64, outputs of order 0.1
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-6, rtol=0)
    assert np.isfinite(float(aux))


def test_dropless_op_reads_no_capacity_and_no_dispatch():
    ff, op = moe_model("swiglu", False)
    assert op.capacity is None and op.dropless
    with_dense = FFModel(FFConfig(batch_size=12, mesh_shape={"data": 1}))
    x = with_dense.create_tensor([12, 16], name="x")
    with_dense.moe(x, 6, 24, k=3, capacity_factor=None, dispatch="dense",
                   expert="swiglu", renormalize=False, name="moe")
    a = jax.make_jaxpr(lambda p, v: op.forward(p, [v]))(
        ff.params["moe"], jnp.zeros((12, 16)))
    b = jax.make_jaxpr(
        lambda p, v: with_dense.get_op_by_name("moe").forward(p, [v]))(
        ff.params["moe"], jnp.zeros((12, 16)))
    assert str(a) == str(b)


def test_dropless_rows_independent_of_other_rows_and_of_masked_rows():
    ff, op = moe_model("swiglu", False)
    p = ff.params["moe"]
    rs = np.random.RandomState(3)
    x = rs.randn(12, 16).astype(np.float32)
    base = np.asarray(op.forward(p, [jnp.asarray(x)])[0])
    # other rows replaced: rows 0-3 keep their output. Not bitwise: a
    # row's place in its expert's group moves, and with it the order in
    # which a blocked matmul may sum; float32, outputs of order 0.1
    x2 = x.copy()
    x2[4:] = rs.randn(8, 16)
    other = np.asarray(op.forward(p, [jnp.asarray(x2)])[0])
    np.testing.assert_allclose(other[:4], base[:4], atol=1e-6, rtol=0)
    # masked rows: output exactly 0, group size 0, live rows unchanged
    mask = np.array([True] * 4 + [False] * 5 + [True] * 3)
    routing = []
    masked = np.asarray(op.forward(p, [jnp.asarray(x2)],
                                   row_mask=jnp.asarray(mask),
                                   routing=routing)[0])
    assert np.all(masked[~mask] == 0)
    np.testing.assert_allclose(masked[mask], other[mask], atol=1e-6, rtol=0)
    assigned, hit = (int(v) for v in routing[0])
    assert assigned == 7 * op.k and 1 <= hit <= 6
    # a batch with no live row at all streams nothing and yields zeros
    routing = []
    none = op.forward(p, [jnp.asarray(x)], row_mask=jnp.zeros(12, bool),
                      routing=routing)[0]
    assert np.all(np.asarray(none) == 0)
    assert [int(v) for v in routing[0]] == [0, 0]


def test_moe_flops_count_three_matmuls_for_swiglu():
    _, glu = moe_model("swiglu", False)
    _, gelu = moe_model("gelu", True)
    assert glu.flops() == 2 * 3 * 12 * 3 * 16 * 24
    assert gelu.flops() == 2 * 2 * 12 * 3 * 16 * 24


def test_moe_weight_partition_shards_every_expert_matrix():
    _, op = moe_model("swiglu", False)
    from flexflow_tpu.parallel.pconfig import EXPERT

    part = op.weight_partition({"expert": EXPERT})
    assert part["router"] == jax.sharding.PartitionSpec(None, None)
    for w in ("w_gate", "w_up", "w_down"):
        assert part[w] == jax.sharding.PartitionSpec("expert", None, None)


# ---- QK-norm alone -----------------------------------------------------------

def attention_op(qk_norm, cls=MultiHeadAttention):
    ff = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=2))
    x = ff.create_tensor([2, 8, 32], name="x")
    op = cls(ff, "attn", [x, x, x], 32, 4, causal=True, bias=False,
             num_kv_heads=2, rope=True, qk_norm=qk_norm, eps=1e-5)
    return op


class OldAttention(MultiHeadAttention):
    """`_project_qkv` as it stood before QK-norm existed."""

    def _project_qkv(self, params, q, k, v, rope_offset=0):
        qh = jnp.einsum("bsd,dhk->bshk", q, params["wq"])
        kh = jnp.einsum("bsd,dhk->bshk", k, params["wk"])
        vh = jnp.einsum("bsd,dhk->bshk", v, params["wv"])
        if self.bias:
            qh = qh + params["bias_q"]
            kh = kh + params["bias_k"]
            vh = vh + params["bias_v"]
        if self.rope:
            qh = _apply_rope(qh, self.rope_theta, rope_offset)
            kh = _apply_rope(kh, self.rope_theta, rope_offset)
        return qh, kh, vh


def attn_params(op, rs):
    return {w.name: jnp.asarray(rs.randn(*w.shape).astype(np.float32) * 0.2
                                + (1.0 if "norm" in w.name else 0.0))
            for w in op.weight_specs()}


def test_qk_norm_off_is_bitwise_the_old_attention():
    new, old = attention_op(False), attention_op(False, OldAttention)
    assert [w.name for w in new.weight_specs()] == ["wq", "wk", "wv", "wo"]
    rs = np.random.RandomState(4)
    p = attn_params(new, rs)
    x = jnp.asarray(rs.randn(2, 8, 32).astype(np.float32))
    run = lambda op: (lambda p, x: op.forward(p, [x, x, x])[0])  # noqa: E731
    assert str(jax.make_jaxpr(run(new))(p, x)) \
        == str(jax.make_jaxpr(run(old))(p, x))
    np.testing.assert_array_equal(np.asarray(run(new)(p, x)),
                                  np.asarray(run(old)(p, x)))


def test_qk_norm_normalises_the_whole_projection_before_rotary():
    op = attention_op(True)
    assert [w.name for w in op.weight_specs()] == \
        ["wq", "wk", "wv", "wo", "q_norm", "k_norm"]
    assert op.weight_specs()[-1].shape == (2 * 8,)
    rs = np.random.RandomState(6)
    p = attn_params(op, rs)
    x = jnp.asarray(rs.randn(2, 8, 32).astype(np.float32))
    qh, kh, vh = op._project_qkv(p, x, x, x)

    def want(w, scale, heads):
        flat = np.einsum("bsd,dhk->bshk", np.asarray(x, np.float64),
                         np.asarray(w, np.float64)).reshape(2, 8, -1)
        flat = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(scale, np.float64)
        return np.asarray(_apply_rope(
            jnp.asarray(flat.reshape(2, 8, heads, 8), jnp.float32),
            op.rope_theta))

    # float32 against float64 before the (float32) rotation
    np.testing.assert_allclose(qh, want(p["wq"], p["q_norm"], 4), atol=2e-6)
    np.testing.assert_allclose(kh, want(p["wk"], p["k_norm"], 2), atol=2e-6)
    np.testing.assert_array_equal(
        vh, jnp.einsum("bsd,dhk->bshk", x, p["wv"]))


def test_llama_lm_takes_rms_norm_eps():
    def eps_of(**kw):
        ff = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
        llama_lm(ff, 1, seq_len=8, hidden=32, layers=1, heads=2,
                 vocab_size=VOCAB, **kw)
        return {ff.get_op_by_name(n).eps for n in ("ln1_0", "ln2_0", "ln_f")}

    assert eps_of() == {1e-6}           # today's program
    assert eps_of(rms_norm_eps=1e-5) == {1e-5}


# ---- the benchmark's yardstick for the op (benchmark/moe_trace.py) --------

_CALL = ('%{} = bf16[256,1024]{{1,0}} custom-call(s32[64]{{0}} %gs, '
         'bf16[256,2048]{{1,0}} %x, bf16[64,2048,1024]{{2,1,0}} %w), '
         'custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("name, is_moe", [
    (_CALL.format("ragged-dot-none.4"), True),      # XLA's kernel, today
    (_CALL.format("ragged-dot-metadata"), True),    # its group layout
    (_CALL.format("moe_3.7"), True),                # a Pallas kernel under
    (_CALL.format("jvp_moe_3_.7"), True),           # the op's jax scope
    (_CALL.format("attn_3.11"), False),             # the paged kernel
    ("%moe_3.9 = bf16[256,64] fusion(bf16[256,2048] %x)", False),
    ("%fusion.2 = bf16[256,2048] fusion(bf16[256,2048] %ragged-dot-none.2)",
     False),
])
def test_trace_reader_books_a_mosaic_call_to_the_moe_op_by_either_name(
        name, is_moe):
    """`moe_device_share` and `moe_expert_hbm_share` find the op's kernels
    by XLA's `ragged-dot` name or by the `moe_<i>` scope a Pallas call
    keeps, so the yardstick survives a swap of the kernel."""
    from benchmark import moe_trace

    assert moe_trace.is_grouped_matmul(name) is is_moe


@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_cell_replays_the_traffic_files_arrangement_whatever_the_seed(seed):
    """`moe-chat-steady`'s step follows the live rows, so the ORDER of the
    window's lengths moved its judged latency by more than its bound: the
    traffic file names the arrangement and `--seed` draws the tokens."""
    from benchmark import spec
    from benchmark.generators import open_loop_serving as base

    gen = spec.load_module("generators", "open_loop_serving_ref")
    traffic = spec.load_traffic("moe-chat-steady")
    want = base.generate(traffic, traffic["arrangement_seed"], 51.0, 50304)
    got = gen.generate(traffic, seed, 51.0, 50304)
    assert np.array_equal(got.due, want.due)
    assert np.array_equal(got.max_new, want.max_new)
    assert [p.size for p in got.prompts] == [p.size for p in want.prompts]
    again = gen.generate(traffic, seed, 51.0, 50304)
    assert all(np.array_equal(x, y)
               for x, y in zip(got.prompts, again.prompts))
    assert not any(np.array_equal(x, y)
                   for x, y in zip(got.prompts, want.prompts) if x.size >= 64)
