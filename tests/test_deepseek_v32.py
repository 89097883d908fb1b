"""DeepSeek-V3.2's decoder through the normal path (models/deepseek_v32.py ->
compile() -> predict / generate / make_serving_engine) against the plain
reference (tests/reference_deepseek_v32.py, the same text as
benchmark/reference/deepseek_v32.py), at a tiny size that keeps every ratio
(4 heads, latent 32 + rope 16, 4 index heads of 32, index_topk 16 against
contexts of 8 to 96, 16 experts in 4 groups of which 2 are kept, top-4, 4
held), in float32 on the CPU; and the mechanisms it forced, each alone: the
latent attention op and its selection (ops/mla.py), the two decode kernels
(ops/pallas_kernels.py, interpret mode), the router's form and the held
experts (ops/moe.py).

Logits are compared, never tokens: with random weights the largest logit
changes on rounding. Every tolerance stands beside its reason.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_deepseek_v32 as ref
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.deepseek_v32 import YARN, deepseek_v32_lm
from flexflow_tpu.ops import mla
from flexflow_tpu.ops.mla import LatentAttention
from flexflow_tpu.ops.moe import MoE

VOCAB, SEQ, TOPK = 128, 96, 16
HELD = (4, 4)
SIZES = dict(num_hidden_layers=3, first_k_dense_replace=1, rms_norm_eps=1e-6,
             rope_theta=1e4, rope_scaling=YARN, qk_nope_head_dim=32,
             qk_rope_head_dim=16, kv_lora_rank=32, index_topk=TOPK,
             num_experts_per_tok=4, n_group=4, topk_group=2,
             routed_scaling_factor=2.5, norm_topk_prob=True,
             experts_held=HELD)

# float32 program against the float32 reference: both round every matmul to
# 2^-24 relative in different orders (absorbed against expanded, grouped
# against dense), logits of order 1. Measured 4e-6; bf16 compute lands near
# 1e-2.
LOGIT_ATOL = 5e-5


def build(batch=2, seq=SEQ, seed=3, gain=1.0, held=HELD, topk=TOPK):
    cfg = FFConfig(batch_size=batch, mesh_shape={"data": 1}, seed=seed)
    ff = FFModel(cfg)
    _, logits = deepseek_v32_lm(
        ff, batch, seq_len=seq, hidden=64, layers=3, heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, index_n_heads=4, index_head_dim=32, index_topk=topk,
        dense_layers=1, ffn_hidden=128, num_experts=16, experts_per_token=4,
        expert_hidden=32, n_group=4, topk_group=2, experts_held=held,
        score_bias_std=0.05, uq_init_gain=gain, vocab_size=VOCAB)
    ff.compile(final_tensor=logits)
    # norm scales initialise to one and the index key's bias to zero, where
    # a missing or misplaced one would pass: spread them
    rs = np.random.RandomState(seed)
    for op, ws in ff.params.items():
        for w, v in ws.items():
            if w in ("scale", "q_norm", "kv_norm", "ik_norm_scale"):
                ff.set_weights(op, w, (1 + 0.3 * rs.randn(*v.shape))
                               .astype(np.float32))
            elif w == "ik_norm_bias":
                ff.set_weights(op, w, (0.3 * rs.randn(*v.shape))
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


def test_graph_has_both_kinds_of_block(ff):
    names = {op.name for op in ff.ops}
    assert {"attn_0", "ffn_gate_0", "moe_1", "moe_2", "ln_f"} <= names
    assert "moe_0" not in names and "ffn_gate_1" not in names
    attn, moe = ff.get_op_by_name("attn_1"), ff.get_op_by_name("moe_1")
    assert isinstance(attn, LatentAttention) and attn.kv_cache_protocol
    assert attn.lat_width == 128 and attn.index_topk == TOPK
    assert (moe.scoring, moe.n_group, moe.topk_group, moe.routed_scaling) \
        == ("sigmoid", 4, 2, 2.5)
    assert (moe.held_first, moe.held_count) == HELD
    assert ff.params["moe_1"]["router"].shape == (64, 16)
    assert ff.params["moe_1"]["w_gate"].shape == (4, 64, 32)
    assert ff.params["moe_1"]["shared_down"].shape == (32, 64)
    assert float(jnp.std(ff.params["moe_1"]["score_bias"])) > 0.01


def test_predict_logits_match_reference(ff):
    """The EXPANDED program (forward) against the reference, over a context
    six times index_topk: the selection is real for most rows."""
    toks = np.random.RandomState(0).randint(1, VOCAB, (2, SEQ)) \
        .astype(np.int32)
    got = np.asarray(ff.predict({"input": toks}))
    for b in range(2):
        want = np.asarray(ref.forward(ff.params, toks[b], SIZES))
        np.testing.assert_allclose(got[b], want, atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("rows", [None, (40, 70), (95, 96)])
def test_reference_does_not_depend_on_its_blocks_or_rows(ff, monkeypatch,
                                                         rows):
    """Three spans of query rows with a key bound each, blocks of 16 rows,
    and a last layer that computes the asked rows' blocks only: the same
    logits as one span of whole blocks."""
    toks = np.random.RandomState(5).randint(1, VOCAB, (SEQ,)).astype(np.int32)
    want = np.asarray(ref.forward(ff.params, toks, SIZES))
    for name, value in (("QUERY_BLOCK", 16), ("KEY_BLOCK", 32),
                        ("ROW_BLOCK", 32)):
        monkeypatch.setattr(ref, name, value)
    trace = {}
    got = np.asarray(ref.forward(ff.params, toks, SIZES, rows=rows,
                                 trace=trace))
    lo, hi = rows or (0, SEQ)
    np.testing.assert_allclose(got, want[lo:hi], atol=1e-5, rtol=0)
    held = [len(t) for t in trace["selected"]]
    assert held == [SEQ, SEQ, -(-hi // 16) * 16 - lo // 16 * 16]


def test_generate_scores_match_reference(ff):
    """Prefill + decode through the contiguous latent cache (the ABSORBED
    form), across index_topk: 10 prompt tokens, 20 emitted."""
    prompt = np.random.RandomState(2).randint(1, VOCAB, (2, 10)) \
        .astype(np.int32)
    out, scores = ff.generate(prompt, max_new_tokens=20, return_scores=True)
    for b in range(2):
        logp = jax.nn.log_softmax(ref.forward(ff.params, out[b], SIZES))
        want = [float(logp[9 + j, out[b, 10 + j]]) for j in range(20)]
        np.testing.assert_allclose(scores[b], want, atol=2 * LOGIT_ATOL,
                                   rtol=0)


@pytest.mark.parametrize("impl", ["einsum", "pallas"])
def test_serving_engine_emits_the_reference_argmax(ff, impl):
    """Prefill (whole and in chunks of 32), both paged pools and decode
    against the reference's full pass, at the level of logits, with contexts
    below (8 + 24) and above (40, 96, 23 + 24) index_topk; `pallas` runs the
    two decode kernels (interpret mode), `einsum` their oracle."""
    eng = ff.make_serving_engine(serve_slots=4, kv_page_size=8,
                                 max_seq_len=160, prefill_chunk=32,
                                 decode_chunk=4, prefix_cache=False,
                                 paged_attention_impl=impl)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(1, VOCAB, (n,)).astype(np.int32)
               for n in (40, 8, 96, 23)]
    reqs = eng.run(prompts, max_new_tokens=24)
    assert [r.state for r in reqs] == ["done"] * 4
    for r in reqs:
        # both sides hold the logit to LOGIT_ATOL: a near-tie flips within
        # twice that
        assert margins(ff, r, 24).max() <= 2 * LOGIT_ATOL
    st = eng.stats()
    assert st["dsa_context_tokens"] > st["dsa_selected_tokens"] > 0
    assert st["index_read_bytes"] == st["dsa_context_tokens"] * 32 * 2
    # what the index kernel's stream moved: whole blocks of 8 pages of 8
    assert st["index_streamed_bytes"] > st["index_read_bytes"]
    assert st["index_streamed_bytes"] % (64 * 32 * 2) == 0
    # page bytes: (lat 128 + index key 32) x f32, 3 layers
    assert st["kv_bytes_per_token"] == (128 + 32) * 4 * 3


def test_prefix_hit_prefill_matches_cold_prefill(ff):
    """The same 88-token prompt cold, then again as a hit of its 10 full
    pages (the tail's 8 rows against the gathered pages): the same tokens,
    each on the reference's maximum."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=160, decode_chunk=4,
                                 prefix_cache=True)
    prompt = np.random.RandomState(9).randint(1, VOCAB, (88,)) \
        .astype(np.int32)
    cold = eng.run([prompt], max_new_tokens=12)[0]
    hit = eng.run([prompt], max_new_tokens=12)[0]
    st = eng.stats()
    assert st["prefix_hits"] == 1 and hit.prefix_tokens == 80
    assert st["prefix_hit_tokens"] == 80 and st["prefix_prompt_tokens"] == 176
    assert hit.tokens == cold.tokens
    assert margins(ff, hit, 12).max() <= 2 * LOGIT_ATOL


# ---- the attention op alone ------------------------------------------------

def attention_op(topk=TOPK, gain=1.0, seq=48, batch=2):
    ff = FFModel(FFConfig(batch_size=batch, mesh_shape={"data": 1}))
    x = ff.create_tensor([batch, seq, 64], name="x")
    op = LatentAttention(ff, "attn", [x], 64, 4, 48, 32, 32, 16, 32, 4, 32,
                         topk, rope_scaling=YARN, uq_init_gain=gain)
    rs = np.random.RandomState(1)
    params = {}
    for w in op.weight_specs():
        scale = {"one": 0.3, "zero": 0.3}.get(w.init, w.shape[0] ** -0.5)
        params[w.name] = jnp.asarray(
            (w.init == "one") + scale * rs.randn(*w.shape), jnp.float32)
    return op, params, jnp.asarray(rs.randn(batch, seq, 64), jnp.float32)


def test_absorbed_form_matches_expanded_form():
    """`forward` builds K and V per head; `prefill_forward` moves W_UK into
    the query and W_UV into the output and attends the cached rows: the same
    numbers up to float32 rounding (outputs of order 1)."""
    op, params, x = attention_op()
    expanded = op.forward(params, [x])[0]
    absorbed, cache = op.prefill_forward(params, [x],
                                         op.init_cache(2, 48, jnp.float32))
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=0)
    assert cache["lat"].shape == (2, 48, 128) and cache["ki"].shape \
        == (2, 48, 32)
    # the padding lanes of a latent row stay zero
    assert not np.asarray(cache["lat"][..., 48:]).any()


@pytest.mark.parametrize("least", [1, 40, 10 ** 6])
def test_a_chunk_given_dead_keys_attends_what_it_attended(monkeypatch, least):
    """A prefill chunk sees the cache's rows past its own end when
    `_MIN_CHUNK_KEYS` says so: they are dead under the live rule, so the
    first two chunks of a prompt give what the whole prompt gives."""
    op, params, x = attention_op()
    whole, _ = op.prefill_forward(params, [x],
                                  op.init_cache(2, 64, jnp.float32))
    monkeypatch.setattr(mla, "_MIN_CHUNK_KEYS", least)
    cache = op.init_cache(2, 64, jnp.float32)
    for start in (0, 16):
        out, cache = op.chunk_forward(params, [x[:, start:start + 16]],
                                      cache, start)
        np.testing.assert_allclose(out, whole[:, start:start + 16],
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("length, k, ties", [
    (40, 16, False), (40, 16, True), (12, 16, False), (129, 1, True),
    (64, 64, True), (300, 37, True)])
def test_threshold_selects_exactly_top_k_ties_to_the_lower_position(
        length, k, ties):
    """`dsa_threshold` + `dsa_chosen` against `jax.lax.top_k` (which breaks
    ties towards the lower index), with dead positions and, with `ties`,
    scores drawn from five values so that the cut falls inside a run."""
    rs = np.random.RandomState(length + k)
    sc = rs.randn(3, length).astype(np.float32)
    if ties:
        sc = rs.randint(-2, 3, (3, length)).astype(np.float32)
    sc[rs.rand(3, length) < 0.2] = -np.inf
    sc[0, 0] = 1.0       # a row always holds its own token
    got = np.asarray(mla.dsa_chosen(sc, *mla.dsa_threshold(
        jnp.asarray(sc), k)))
    want = np.zeros_like(got)
    idx = np.asarray(jax.lax.top_k(jnp.asarray(sc), min(k, length))[1])
    want[np.arange(3)[:, None], idx] = True
    want &= sc > -np.inf
    np.testing.assert_array_equal(got, want)


def paged_state(op, params, case: str):
    """Three slots over a pool of pages of 8 whose tables hold 64 tokens,
    four times index_topk, so the Pallas path takes the gathered core.
    `plain`: contexts 43 (several pages over index_topk, a hole of padding
    at 30..31), 9 (under it: a short list) and an idle slot (a list of one).
    `tie`: the index keys of slot 0's pages 1-3 are one row repeated, so 22
    live scores are equal and the cut of 16 falls among them, across page
    edges. `few_over`: contexts 18 and 17, one and two tokens over
    index_topk. `shared_doc`: slots 0 and 1 read the same four pages of one
    document and append to pages of their own."""
    rs = np.random.RandomState(4)
    pool = op.init_paged_cache(24, 8, jnp.float32)
    pool = {n: jnp.asarray(rs.randn(*a.shape), jnp.float32)
            for n, a in pool.items()}
    pool["lat"] = pool["lat"].at[..., 48:].set(0.0)
    table = np.zeros((3, 8), np.int32)
    table[0, :6] = [3, 7, 1, 12, 9, 20]
    table[1, :2] = [5, 2]
    if case == "tie":
        pool["ki"] = pool["ki"].at[jnp.asarray([7, 1, 12])].set(
            pool["ki"][7, 0])
    write_pos = np.asarray([42, 8, 0], np.int32)
    row_len = np.asarray([30, 5, 0], np.int32)      # 30..31 and 5..7: padding
    prompt_pad = np.asarray([32, 8, 0], np.int32)
    if case == "few_over":
        table[1, :3] = [5, 2, 14]
        write_pos = np.asarray([17, 18, 0], np.int32)
        row_len = np.asarray([0, 12, 0], np.int32)  # 12..13: padding
        prompt_pad = np.asarray([0, 14, 0], np.int32)
    if case == "shared_doc":
        table[1, :5] = [3, 7, 1, 12, 5]
        write_pos = np.asarray([42, 35, 0], np.int32)
        row_len = np.asarray([30, 30, 0], np.int32)
        prompt_pad = np.asarray([32, 32, 0], np.int32)
    x = jnp.asarray(rs.randn(3, 1, 64), jnp.float32)
    return pool, jnp.asarray(table), write_pos, row_len, prompt_pad, x


@pytest.mark.parametrize("case", ["plain", "tie", "few_over", "shared_doc"])
def test_pallas_decode_kernels_match_the_einsum_oracle(case):
    """`dsa_index_scores` and the gathered core (interpret mode) read the
    pools through the page tables; the oracle gathers the pages and runs the
    blocked XLA attention. Same appended rows, same output, also when the
    selection's cut falls inside a run of equal scores, when a context is a
    token or two over index_topk, and when two slots share a document."""
    tie = case == "tie"
    op, params, _ = attention_op(seq=1, batch=3)
    pool, table, wp, rl, pp, x = paged_state(op, params, case)
    args = (params, [x], pool, table, jnp.asarray(wp), jnp.asarray(wp - 2),
            jnp.asarray(rl), jnp.asarray(pp))
    want, pool_e = op.paged_decode_forward(*args, impl="einsum")
    got, pool_p = op.paged_decode_forward(*args, impl="pallas")
    for n in ("lat", "ki"):
        np.testing.assert_array_equal(pool_p[n], pool_e[n])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    if tie:
        from flexflow_tpu.ops.pallas_kernels import dsa_index_scores_pallas

        pr = op._project(params, x, jnp.asarray(wp - 2)[:, None])
        sc = np.asarray(dsa_index_scores_pallas(
            pr["qi"][:, 0], pr["w"][:, 0], pool_p["ki"], table,
            jnp.asarray(wp), jnp.asarray(rl), jnp.asarray(pp)))
        assert len(set(sc[0, 8:30])) == 1            # the run of ties
        assert np.isinf(sc[0, 30:32]).all() and np.isinf(sc[0, 43:]).all()
        chosen = np.asarray(mla.dsa_chosen(
            sc, *mla.dsa_threshold(jnp.asarray(sc), TOPK)))[0]
        tied = np.flatnonzero(chosen[8:30])
        # what the cut takes of the run is its lowest positions
        assert chosen.sum() == TOPK and 0 < tied.size < 22
        np.testing.assert_array_equal(tied, np.arange(tied.size))


# ---- the index kernel's block turn (ops/pallas_kernels.py) ------------------

G = 8       # pallas_kernels._INDEX_BLOCK_PAGES: pages a turn fetches and scores

# name -> (page size, table width, [(row_len, prompt_pad, write_pos)] a slot;
# an idle slot is (0, 0, 0)). Contexts end inside their last page unless the
# name says otherwise, and prompts leave a hole of padding before prompt_pad.
INDEX_CASES = {
    "one_page": (8, 20, [(3, 4, 6)]),
    "g_minus_1_pages": (8, 20, [(40, 48, 8 * (G - 1) - 3), (0, 0, 2)]),
    "g_pages_to_the_last_column": (8, 20, [(40, 48, 8 * G - 1), (5, 8, 9)]),
    "g_plus_1_pages": (8, 20, [(0, 0, 8 * G), (61, 64, 8 * G + 5)]),
    "two_g_plus_3_pages": (8, 20, [(100, 104, 8 * (2 * G + 3) - 2)]),
    "idle_slot_between_live_ones": (8, 20, [(100, 104, 8 * (2 * G + 3) - 2),
                                            (0, 0, 0),
                                            (0, 0, 8 * G + 1),
                                            (0, 0, 0)]),
    # the cell's table width (260 pages, no whole number of blocks), a slot
    # that fills it to its last column beside a short one
    "full_table_of_260_pages": (4, 260, [(1000, 1024, 4 * 260 - 1),
                                         (9, 12, 13)]),
}


def index_case(name, tie_pages=()):
    """Random index keys and queries (float32, 4 heads of 16) for
    INDEX_CASES[name]: every slot's live pages are pages of its own, every
    table entry past them and the pad are the scratch page 0, which holds
    NaN (fetched by a block's tail, never to be scored); `tie_pages` of
    slot 0 hold one key row repeated."""
    from flexflow_tpu.ops.pallas_kernels import dsa_index_scores_pallas

    ps, width, slots = INDEX_CASES[name]
    rs = np.random.RandomState(len(name))
    rl, pp, wp = (np.asarray(c, np.int32) for c in zip(*slots))
    pages = np.maximum(wp, rl - 1) // ps + 1
    pool = rs.randn(1 + pages.sum(), ps, 16).astype(np.float32)
    pool[0] = np.nan
    table = np.zeros((len(slots), width), np.int32)
    ids = rs.permutation(np.arange(1, 1 + pages.sum()))
    for b, n in enumerate(pages):
        table[b, :n], ids = ids[:n], ids[n:]
    for t in tie_pages:
        pool[table[0, t]] = pool[table[0, tie_pages[0]], 0]
    qi = rs.randn(len(slots), 4, 16).astype(np.float32)
    w = rs.randn(len(slots), 4).astype(np.float32)
    got = np.asarray(dsa_index_scores_pallas(
        *(jnp.asarray(a) for a in (qi, w, pool, table, wp, rl, pp))))
    # the oracle: I_s = sum_j w_j ReLU(qI_j . kI_s) over the gathered pages,
    # -inf off the live rule
    keys = pool[table].reshape(len(slots), width * ps, 16)
    sc = np.einsum("bj,bjs->bs", w, np.maximum(
        np.einsum("bjd,bsd->bjs", qi, np.nan_to_num(keys)), 0.0))
    j = np.arange(width * ps)[None]
    live = (j < rl[:, None]) | ((j >= pp[:, None]) & (j <= wp[:, None]))
    return got, np.where(live, sc, -np.inf), live


@pytest.mark.parametrize("name", sorted(INDEX_CASES))
def test_index_kernel_scores_whole_blocks_like_the_oracle(name):
    """`dsa_index_scores` (interpret mode) fetches and scores a slot's
    context in blocks of G pages: contexts of 1, G - 1, G, G + 1 and
    2G + 3 pages, idle slots the stream runs ahead across, a table that is
    no whole number of blocks filled to its last column. Finite exactly
    where the oracle is, also in the pages a block fetched past the slot's
    last live one (scratch page 0, here NaN) and in the table's pad."""
    got, want, live = index_case(name)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.isneginf(got), ~live)
    np.testing.assert_array_equal(np.isfinite(got), live)
    # float32 both sides, sums of 16 and 4 products of order 1
    np.testing.assert_allclose(got[live], want[live], atol=1e-5, rtol=0)


def test_index_kernel_ties_are_one_value_across_a_block_boundary():
    """Pages G - 2 .. G + 1 of slot 0 hold one key row repeated: their
    live columns, the last two pages of the first block and the first two
    of the second, read ONE value bit for bit (the threshold's cut inside a
    run of ties counts on it)."""
    got, want, live = index_case("two_g_plus_3_pages",
                                 tie_pages=(G - 2, G - 1, G, G + 1))
    run = got[0, 8 * (G - 2):8 * (G + 2)]
    assert live[0, 8 * (G - 2):8 * (G + 2)].all() and len(set(run)) == 1
    np.testing.assert_allclose(run, want[0, 8 * (G - 2):8 * (G + 2)],
                               atol=1e-5, rtol=0)
    assert len(set(got[0, :8 * (G - 2)])) > 8


@pytest.mark.parametrize("contexts, whole", [
    ([[1, 2], [8 * 5 * G - 1, 8 * 5 * G]], False),
    ([[8 * G, 8 * 3 * G]], True)])
def test_streamed_index_bytes_round_contexts_up_to_whole_blocks(contexts,
                                                                whole):
    """`decode_span_counts` with the pool's page size: what the block
    stream moves is every (row, step)'s context rounded up to G pages,
    never under `index_read_bytes` and equal to it at whole blocks; without
    a page size the count is left out."""
    from flexflow_tpu.ops.pallas_kernels import (_INDEX_BLOCK_PAGES,
                                                 dsa_index_block_tokens)

    assert _INDEX_BLOCK_PAGES == G and dsa_index_block_tokens(8) == 8 * G
    op, _, _ = attention_op()
    ctx = np.asarray(contexts)
    got = op.decode_span_counts(ctx, page_size=8)
    blocks = sum(-(-int(c) // (8 * G)) for c in ctx.ravel())
    assert got["index_streamed_bytes"] == blocks * 8 * G * 32 * 2
    assert got["index_read_bytes"] == ctx.sum() * 32 * 2
    assert (got["index_streamed_bytes"] == got["index_read_bytes"]) == whole
    assert got["index_streamed_bytes"] >= got["index_read_bytes"]
    assert "index_streamed_bytes" not in op.decode_span_counts(ctx)


def core_case(case: str):
    """Hand-made index scores of two slots over tables of 8 pages of 8
    (-inf = dead under the live rule) and their page tables, for the
    gathered core alone; index_topk is 16."""
    rs = np.random.RandomState(len(case))
    sc = np.full((2, 64), -np.inf, np.float32)
    table = np.stack([rs.permutation(23)[:8] + 1 for _ in range(2)])

    def live(b, n):
        sc[b, :n] = rs.permutation(n) * 0.25 - 3.0      # distinct scores

    if case == "under_topk":
        live(0, 9), live(1, 16)
    elif case == "few_over":
        live(0, 17), live(1, 19)
    elif case == "pages_over":
        live(0, 43), live(1, 64)
    elif case == "tie_straddles_page_edge":
        # twelve scores above a run of six equal ones at 13..18: the cut
        # takes four of the run, 13..15 of page 1 and 16 of page 2
        live(0, 40), live(1, 40)
        for b in range(2):
            sc[b, 13:19] = 20.0
            sc[b, rs.permutation(np.r_[0:13, 19:40])[:12]] += 40.0
    elif case == "prompt_pad_hole":
        live(0, 43), live(1, 30)
        sc[0, 30:32] = -np.inf          # row_len 30, prompt_pad 32
        sc[1, 5:8] = -np.inf
    elif case == "inactive_slot":
        live(0, 43)
        sc[1, 0], table[1] = 0.0, 0     # one live position of the scratch page
    elif case == "shared_pages":
        live(0, 40), live(1, 40)
        table[1, :4] = table[0, :4]     # one document under both slots
    return sc, table.astype(np.int32)


@pytest.mark.parametrize("case", [
    "under_topk", "few_over", "pages_over", "tie_straddles_page_edge",
    "prompt_pad_hole", "inactive_slot", "shared_pages"])
def test_gathered_core_attends_exactly_the_selected_rows(case):
    """`dsa_selected` + `mla_gathered_core_pallas` (interpret mode) against
    a dense softmax under `dsa_chosen`'s mask over the slots' gathered
    pages: the same rows, so the same numbers up to float32 rounding."""
    from flexflow_tpu.ops.pallas_kernels import mla_gathered_core_pallas

    sc, table = core_case(case)
    rs = np.random.RandomState(7)
    lat = jnp.asarray(rs.randn(24, 8, 128), jnp.float32)
    q = jnp.asarray(rs.randn(2, 4, 128), jnp.float32)
    thr, cut = mla.dsa_threshold(jnp.asarray(sc), TOPK)
    chosen = np.asarray(mla.dsa_chosen(sc, thr, cut))
    if case == "tie_straddles_page_edge":
        np.testing.assert_array_equal(np.flatnonzero(chosen[0, 13:19]),
                                      np.arange(4))
    rows, n_sel = mla.dsa_selected(jnp.asarray(sc), thr, cut, TOPK, 8,
                                   jnp.asarray(table))
    np.testing.assert_array_equal(n_sel, np.minimum(
        (sc > -np.inf).sum(-1), TOPK))
    got = mla_gathered_core_pallas(q, rows, n_sel, lat, scale=0.3, c=32)
    mine = lat[table].reshape(2, 64, 128)
    logits = jnp.where(chosen[:, None, :],
                       jnp.einsum("bhw,blw->bhl", q, mine) * 0.3, -jnp.inf)
    want = jnp.einsum("bhl,blc->bhc", jax.nn.softmax(logits, axis=-1),
                      mine[..., :32])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("length, ps, k, ties", [
    (64, 8, 16, False), (64, 8, 16, True), (48, 8, 64, False),
    (1024, 128, 300, True), (512, 256, 100, True), (33280, 128, 2048, True)])
def test_selected_list_is_the_chosen_set_in_order(length, ps, k, ties):
    """`dsa_selected`'s first n_sel entries are the pool's rows of
    `dsa_chosen`'s positions, ascending by position, ties at the cut
    included (under an identity table the positions themselves); past n_sel
    the list repeats a valid entry."""
    rs = np.random.RandomState(length + k)
    sc = rs.randn(3, length).astype(np.float32)
    if ties:
        sc = rs.randint(-2, 3, (3, length)).astype(np.float32)
    sc[rs.rand(3, length) < 0.2] = -np.inf
    sc[:, 0] = 1.0
    sc[2, 1:] = -np.inf                 # an idle slot: its own token alone
    table = rs.randint(0, 1 << 22, (3, length // ps)).astype(np.int32)
    thr, cut = mla.dsa_threshold(jnp.asarray(sc), k)
    chosen = np.asarray(mla.dsa_chosen(sc, thr, cut))
    sel, n_sel = mla.dsa_selected(jnp.asarray(sc), thr, cut, k, ps,
                                  np.tile(np.arange(length // ps), (3, 1)))
    rows, n_rows = mla.dsa_selected(jnp.asarray(sc), thr, cut, k, ps,
                                    jnp.asarray(table))
    np.testing.assert_array_equal(n_sel, chosen.sum(-1))
    np.testing.assert_array_equal(n_rows, n_sel)
    for b, n in enumerate(np.asarray(n_sel)):
        want = np.flatnonzero(chosen[b])
        np.testing.assert_array_equal(sel[b, :n], want)
        np.testing.assert_array_equal(
            rows[b, :n], table[b, want // ps] * ps + want % ps)
        assert (np.asarray(sel[b, n:]) == 0).all()
        assert (np.asarray(rows[b, n:]) == table[b, 0] * ps).all()


def test_a_pair_that_marks_more_than_k_lists_the_first_k():
    """`dsa_threshold` never marks more than k positions; a planted fault
    may (benchmark/dsa_controls.py switches the selection off with
    (-inf, L)). The list then holds the first k marked and n_sel is k, so
    the gathered core's loop stays inside its k rows."""
    sc = np.random.RandomState(0).randn(2, 64).astype(np.float32)
    sc[1, 40:] = -np.inf
    thr = jnp.full((2,), -jnp.inf, jnp.float32)
    cut = jnp.full((2,), 64, jnp.int32)
    sel, n_sel = mla.dsa_selected(jnp.asarray(sc), thr, cut, TOPK, 8,
                                  np.tile(np.arange(8), (2, 1)))
    np.testing.assert_array_equal(n_sel, [TOPK, TOPK])
    np.testing.assert_array_equal(sel, np.tile(np.arange(TOPK), (2, 1)))


@pytest.mark.parametrize("pages", [1, 2, 3])
def test_a_table_no_larger_than_the_selection_takes_the_same_core(pages):
    """One core whatever the table's shape: tables of 1 or 2 pages of 8
    hold at most index_topk = 16 tokens, so every live token is selected
    and the list is as long as the table; with 3 pages it is index_topk.
    The decode program holds `mla_paged_core_gathered` once either way and
    agrees with the oracle."""
    import re

    op, params, _ = attention_op(seq=1, batch=3)
    pool, table, *_, x = paged_state(op, params, "plain")
    table = table[:, :pages]
    wp = jnp.asarray([min(13, pages * 8 - 1), min(8, pages * 8 - 1), 0],
                     jnp.int32)
    rl = jnp.asarray([min(9, pages * 8 - 3), 5, 0], jnp.int32)
    pp = jnp.asarray([min(10, pages * 8 - 2), min(8, pages * 8 - 1), 0],
                     jnp.int32)

    def step(impl):
        return op.paged_decode_forward(params, [x], pool, table, wp, wp, rl,
                                       pp, impl=impl)[0]

    text = str(jax.make_jaxpr(lambda: step("pallas"))())
    assert re.findall(r"name=(mla_paged_core\w*)", text) == [
        "mla_paged_core_gathered"]
    np.testing.assert_allclose(step("pallas"), step("einsum"), atol=2e-5,
                               rtol=0)


def test_export_import_moves_a_page_of_both_pools():
    op, params, _ = attention_op()
    rs = np.random.RandomState(6)
    pool = {n: jnp.asarray(rs.randn(*a.shape), jnp.float32)
            for n, a in op.init_paged_cache(6, 8, jnp.float32).items()}
    payload = op.export_page(pool, jnp.asarray([4, 2]))
    assert payload["lat"].shape == (2, 8, 128) \
        and payload["ki"].shape == (2, 8, 32)
    other = op.init_paged_cache(6, 8, jnp.float32)
    other = op.import_page(other, jnp.asarray([1, 5]), payload)
    for n in ("lat", "ki"):
        np.testing.assert_array_equal(other[n][1], pool[n][4])
        np.testing.assert_array_equal(other[n][5], pool[n][2])
        assert not np.asarray(other[n][0]).any()
    got = op.gather_paged_kv(other, jnp.asarray([5, 1]))
    np.testing.assert_array_equal(got["lat"][0, :8], pool["lat"][2])
    assert got["ki"].shape == (1, 16, 32)


def test_slab_export_import_round_trips_through_the_engine(ff):
    """A prompt's pages leave one engine as a slab and serve a hit in
    another: both pools of every layer travel."""
    kw = dict(serve_slots=2, kv_page_size=8, max_seq_len=96, decode_chunk=4,
              prefix_cache=True)
    a, b = ff.make_serving_engine(**kw), ff.make_serving_engine(**kw)
    prompt = np.random.RandomState(11).randint(1, VOCAB, (40,)) \
        .astype(np.int32)
    want = a.run([prompt], max_new_tokens=8)[0].tokens
    slab = a.export_prefix_slab(prompt)
    assert set(slab["payload"][0][("t", "attn_0")]) == {"lat", "ki"}
    assert b.import_prefix_slab(slab) == 5
    got = b.run([prompt], max_new_tokens=8)[0]
    assert got.prefix_tokens == 32 and got.tokens == want


def test_speculative_verify_is_refused_clearly():
    op, params, x = attention_op()
    with pytest.raises(NotImplementedError, match="speculative verify"):
        op.paged_verify_forward(params, [x], None, None, None, None, None,
                                None)


# ---- the router's form and the held experts ---------------------------------

def moe_op(held=None, bias=0.05, n=24, d=16, f=24, e=16, k=4):
    ff = FFModel(FFConfig(batch_size=n, mesh_shape={"data": 1}))
    x = ff.create_tensor([n, d], name="x")
    op = MoE(ff, "moe", [x], e, f, k, None, expert="swiglu",
             scoring="sigmoid", score_bias=bias, n_group=4, topk_group=2,
             routed_scaling=2.5, shared_hidden_dim=f, experts_held=held)
    rs = np.random.RandomState(0)
    p = {w.name: jnp.asarray(rs.randn(*w.shape) * (
        0.5 if w.name == "score_bias" else w.shape[-2] ** -0.5 if len(
            w.shape) > 1 else 1.0), jnp.float32) for w in op.weight_specs()}
    return op, p, jnp.asarray(rs.randn(n, d), jnp.float32)


def test_selection_bias_changes_the_chosen_set_and_not_the_gates():
    """s' = s + b selects; the gates are s of the chosen experts,
    renormalised and times 2.5: with b the chosen sets differ, and where a
    token's set is the same so are its gates."""
    op, p, x = moe_op()
    s, g_b, e_b = op._route(p, x)
    _, g_0, e_0 = op._route({**p, "score_bias": jnp.zeros(16)}, x)
    same = (np.sort(e_b, -1) == np.sort(e_0, -1)).all(-1)
    assert 0 < same.sum() < len(same)
    want = np.take_along_axis(np.asarray(s), np.asarray(e_b), -1)
    want = 2.5 * want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(g_b, want, rtol=1e-6)
    for t in np.flatnonzero(same):
        np.testing.assert_allclose(np.sort(g_b[t]), np.sort(g_0[t]),
                                   rtol=1e-6)
    # group-limited: every chosen expert lies in one of two groups of four
    assert all(len({int(e) // 4 for e in row}) <= 2 for row in np.asarray(e_b))


def test_four_shares_sum_to_the_uncut_layer():
    """The guide's share test: four layers holding experts 0-3, 4-7, 8-11
    and 12-15 of the same weights, the shared expert counted once, give the
    uncut layer's output."""
    whole, p, x = moe_op()
    full = whole.forward(p, [x])[0]
    shared = whole._shared_expert(p, x)
    routed = jnp.zeros_like(full)
    for first in range(0, 16, 4):
        part, pp, _ = moe_op(held=(first, 4))
        pp = {n: (v[first:first + 4] if n in MoE._EXPERT_WEIGHTS else v)
              for n, v in p.items()}
        assert pp["w_gate"].shape == part.weight_specs()[1].shape
        routing = []
        routed = routed + part.forward(pp, [x], routing=routing)[0] - shared
        # the share's counters count ITS experts' assignments only
        assert int(routing[0][0]) <= 24 * 4 and int(routing[0][1]) <= 4
    np.testing.assert_allclose(routed + shared, full, atol=2e-5, rtol=0)


def test_streamed_lowering_of_held_experts_matches_grouped(monkeypatch):
    """A decode-shaped call of a layer that holds 4 of 16 experts through
    the expert-stream kernel (interpret mode) against its grouped
    lowering."""
    import flexflow_tpu.ops.moe as moe_mod

    op, p, x = moe_op(held=(8, 4), n=8, d=128, f=128)
    p = {n: (v[8:12] if n in MoE._EXPERT_WEIGHTS else v)
         for n, v in moe_op(n=8, d=128, f=128)[1].items()}
    mask = jnp.asarray([True] * 6 + [False] * 2)
    want, took = op.forward(p, [x], row_mask=mask)[0], []
    monkeypatch.setattr(moe_mod, "_backend", lambda: "tpu")
    got = op.forward(p, [x], row_mask=mask, lowerings=took)[0]
    assert took == ["streamed"]
    # dead rows: the routed part is zero, the shared expert still runs
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_plain_softmax_op_takes_none_of_the_new_arguments():
    with pytest.raises(ValueError, match="dropless"):
        ff = FFModel(FFConfig(batch_size=4, mesh_shape={"data": 1}))
        MoE(ff, "m", [ff.create_tensor([4, 16], name="x")], 8, 16, 2,
            scoring="sigmoid")


# ---- the check can see the selection ----------------------------------------

CONFIG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs",
    "deepseek-v3.2-serve.json")


@pytest.mark.parametrize("fault", ["off", "shifted"])
def test_check_a_fails_when_the_selection_is_off_or_shifted(monkeypatch,
                                                            fault):
    """With glorot weights attention is nearly uniform over what it selects
    and a program that attended to every live token would pass check (a).
    The configuration's seeded draw widens W_UQ (`seeded_w_uq_gain`) so that
    attention is peaked: then a selection switched off, or shifted by one
    page (8 here), moves the logits beyond the configuration's own
    tolerance, while the intact program stays far inside it."""
    cfg = json.load(open(CONFIG))
    tol = cfg["tolerances"]["predict_rel_rms"]
    ff = build(batch=1, gain=cfg["seeded_w_uq_gain"])
    toks = np.random.RandomState(1).randint(1, VOCAB, (1, SEQ)) \
        .astype(np.int32)
    want = np.asarray(ref.forward(ff.params, toks[0], SIZES))

    def rel(got):
        return float(np.linalg.norm(np.asarray(got)[0] - want)
                     / np.linalg.norm(want))

    assert rel(ff.predict({"input": toks})) < tol / 100
    chosen = mla.dsa_chosen

    def faulty(scores, thr, cut):
        live = scores > -jnp.inf
        if fault == "off":
            return live
        return jnp.roll(chosen(scores, thr, cut), 8, axis=-1) & live | (
            live & (jnp.cumsum(live, axis=-1) <= 1))

    monkeypatch.setattr(mla, "dsa_chosen", faulty)
    ff2 = build(batch=1, gain=cfg["seeded_w_uq_gain"])
    assert rel(ff2.predict({"input": toks})) > tol
