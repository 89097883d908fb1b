"""Pallas paged-attention decode kernel (ops/pallas_kernels.py
paged_attention_fwd_pallas) and its routing (the engine's
paged_attention_impl argument).

Correctness anchors:
  * kernel vs the einsum page-gather oracle (bitwise the dense-cache
    attention) within kernel tolerance — decode (S=1), verify slab
    (S=K+1 with per-position frontiers), GQA head grouping, ragged
    row_len/prompt_pad, scrambled page tables, the inactive-slot
    scratch-page-0 state;
  * a full greedy serving run (prefix cache + speculation ON) is
    TOKEN-IDENTICAL between impl='pallas' and impl='einsum' — the kernel
    is a perf mechanism, never semantics;
  * the recompile counter stays flat under warm traffic with the kernel
    path enabled (the kernel does not break the one-program contract);
  * the kernel's page stream (ISSUE 26): every seam between slots, pages
    and ring buffers against the oracle, and a poisoned pool proving that
    a dead page never reaches the arithmetic.

On CPU the kernel runs in interpret mode — the REAL kernel code path,
executed by the suite (the ISSUE-7 routing requirement).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.ops.attention import resolve_paged_attention_impl
from flexflow_tpu.ops.pallas_kernels import paged_attention_fwd_pallas

VOCAB = 89
TOL = dict(rtol=2e-5, atol=2e-5)


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    # kv_heads=2 < heads=4: the GQA grouping is always exercised
    _, logits = llama_lm(model, 2, seq_len=16, hidden=64, layers=2,
                         heads=4, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


@pytest.fixture(scope="module")
def attn(ff):
    return next(op for op in ff.ops
                if type(op).__name__ == "MultiHeadAttention")


def _pool(rs, attn, n_pages=10, page=4):
    return {
        "k": jnp.asarray(rs.randn(n_pages, page, attn.num_kv_heads,
                                  attn.qk_head_dim), jnp.float32),
        "v": jnp.asarray(rs.randn(n_pages, page, attn.num_kv_heads,
                                  attn.v_head_dim), jnp.float32),
    }


def _params(ff, attn):
    return {k: jnp.asarray(v) for k, v in ff.params[attn.name].items()}


def test_kernel_matches_einsum_decode_ragged_scrambled(ff, attn):
    """S=1 decode step over a deliberately non-identity page table with
    ragged row_len/prompt_pad: the kernel's online softmax must match
    the page-gather einsum (itself bitwise the dense-cache attention,
    tests/test_serving.py) to kernel tolerance."""
    rs = np.random.RandomState(3)
    pool = _pool(rs, attn)
    params = _params(ff, attn)
    table = jnp.asarray([[5, 2, 7, 1], [3, 6, 4, 8]], jnp.int32)
    x = jnp.asarray(rs.randn(2, 1, attn.q_in), jnp.float32)
    wp = jnp.asarray([9, 13], jnp.int32)
    rope = jnp.asarray([4, 7], jnp.int32)
    row_len = jnp.asarray([3, 7], jnp.int32)       # ragged true prompts
    pad = jnp.asarray([8, 8], jnp.int32)           # bucket-padded width
    out_e, cache_e = attn.paged_decode_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="einsum")
    out_p, cache_p = attn.paged_decode_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="pallas")
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_p), **TOL)
    # the scatter half is shared code — the pools must be BITWISE equal
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cache_e[n]),
                                      np.asarray(cache_p[n]))


TURNS = [1, 2, 4]


def _set_turn(monkeypatch, page, rows, width, turn):
    """Make a turn of the kernel take `turn` pages of `page` tokens x `rows`
    rows (fewer under a narrower table) the way the rule itself would: by
    the columns its one constant asks of a turn, never through an argument
    of the kernel."""
    from flexflow_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_PAGED_TURN_COLS",
                        turn * page * rows)
    want = max(g for g in TURNS if g <= min(turn, width))
    assert pallas_kernels.paged_turn_pages(page, rows, width) == want
    return want


@pytest.mark.parametrize("turn", TURNS)
def test_kernel_matches_einsum_verify_slab(ff, attn, monkeypatch, turn):
    """S=4 speculative-verify slab: per-position write frontiers give
    in-slab causality; every position's context must match the oracle. The
    second slot's frontiers 11..13 straddle the edge of a block of 2 pages,
    the first's 9..12 end in the third page: a block and a tail page."""
    _set_turn(monkeypatch, 4, attn.num_kv_heads, 4, turn)
    rs = np.random.RandomState(5)
    pool = _pool(rs, attn)
    params = _params(ff, attn)
    table = jnp.asarray([[5, 2, 7, 1], [3, 6, 4, 8]], jnp.int32)
    s = 4
    x = jnp.asarray(rs.randn(2, s, attn.q_in), jnp.float32)
    wp0 = jnp.asarray([9, 11], jnp.int32)
    # nondecreasing frontiers incl. the budget clamp (equal tail)
    wp = jnp.minimum(wp0[:, None] + jnp.arange(s)[None, :], 13)
    rope = jnp.asarray([4, 7], jnp.int32)
    row_len = jnp.asarray([3, 7], jnp.int32)
    pad = jnp.asarray([8, 8], jnp.int32)
    out_e, _ = attn.paged_verify_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="einsum")
    out_p, _ = attn.paged_verify_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="pallas")
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_p), **TOL)


@pytest.mark.parametrize("turn", TURNS)
def test_kernel_inactive_slot_scratch_page(ff, attn, monkeypatch, turn):
    """The serving engine's inactive-slot state (zeroed table -> every
    write lands in scratch page 0, write_pos=row_len=prompt_pad=0): the
    kernel must produce the same finite output as the oracle — its live
    rule admits j=0, so the online softmax never divides by zero. A turn of
    several pages takes the idle slot's one page as a tail."""
    _set_turn(monkeypatch, 4, attn.num_kv_heads, 4, turn)
    rs = np.random.RandomState(7)
    pool = _pool(rs, attn)
    params = _params(ff, attn)
    table = jnp.asarray([[5, 2, 7, 1], [0, 0, 0, 0]], jnp.int32)
    x = jnp.asarray(rs.randn(2, 1, attn.q_in), jnp.float32)
    wp = jnp.asarray([9, 0], jnp.int32)
    rope = jnp.asarray([4, 0], jnp.int32)
    row_len = jnp.asarray([3, 0], jnp.int32)
    pad = jnp.asarray([8, 0], jnp.int32)
    out_e, _ = attn.paged_decode_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="einsum")
    out_p, _ = attn.paged_decode_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="pallas")
    assert bool(jnp.isfinite(out_p).all())
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_p), **TOL)


def test_kernel_live_pages_cover_prompt_past_frontier(ff, attn):
    """The live-page bound must honor BOTH halves of the live rule: a
    caller querying with write_pos INSIDE the prompt (write_pos <
    row_len — never produced by the serving engine, but legal at the op
    boundary) still attends the whole live prompt, j < row_len. A
    frontier-only bound would silently skip the prompt's tail pages."""
    rs = np.random.RandomState(23)
    pool = _pool(rs, attn)
    params = _params(ff, attn)
    table = jnp.asarray([[5, 2, 7, 1], [3, 6, 4, 8]], jnp.int32)
    x = jnp.asarray(rs.randn(2, 1, attn.q_in), jnp.float32)
    wp = jnp.asarray([5, 2], jnp.int32)            # frontier in page 1/0
    rope = jnp.asarray([5, 2], jnp.int32)
    row_len = jnp.asarray([14, 11], jnp.int32)     # prompt spans 4/3 pages
    pad = jnp.asarray([16, 16], jnp.int32)
    out_e, _ = attn.paged_decode_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="einsum")
    out_p, _ = attn.paged_decode_forward(
        params, [x, x, x], pool, table, wp, rope, row_len, pad,
        impl="pallas")
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_p), **TOL)


def test_kernel_vs_dense_cache_tolerance(ff, attn):
    """The ISSUE-7 pin: the kernel against decode_forward on the
    EQUIVALENT contiguous dense cache (the pre-paged ground truth) —
    one tolerance bound covering kernel + page-table lookup together."""
    rs = np.random.RandomState(11)
    params = _params(ff, attn)
    b, page, n_pages = 2, 4, 4
    max_len = page * n_pages
    kvh, dqk, dv = attn.num_kv_heads, attn.qk_head_dim, attn.v_head_dim
    dense = {"k": jnp.asarray(rs.randn(b, max_len, kvh, dqk), jnp.float32),
             "v": jnp.asarray(rs.randn(b, max_len, kvh, dv), jnp.float32)}
    x = jnp.asarray(rs.randn(b, 1, attn.q_in), jnp.float32)
    pos, prompt_pad = 9, 8
    rope = jnp.asarray([4, 7], jnp.int32)
    row_len = jnp.asarray([3, 7], jnp.int32)
    table = np.array([[5, 2, 7, 1], [3, 6, 4, 8]], np.int32)
    pool = {"k": jnp.zeros((10, page, kvh, dqk), jnp.float32),
            "v": jnp.zeros((10, page, kvh, dv), jnp.float32)}
    for row in range(b):
        for p in range(n_pages):
            for name in ("k", "v"):
                pool[name] = pool[name].at[table[row, p]].set(
                    dense[name][row, p * page:(p + 1) * page])
    out_d, _ = attn.decode_forward(
        params, [x, x, x], dense, pos, rope_pos=rope,
        row_lengths=row_len, prompt_len=prompt_pad)
    out_k, _ = attn.paged_decode_forward(
        params, [x, x, x], pool, jnp.asarray(table),
        jnp.full((b,), pos, jnp.int32), rope, row_len,
        jnp.full((b,), prompt_pad, jnp.int32), impl="pallas")
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_k), **TOL)


def test_kernel_raw_entrypoint_gqa_rows(ff, attn):
    """Direct kernel call: the GQA row layout (query head h reads kv
    head h // group) must match _grouped_cache_attention's reshape —
    checked by feeding DISTINCT per-head queries through both paths."""
    rs = np.random.RandomState(13)
    b, s, h, kvh, d, page = 2, 2, 4, 2, attn.qk_head_dim, 4
    q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
    pool = _pool(rs, attn, n_pages=9, page=page)
    table = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    wp = jnp.asarray([[6, 7], [9, 10]], jnp.int32)
    row_len = jnp.asarray([2, 5], jnp.int32)
    pad = jnp.asarray([4, 6], jnp.int32)
    scale = 0.37
    out = paged_attention_fwd_pallas(q, pool["k"], pool["v"], table, wp,
                                     row_len, pad, scale)
    # oracle: gather + grouped einsum (the _grouped_cache_attention math
    # with an explicit scale)
    max_len = table.shape[1] * page
    gk = pool["k"][table].reshape(b, max_len, kvh, d)
    gv = pool["v"][table].reshape(b, max_len, kvh, d)
    idx = jnp.arange(max_len)
    live = (idx[None, None, :] < row_len[:, None, None]) \
        | ((idx[None, None, :] >= pad[:, None, None])
           & (idx[None, None, :] <= wp[:, :, None]))
    qg = q.reshape(b, s, kvh, h // kvh, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, gk,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(live[:, None, None, :, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    want = jnp.einsum("bkgqs,bskd->bqkgd", probs, gv).reshape(b, s, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_resolve_impl_knob():
    """auto resolves per backend; bad values are rejected; FFConfig has no
    field or flag for it (the engine's argument is the one way to ask)."""
    want_auto = "pallas" if jax.default_backend() == "tpu" else "einsum"
    assert resolve_paged_attention_impl(None) == want_auto
    assert resolve_paged_attention_impl("auto") == want_auto
    assert resolve_paged_attention_impl("pallas") == "pallas"
    assert resolve_paged_attention_impl("einsum") == "einsum"
    with pytest.raises(ValueError, match="paged_attention_impl"):
        resolve_paged_attention_impl("cuda")
    with pytest.raises(TypeError, match="paged_attention_impl"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 paged_attention_impl="einsum")
    cfg = FFConfig.parse_args(["--batch-size", "2",
                               "--paged-attention-impl", "pallas"])
    assert not hasattr(cfg, "paged_attention_impl")  # an unknown flag


def _engine(ff, impl):
    return ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                  max_seq_len=32, paged_attention_impl=impl)


@pytest.mark.parametrize("impl", ["auto", "pallas", "einsum"])
def test_stats_report_one_impl_under_both_names(ff, impl):
    """An engine makes ONE choice for the decode attention and the prefill
    page write; stats() gives it under the two names its readers ask for
    (the benchmark's generator and chip_smoke.py fail a run unless both
    say pallas), and carries nothing of a tune table."""
    st = _engine(ff, impl).stats()
    assert st["paged_attention_impl"] == st["paged_prefill_impl"] \
        == resolve_paged_attention_impl(impl)
    assert not [k for k in st if k.startswith("kernel_tune")]


@pytest.mark.parametrize("turn", TURNS)
def test_engine_counts_what_the_turns_fetch(ff, monkeypatch, turn):
    """Host only: beside `kv_read_bytes` (the live pages of each slot and
    step) the engine counts `kv_streamed_bytes`, what the kernel's turns
    fetch for them, from `paged_turn_pages`, the pages a turn takes on its
    pools (a static of their shapes and the table's width). A slot's whole
    blocks and, one a turn, the pages past them: never less than is read,
    the same at one page a turn, and under this tail the same at any. With
    pages that an earlier slot holds too (ISSUE 49) a step reads each
    distinct page once and fetches it once a group: what the slots attend,
    less `held_again` and less `saved` pages a step."""
    g = _set_turn(monkeypatch, 4, 2, 8, turn)
    eng = _engine(ff, "pallas")
    assert eng.stats()["paged_turn_pages"] == g
    assert _engine(ff, "einsum").stats()["paged_turn_pages"] == 1
    eng.active[:] = True
    budget = np.asarray([32, 32])
    # two steps a slot: 2 and 3 live pages, 6 and 8 (the budget's clamp)
    frontier = np.asarray([[7, 8], [22, 40]])
    attended, read, streamed, distinct = eng._note_pages_touched(frontier,
                                                                 budget)
    per_page = eng.page_size * eng.stats()["kv_bytes_per_token"]
    assert read == distinct * per_page == (2 + 3 + 6 + 8) * per_page
    assert streamed == read == attended
    st = eng.stats()
    assert (st["kv_read_bytes"], st["kv_streamed_bytes"],
            st["kv_attended_bytes"]) == (read, streamed, attended)
    assert st["pages_touched"] == st["last_pages_touched"] == 3 + 8
    # both slots begin with the same 2 pages, fetched once for the pair
    # (or, the group split, not at all: saved 0)
    for saved in (2, 0):
        assert eng._note_pages_touched(frontier, budget, 2, saved) == (
            attended, attended - 2 * 2 * per_page,
            attended - 2 * saved * per_page, distinct - 2 * 2)


FLASH_SEQ, FLASH_HEADS, FLASH_DIM = 256, 2, 16


@pytest.fixture
def planted_table(ff, tmp_path, monkeypatch):
    """The file a kernel autotuner this repo once had would have read, at
    both places it looked (`FF_KERNEL_TUNE_TABLE`, and
    `~/.cache/flexflow_tpu/kernel_tune.json`), keyed as it keyed them: for
    the engine's exact shape the impl the backend does NOT choose, for a
    flash call's exact shape tiles the static rule does not give."""
    from flexflow_tpu.search import table_store

    eng = _engine(ff, "einsum")
    op0 = eng.gen.attn_ops[0]
    wrong = ("einsum" if resolve_paged_attention_impl("auto") == "pallas"
             else "pallas")
    dtype = np.dtype(eng.kv.pool[op0.name]["k"].dtype).name
    ctx = eng.pages_per_slot * eng.page_size
    env = table_store.env_key()

    def shape(sq, sk, d, b, h, causal, dt):
        return (f"sq{sq}|sk{sk}|d{d}|b{b}|h{h}"
                f"|{'causal' if causal else 'full'}|{dt}")

    paged = (op0.qk_head_dim, eng.slots, op0.num_heads)
    flash = shape(FLASH_SEQ, FLASH_SEQ, FLASH_DIM, 1, FLASH_HEADS, True,
                  "float32")
    entries = {
        f"paged_fwd|{env}|{shape(1, ctx, *paged, True, dtype)}":
            {"impl": wrong},
        f"paged_prefill|{env}|"
        f"{shape(eng.page_size, ctx, *paged, False, dtype)}":
            {"impl": wrong},
        f"flash_fwd|{env}|{flash}": {"blocks": [128, 128]},
        f"flash_bwd|{env}|{flash}": {"blocks": [128, 128]},
    }
    home = tmp_path / "home"
    at_home = home / ".cache" / "flexflow_tpu" / "kernel_tune.json"
    at_home.parent.mkdir(parents=True)
    named = tmp_path / "named.json"
    for path in (at_home, named):
        table_store.publish(str(path), entries)
    monkeypatch.setenv("HOME", str(home))
    monkeypatch.setenv("FF_KERNEL_TUNE_TABLE", str(named))
    return wrong


@pytest.mark.parametrize("what", ["decode_impl", "prefill_impl",
                                  "flash_forward", "flash_backward"])
def test_a_planted_tune_table_is_ignored(ff, planted_table, monkeypatch,
                                         what):
    """What chooses a kernel is the backend, and what chooses a tile is the
    call's shape: no file on the machine changes what a program compiles
    to. With a table planted that names the other impl and other tiles for
    these exact shapes, the engine on `auto` and the flash kernels' entry
    points give what they give with no file."""
    from flexflow_tpu.ops import pallas_kernels as pk

    if what.endswith("_impl"):
        key = {"decode_impl": "paged_attention_impl",
               "prefill_impl": "paged_prefill_impl"}[what]
        got = _engine(ff, "auto").stats()[key]
        assert got == resolve_paged_attention_impl("auto") != planted_table
        return
    seen = {}

    def capture(*args, block_q, block_k, **kw):
        seen["blocks"] = (block_q, block_k)
        raise StopIteration  # the tiles are chosen; nothing need run

    call = {"flash_forward": "_flash_fwd_call",
            "flash_backward": "_flash_bwd_call"}[what]
    monkeypatch.setattr(pk, call, capture)
    q = jnp.zeros((1, FLASH_SEQ, FLASH_HEADS, FLASH_DIM), jnp.float32)
    with pytest.raises(StopIteration):
        if what == "flash_forward":
            pk.flash_attention_fwd_pallas(q, q, q, True, 0.25)
        else:
            lse = jnp.zeros((FLASH_HEADS, FLASH_SEQ, 8), jnp.float32)
            pk.flash_attention_bwd_pallas(q, q, q, q, lse, q, True, 0.25)
    assert seen["blocks"] == (FLASH_SEQ, FLASH_SEQ)
    assert seen["blocks"] == pk._resolve_blocks(FLASH_SEQ, FLASH_SEQ,
                                                None, None)


@pytest.mark.slow  # ~40 s: two engines, interpret-mode kernel
def test_serving_token_identity_pallas_vs_einsum(ff):
    """THE acceptance pin: a full greedy serving run — prefix cache ON,
    speculative decoding ON (self-draft: the accept path genuinely
    runs) — emits exactly the same token streams under impl='pallas'
    (interpret-mode kernel on CPU) and impl='einsum'."""
    rs = np.random.RandomState(17)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)  # 2 shared pages
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (L,)).astype(np.int32)])
               for L in (2, 5, 1, 4)] \
        + [rs.randint(1, VOCAB, (6,)).astype(np.int32)]
    outs = {}
    for impl in ("einsum", "pallas"):
        eng = ff.make_serving_engine(
            serve_slots=2, kv_page_size=4, max_seq_len=64,
            draft_model=ff, speculate_k=2, paged_attention_impl=impl)
        reqs = eng.run(prompts, max_new_tokens=5)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        outs[impl] = [np.asarray(r.tokens, np.int32) for r in reqs]
        st = eng.stats()
        assert st["paged_attention_impl"] == impl
        assert st["prefix_hits"] > 0 and st["spec_accepted"] > 0
        assert st["pages_touched"] > 0 and st["last_pages_touched"] >= 0
    for a, b in zip(outs["einsum"], outs["pallas"]):
        np.testing.assert_array_equal(
            a, b, err_msg="pallas paged-attention changed the greedy "
                          "token stream (must be a pure perf mechanism)")


@pytest.mark.slow  # ~20 s
def test_recompile_flat_with_pallas_impl(ff):
    """The one-program serving contract survives the kernel path: after
    bucket warmup, mixed same-bucket traffic through the pallas impl
    compiles nothing new."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64,
                                 paged_attention_impl="pallas")
    rs = np.random.RandomState(19)
    eng.run([rs.randint(1, VOCAB, (5,)).astype(np.int32),
             rs.randint(1, VOCAB, (12,)).astype(np.int32)],
            max_new_tokens=4)                     # warm buckets 8 + 16
    warm = eng.recompile_count
    eng.run([rs.randint(1, VOCAB, (n,)).astype(np.int32)
             for n in (6, 3, 9, 14, 2)], max_new_tokens=6)
    assert eng.recompile_count == warm, \
        "warm traffic with the pallas kernel path must not recompile"
    st = eng.stats()
    assert st["paged_attention_impl"] == "pallas"
    assert st["pages_touched"] > 0


# ---- the page stream and its ring (ISSUE 26) ------------------------------
#
# The kernel walks ONE stream of pages over all slots through a ring of VMEM
# buffers, fetching some pages ahead of the arithmetic. These cases put the
# stream's seams where the ring's are not: live pages that are no multiple of
# the ring, tables narrower than it, slots of one page, all at three ring
# depths (the depth the shapes give, and two small ones forced through the
# module's cap so that buffer indices wrap many times).


def _oracle(q, pool, table, wp, row_len, pad, scale):
    """Gather + grouped einsum over the full live rule: the
    _grouped_cache_attention math with an explicit scale, dequantizing a
    quantized pool after the gather as the einsum path does."""
    from flexflow_tpu.ops.attention import page_dequantize

    b, s, h, d = q.shape
    page = pool["k"].shape[1]
    # a packed pool's row holds 128 / d neighbouring heads: the same bytes
    kvh = pool["k"].shape[2] * pool["k"].shape[3] // d
    max_len = table.shape[1] * page
    gk, gv = pool["k"][table], pool["v"][table]
    if "k_scale" in pool:
        gk = page_dequantize(gk, pool["k_scale"][table])
        gv = page_dequantize(gv, pool["v_scale"][table])
    gk = gk.reshape(b, max_len, kvh, -1)
    gv = gv.reshape(b, max_len, kvh, -1)
    idx = jnp.arange(max_len)
    live = (idx[None, None, :] < row_len[:, None, None]) \
        | ((idx[None, None, :] >= pad[:, None, None])
           & (idx[None, None, :] <= wp[:, :, None]))
    qg = q.reshape(b, s, kvh, h // kvh, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, gk,
                        preferred_element_type=jnp.float32) * scale
    # where, not a bias: a dead position may hold anything (the poison test)
    logits = jnp.where(live[:, None, None, :, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, gv).reshape(b, s, h, -1)


def _stream_case(name):
    """(heads, kv_heads, table width, per-slot (row_len, pad, first
    frontier), slab length, quantized) at page size 4."""
    page = 4
    return {
        # live pages 3 + 1 + 5 + 2 = 11: no multiple of a ring of 2, 3 or 8
        "ragged-live-pages": (4, 2, 6, [(3, 4, 9), (1, 2, 3), (7, 8, 19),
                                        (2, 4, 6)], 1, False),
        # every slot ends inside its first page
        "one-live-page": (4, 2, 4, [(1, 1, 1), (2, 2, 3), (0, 0, 0),
                                    (3, 3, 3)], 1, False),
        # a table of 3 pages: narrower than the ring of 8, no multiple of 2
        "narrow-table": (4, 2, 3, [(5, 8, 11), (2, 4, 5), (9, 9, 10)], 1,
                         False),
        "gqa-g2": (4, 2, 5, [(3, 4, 17), (6, 8, 9)], 1, False),
        "mha-g1": (4, 4, 5, [(3, 4, 17), (6, 8, 9)], 1, False),
        # slab frontiers 6..9 and 14..17 cross from one page into the next
        "verify-straddle": (4, 2, 5, [(3, 4, 6), (10, 12, 14)], 4, False),
        "quantized": (4, 2, 6, [(3, 4, 9), (1, 2, 3), (7, 8, 19)], 1, True),
        "quantized-verify": (4, 2, 5, [(3, 4, 6), (10, 12, 14)], 3, True),
        # the engine's idle state in every slot: zeroed table rows
        "all-inactive": (4, 2, 4, [(0, 0, 0)] * 4, 1, False),
        # live pages 1, 2, 3, 4, 5: at a turn of g = 2 or 4 pages a slot of
        # one page, of g - 1, g and g + 1
        "block-edges": (4, 2, 6, [(1, 2, 2), (3, 4, 6), (9, 9, 10),
                                  (2, 4, 14), (7, 8, 18)], 1, False),
        # granite's: 8 KV heads of 64, two a 128-lane row of the pool
        # (ops/attention.py `pool_pack`), 4 rows a token
        "packed-heads-of-64": (32, 8, 6, [(3, 4, 9), (1, 2, 3), (7, 8, 19),
                                          (2, 4, 14)], 1, False),
        # Nemotron's: 2 KV heads under 8 query heads, 2 rows a token
        "two-kv-heads": (8, 2, 6, [(3, 4, 9), (1, 2, 3), (7, 8, 19),
                                   (9, 12, 15)], 1, False),
    }[name] + (page,)


STREAM_CASES = ["ragged-live-pages", "one-live-page", "narrow-table",
                "gqa-g2", "mha-g1", "verify-straddle", "quantized",
                "quantized-verify", "all-inactive", "block-edges",
                "packed-heads-of-64", "two-kv-heads"]


def _stream_inputs(name, seed):
    h, kvh, pps, slots, s, quantized, page = _stream_case(name)
    rs = np.random.RandomState(seed)
    b, d = len(slots), 64 if name == "packed-heads-of-64" else 16
    n_pages = 1 + b * pps
    kf = rs.randn(n_pages, page, kvh, d).astype(np.float32)
    vf = rs.randn(n_pages, page, kvh, d).astype(np.float32)
    if quantized:
        from flexflow_tpu.ops.attention import page_quantize, page_scale

        ks, vs = page_scale(kf, 127.0), page_scale(vf, 127.0)
        pool = {"k": page_quantize(kf, ks, 127.0, jnp.int8),
                "v": page_quantize(vf, vs, 127.0, jnp.int8),
                "k_scale": ks, "v_scale": vs}
    else:
        pool = {"k": jnp.asarray(kf), "v": jnp.asarray(vf)}
    if d == 64:
        # the pool as `init_paged_cache` holds it: (.., KVH / 2, 128)
        pool = {n: x.reshape(n_pages, page, kvh // 2, 128)
                for n, x in pool.items()}
    row_len = np.asarray([r for r, _, _ in slots], np.int32)
    pad = np.asarray([p for _, p, _ in slots], np.int32)
    wp = (np.asarray([w for _, _, w in slots], np.int32)[:, None]
          + np.arange(s, dtype=np.int32)[None, :])
    assert wp.max() < pps * page
    table = rs.permutation(np.arange(1, n_pages)).reshape(b, pps)
    last = np.maximum(wp.max(axis=1), row_len - 1) // page
    if name == "all-inactive":
        table[:] = 0
    table = table.astype(np.int32)
    q = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
    return q, pool, table, wp, row_len, pad, last


def _run_kernel(q, pool, table, wp, row_len, pad, scale=0.29):
    return paged_attention_fwd_pallas(
        q, pool["k"], pool["v"], jnp.asarray(table), jnp.asarray(wp),
        jnp.asarray(row_len), jnp.asarray(pad), scale,
        k_scales=pool.get("k_scale"), v_scales=pool.get("v_scale"))


@pytest.mark.parametrize("ring,turn", [(2, 1), (3, 1), (4, 1), (2, 2),
                                       (3, 4)])
@pytest.mark.parametrize("name", STREAM_CASES)
def test_kernel_page_stream_matches_oracle(monkeypatch, name, ring, turn):
    """Every seam of the page stream against the einsum oracle: each ring
    depth at one page a turn, and each number of pages the rule can give a
    turn under a ring that wraps inside a block's run of turns."""
    from flexflow_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_PAGED_RING_MAX", ring)
    q, pool, table, wp, row_len, pad, _ = _stream_inputs(name, 29)
    _set_turn(monkeypatch, *pool["k"].shape[1:3], table.shape[1], turn)
    out = _run_kernel(q, pool, table, wp, row_len, pad)
    want = _oracle(q, pool, jnp.asarray(table), jnp.asarray(wp),
                   jnp.asarray(row_len), jnp.asarray(pad), 0.29)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


@pytest.mark.parametrize("name,turn", [
    (name, turn) for name in ("ragged-live-pages", "verify-straddle",
                              "all-inactive") for turn in TURNS]
    + [(name, turn) for name in ("block-edges", "packed-heads-of-64",
                                 "two-kv-heads") for turn in TURNS[1:]])
def test_kernel_never_reads_a_dead_page(monkeypatch, name, turn):
    """Poison: every pool page that is live for NO slot is NaN — the
    table's entries past each slot's last live page point at such pages
    too — and the output still equals the oracle's on the clean pool:
    a dead page is neither fetched into the arithmetic nor multiplied by
    a zero probability, whether a turn takes one page or a block of them
    (a slot's pages past its last whole block go one a turn)."""
    q, pool, table, wp, row_len, pad, last = _stream_inputs(name, 31)
    _set_turn(monkeypatch, *pool["k"].shape[1:3], table.shape[1], turn)
    want = _oracle(q, pool, jnp.asarray(table), jnp.asarray(wp),
                   jnp.asarray(row_len), jnp.asarray(pad), 0.29)
    live_pages = {int(table[b, t]) for b in range(table.shape[0])
                  for t in range(int(last[b]) + 1)}
    dead = np.asarray([p for p in range(pool["k"].shape[0])
                       if p not in live_pages])
    assert len(dead) > 0
    poisoned = {n: pool[n].at[dead].set(jnp.nan) for n in ("k", "v")}
    out = _run_kernel(q, poisoned, table, wp, row_len, pad)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_ring_depth_follows_the_shapes():
    """The ring is sized from what the kernel sees (page bytes in VMEM,
    padded to the dtype's tile), never from a model's name: both serving
    cells' pages get the 4 buffers a tensor past which the chip showed
    no gain (PERF.md section 6, PR 26); a page eight times InternLM2's
    gets as many as the budget holds, and never under 2."""
    from flexflow_tpu.ops.pallas_kernels import (_PAGED_RING_BUDGET,
                                                 _paged_ring)

    def ring(ps, kvh, dqk, dv, dtype, g=1):
        return _paged_ring(g, dtype, (ps, kvh, dqk), (ps, kvh, dv))

    assert ring(128, 8, 128, 128, jnp.bfloat16) == 4    # InternLM2
    assert ring(128, 16, 128, 128, jnp.bfloat16) == 4   # OLMoE
    assert ring(128, 8, 128, 128, jnp.int8) == 4
    wide = ring(256, 32, 128, 128, jnp.bfloat16)
    assert wide == 2
    assert 2 * wide * 256 * 32 * 128 * 2 <= _PAGED_RING_BUDGET
    assert ring(256, 16, 128, 128, jnp.bfloat16) == 4
    assert ring(256, 16, 256, 128, jnp.bfloat16) == 2
    assert ring(512, 64, 256, 256, jnp.float32) == 2    # the floor
    assert ring(4, 2, 16, 16, jnp.float32) == 4         # the tests'
    # a turn of g pages is g pages of buffer: granite's block of 2 (4 rows
    # a token, padded to the 16 of a bf16 tile), Nemotron's of 4 (2 rows)
    assert ring(128, 4, 128, 128, jnp.bfloat16, g=2) == 4
    assert ring(128, 2, 128, 128, jnp.bfloat16, g=4) == 2
    # the index kernel's stream: blocks of 8 pages of (128, 128) keys
    assert _paged_ring(8, jnp.bfloat16, (128, 128)) == 4


def test_turn_pages_follow_the_shapes():
    """A turn takes the smallest power-of-two block of pages that has the
    columns (tokens x rows a token) of the turn the kernel reaches its roof
    on, from the shapes the call sees, never from a model's name: one page
    where a page has them already, never more than the index kernel's 8 nor
    than the table is wide."""
    from flexflow_tpu.ops.pallas_kernels import (dsa_index_block_tokens,
                                                 paged_turn_pages)

    assert paged_turn_pages(128, 8, 66) == 1      # InternLM2: 8 KV heads
    assert paged_turn_pages(128, 16, 32) == 1     # OLMoE: 16
    assert paged_turn_pages(128, 8, 264) == 1     # K-EXAONE, a global layer
    assert paged_turn_pages(128, 8, 2) == 1       # and a window layer's ring
    assert paged_turn_pages(128, 4, 134) == 2     # granite: 8 heads of 64
    assert paged_turn_pages(128, 2, 32) == 4      # Nemotron: 2 heads of 128
    assert paged_turn_pages(128, 8, 66) == 1      # an int8 pool of 8 heads
    assert paged_turn_pages(256, 2, 64) == 2 and paged_turn_pages(64, 8, 64) \
        == 2
    assert paged_turn_pages(4, 2, 100) == 8       # the tests': the cap
    assert [paged_turn_pages(4, 2, w) for w in (1, 2, 3, 4, 7, 8)] \
        == [1, 2, 2, 4, 4, 8]                     # and the table's width
    assert dsa_index_block_tokens(128) == 1024


# ---- paged prefill/append write kernel (ISSUE 18) -------------------------


def _quant_pool(rs, attn, n_pages=10, page=4):
    from flexflow_tpu.ops.attention import page_quantize, page_scale

    kf = jnp.asarray(rs.randn(n_pages, page, attn.num_kv_heads,
                              attn.qk_head_dim), jnp.float32)
    vf = jnp.asarray(rs.randn(n_pages, page, attn.num_kv_heads,
                              attn.v_head_dim), jnp.float32)
    ks, vs = page_scale(kf, 127.0), page_scale(vf, 127.0)
    return {
        "k": page_quantize(kf, ks, 127.0, jnp.int8),
        "v": page_quantize(vf, vs, 127.0, jnp.int8),
        "k_scale": ks, "v_scale": vs,
    }


@pytest.mark.slow  # interpret-mode kernel
@pytest.mark.parametrize("length", [5, 13, 16])
def test_prefill_write_kernel_bitwise_full_width(ff, attn, length):
    """The page-at-a-time VMEM scatter vs the einsum big-scatter oracle:
    BITWISE pool equality on every page — the written scatter list AND
    the untouched pages (the aliasing contract: a grid that only visits
    the scatter list must leave every other pool page's bytes alone).
    Ragged tails (length not a page multiple) pad exactly like the
    oracle."""
    rs = np.random.RandomState(11)
    pool = _pool(rs, attn)
    n_pages = -(-length // 4)
    kh = jnp.asarray(rs.randn(1, length, attn.num_kv_heads,
                              attn.qk_head_dim), jnp.float32)
    vh = jnp.asarray(rs.randn(1, length, attn.num_kv_heads,
                              attn.v_head_dim), jnp.float32)
    pages = np.asarray([7, 2, 9, 4][:n_pages], np.int32)
    # both arms jitted: that is how the serving prefill programs run
    # them, and what the bitwise contract is stated over
    out_e = jax.jit(lambda c, k, v: attn.paged_prefill_write(
        c, k, v, pages, impl="einsum"))(pool, kh, vh)
    out_p = jax.jit(lambda c, k, v: attn.paged_prefill_write(
        c, k, v, pages, impl="pallas"))(pool, kh, vh)
    for n in ("k", "v"):
        assert out_p[n].dtype == pool[n].dtype
        np.testing.assert_array_equal(np.asarray(out_e[n]),
                                      np.asarray(out_p[n]))
    # untouched pages kept the incoming pool bytes
    untouched = [p for p in range(10) if p not in pages.tolist()]
    np.testing.assert_array_equal(
        np.asarray(out_p["k"][np.asarray(untouched)]),
        np.asarray(pool["k"][np.asarray(untouched)]))


@pytest.mark.slow  # interpret-mode kernel
@pytest.mark.parametrize("length", [6, 16])
def test_prefill_write_kernel_bitwise_quantized(ff, attn, length):
    """Quantized pools: the kernel computes page_scale/page_quantize
    in-register (per-page amax over the slab tile) — payload AND scale
    planes must equal the oracle bitwise, scatter list and untouched
    pages alike (PR 11 published-state contract)."""
    rs = np.random.RandomState(13)
    pool = _quant_pool(rs, attn)
    n_pages = -(-length // 4)
    kh = jnp.asarray(rs.randn(1, length, attn.num_kv_heads,
                              attn.qk_head_dim), jnp.float32)
    vh = jnp.asarray(rs.randn(1, length, attn.num_kv_heads,
                              attn.v_head_dim), jnp.float32)
    pages = np.asarray([3, 8, 1, 6][:n_pages], np.int32)
    out_e = jax.jit(lambda c, k, v: attn.paged_prefill_write(
        c, k, v, pages, impl="einsum"))(pool, kh, vh)
    out_p = jax.jit(lambda c, k, v: attn.paged_prefill_write(
        c, k, v, pages, impl="pallas"))(pool, kh, vh)
    for n in ("k", "v", "k_scale", "v_scale"):
        assert out_p[n].dtype == pool[n].dtype
        np.testing.assert_array_equal(np.asarray(out_e[n]),
                                      np.asarray(out_p[n]))


# ---- a window layer's ring of pages ---------------------------------------
#
# A window layer keeps a RING of ceil(window / page) + 1 pages a slot: the
# page of sequence positions [t * page, (t + 1) * page) in column t % ring,
# rows addressed by sequence position (no bucket pad). The kernel starts a
# slot's loop and its page stream at the window's first page.

WINDOW_CASES = {
    # window: (ring, the slots' sequence positions), page size 4
    3: (2, [0, 2, 3, 4, 9, 23]),            # smaller than a page
    4: (2, [3, 4, 7, 8, 21, 0]),            # a page exactly
    9: (4, [0, 5, 8, 12, 30, 47]),          # two pages and a row
}


def _ring_positions(pos, ring, page):
    """(B, ring * page) the sequence position each row of the gathered ring
    holds: column c holds the newest logical page congruent to c."""
    last = (pos // page)[:, None]
    col = np.arange(ring)[None, :]
    logical = last - (last - col) % ring
    return (logical[:, :, None] * page
            + np.arange(page)[None, None, :]).reshape(pos.size, -1)


def _window_oracle(q, pool, table, pos, window, scale):
    from flexflow_tpu.ops.attention import page_dequantize

    b, s, h, d = q.shape
    page, kvh = pool["k"].shape[1], pool["k"].shape[2]
    gk, gv = pool["k"][table], pool["v"][table]
    if "k_scale" in pool:
        gk = page_dequantize(gk, pool["k_scale"][table])
        gv = page_dequantize(gv, pool["v_scale"][table])
    gk = gk.reshape(b, -1, kvh, gk.shape[-1])
    gv = gv.reshape(b, -1, kvh, gv.shape[-1])
    at = _ring_positions(pos, table.shape[1], page)
    live = (at >= 0) & (at <= pos[:, None]) & (at > pos[:, None] - window)
    qg = q.reshape(b, s, kvh, h // kvh, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, gk,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(jnp.asarray(live)[:, None, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, gv).reshape(b, s, h, -1)


def _window_inputs(window, seed, quantized=False):
    ring, positions = WINDOW_CASES[window]
    rs = np.random.RandomState(seed)
    page, h, kvh, d = 4, 4, 2, 16
    b = len(positions)
    n_pages = 1 + b * ring
    kf = rs.randn(n_pages, page, kvh, d).astype(np.float32)
    vf = rs.randn(n_pages, page, kvh, d).astype(np.float32)
    if quantized:
        from flexflow_tpu.ops.attention import page_quantize, page_scale

        ks, vs = page_scale(kf, 127.0), page_scale(vf, 127.0)
        pool = {"k": page_quantize(kf, ks, 127.0, jnp.int8),
                "v": page_quantize(vf, vs, 127.0, jnp.int8),
                "k_scale": ks, "v_scale": vs}
    else:
        pool = {"k": jnp.asarray(kf), "v": jnp.asarray(vf)}
    table = rs.permutation(np.arange(1, n_pages)).reshape(b, ring) \
        .astype(np.int32)
    pos = np.asarray(positions, np.int32)
    q = jnp.asarray(rs.randn(b, 1, h, d), jnp.float32)
    return q, pool, table, pos


def _run_window_kernel(q, pool, table, pos, window, scale=0.29):
    zero = jnp.zeros_like(jnp.asarray(pos))
    return paged_attention_fwd_pallas(
        q, pool["k"], pool["v"], jnp.asarray(table),
        jnp.asarray(pos)[:, None], zero, zero, scale,
        k_scales=pool.get("k_scale"), v_scales=pool.get("v_scale"),
        window=window)


@pytest.mark.parametrize("depth", [2, 8])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("window", sorted(WINDOW_CASES))
def test_window_kernel_matches_the_oracle(monkeypatch, window, quantized,
                                          depth):
    """Windows smaller than, equal to and larger than a page; slots at
    position 0, inside the first window, on a page edge, one past it, and
    after the ring has wrapped many times; the stream's buffers at two
    depths."""
    from flexflow_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "_PAGED_RING_MAX", depth)
    q, pool, table, pos = _window_inputs(window, 41, quantized)
    out = _run_window_kernel(q, pool, table, pos, window)
    want = _window_oracle(q, pool, table, pos, window, 0.29)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


@pytest.mark.parametrize("window", sorted(WINDOW_CASES))
def test_window_kernel_never_reads_a_page_below_the_window(window):
    """Poison: a column of the ring whose page lies wholly below the window
    (or was never written: a sequence shorter than its ring) is NaN, and
    the output is the oracle's on the clean pool."""
    q, pool, table, pos = _window_inputs(window, 43)
    want = _window_oracle(q, pool, table, pos, window, 0.29)
    ring, page = table.shape[1], 4
    at = _ring_positions(pos, ring, page).reshape(pos.size, ring, page)
    seen = ((at >= 0) & (at <= pos[:, None, None])
            & (at > pos[:, None, None] - window)).any(-1)
    dead = table[~seen]
    assert dead.size > 0
    poisoned = {n: pool[n].at[jnp.asarray(dead)].set(jnp.nan)
                for n in ("k", "v")}
    out = _run_window_kernel(q, poisoned, table, pos, window)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_window_kernel_idle_slots_read_the_scratch_page():
    q, pool, table, pos = _window_inputs(3, 45)
    table[:], pos[:] = 0, 0
    out = _run_window_kernel(q, pool, table, pos, 3)
    want = _window_oracle(q, pool, table, pos, 3, 0.29)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


@pytest.mark.parametrize("name", ["ragged-live-pages", "one-live-page",
                                  "quantized"])
def test_a_window_that_sees_everything_is_the_global_kernel_bit_for_bit(
        name):
    """With `window=None` the kernel takes no new operand (a static
    parameter: the call it was). A window wider than any context over the same table (a ring as wide
    as the table: column t % width is column t) walks the same pages from
    page 0 and masks nothing more: the same bits."""
    q, pool, table, wp, row_len, pad, _ = _stream_inputs(name, 29)
    # sequence positions without a bucket pad: the window path's addressing
    zero = np.zeros_like(row_len)
    assert wp.max() < (table.shape[1] - 1) * 4 + 1
    plain = paged_attention_fwd_pallas(
        q, pool["k"], pool["v"], jnp.asarray(table), jnp.asarray(wp),
        jnp.asarray(zero), jnp.asarray(zero), 0.29,
        k_scales=pool.get("k_scale"), v_scales=pool.get("v_scale"))
    wide = paged_attention_fwd_pallas(
        q, pool["k"], pool["v"], jnp.asarray(table), jnp.asarray(wp),
        jnp.asarray(zero), jnp.asarray(zero), 0.29,
        k_scales=pool.get("k_scale"), v_scales=pool.get("v_scale"),
        window=(table.shape[1] - 1) * 4 + 1)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(wide))


def test_a_ring_too_narrow_for_its_window_is_refused():
    q, pool, table, pos = _window_inputs(3, 47)
    with pytest.raises(AssertionError, match="cannot hold a window"):
        _run_window_kernel(q, pool, table, pos, 9)


# ---- a sink in the denominator; keys of 192 beside values of 128 (flat pages)


def _sink_softmax(logits, sink):
    """Softmax over the last axis with one more logit a head (kvh, grp) in
    the denominator, whose probability is dropped."""
    col = jnp.broadcast_to(sink[None, :, :, None, None],
                           logits.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([logits, col], axis=-1),
                          axis=-1)[..., :-1]


def _flat_oracle(q, pool, table, live, kvh, scale, sink=None):
    """Gather + grouped einsum over a FLAT pool ((pages, page, KVH x D): a
    token's KV heads side by side), `live` (B, S, L) given."""
    b, s, h, d = q.shape
    gk = pool["k"][table].reshape(b, -1, kvh, d)
    gv = pool["v"][table].reshape(b, gk.shape[1], kvh, -1)
    qg = q.reshape(b, s, kvh, h // kvh, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, gk,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(live[:, None, None, :, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1) if sink is None \
        else _sink_softmax(logits, sink.reshape(kvh, h // kvh))
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, gv).reshape(b, s, h, -1)


# MiMo-V2's two kinds of page at a quarter of the query heads: (kv heads,
# window, sink)
FLAT_CASES = {"global-4kv": (4, None, False), "global-4kv-sink": (4, None, True),
              "window-8kv-sink": (8, 4, True), "window-8kv": (8, 4, False)}


@pytest.mark.parametrize("turn", [1, 2])
@pytest.mark.parametrize("name", sorted(FLAT_CASES))
def test_flat_pages_of_wide_keys_and_a_sink_match_the_oracle(monkeypatch,
                                                             name, turn):
    """Keys of 192 beside values of 128 in one row a token (768 / 512 lanes
    at 4 KV heads, 1536 / 1024 at 8), 16 query heads; slots of one page, of a
    block and of a block and a tail; a sink a query head or none."""
    from flexflow_tpu.ops import pallas_kernels

    kvh, window, has_sink = FLAT_CASES[name]
    rs = np.random.RandomState(len(name))
    page, h, d, dv = 4, 16, 192, 128
    pps = 2 if window else 6
    slots = [3, 9, 22, 0] if window else [(3, 4, 9), (1, 2, 3), (7, 8, 19),
                                          (0, 0, 0)]
    b = len(slots)
    n_pages = 1 + b * pps
    pool = {"k": jnp.asarray(rs.randn(n_pages, page, kvh * d), jnp.float32),
            "v": jnp.asarray(rs.randn(n_pages, page, kvh * dv), jnp.float32)}
    table = rs.permutation(np.arange(1, n_pages)).reshape(b, pps) \
        .astype(np.int32)
    q = jnp.asarray(rs.randn(b, 1, h, d), jnp.float32)
    sink = jnp.asarray(2 * rs.randn(h), jnp.float32) if has_sink else None
    monkeypatch.setattr(pallas_kernels, "_PAGED_TURN_COLS",
                        turn * page * (kvh * d // 128))
    assert pallas_kernels.paged_turn_pages(page, 1, pps, kvh * d) == turn
    if window:
        pos = np.asarray(slots, np.int32)
        at = _ring_positions(pos, pps, page)
        live = (at >= 0) & (at <= pos[:, None]) & (at > pos[:, None] - window)
        zero = jnp.zeros_like(jnp.asarray(pos))
        args = (jnp.asarray(pos)[:, None], zero, zero)
    else:
        row_len, pad, wp = (np.asarray(x, np.int32) for x in zip(*slots))
        idx = np.arange(pps * page)
        live = (idx[None] < row_len[:, None]) \
            | ((idx[None] >= pad[:, None]) & (idx[None] <= wp[:, None]))
        args = (jnp.asarray(wp)[:, None], jnp.asarray(row_len),
                jnp.asarray(pad))
    out = paged_attention_fwd_pallas(
        q, pool["k"], pool["v"], jnp.asarray(table), *args, 0.07,
        window=window, sink=sink, kv_heads=kvh)
    want = _flat_oracle(q, pool, table, jnp.asarray(live)[:, None, :], kvh,
                        0.07, sink)
    assert out.shape == (b, 1, h, dv) and bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)
    if has_sink:    # and the sink is not nothing
        none = _flat_oracle(q, pool, table, jnp.asarray(live)[:, None, :],
                            kvh, 0.07)
        assert np.abs(np.asarray(none) - np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("window", sorted(WINDOW_CASES))
def test_window_kernel_with_a_sink_matches_the_oracle(window):
    """The sink in the kernel that existed (rows of KV heads, not flat)."""
    q, pool, table, pos = _window_inputs(window, 43)
    sink = jnp.asarray([1.5, -0.5, 0.25, 3.0], jnp.float32)
    zero = jnp.zeros_like(jnp.asarray(pos))
    out = paged_attention_fwd_pallas(
        q, pool["k"], pool["v"], jnp.asarray(table),
        jnp.asarray(pos)[:, None], zero, zero, 0.29, window=window, sink=sink)
    b, s, h, d = q.shape
    flat = {n: x.reshape(*x.shape[:2], -1) for n, x in pool.items()}
    at = _ring_positions(pos, table.shape[1], 4)
    live = (at >= 0) & (at <= pos[:, None]) & (at > pos[:, None] - window)
    want = _flat_oracle(q, flat, table, jnp.asarray(live)[:, None, :], 2,
                        0.29, sink)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_an_op_with_wide_keys_holds_flat_pages_and_both_impls_agree():
    """`MultiHeadAttention` with keys of 192 beside values of 128: its pool
    is (pages, page, KVH x D) whatever the kind of layer, the prefill write,
    the decode append and the decode attention agree between the einsum
    oracle and the kernels, and a sink and a window ride through both."""
    from flexflow_tpu.ops.attention import MultiHeadAttention

    m = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
    x = m.create_tensor([1, 8, 32], name="x")
    for kvh, window, sink in ((2, 0, None), (4, 4, 1.0)):
        op = MultiHeadAttention(
            m, f"attn_{kvh}", [x, x, x], 32, 8, kdim=8 * 192, vdim=8 * 128,
            bias=False, causal=True, num_kv_heads=kvh, rope=True,
            rope_dim=64, window=window, sink=sink, value_scale=0.707)
        assert op.pool_pack() == kvh and op.pool_pack(quantized=True) == 1
        rs = np.random.RandomState(kvh)
        params = {w.name: jnp.asarray(0.2 * rs.randn(*w.shape), jnp.float32)
                  for w in op.weights()}
        kh = jnp.asarray(rs.randn(1, 6, kvh, 192), jnp.float32)
        vh = jnp.asarray(rs.randn(1, 6, kvh, 128), jnp.float32)
        pools = {}
        for impl in ("einsum", "pallas"):
            cache = op.init_paged_cache(7, 4, jnp.float32)
            assert cache["k"].shape == (7, 4, kvh * 192)
            assert cache["v"].shape == (7, 4, kvh * 128)
            pages = jnp.asarray([3, 5], jnp.int32)
            cache = op.paged_prefill_write(cache, kh, vh, pages, impl=impl)
            got = op.gather_paged_kv(cache, pages)
            np.testing.assert_array_equal(got["k"][0, :6], kh[0])
            np.testing.assert_array_equal(got["v"][0, :6], vh[0])
            tok = jnp.asarray(np.random.RandomState(9).randn(2, 1, 32),
                              jnp.float32)
            table = jnp.asarray([[3, 5], [0, 0]], jnp.int32)
            pos = jnp.asarray([6, 0], jnp.int32)
            zero = jnp.zeros_like(pos)
            out, cache = op.paged_decode_forward(
                params, [tok, tok, tok], cache, table, pos, pos,
                jnp.asarray([6, 0], jnp.int32), jnp.asarray([6, 0], jnp.int32)
                if not window else zero, impl=impl)
            pools[impl] = (out, cache)
        (a, ca), (b, cb) = pools["einsum"], pools["pallas"]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
        np.testing.assert_array_equal(np.asarray(ca["k"]), np.asarray(cb["k"]))


# ---- a page several live slots hold, streamed once for all of them --------
#
# The shared-page form (ISSUE 49): the slots of a group hold the same pool
# pages in their first columns; `_paged_shared_kernel` streams them once a
# group and the per-slot kernel goes on from the partial state that leaves a
# member. Every case is held to the oracle, which knows nothing of groups.

# page size 4, a table of 9 pages. A slot: (document or None, row_len, pad,
# frontier); `docs`: the pages a document's holders share. `cap`: the most
# members of a group; `groups`: None for `shared_page_groups`' own answer,
# else [(members, pages)] handed to the kernel as they are
SHARED_CASES = {
    # two groups of 2, one slot that holds a document alone (a group of 1
    # is no group: it streams its pages itself), one with no document
    "pairs-and-loners": dict(
        docs={"a": 3, "b": 5, "c": 2},
        slots=[("a", 13, 16, 18), ("b", 21, 24, 27), ("a", 14, 16, 16),
               (None, 9, 12, 12), ("b", 22, 24, 24), ("c", 9, 12, 14)],
        cap=4, want=[([0, 2], 3), ([1, 4], 5)]),
    # a group of 5: three sub-blocks of two members, the last half empty
    "five-members": dict(
        docs={"a": 4},
        slots=[("a", 17, 20, 20 + i) for i in range(5)] + [(None, 3, 4, 5)],
        cap=8, want=[([0, 1, 2, 3, 4], 4)]),
    # 7 holders under a cap of 4: two groups, each streams the document
    "more-than-the-cap": dict(
        docs={"a": 3},
        slots=[("a", 13 + i % 3, 16, 17 + i) for i in range(7)],
        cap=4, want=[([0, 2, 4, 6], 3), ([1, 3, 5], 3)]),
    # nobody shares: the arrays hold no group, every grid step of the
    # shared stream idles
    "no-shared-page": dict(
        docs={},
        slots=[(None, 3, 4, 9), (None, 1, 2, 3), (None, 7, 8, 19),
               (None, 2, 4, 6)],
        cap=4, want=[]),
    # every page shared but the one the slot writes
    "all-but-the-last-page": dict(
        docs={"a": 6},
        slots=[("a", 24, 24, 24), ("a", 24, 24, 26), ("a", 24, 24, 27)],
        cap=4, want=[([0, 1, 2], 6)]),
    # one member a page past the document, one six pages
    "unequal-frontiers": dict(
        docs={"a": 2},
        slots=[("a", 9, 12, 12), ("a", 11, 12, 35), ("a", 8, 8, 21)],
        cap=4, want=[([0, 1, 2], 2)]),
    # idle slots (zeroed rows, scratch page 0) between the members
    "inactive-beside-live": dict(
        docs={"a": 3},
        slots=[(None, 0, 0, 0), ("a", 13, 16, 18), (None, 0, 0, 0),
               ("a", 14, 16, 16), (None, 0, 0, 0), (None, 5, 8, 9)],
        cap=4, want=[([1, 3], 3)]),
    # slot 2 took the document's first 2 pages only, then pages of its own:
    # by the rule it is left out of the pair that shares all 5
    "agrees-on-the-first-pages": dict(
        docs={"a": 5}, partial={2: 2},
        slots=[("a", 21, 24, 24), ("a", 22, 24, 27), ("a", 23, 24, 25)],
        cap=4, want=[([0, 1], 5)]),
    # the same tables, the three handed over as ONE group of the 2 pages
    # they all hold: the rest of the document is each member's own to stream
    "one-group-of-the-first-pages": dict(
        docs={"a": 5}, partial={2: 2},
        slots=[("a", 21, 24, 24), ("a", 22, 24, 27), ("a", 23, 24, 25)],
        cap=4, groups=[([0, 1, 2], 2)]),
}
# (query heads, KV heads, key width, value width, sink): the plain 4-d
# pool, granite's two heads of 64 a row, MiMo's flat row a token with a sink
SHARED_LAYOUTS = {"plain": (4, 2, 16, 16, False),
                  "packed-64": (8, 4, 64, 64, False),
                  "flat-192-sink": (16, 4, 192, 128, True)}


def _shared_inputs(case, layout, seed):
    page, width = 4, 9
    h, kvh, d, dv, has_sink = SHARED_LAYOUTS[layout]
    rs = np.random.RandomState(seed)
    slots = case["slots"]
    b = len(slots)
    n_pages = 1 + b * width + sum(case["docs"].values())
    k = rs.randn(n_pages, page, kvh, d).astype(np.float32)
    v = rs.randn(n_pages, page, kvh, dv).astype(np.float32)
    free = list(rs.permutation(np.arange(1, n_pages)))
    docs = {name: [free.pop() for _ in range(n)]
            for name, n in case["docs"].items()}
    table = np.zeros((b, width), np.int32)
    for s, (doc, row_len, pad, wp) in enumerate(slots):
        if wp == 0:
            continue                    # idle: the scratch page everywhere
        table[s] = [free.pop() for _ in range(width)]
        if doc is not None:
            held = case.get("partial", {}).get(s, len(docs[doc]))
            table[s, :held] = docs[doc][:held]
    row_len, pad, wp = (np.asarray(x, np.int32)
                        for x in zip(*[s[1:] for s in slots]))
    q = jnp.asarray(rs.randn(b, 1, h, d), jnp.float32)
    sink = jnp.asarray(2 * rs.randn(h), jnp.float32) if has_sink else None
    return q, k, v, table, wp[:, None], row_len, pad, sink


@pytest.mark.parametrize("layout", sorted(SHARED_LAYOUTS))
@pytest.mark.parametrize("name", sorted(SHARED_CASES))
def test_shared_pages_streamed_once_match_the_oracle(monkeypatch, name,
                                                     layout):
    """The shared-page form against the einsum oracle, on a pool whose
    pages that are live for NO slot hold NaN: groups read off the tables
    (`shared_page_groups`) or handed over, sub-blocks of two members."""
    from flexflow_tpu.ops import pallas_kernels
    from flexflow_tpu.runtime.kv_pool import shared_page_groups

    case = SHARED_CASES[name]
    h, kvh, d, dv, _ = SHARED_LAYOUTS[layout]
    page = 4
    # query rows a member puts through one matmul: a flat pool's go a KV
    # head at a time
    per = h // kvh if layout == "flat-192-sink" else h
    monkeypatch.setattr(pallas_kernels, "_SHARED_BLOCK_ROWS", 2 * per)
    assert pallas_kernels._shared_block_members(case["cap"], per) == 2
    q, k, v, table, wp, row_len, pad, sink = _shared_inputs(
        case, layout, len(name))
    b = table.shape[0]
    groups = case.get("groups")
    if groups is None:
        active = wp[:, 0] > 0
        groups = shared_page_groups(
            table, np.where(active, row_len // page, 0), case["cap"])
        assert sorted(groups) == case["want"]
    shared = pallas_kernels.pack_shared_groups(groups, b, case["cap"])
    last = np.maximum(wp.max(axis=1), row_len - 1) // page
    live_pages = {int(table[s, t]) for s in range(b)
                  for t in range(int(last[s]) + 1)}
    dead = np.asarray([p for p in range(k.shape[0]) if p not in live_pages])
    if layout == "flat-192-sink":
        pool = {"k": jnp.asarray(k.reshape(*k.shape[:2], -1)),
                "v": jnp.asarray(v.reshape(*v.shape[:2], -1))}
        idx = np.arange(table.shape[1] * page)
        live = (idx[None] < row_len[:, None]) \
            | ((idx[None] >= pad[:, None]) & (idx[None] <= wp))
        want = _flat_oracle(q, pool, table, jnp.asarray(live)[:, None, :],
                            kvh, 0.07, sink)
    else:
        pool = {"k": jnp.asarray(k), "v": jnp.asarray(v)}
        if layout == "packed-64":
            pool = {n: x.reshape(x.shape[0], page, kvh // 2, 128)
                    for n, x in pool.items()}
        want = _oracle(q, pool, jnp.asarray(table), jnp.asarray(wp),
                       jnp.asarray(row_len), jnp.asarray(pad), 0.07)
    poisoned = {n: x.at[dead].set(jnp.nan) for n, x in pool.items()}
    out = paged_attention_fwd_pallas(
        q, poisoned["k"], poisoned["v"], jnp.asarray(table), jnp.asarray(wp),
        jnp.asarray(row_len), jnp.asarray(pad), 0.07, sink=sink,
        kv_heads=kvh, shared=shared)
    assert out.shape == want.shape and bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL)


def test_shared_members_cap_follows_the_heads():
    """The most members of a group is a static of the op's heads: whole
    sub-blocks of 128 query rows, 512 rows in all."""
    from flexflow_tpu.ops import pallas_kernels as pk

    assert [pk.shared_members_cap(h) for h in (64, 40, 32, 16, 4)] \
        == [8, 12, 16, 32, 128]
    assert [pk._shared_block_members(m, h)
            for m, h in ((8, 64), (12, 40), (16, 32), (6, 4), (5, 64))] \
        == [2, 3, 4, 6, 1]


def test_shared_form_refuses_what_keeps_the_per_slot_path():
    """A verify slab, a window's ring and a quantized pool are the per-slot
    kernel's: handing them groups is a caller's error."""
    from flexflow_tpu.ops import pallas_kernels

    q, pool, table, wp, row_len, pad, _ = _stream_inputs("verify-straddle", 3)
    shared = pallas_kernels.pack_shared_groups([], table.shape[0], 2)
    with pytest.raises(AssertionError, match="shared-page form"):
        paged_attention_fwd_pallas(
            q, pool["k"], pool["v"], jnp.asarray(table), jnp.asarray(wp),
            jnp.asarray(row_len), jnp.asarray(pad), 0.29, shared=shared)
