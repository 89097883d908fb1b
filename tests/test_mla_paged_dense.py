"""A latent attention WITHOUT an indexer through the paged pool (ops/mla.py,
PR 53): one pool of latent rows on the page table, the dense core
`mla_paged_core_dense` (ops/pallas_kernels.py, interpret mode) against the
einsum page-gather oracle, the page movers on the pools the op has, the two
LoRA scales, and the refusals that remain. float32 on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.ops import pallas_kernels as pk
from flexflow_tpu.ops.mla import LatentAttention

D, H, RQ, C, DN, DR, DV = 64, 4, 48, 32, 32, 16, 32


def attention_op(batch=3, seq=1, sq=1.0, skv=1.0, topk=None):
    ff = FFModel(FFConfig(batch_size=batch, mesh_shape={"data": 1}))
    x = ff.create_tensor([batch, seq, D], name="x")
    index = (4, 32, topk) if topk else (None, None, None)
    op = LatentAttention(ff, "attn", [x], D, H, RQ, C, DN, DR, DV, *index,
                         rope_theta=1e7, q_lora_scale=sq, kv_lora_scale=skv)
    rs = np.random.RandomState(1)
    params = {}
    for w in op.weight_specs():
        scale = {"one": 0.3, "zero": 0.3}.get(w.init, w.shape[0] ** -0.5)
        params[w.name] = jnp.asarray(
            (w.init == "one") + scale * rs.randn(*w.shape), jnp.float32)
    return op, params, jnp.asarray(rs.randn(batch, seq, D), jnp.float32)


# slots over a pool of pages of 8 tokens, tables 12 pages wide (a turn of the
# kernel takes 4): write_pos, row_len, prompt_pad a slot
CASES = {
    # 43 tokens with a hole of padding at 30..31 (one whole block of 4 pages
    # and two tail pages), 9 tokens (tail pages only), an inactive slot
    "ragged": ([42, 8, 0], [30, 5, 0], [32, 8, 0]),
    # a context of exactly one page, of one token, of one whole block
    "one_page": ([7, 0, 31], [0, 0, 0], [0, 0, 0]),
    # no multiple of the block: 4 pages + 3, 8 pages + 1 token
    "off_block": ([54, 64, 37], [50, 0, 20], [52, 0, 24]),
    # two slots read the same document's pages and append to their own
    "shared_doc": ([42, 35, 0], [30, 30, 0], [32, 32, 0]),
}


def paged_state(op, case):
    rs = np.random.RandomState(4)
    pool = {n: jnp.asarray(rs.randn(*a.shape), jnp.float32)
            for n, a in op.init_paged_cache(40, 8, jnp.float32).items()}
    pool["lat"] = pool["lat"].at[..., C + DR:].set(0.0)
    table = np.zeros((3, 12), np.int32)
    table[0, :9] = [3, 7, 1, 12, 9, 20, 22, 23, 24]
    table[1, :9] = [5, 2, 14, 15, 16, 17, 18, 19, 21]
    table[2, :5] = [30, 31, 32, 33, 34]
    if case == "shared_doc":
        table[1, :5] = [3, 7, 1, 12, 5]
    wp, rl, pp = (np.asarray(v, np.int32) for v in CASES[case])
    return pool, jnp.asarray(table), wp, rl, pp


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_core_matches_the_einsum_oracle(case):
    """The kernel walks each slot's live pages through its table (whole
    blocks, then the tail a page at a time); the oracle gathers the table's
    pages and runs the blocked XLA attention under the same live rule. Same
    appended rows, same output."""
    op, params, x = attention_op(sq=2.0, skv=1.7)
    pool, table, wp, rl, pp = paged_state(op, case)
    assert pk.mla_dense_turn_pages(table.shape[1]) == 4
    args = (params, [x], pool, table, jnp.asarray(wp),
            jnp.asarray(np.maximum(wp - 2, 0)), jnp.asarray(rl),
            jnp.asarray(pp))
    want, pool_e = op.paged_decode_forward(*args, impl="einsum")
    got, pool_p = op.paged_decode_forward(*args, impl="pallas")
    assert set(pool_p) == {"lat"}
    np.testing.assert_array_equal(pool_p["lat"], pool_e["lat"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_dense_core_reads_no_page_past_the_last_live_one():
    """A dead page may hold anything (0 x NaN in p v is NaN): every pool
    page that is no slot's live page holds NaN, the scratch page and the
    pages behind a slot's last live one included, and the result is
    finite."""
    op, params, x = attention_op()
    pool, table, wp, rl, pp = paged_state(op, "off_block")
    live = {int(p) for b in range(3)
            for p in np.asarray(table)[b, :max(wp[b], rl[b] - 1) // 8 + 1]}
    dead = jnp.asarray(sorted(set(range(40)) - live))
    pool = {"lat": pool["lat"].at[dead].set(jnp.nan)}
    q = op._absorb(params, *(jnp.asarray(np.random.RandomState(2).randn(
        3, H, d), jnp.float32) for d in (DN, DR)))
    out = pk.mla_dense_core_pallas(
        q, pool["lat"], table, jnp.asarray(wp), jnp.asarray(rl),
        jnp.asarray(pp), scale=op.scale, c=C)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("width, want", [(1, 1), (3, 2), (4, 4), (264, 4)])
def test_turn_pages_follow_the_table(width, want):
    assert pk.mla_dense_turn_pages(width) == want


def test_lora_scales_multiply_the_query_and_the_normed_latent():
    """`q_lora_scale` multiplies both parts of the projected query (before
    the rotary), `kv_lora_scale` the normalised latent and NOT the rotary
    key; the cached row holds the scaled latent."""
    plain, params, _ = attention_op(batch=2, seq=6)
    scaled, _, x = attention_op(batch=2, seq=6, sq=2.0, skv=3.0)
    pos = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (2, 6))
    a, b = plain._project(params, x, pos), scaled._project(params, x, pos)
    np.testing.assert_allclose(b["q_nope"], 2.0 * a["q_nope"], rtol=1e-6)
    np.testing.assert_allclose(b["q_rope"], 2.0 * a["q_rope"], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(b["lat"][..., :C], 3.0 * a["lat"][..., :C],
                               rtol=1e-6)
    np.testing.assert_array_equal(b["lat"][..., C:], a["lat"][..., C:])
    # absorbed (cache) and expanded (forward) forms agree under the scales
    expanded = scaled.forward(params, [x])[0]
    absorbed, cache = scaled.prefill_forward(
        params, [x], scaled.init_cache(2, 6, jnp.float32))
    # outputs of order 25 under these scales: float32 rounding is relative
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(cache["lat"], b["lat"])
    assert np.abs(np.asarray(expanded - plain.forward(params, [x])[0])).max() \
        > 1e-2


def test_page_movers_work_on_the_pools_the_op_has():
    """scatter_cache_tail, export_page / import_page and gather_paged_kv of
    an op without an indexer move `lat` alone; with one, `lat` and `ki`."""
    for topk, names in ((None, {"lat"}), (16, {"lat", "ki"})):
        op, params, x = attention_op(batch=1, seq=20, topk=topk)
        assert set(op.init_paged_cache(6, 8, jnp.float32)) == names
        _, cache = op.prefill_forward(params, [x],
                                      op.init_cache(1, 24, jnp.float32))
        pool = op.scatter_cache_tail(op.init_paged_cache(6, 8, jnp.float32),
                                     cache, 8, jnp.asarray([4, 2]))
        assert set(pool) == names
        for n in names:
            np.testing.assert_array_equal(pool[n][4], cache[n][0, 8:16])
            np.testing.assert_array_equal(pool[n][2], cache[n][0, 16:24])
        payload = op.export_page(pool, 4)
        moved = op.import_page(pool, 5, payload)
        got = op.gather_paged_kv(moved, jnp.asarray([5, 2]))
        for n in names:
            np.testing.assert_array_equal(moved[n][5], pool[n][4])
            np.testing.assert_array_equal(got[n][0], cache[n][0, 8:24])


def test_what_the_engine_still_refuses_says_so():
    """Speculative verify over a latent pool and a quantized latent cache
    stay refused, each with its message; the pool without an indexer is no
    longer."""
    op, params, x = attention_op()
    op.init_paged_cache(4, 8, jnp.float32)
    with pytest.raises(NotImplementedError,
                       match="speculative verify over a latent cache"):
        op.paged_verify_forward()
    with pytest.raises(NotImplementedError,
                       match="quantized latent cache"):
        op.init_paged_cache(4, 8, jnp.float32, kv_dtype="int8")
    assert op.decode_span_counts(np.zeros((0, 1), np.int64), 8) == {}
    assert op.cache_bytes_per_token() == op.lat_width * 2
