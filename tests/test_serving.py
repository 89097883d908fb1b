"""Continuous-batching serving runtime (runtime/serving.py).

Correctness anchors:
  * greedy continuous batching is TOKEN-IDENTICAL to sequential
    per-request Generator.generate — the slot scheduler, shape buckets and
    paged cache are pure performance mechanics, never semantics;
  * the page-table gather is BITWISE the dense-cache attention;
  * decode early-exit returns exactly the full-length scan's tokens;
  * warm buckets never recompile (the counter proves it);
  * a poisoned request (FF_FAULT nan_loss@serve) retires as failed
    without stalling the rest of the batch;
  * the radix prefix cache is invisible to tokens: shared-prefix
    admissions emit exactly the cold-cache stream, copy-on-write keeps
    divergent continuations from ever touching each other's pages, and
    drain() leaves zero live refcounts;
  * speculative decoding is invisible to tokens: every emitted token is
    the TARGET's greedy argmax, at any K — the draft only changes how
    many dispatches that stream costs.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject

VOCAB = 89


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=64, layers=2,
                         heads=4, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (L,)).astype(np.int32) for L in lengths]


def test_continuous_batching_token_identical_to_sequential(ff):
    """More requests than slots, mixed lengths spanning several buckets:
    every request's emitted tokens equal its SOLO (one-request-at-a-time)
    generate run — admission order, bucket padding, page allocation and
    slot reuse never leak into the tokens."""
    prompts = _prompts(0, [5, 9, 3, 12, 7, 6, 17, 2, 11])
    eng = ff.make_serving_engine(serve_slots=3, kv_page_size=4,
                                 max_seq_len=64)
    reqs = eng.run(prompts, max_new_tokens=6)
    assert [r.state for r in reqs] == ["done"] * len(prompts)
    for r in reqs:
        solo = ff.generate(r.prompt[None, :], max_new_tokens=6)
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), solo[0, r.prompt.size:],
            err_msg=f"request {r.rid} (len {r.prompt.size}) diverged "
                    f"from its solo run")
    st = eng.stats()
    assert st["completed"] == len(prompts)
    # every page is either free or cached (warm prefix KV, refcount 0);
    # flushing the cache returns the remainder — no page leaks
    assert st["free_pages"] + st["kv_pages_cached"] == st["kv_pages"] - 1
    assert st["prefix_refs_live"] == 0
    eng.flush_prefix_cache()
    assert eng.stats()["free_pages"] == st["kv_pages"] - 1
    assert 0.0 < st["occupancy"] <= 1.0


@pytest.mark.slow  # 8 s
def test_serve_api_and_eos_retirement(ff):
    """FFModel.serve: eos retires a slot early (freeing it for the queue)
    and outputs match per-request generate with the same eos."""
    prompts = _prompts(1, [4, 6, 5, 8])
    probe = ff.generate(prompts[0][None, :], max_new_tokens=8)
    eos = int(probe[0, prompts[0].size])  # first emitted token of req 0
    outs, st = ff.serve(prompts, max_new_tokens=8, serve_slots=2,
                        kv_page_size=4, max_seq_len=64, eos_id=eos)
    assert st["completed"] == 4 and st["failed"] == 0
    for p, out in zip(prompts, outs):
        solo = ff.generate(p[None, :], max_new_tokens=8, eos_token_id=eos)
        new = solo[0, p.size:]
        hits = np.where(new == eos)[0]
        want = new[:hits[0] + 1] if hits.size else new
        np.testing.assert_array_equal(out[p.size:], want)


def test_paged_gather_matches_dense_cache_bitwise(ff):
    """paged_decode_forward through a SCRAMBLED page table must equal
    decode_forward on the equivalent contiguous cache bitwise: the gather
    reassembles the identical (B, L, KVH, Dh) operand, and the attention
    math after it is the same einsum program."""
    op = ff.make_serving_engine(max_seq_len=32).gen.attn_ops[0]
    params = {k: jnp.asarray(v) for k, v in ff.params[op.name].items()}
    rs = np.random.RandomState(3)
    b, page, n_pages = 2, 4, 4
    max_len = page * n_pages
    kvh, dqk, dv = op.num_kv_heads, op.qk_head_dim, op.v_head_dim
    dense = {
        "k": jnp.asarray(rs.randn(b, max_len, kvh, dqk), jnp.float32),
        "v": jnp.asarray(rs.randn(b, max_len, kvh, dv), jnp.float32),
    }
    x = jnp.asarray(rs.randn(b, 1, op.q_in), jnp.float32)
    pos, prompt_pad = 9, 8
    rope_pos = jnp.asarray([4, 7], jnp.int32)   # logical, not slot, pos
    row_len = jnp.asarray([3, 7], jnp.int32)

    # pool with a deliberately non-identity slot->page mapping
    table = np.array([[5, 2, 7, 1], [3, 6, 4, 8]], np.int32)
    pool = {
        "k": jnp.zeros((10, page, kvh, dqk), jnp.float32),
        "v": jnp.zeros((10, page, kvh, dv), jnp.float32),
    }
    for row in range(b):
        for p in range(n_pages):
            for name in ("k", "v"):
                pool[name] = pool[name].at[table[row, p]].set(
                    dense[name][row, p * page:(p + 1) * page])

    out_d, cache_d = op.decode_forward(
        params, [x, x, x], dense, pos, rope_pos=rope_pos,
        row_lengths=row_len, prompt_len=prompt_pad)
    out_p, cache_p = op.paged_decode_forward(
        params, [x, x, x], pool, jnp.asarray(table),
        jnp.full((b,), pos, jnp.int32), rope_pos, row_len,
        jnp.full((b,), prompt_pad, jnp.int32))
    np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
    # and the scatter wrote the SAME k/v the contiguous cache holds
    for name in ("k", "v"):
        gathered = np.asarray(cache_p[name])[table].reshape(
            b, max_len, kvh, -1)
        np.testing.assert_array_equal(np.asarray(cache_d[name]), gathered)


@pytest.mark.slow  # 11 s
def test_early_exit_identical_to_full_scan(ff):
    """The while_loop early-exit path: identical tokens (and scores) to
    the full-length scan, with and without eos; without eos_id it simply
    runs the full length."""
    rs = np.random.RandomState(5)
    prompt = rs.randint(1, VOCAB, (2, 5)).astype(np.int32)
    probe = ff.generate(prompt, max_new_tokens=8)
    eos = int(probe[0, 5])
    full = ff.generate(prompt, max_new_tokens=8, eos_token_id=eos)
    fast = ff.generate(prompt, max_new_tokens=8, eos_token_id=eos,
                       early_exit=True)
    np.testing.assert_array_equal(full, fast)

    a, sa = ff.generate(prompt, max_new_tokens=6, eos_token_id=eos,
                        return_scores=True)
    b, sb = ff.generate(prompt, max_new_tokens=6, eos_token_id=eos,
                        return_scores=True, early_exit=True)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(sa, sb, rtol=0, atol=0)

    no_eos = ff.generate(prompt, max_new_tokens=5, early_exit=True)
    np.testing.assert_array_equal(no_eos,
                                  ff.generate(prompt, max_new_tokens=5))

    # ragged prompts ride the same step body
    lengths = np.array([3, 5], np.int32)
    r_full = ff.generate(prompt, 6, eos_token_id=eos,
                         prompt_lengths=lengths)
    r_fast = ff.generate(prompt, 6, eos_token_id=eos,
                         prompt_lengths=lengths, early_exit=True)
    np.testing.assert_array_equal(r_full, r_fast)


def test_recompile_counter_flat_within_buckets(ff):
    """Power-of-two buckets: after one request has warmed a bucket, any
    mix of prompt lengths inside it (and any max_new_tokens) reuses the
    warm programs — the recompile counter must not move."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64)
    eng.run(_prompts(7, [5, 12]), max_new_tokens=4)   # warm buckets 8, 16
    warm = eng.recompile_count
    assert warm == 3  # prefill(8) + prefill(16) + the one decode program
    eng.run(_prompts(8, [6, 8, 3, 9, 16, 11, 2, 13]), max_new_tokens=7)
    assert eng.recompile_count == warm, \
        "mixed lengths within warm buckets must not recompile"
    # a NEW bucket is exactly one more prefill program
    eng.run(_prompts(9, [20]), max_new_tokens=4)
    assert eng.recompile_count == warm + 1


def test_poisoned_request_retired_without_stalling(ff, monkeypatch):
    """FF_FAULT=nan_loss@serve:3 poisons the 3rd admitted request's
    logits in-graph; the engine must retire exactly that request as
    failed (non-finite logits) while every other request completes with
    its solo-run tokens."""
    monkeypatch.setenv("FF_FAULT", "nan_loss@serve:3")
    faultinject.reset()
    try:
        prompts = _prompts(11, [5, 9, 3, 12, 7, 6])
        eng = ff.make_serving_engine(serve_slots=3, kv_page_size=4,
                                     max_seq_len=64)
        reqs = eng.run(prompts, max_new_tokens=5)
    finally:
        monkeypatch.delenv("FF_FAULT")
        faultinject.reset()
    states = [r.state for r in reqs]
    assert states[2] == "failed" and reqs[2].error == "non-finite logits"
    for i, r in enumerate(reqs):
        if i == 2:
            continue
        assert r.state == "done"
        solo = ff.generate(r.prompt[None, :], max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      solo[0, r.prompt.size:])
    # the poisoned slot's pages were freed for reuse (its prefill is
    # never published to the prefix cache); the healthy requests' full
    # pages stay cached at refcount 0 until flushed
    st = eng.stats()
    assert st["failed"] == 1
    assert st["free_pages"] + st["kv_pages_cached"] == st["kv_pages"] - 1
    assert st["prefix_refs_live"] == 0
    eng.flush_prefix_cache()
    assert eng.stats()["free_pages"] == st["kv_pages"] - 1


def test_page_pool_pressure_blocks_admission_not_progress(ff):
    """A pool too small for all slots at once: admission waits for
    retirements instead of deadlocking, and every request still finishes
    with its solo tokens."""
    # 2 slots x ceil(64/4)=16 pages would want 33; grant 21 — enough for
    # one max-size request (16+1) plus a small one, never two max-size
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, kv_pages=21)
    prompts = _prompts(13, [30, 25, 6, 28])
    reqs = eng.run(prompts, max_new_tokens=4)
    assert [r.state for r in reqs] == ["done"] * 4
    for r in reqs:
        solo = ff.generate(r.prompt[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      solo[0, r.prompt.size:])


def test_serving_validation(ff):
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=32)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(np.arange(1, 30, dtype=np.int32), max_new_tokens=16)
    with pytest.raises(ValueError, match="empty"):
        eng.submit(np.zeros((0,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=0)
    with pytest.raises(ValueError, match="kv_pages"):
        ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                               max_seq_len=32, kv_pages=4)
    with pytest.raises(ValueError, match="bucket"):
        eng2 = ff.make_serving_engine(decode_buckets=[8, 16],
                                      kv_page_size=4, max_seq_len=64)
        eng2.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=0)
    with pytest.raises(ValueError):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 decode_buckets=[16, 8])


@pytest.mark.slow  # 17 s
def test_decode_chunk_invariance(ff):
    """decode_chunk trades dispatch overhead for retirement granularity
    ONLY: any chunk size produces identical tokens — including requests
    whose eos lands mid-chunk (the in-graph over-decode is truncated by
    the host) and whose max_new_tokens is not a chunk multiple."""
    prompts = _prompts(19, [5, 9, 3, 12])
    probe = ff.generate(prompts[0][None, :], max_new_tokens=10)
    eos = int(probe[0, prompts[0].size + 2])  # eos somewhere mid-stream
    outs = {}
    for chunk in (1, 3, 16):
        eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                     max_seq_len=64, decode_chunk=chunk,
                                     eos_id=eos)
        reqs = eng.run(prompts, max_new_tokens=10)
        assert [r.state for r in reqs] == ["done"] * 4
        outs[chunk] = [np.asarray(r.tokens, np.int32) for r in reqs]
    for chunk in (3, 16):
        for a, b in zip(outs[1], outs[chunk]):
            np.testing.assert_array_equal(
                a, b, err_msg=f"decode_chunk={chunk} changed tokens")
    # and chunk=1 equals the solo batch path under the same eos
    for p, got in zip(prompts, outs[1]):
        solo = ff.generate(p[None, :], max_new_tokens=10, eos_token_id=eos)
        new = solo[0, p.size:]
        hits = np.where(new == eos)[0]
        want = new[:hits[0] + 1] if hits.size else new
        np.testing.assert_array_equal(got, want)


# ---- radix prefix cache: pure-host trie semantics (sub-second) ----------


def _trie(ps=4):
    from flexflow_tpu.runtime.kv_pool import RadixPrefixCache

    return RadixPrefixCache(ps)


def test_radix_trie_match_insert_roundtrip():
    """A published prefix is found page-aligned: full pages only, longest
    path wins, the partial last page never enters the trie."""
    pc = _trie(4)
    prompt = np.arange(1, 14, dtype=np.int32)         # 13 tokens: 3 full
    created = pc.insert(prompt, [], 0, [7, 8, 9])
    assert [n.page for n in created] == [7, 8, 9] and pc.pages == 3
    # identical prompt: all 3 pages match (cap at the last FULL page)
    assert [n.page for n in pc.match(prompt, 3)] == [7, 8, 9]
    # shares only the first 8 tokens: 2 pages
    other = prompt.copy()
    other[9] = 77
    assert [n.page for n in pc.match(other, 3)] == [7, 8]
    # a max_pages cap truncates the walk
    assert [n.page for n in pc.match(prompt, 1)] == [7]
    # nothing in common: no match
    assert pc.match(np.full((8,), 60, np.int32), 2) == []


def test_radix_trie_insert_stops_at_existing_chunk():
    """Publishing under a capped match stops at the first chunk that
    already exists — the duplicate page stays the caller's."""
    pc = _trie(4)
    prompt = np.arange(1, 13, dtype=np.int32)
    pc.insert(prompt, [], 0, [5, 6])
    # same prompt published again with different pages: nothing created
    assert pc.insert(prompt, [], 0, [11, 12]) == []
    assert pc.pages == 2
    # extend past the existing path
    m = pc.match(prompt, 3)
    created = pc.insert(prompt, m, 2, [13])
    assert [n.page for n in created] == [13] and pc.pages == 3


def test_radix_trie_refcounts_and_eviction():
    """Refcounted pages never evict; refcount-0 leaves evict LRU-first
    and cascade to exposed parents; a protected path survives."""
    pc = _trie(4)
    a = np.arange(1, 9, dtype=np.int32)
    b = np.full((4,), 50, np.int32)
    na = pc.insert(a, [], 0, [1, 2])      # chain 1 -> 2
    nb = pc.insert(b, [], 0, [3])         # leaf 3
    pc.release(na)
    pc.release(nb)
    assert pc.live_refs() == 0 and pc.pages == 3
    pc.match(a, 2)                        # touch chain a (newer last_use)
    assert pc.evict(1) == [3]             # LRU leaf goes first
    # cascade: evicting leaf 2 exposes 1
    assert sorted(pc.evict(2)) == [1, 2] and pc.pages == 0
    # refcount protection: a mounted path never evicts
    nc = pc.insert(a, [], 0, [4, 5])
    assert pc.evict(5) == [] and pc.pages == 2
    pc.release(nc)
    # protect= excludes a just-matched path about to be acquired
    assert pc.evict(5, protect=nc) == [] and pc.pages == 2
    assert sorted(pc.evict(5)) == [4, 5]
    with pytest.raises(AssertionError, match="underflow"):
        pc.release(nc)


# ---- radix prefix cache: engine semantics --------------------------------


def test_prefix_cache_token_identical_to_cold(ff):
    """Skewed shared-prefix traffic: requests sharing a system prompt hit
    the cache (prefill only the tail) yet emit exactly the tokens a
    cold-cache engine — and a solo generate run — produces. The cache is
    a perf mechanism, never semantics."""
    rs = np.random.RandomState(23)
    system = rs.randint(1, VOCAB, (12,)).astype(np.int32)  # 3 full pages
    tails = [rs.randint(1, VOCAB, (L,)).astype(np.int32)
             for L in (3, 7, 1, 5, 9)]
    prompts = [np.concatenate([system, t]) for t in tails]

    warm = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                  max_seq_len=64)
    cold = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                  max_seq_len=64, prefix_cache=False)
    w_reqs = warm.run(prompts, max_new_tokens=6)
    c_reqs = cold.run(prompts, max_new_tokens=6)
    assert [r.state for r in w_reqs] == ["done"] * len(prompts)
    for w, c in zip(w_reqs, c_reqs):
        np.testing.assert_array_equal(
            np.asarray(w.tokens, np.int32), np.asarray(c.tokens, np.int32),
            err_msg=f"prefix cache changed request {w.rid}'s tokens")
        solo = ff.generate(w.prompt[None, :], max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(w.tokens, np.int32),
                                      solo[0, w.prompt.size:])
    ws, cs = warm.stats(), cold.stats()
    # every request after the first matched the shared 12-token prefix
    assert ws["prefix_hits"] == len(prompts) - 1
    assert ws["prefill_tokens_saved"] == (len(prompts) - 1) * 12
    assert cs["prefix_lookups"] == 0 and not cs["prefix_cache"]
    # the cold engine holds nothing back; the warm one caches pages
    assert cs["free_pages"] == cs["kv_pages"] - 1
    assert ws["free_pages"] + ws["kv_pages_cached"] == ws["kv_pages"] - 1


def test_prefix_cow_isolation(ff):
    """Copy-on-write: concurrent requests mounting the same cached prefix
    write their divergent tails and decode tokens into their OWN pages —
    the donor's published pages are bitwise untouched, and every stream
    matches its solo run."""
    rs = np.random.RandomState(29)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)   # 2 full pages
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (L,)).astype(np.int32)])
               for L in (2, 6, 4, 3)]
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64)
    eng.run([prompts[0]], max_new_tokens=4)      # publish the prefix
    pc = eng.prefix_cache
    shared = []
    node = pc.root
    while node.children:
        node = next(iter(node.children.values()))
        shared.append(node.page)
    assert len(shared) >= 2                      # the 2 system pages
    shared = np.asarray(shared, np.int32)
    before = {op.name: {n: np.asarray(eng.kv.pool[op.name][n][shared])
                        for n in ("k", "v")}
              for op in eng.gen.attn_ops}

    reqs = eng.run(prompts[1:], max_new_tokens=4)
    for r in reqs:
        assert r.prefix_tokens >= 8              # mounted the shared pages
        solo = ff.generate(r.prompt[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      solo[0, r.prompt.size:])
    after = {op.name: {n: np.asarray(eng.kv.pool[op.name][n][shared])
                       for n in ("k", "v")}
             for op in eng.gen.attn_ops}
    for name, kv in before.items():
        for n in ("k", "v"):
            np.testing.assert_array_equal(
                kv[n], after[name][n],
                err_msg=f"shared page of {name}/{n} was written in place "
                        f"(copy-on-write violated)")


def test_prefix_evict_under_pressure(ff):
    """A pool sized for exactly one max request: cached pages from
    retired traffic are reclaimed (LRU) when admission needs them, and
    everything still completes with solo-identical tokens."""
    eng = ff.make_serving_engine(serve_slots=1, kv_page_size=4,
                                 max_seq_len=32, kv_pages=9)
    rs = np.random.RandomState(31)
    prompts = [rs.randint(1, VOCAB, (14,)).astype(np.int32)
               for _ in range(4)]
    reqs = eng.run(prompts, max_new_tokens=4)
    assert [r.state for r in reqs] == ["done"] * 4
    for r in reqs:
        solo = ff.generate(r.prompt[None, :], max_new_tokens=4)
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      solo[0, r.prompt.size:])
    st = eng.stats()
    assert st["prefix_evictions"] > 0, \
        "distinct 14-token prompts must force cache eviction in 9 pages"
    assert st["free_pages"] + st["kv_pages_cached"] == st["kv_pages"] - 1
    assert st["prefix_refs_live"] == 0


def test_prefix_refcounts_clean_after_drain(ff):
    """drain() with slots mid-flight: every trie refcount drops to zero,
    pages are either free or cached, and flush_prefix_cache() returns the
    pool to exactly kv_pages - 1 free (the leak check)."""
    rs = np.random.RandomState(37)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (3,)).astype(np.int32)])
               for _ in range(5)]
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, decode_chunk=2)
    for p in prompts:
        eng.submit(p, max_new_tokens=12)
    eng.step()                    # slots mid-flight, queue non-empty
    snap = eng.drain()
    assert snap["drained"] and snap["prefix_refs_live"] == 0
    assert snap["queued"] == len(prompts) - eng.slots
    st = eng.stats()
    assert st["free_pages"] + st["kv_pages_cached"] == st["kv_pages"] - 1
    freed = eng.flush_prefix_cache()
    assert freed == st["kv_pages_cached"]
    assert eng.stats()["free_pages"] == st["kv_pages"] - 1


def test_pool_exhaustion_flood_tiny_pool(ff):
    """Regression (satellite): flooding a tiny pool must never fail a
    request — admission leaves what doesn't fit in the queue and run()
    keeps making progress via retirements until the flood drains."""
    eng = ff.make_serving_engine(serve_slots=4, kv_page_size=4,
                                 max_seq_len=32, kv_pages=9)
    rs = np.random.RandomState(41)
    prompts = [rs.randint(1, VOCAB, (rs.randint(2, 15),)).astype(np.int32)
               for _ in range(12)]
    reqs = eng.run(prompts, max_new_tokens=3)
    assert [r.state for r in reqs] == ["done"] * len(prompts)
    st = eng.stats()
    assert st["failed"] == 0 and st["completed"] == len(prompts)
    assert st["free_pages"] + st["kv_pages_cached"] == st["kv_pages"] - 1


# ---- speculative decoding ------------------------------------------------


@pytest.fixture(scope="module")
def draft(ff):
    """A smaller draft LM over the SAME vocabulary (random weights — its
    proposals rarely match, which exercises the reject path hard)."""
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


@pytest.mark.slow  # 35 s
def test_speculative_greedy_token_identity(ff, draft):
    """Speculative decoding at several K — including K larger than
    max_new_tokens — emits exactly the non-speculative greedy stream.
    Two drafts: a random small model (near-0 accept rate, the all-reject
    path) and the target itself (near-1 accept rate, the long-accept
    path); the tokens must not depend on either."""
    prompts = _prompts(43, [5, 9, 3, 12])
    base = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                  max_seq_len=64)
    want = [np.asarray(r.tokens, np.int32)
            for r in base.run(prompts, max_new_tokens=5)]
    for dm in (draft, ff):
        for k in (1, 3, 8):      # 8 > max_new_tokens=5
            eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                         max_seq_len=64, draft_model=dm,
                                         speculate_k=k)
            reqs = eng.run(prompts, max_new_tokens=5)
            assert [r.state for r in reqs] == ["done"] * len(prompts)
            for w, r in zip(want, reqs):
                np.testing.assert_array_equal(
                    w, np.asarray(r.tokens, np.int32),
                    err_msg=f"speculate_k={k} draft={'self' if dm is ff else 'small'} "
                            f"changed request {r.rid}'s tokens")
            st = eng.stats()
            assert st["spec_proposed"] > 0
            if dm is ff:
                # self-draft: proposals are the target's own argmax —
                # the accept path must actually run
                assert st["spec_accepted"] > 0
            assert st["free_pages"] + st["kv_pages_cached"] \
                == st["kv_pages"] - 1


@pytest.mark.slow  # 12 s
def test_speculative_with_eos_and_prefix_cache(ff, draft):
    """eos retirement mid-verify-window truncates cleanly, and the prefix
    cache + speculation compose: identical tokens to the plain engine
    under the same eos."""
    rs = np.random.RandomState(47)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (L,)).astype(np.int32)])
               for L in (2, 5, 3)]
    probe = ff.generate(prompts[0][None, :], max_new_tokens=8)
    eos = int(probe[0, prompts[0].size + 2])
    base = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                  max_seq_len=64, eos_id=eos)
    want = [np.asarray(r.tokens, np.int32)
            for r in base.run(prompts, max_new_tokens=8)]
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, eos_id=eos,
                                 draft_model=draft, speculate_k=2)
    reqs = eng.run(prompts, max_new_tokens=8)
    for w, r in zip(want, reqs):
        np.testing.assert_array_equal(w, np.asarray(r.tokens, np.int32))
    assert eng.stats()["prefix_hits"] >= len(prompts) - 1


def test_recompile_flat_with_prefix_and_speculation(ff, draft):
    """Warm-window flatness with BOTH features on: after one pass has
    warmed the buckets (cold + hit prefills, draft mirrors, draft decode
    and verify), further same-bucket traffic compiles nothing."""
    rs = np.random.RandomState(53)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)

    def mk(n, lo, hi):
        return [np.concatenate([system, rs.randint(
            1, VOCAB, (rs.randint(lo, hi),)).astype(np.int32)])
            for _ in range(n)]

    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, draft_model=draft,
                                 speculate_k=2)
    eng.run(mk(6, 1, 8), max_new_tokens=4)      # warm bucket 16 paths
    warm = eng.recompile_count
    eng.run(mk(10, 1, 8), max_new_tokens=6)
    assert eng.recompile_count == warm, \
        "warm shared-prefix + speculative traffic must not recompile"
    st = eng.stats()
    assert st["prefix_hits"] > 0 and st["spec_proposed"] > 0


def test_speculative_validation(ff, draft):
    """The accept rule's preconditions are enforced at construction.
    (temperature > 0 + speculation is no longer an error: ISSUE 14's
    rejection-sampled speculation serves sampled requests — the sampling
    params themselves are validated instead.)"""
    with pytest.raises(ValueError, match="draft model"):
        ff.make_serving_engine(speculate_k=2)
    with pytest.raises(ValueError, match="must be >= 0"):
        ff.make_serving_engine(speculate_k=-1, draft_model=draft)
    # sampled speculation constructs fine; bad sampling params do not
    eng = ff.make_serving_engine(speculate_k=2, draft_model=draft,
                                 temperature=0.7, kv_page_size=4,
                                 max_seq_len=64)
    assert eng.speculate_k == 2 and eng.default_temperature == 0.7
    with pytest.raises(ValueError, match="temperature"):
        ff.make_serving_engine(temperature=-0.5)
    with pytest.raises(ValueError, match="top_p"):
        ff.make_serving_engine(top_p=0.0)
    with pytest.raises(ValueError, match="top_k"):
        ff.make_serving_engine(top_k=-3)


@pytest.mark.slow  # 8 s; one extra model compile
def test_speculative_vocab_mismatch_rejected(ff):
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB + 7)
    model.compile(final_tensor=logits)
    with pytest.raises(ValueError, match="vocab mismatch"):
        ff.make_serving_engine(speculate_k=2, draft_model=model)


def test_serving_config_knob_validation():
    """FFConfig __post_init__ guards + parse_args flags (satellite)."""
    with pytest.raises(ValueError, match="power of two"):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, kv_page_size=12)
    with pytest.raises(ValueError, match="serve_speculate_k"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 serve_speculate_k=-2)
    cfg = FFConfig.parse_args([
        "--batch-size", "2", "--serve-slots", "6", "--kv-page-size", "64",
        "--kv-pages", "40", "--no-prefix-cache",
        "--serve-speculate-k", "3"])
    assert cfg.serve_slots == 6 and cfg.kv_page_size == 64
    assert cfg.kv_pages == 40 and cfg.serve_prefix_cache is False
    assert cfg.serve_speculate_k == 3
    dflt = FFConfig.parse_args(["--batch-size", "2"])
    assert dflt.serve_prefix_cache is True and dflt.serve_speculate_k == 0


def test_stats_and_health_expose_pool_observability(ff):
    """The router-facing observability keys (satellite): pool occupancy,
    prefix-cache and speculation signals present in stats() AND mirrored
    in health() without compiling anything."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=32)
    st = eng.stats()
    for key in ("pages_in_use", "free_pages", "kv_pages_cached",
                "kv_pages_shared", "prefix_hit_rate", "prefix_hits",
                "prefill_tokens_saved", "prefix_evictions",
                "prefix_refs_live", "spec_accept_rate", "spec_proposed",
                "spec_accepted", "speculate_k",
                # decode-attention hot path (ISSUE 7): impl routing,
                # pages the last dispatch's attention read
                "paged_attention_impl", "pages_touched",
                "last_pages_touched"):
        assert key in st, f"stats() missing {key}"
    assert st["pages_in_use"] == 0 and st["prefix_hit_rate"] == 0.0
    assert st["paged_attention_impl"] in ("pallas", "einsum")
    assert st["pages_touched"] == 0 and st["last_pages_touched"] == 0
    before = eng.recompile_count
    h = eng.health()
    assert eng.recompile_count == before     # health never compiles
    for key in ("pages_in_use", "kv_pages_shared", "prefix_hit_rate",
                "spec_accept_rate"):
        assert key in h, f"health() missing {key}"
    assert h["status"] == "idle"


def test_engine_deadline_expires_in_queue_without_dispatch(ff):
    """submit(deadline=): a request that expires while queued retires as
    "timeout" at the next tick — no prefill, no pages, no compile (the
    engine half of the router's per-request-deadline contract). An
    unexpired sibling is untouched."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=32)
    now = time.perf_counter()
    dead = eng.submit(np.arange(1, 6, dtype=np.int32), 4, deadline=now)
    live = eng.submit(np.arange(1, 7, dtype=np.int32), 4,
                      deadline=now + 3600.0)
    free0 = eng.kv.free_pages
    eng._expire_queued()   # what _admit runs first, without the prefill
    assert dead.state == "timeout" and "deadline" in dead.error
    assert dead.tokens == [] and dead.t_done > 0
    assert live.state == "queued"
    st = eng.stats()
    assert st["timeouts"] == 1 and st["requests"] == 2
    assert eng.recompile_count == 0, "expired work must never compile"
    assert eng.kv.free_pages == free0, "expired work must hold no pages"
    assert "timeouts" in eng.health()
    # load() is the router's lock-free dispatch signal
    assert eng.load() == {"active_slots": 0, "queued": 1}


# the router drives each replica from its own thread — this pins the
# one-engine-lock contract under real contention
def test_engine_thread_safe_under_concurrent_submit(ff):
    """Concurrent-submit stress: four threads submit while the main
    thread drives step() — every request completes exactly once, the
    counters add up, and the page accounting survives (the invariants a
    torn queue/slot mutation would break)."""
    import threading

    eng = ff.make_serving_engine(serve_slots=3, kv_page_size=4,
                                 max_seq_len=64)
    per_thread, n_threads = 6, 4
    all_reqs, errs = [], []
    lock = threading.Lock()
    done_submitting = threading.Event()
    barrier = threading.Barrier(n_threads + 1)

    def submitter(seed):
        rs = np.random.RandomState(seed)
        barrier.wait()
        try:
            for _ in range(per_thread):
                p = rs.randint(1, VOCAB,
                               (int(rs.randint(2, 14)),)).astype(np.int32)
                r = eng.submit(p, int(rs.randint(2, 6)))
                with lock:
                    all_reqs.append(r)
                time.sleep(0.001 * rs.randint(0, 4))
        except Exception as e:  # noqa: BLE001 — surfaced to the assert
            with lock:
                errs.append(e)

    threads = [threading.Thread(target=submitter, args=(60 + i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    barrier.wait()

    def stepper():
        while not done_submitting.is_set() or eng.pending():
            if not eng.step():
                time.sleep(0.001)

    step_thread = threading.Thread(target=stepper)
    step_thread.start()
    for t in threads:
        t.join()
    done_submitting.set()
    step_thread.join()

    assert not errs, errs
    total = per_thread * n_threads
    assert len(all_reqs) == total
    assert [r.state for r in all_reqs] == ["done"] * total
    st = eng.stats()
    assert st["requests"] == total and st["completed"] == total
    assert st["failed"] == 0 and st["timeouts"] == 0
    assert st["free_pages"] + st["kv_pages_cached"] == st["kv_pages"] - 1
    assert st["prefix_refs_live"] == 0
    # spot-check token identity through the contention
    for r in all_reqs[::7]:
        solo = ff.generate(r.prompt[None, :],
                           max_new_tokens=r.max_new_tokens)
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      solo[0, r.prompt.size:])


@pytest.mark.slow  # 7 s
def test_explicit_buckets_and_per_request_max_new(ff):
    """Pinned decode_buckets honor their boundaries; per-request
    max_new_tokens mixes freely in one batch."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, decode_buckets=[8, 24])
    rs = np.random.RandomState(17)
    reqs = [eng.submit(rs.randint(1, VOCAB, (L,)).astype(np.int32), m)
            for L, m in [(5, 3), (20, 6), (8, 2), (11, 5)]]
    assert [r.bucket for r in reqs] == [8, 24, 8, 24]
    while eng.step():
        pass
    for r in reqs:
        assert r.state == "done" and len(r.tokens) == r.max_new_tokens
        solo = ff.generate(r.prompt[None, :],
                           max_new_tokens=r.max_new_tokens)
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32),
                                      solo[0, r.prompt.size:])


# ---- a page several live slots hold, streamed once (ISSUE 49) -------------


def _doc_questions(seed):
    """Two documents of whole pages (6 and 4 pages of 4) and five questions
    that begin with one of them."""
    rs = np.random.RandomState(seed)
    docs = [rs.randint(1, VOCAB, (24,)).astype(np.int32),
            rs.randint(1, VOCAB, (16,)).astype(np.int32)]
    asks = [np.concatenate([docs[i % 2], rs.randint(1, VOCAB, (3 + i,))])
            .astype(np.int32) for i in range(5)]
    return docs, asks


def _hand_counts(eng, k):
    """What the NEXT decode dispatch of `k` steps attends, must read and
    would stream with every document fetched once for its holders, counted
    from the page tables slot by slot and page by page: (attended pages,
    distinct pages, streamed pages, first step's attended tokens, first
    step's distinct tokens, groups)."""
    ps = eng.page_size
    live = [s for s in range(eng.slots) if eng.active[s]]
    wp = {s: int(eng.prompt_pad[s] + eng.emitted[s] - 1) for s in live}
    last = {s: eng.slot_req[s].bucket + eng.slot_req[s].max_new_tokens - 1
            for s in live}
    # a slot's document: the whole pages of its prompt another slot holds too
    holders = {}
    for s in live:
        run = tuple(int(p) for p in eng.page_tables[s, :eng.row_len[s] // ps])
        for t in live:
            if t != s:
                other = eng.page_tables[t, :eng.row_len[t] // ps]
                n = 0
                while n < min(len(run), len(other)) and run[n] == other[n]:
                    n += 1
                if n:
                    holders.setdefault(run[:n], set()).update((s, t))
    attended = distinct = streamed = 0
    for i in range(k):
        pages = {s: min(wp[s] + i, last[s]) // ps + 1 for s in live}
        attended += sum(pages.values())
        distinct += len({int(eng.page_tables[s, c])
                         for s in live for c in range(pages[s])})
        streamed += sum(pages.values()) - sum(
            (len(m) - 1) * len(run) for run, m in holders.items())
    first = {s: min(wp[s], last[s]) + 1 for s in live}
    dup = sum((len(m) - 1) * len(run) for run, m in holders.items())
    return (attended, distinct, streamed, sum(first.values()),
            sum(first.values()) - dup * ps, len(holders))


@pytest.mark.parametrize("impl", ["pallas", "einsum"])
def test_shared_documents_are_streamed_once_and_read_the_same(ff, impl):
    """Two seated documents, five concurrent questions: every request
    emits the tokens it emits ALONE, the documents' pages are bitwise what
    they were, and the dispatch's counts are the hand count: each distinct
    page once in `context_tokens` / `kv_read_bytes`, a document fetched
    once for its holders in `kv_streamed_bytes` / `shared_pages_saved`
    where the kernel forms groups (`pallas`), once a slot where the einsum
    gathers (`kv_streamed_bytes` == `kv_attended_bytes`, no group)."""
    from flexflow_tpu.runtime import telemetry

    docs, asks = _doc_questions(7)
    eng = ff.make_serving_engine(serve_slots=6, kv_page_size=4, kv_pages=80,
                                 max_seq_len=64, decode_chunk=2,
                                 prefix_cache=True,
                                 paged_attention_impl=impl)
    for d in docs:
        eng.prefill_into_cache(d)
    held = sorted({int(p) for n in eng.prefix_cache._iter_nodes()
                   for p in [n.page]})
    assert len(held) == 10
    before = {op: {n: np.asarray(x[np.asarray(held)])
                   for n, x in eng.kv.pool[op].items()}
              for op in eng.kv.pool}
    reqs = [eng.submit(a, 6) for a in asks]
    eng.step()                      # all five seated, one dispatch done
    assert int(eng.active.sum()) == 5
    k, page_bytes = 2, 4 * eng.stats()["kv_bytes_per_token"]
    attended, distinct, streamed, ctx, ctx_distinct, groups = \
        _hand_counts(eng, k)
    assert groups == 2 and distinct < attended
    st0 = eng.stats()
    eng.step()
    st1 = eng.stats()
    moved = {n: st1[n] - st0[n] for n in (
        "kv_attended_bytes", "kv_read_bytes", "kv_streamed_bytes",
        "shared_groups", "shared_pages_saved")}
    sharing = impl == "pallas"
    assert moved == {
        "kv_attended_bytes": attended * page_bytes,
        "kv_read_bytes": distinct * page_bytes,
        "kv_streamed_bytes": (streamed if sharing else attended) * page_bytes,
        "shared_groups": groups if sharing else 0,
        "shared_pages_saved": attended - streamed if sharing else 0}
    span = telemetry.tracer().events(name="decode_dispatch")[-1]["args"]
    assert span["context_tokens"] == ctx_distinct
    assert span["context_tokens_attended"] == ctx
    assert span["shared_groups"] == moved["shared_groups"]
    assert span["shared_pages_saved"] == moved["shared_pages_saved"]
    assert span["program"] == ("decode_k2_shared6" if sharing
                               else "decode_k2")
    while eng.pending():
        eng.step()
    for r in reqs:
        solo = ff.generate(r.prompt[None, :], max_new_tokens=6)
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), solo[0, r.prompt.size:],
            err_msg=f"request {r.rid} diverged from its solo run")
    for op, arrays in before.items():
        for n, x in arrays.items():
            np.testing.assert_array_equal(
                np.asarray(eng.kv.pool[op][n][np.asarray(held)]), x,
                err_msg=f"{op}/{n}: a document's page changed")
    assert eng.stats()["prefix_refs_live"] == 0


def test_no_shared_page_counts_and_program_are_the_per_slot_ones(ff):
    """Distinct prompts under a prefix cache: nobody hits, so the decode
    program's key is the per-slot one and every count is the per-slot
    formula: attended = read = streamed, no group, nothing saved."""
    from flexflow_tpu.runtime import telemetry

    eng = ff.make_serving_engine(serve_slots=4, kv_page_size=4,
                                 max_seq_len=64, decode_chunk=2,
                                 prefix_cache=True,
                                 paged_attention_impl="pallas")
    eng.run(_prompts(11, [9, 13, 6, 10]), max_new_tokens=5)
    st = eng.stats()
    assert st["shared_members_cap"] == 4
    assert [k for k in eng._programs if k[0] == "decode"] == [("decode", 2)]
    assert st["kv_attended_bytes"] == st["kv_read_bytes"] \
        == st["kv_streamed_bytes"] > 0
    assert st["shared_groups"] == st["shared_pages_saved"] == 0
    for e in telemetry.tracer().events(name="decode_dispatch")[-2:]:
        a = e["args"]
        assert a["context_tokens"] == a["context_tokens_attended"]
        assert a["kv_read_bytes"] == a["kv_streamed_bytes"] \
            == a["kv_attended_bytes"]
        assert a["shared_groups"] == a["shared_pages_saved"] == 0
        assert a["program"] == "decode_k2"


def test_shared_page_groups_follow_the_tables():
    """`shared_page_groups`: rows that begin alike are one group, a slot
    alone or idle is none, a group over the cap is split evenly, and a
    slot that shares a document's first pages only is left out of the
    group that shares all of them."""
    from flexflow_tpu.runtime.kv_pool import shared_page_groups

    t = np.zeros((8, 6), np.int32)
    t[0] = [11, 12, 13, 14, 40, 41]
    t[1] = [11, 12, 13, 14, 42, 43]
    t[2] = [21, 22, 44, 45, 46, 47]
    t[3] = [11, 12, 13, 14, 48, 49]
    t[4] = [21, 22, 50, 51, 52, 53]
    t[5] = [11, 12, 54, 55, 56, 57]     # the first two pages only
    t[6] = [31, 32, 33, 58, 59, 60]     # alone with its document
    share = np.asarray([4, 4, 2, 4, 2, 4, 3, 0])
    assert sorted(shared_page_groups(t, share, 8)) == [
        ([0, 1, 3], 4), ([2, 4], 2)]
    assert sorted(shared_page_groups(t, share, 2)) == [
        ([0, 3], 4), ([2, 4], 2)]
    assert shared_page_groups(t, share, 1) == []
    assert shared_page_groups(t, np.zeros(8, int), 8) == []
    # without the three whole-document holders the two-page run IS the
    # longest there is
    share[[0, 1]] = 0
    assert sorted(shared_page_groups(t, share, 8)) == [
        ([2, 4], 2), ([3, 5], 2)]
