"""Chunk-interleaved prefill admission (ISSUE 18 tentpole, layer a).

A cold prompt longer than one ``prefill_chunk`` no longer prefills to
completion at admission: under ``FFConfig.prefill_interleave_chunks``
its chunks become schedulable quanta interleaved with decode ticks, so
a monster prompt cannot head-of-line-block the replica's decode
streams. Pinned here:

  * token identity — interleaved admission emits exactly the
    run-to-completion stream (greedy AND sampled, einsum AND pallas
    write impls, full-width AND int8 pools): the chunk programs are
    iteration-for-iteration Generator._prefill's ragged chunked loop;
  * the kv_pages default derive leaves prefix-cache slack (the PR 11
    zero-slack finding, fixed here) and logs the split;
  * mid-prefill deadline/fault/drain legs — a slot parked between
    chunks retires/completes exactly like an active one;
  * observability — the new stats keys and the inter-token histogram.

Sequence-parallel prefill (layer b) is pinned in test_seq_parallel.py;
the Pallas write kernel (layer c) in test_pallas_paged.py.
"""

import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject

VOCAB = 61
PS = 4


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=2,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()
    yield
    faultinject.reset()


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (L,)).astype(np.int32) for L in lengths]


# ---- knobs and validation (host-side, tier-1 fast) ------------------------


def test_longctx_knob_validation():
    with pytest.raises(ValueError, match="prefill_interleave_chunks"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 prefill_interleave_chunks=-1)
    with pytest.raises(ValueError, match="seq_parallel_shards"):
        FFConfig(batch_size=2, mesh_shape={"data": 1},
                 seq_parallel_shards=1)
    cfg = FFConfig.parse_args(
        ["--prefill-interleave-chunks", "2", "--seq-parallel-shards", "2",
         "--batch-size", "2"])
    assert cfg.prefill_interleave_chunks == 2
    assert cfg.seq_parallel_shards == 2


@pytest.mark.slow  # model fixture
def test_longctx_engine_router_validation(ff):
    # the chunk is the interleave quantum: interleaving without chunked
    # prefill has no unit of work to schedule
    with pytest.raises(ValueError, match="prefill_chunk"):
        ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                               max_seq_len=32, prefill_chunk=0,
                               prefill_interleave_chunks=1)
    with pytest.raises(ValueError, match="seq_parallel_shards"):
        ff.make_serving_router(replicas=2, roles="prefill,decode",
                               seq_parallel_shards=1, max_seq_len=32,
                               start=False)


@pytest.mark.slow  # builds 4 engines
def test_kv_pages_default_derive_leaves_prefix_slack(ff):
    """The PR 11 finding, fixed: the derived pool must leave slack
    beyond the slots' own pages, or every published prefix page fights
    the next admission and the cache silently goes cold. Derive = 1
    scratch + slots * pages_per_slot + max(pages_per_slot,
    slot_pages // 2) when the prefix cache is on."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=32)
    # pages_per_slot = 32/4 = 8; slots 2 -> slot pages 16; slack 8
    assert eng.pages_per_slot == 8
    assert eng.num_pages == 1 + 16 + 8
    # no prefix cache -> nothing to leave slack for
    bare = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                  max_seq_len=32, prefix_cache=False)
    assert bare.num_pages == 1 + 16
    # an explicit kv_pages is always honored verbatim
    pinned = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                    max_seq_len=32, kv_pages=40)
    assert pinned.num_pages == 40
    # big slot counts get at least half the slot pages as slack
    wide = ff.make_serving_engine(serve_slots=4, kv_page_size=PS,
                                  max_seq_len=32)
    assert wide.num_pages == 1 + 32 + 16


def test_longctx_stats_keys_pinned(ff):
    eng = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    st = eng.stats()
    for key in ("prefill_interleave_chunks", "prefill_chunks_interleaved",
                "prefill_preempted_ticks", "prefill_partial_slots",
                "partial_slab_imports"):
        assert key in st, key
    assert st["prefill_interleave_chunks"] == 1
    assert st["prefill_chunks_interleaved"] == 0
    reqs = eng.run(_prompts(7, [11]), max_new_tokens=3)
    assert reqs[0].state == "done"
    st = eng.stats()
    assert st["prefill_chunks_interleaved"] == 4   # bucket 16 / chunk 4
    assert st["prefill_partial_slots"] == 0


# ---- token identity -------------------------------------------------------


def test_interleaved_prefill_token_identical(ff):
    """Interleaved admission vs run-to-completion, greedy and sampled,
    more requests than slots so mid-prefill slots coexist with live
    decode streams: every emitted stream must be identical — the chunk
    quanta replay Generator._prefill's exact loop, so scheduling is
    invisible in the tokens."""
    prompts = _prompts(17, [13, 5, 11, 9, 14, 3, 7])
    base = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                  max_seq_len=32, prefill_chunk=PS)
    want = [list(r.tokens) for r in base.run(prompts, max_new_tokens=5)]
    for budget in (1, 2):
        eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                     max_seq_len=32, prefill_chunk=PS,
                                     prefill_interleave_chunks=budget)
        got = [list(r.tokens) for r in eng.run(prompts, max_new_tokens=5)]
        assert got == want, f"budget {budget} changed a greedy stream"
        st = eng.stats()
        assert st["prefill_chunks_interleaved"] > 0
        assert st["prefill_partial_slots"] == 0
    # sampled: same seeds -> same streams regardless of scheduling
    kw = dict(temperature=0.9, top_p=0.8, top_k=7)
    want_s = [list(r.tokens) for r in base.run(
        prompts, max_new_tokens=5, seed=123, **kw)]
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    got_s = [list(r.tokens) for r in eng.run(
        prompts, max_new_tokens=5, seed=123, **kw)]
    assert got_s == want_s, "interleaving changed a sampled stream"


@pytest.mark.slow  # ~15 s
def test_interleaved_prefill_identity_int8_and_pallas(ff):
    """The same identity under an int8 pool and the pallas write impl:
    the interleaved final scatter must land bitwise the pages the
    run-to-completion program lands (scales included), so the streams
    cannot diverge."""
    prompts = _prompts(19, [12, 6, 9])
    for kw in (dict(kv_cache_dtype="int8"),
               dict(paged_attention_impl="pallas"),
               dict(kv_cache_dtype="int8",
                    paged_attention_impl="pallas")):
        base = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                      max_seq_len=32, prefill_chunk=PS,
                                      **kw)
        want = [list(r.tokens)
                for r in base.run(prompts, max_new_tokens=4)]
        eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                     max_seq_len=32, prefill_chunk=PS,
                                     prefill_interleave_chunks=1, **kw)
        got = [list(r.tokens) for r in eng.run(prompts, max_new_tokens=4)]
        assert got == want, f"interleave changed a stream under {kw}"


@pytest.mark.parametrize("budget", [1, 0], ids=["interleaved",
                                                "run_to_completion"])
def test_decode_stream_and_a_long_prompts_prefill(ff, budget):
    """The head-of-line contract, in ticks, not seconds: a stream is
    mid-decode when a long prompt arrives. Interleaved admission spends
    one chunk a tick, so the stream emits a token on every tick the long
    prompt's slot is mid-prefill; run-to-completion admission never
    leaves a slot mid-prefill between ticks (the whole prefill sits in
    one tick, in front of the stream). The streams are the same."""
    stream, long = _prompts(37, [5, 14])
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=budget,
                                 prefix_cache=False, decode_chunk=1)
    fr = eng.submit(stream, max_new_tokens=12)
    while len(fr.tokens) < 2:          # a live stream, not a cold start
        eng.step()
    mr = eng.submit(long, max_new_tokens=3)
    mid_prefill_ticks = stream_tokens_meanwhile = 0
    while {fr.state, mr.state} - {"done", "failed", "timeout"}:
        before = len(fr.tokens)
        eng.step()
        if eng.stats()["prefill_partial_slots"]:
            mid_prefill_ticks += 1
            stream_tokens_meanwhile += len(fr.tokens) - before
    assert (fr.state, mr.state) == ("done", "done")
    if budget:
        assert mid_prefill_ticks >= 16 // PS - 1   # bucket 16, a chunk a tick
        assert stream_tokens_meanwhile >= mid_prefill_ticks
    else:
        assert mid_prefill_ticks == 0
    solo = [list(r.tokens) for r in ff.make_serving_engine(
        serve_slots=2, kv_page_size=PS, max_seq_len=32, prefill_chunk=PS,
        prefix_cache=False).run([stream, long], max_new_tokens=12)]
    assert list(fr.tokens) == solo[0] and list(mr.tokens) == solo[1][:3]


# ---- mid-prefill deadline / fault / drain legs ----------------------------


@pytest.mark.slow  # model fixture
def test_mid_prefill_deadline_expires(ff):
    eng = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    req = eng.submit(_prompts(23, [13])[0], max_new_tokens=4,
                     deadline=time.perf_counter() + 60.0)
    eng.step()                       # admit + first chunk
    assert eng.stats()["prefill_partial_slots"] == 1
    req.deadline = time.perf_counter() - 0.001
    eng.step()                       # deadline sweep fires pre-budget
    assert req.state == "timeout"
    assert eng.stats()["prefill_partial_slots"] == 0
    assert eng.stats()["timeouts"] == 1
    # the slot and its pages are reusable: a follow-up completes
    done = eng.run(_prompts(24, [9, 5]), max_new_tokens=4)
    assert [r.state for r in done] == ["done", "done"]


@pytest.mark.slow  # model fixture
def test_mid_prefill_nan_poison_fails_request(ff, monkeypatch):
    """The nan_loss drill must catch an interleaved admission too: the
    poison rides the slot-resident partial state into the FINAL chunk's
    logits, the request retires "failed", and the engine keeps
    serving."""
    monkeypatch.setenv("FF_FAULT", "nan_loss@serve:1")
    faultinject.reset()
    eng = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    prompts = _prompts(29, [13, 7])
    reqs = eng.run(prompts, max_new_tokens=4)
    assert reqs[0].state == "failed"
    assert "non-finite" in reqs[0].error
    assert reqs[1].state == "done"
    assert eng.stats()["failed"] == 1


@pytest.mark.slow  # model fixture
def test_drain_completes_mid_prefill_slots(ff):
    """An admitted request is never cancelled: drain() must keep
    spending prefill quanta until mid-prefill slots finish and decode
    out, even though admission is closed."""
    eng = ff.make_serving_engine(serve_slots=1, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    req = eng.submit(_prompts(31, [13])[0], max_new_tokens=3)
    eng.step()                       # admit + first chunk only
    assert eng.stats()["prefill_partial_slots"] == 1
    st = eng.drain()
    assert req.state == "done" and len(req.tokens) == 3
    assert st["drained"] and eng.stats()["prefill_partial_slots"] == 0


def test_interleave_emits_intertoken_histogram(ff):
    """The inter-token histogram (the head-of-line metric this ISSUE
    exists to flatten) must keep counting under interleaved admission."""
    from flexflow_tpu.runtime import telemetry

    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=PS,
                                 max_seq_len=32, prefill_chunk=PS,
                                 prefill_interleave_chunks=1)
    eng.set_telemetry_identity("lc0", "longctx-test")
    reqs = eng.run(_prompts(37, [11, 6]), max_new_tokens=4)
    assert all(r.state == "done" for r in reqs)
    itl = telemetry.registry().histogram(
        "ff_serving_intertoken_seconds", labels=("replica", "role"))
    assert itl.labels("lc0", "longctx-test").count == 2 * 3
