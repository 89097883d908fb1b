#!/usr/bin/env python
"""CI long-context serving smoke (ci/run_ci.sh `longctx` tier).

Phase 1 — chunk-interleaved admission under a decode flood: a decode
stream is mid-flight when a MAXIMAL prompt (the largest the engine
admits) arrives. Run-to-completion admission stalls the stream for the
whole multi-chunk prefill; interleaved admission spends one chunk per
tick. Proves the ISSUE-18 head-of-line acceptance end to end on CPU:

  * the flood stream's worst inter-token gap shrinks with interleave ON
    vs OFF while the maximal prompt admits (same warm engines, same
    cold workload);
  * both arms emit IDENTICAL tokens — scheduling is invisible in the
    streams;
  * ZERO recompiles in the timed window (the warm round drove every
    chunk/final variant the workload reaches).

Phase 2 — sequence-parallel prefill: a 2-shard partial-slab merge lands
the decode pool BITWISE identical to a single-replica prefill, and a
1-prefill+1-prefill+1-decode fleet with ``seq_parallel_shards=2`` emits
greedy streams token-identical to solo generate while the new fleet
counters account the sharded handoffs.

Under FF_SANITIZE both phases must leave the sanitizer evidence rings
empty (no lock-order violations, no post-warmup retraces).

Usage: [FF_SANITIZE=1] python scripts/longctx_smoke.py [N_FLOOD_TOKENS]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flexflow_tpu._env import force_cpu_devices  # noqa: E402

force_cpu_devices(1)

import numpy as np  # noqa: E402

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.models.llama import llama_lm  # noqa: E402

VOCAB = 128
PS = 8
MAX_SEQ = 520       # 65 pages/slot; explicit buckets [16, 512]
CHUNK = 16
MONSTER = 500       # buckets to 512: 32 prefill chunks of 16


def build_model():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_slots=2,
                   kv_page_size=PS)
    ff = FFModel(cfg)
    # heavy enough that a full-prompt prefill visibly stalls a decode
    # tick (the head-of-line effect the interleave phase measures);
    # 2 layers x hidden 128 puts the 32-chunk stall well above CPU
    # dispatch noise
    _, logits = llama_lm(ff, 2, seq_len=16, hidden=128, layers=2, heads=4,
                         kv_heads=2, vocab_size=VOCAB)
    ff.compile(final_tensor=logits)
    return ff


def flood_round(eng, flood_prompt, monster_prompt, flood_tokens):
    """One cold round: start the flood stream decoding, drop the
    maximal prompt on it mid-stream, and record the flood's inter-token
    gaps until both retire. Returns (gaps, flood_tokens, monster_tokens)."""
    fr = eng.submit(flood_prompt, max_new_tokens=flood_tokens)
    while len(fr.tokens) < 4:           # a live stream, not a cold start
        eng.step()
    mr = eng.submit(monster_prompt, max_new_tokens=4)
    gaps, last, prev = [], len(fr.tokens), time.perf_counter()
    while fr.state not in ("done", "failed") \
            or mr.state not in ("done", "failed"):
        eng.step()
        now = time.perf_counter()
        if len(fr.tokens) > last:
            gaps.append((now - prev) / (len(fr.tokens) - last))
            last, prev = len(fr.tokens), now
    assert fr.state == "done" and mr.state == "done", \
        f"flood={fr.state} monster={mr.state}"
    return gaps, list(fr.tokens), list(mr.tokens)


def interleave_phase(ff, flood_tokens):
    rs = np.random.RandomState(2)
    flood = rs.randint(1, VOCAB, (12,)).astype(np.int32)
    monster = rs.randint(1, VOCAB, (MONSTER,)).astype(np.int32)

    results = {}
    for budget in (0, 1):
        # prefix cache OFF so the timed round replays the warm round's
        # exact cold programs (a HIT round would skip the chunks)
        eng = ff.make_serving_engine(
            serve_slots=2, kv_page_size=PS, max_seq_len=MAX_SEQ,
            decode_buckets=[16, 512],
            prefill_chunk=CHUNK, prefill_interleave_chunks=budget,
            prefix_cache=False)
        flood_round(eng, flood, monster, flood_tokens)      # warm
        rc = eng.recompile_count
        # min over rounds: a scheduler blip can inflate one round's
        # worst gap, but only the admission policy inflates ALL of them
        worst, ftoks, mtoks = None, None, None
        for _ in range(2):
            gaps, ftoks, mtoks = flood_round(eng, flood, monster,
                                             flood_tokens)
            worst = min(worst, max(gaps)) if worst else max(gaps)
        assert eng.recompile_count == rc, (
            f"{eng.recompile_count - rc} programs compiled in the timed "
            f"window (interleave={budget})")
        results[budget] = (worst, ftoks, mtoks)
        st = eng.stats()
        if budget:
            assert st["prefill_chunks_interleaved"] >= 2 * (MONSTER
                                                            // CHUNK), \
                "the monster's chunks never rode the interleave quanta"
            assert st["prefill_partial_slots"] == 0

    off, on = results[0], results[1]
    assert on[1:] == off[1:], \
        "interleaved admission changed a greedy stream"
    print(f"longctx_smoke[interleave]: flood worst inter-token gap "
          f"{off[0] * 1e3:.1f}ms run-to-completion -> {on[0] * 1e3:.1f}ms"
          f" interleaved ({MONSTER}-token monster, chunk {CHUNK})")
    assert on[0] < off[0], (
        f"interleave did not flatten the head-of-line stall: "
        f"{on[0] * 1e3:.1f}ms >= {off[0] * 1e3:.1f}ms")


def seq_parallel_phase(ff):
    rs = np.random.RandomState(3)
    prompt = rs.randint(1, VOCAB, (48,)).astype(np.int32)   # 6 pages
    kw = dict(serve_slots=2, kv_page_size=PS, max_seq_len=64)

    # engine-level 2-shard merge, bitwise vs one-replica prefill
    ref = ff.make_serving_engine(**kw)
    assert ref.prefill_into_cache(prompt) == 6
    a = ff.make_serving_engine(**kw)
    assert a.prefill_into_cache(prompt[:3 * PS]) == 3
    slab0 = a.export_prefix_slab(prompt[:3 * PS])
    b = ff.make_serving_engine(**kw)
    assert b.import_prefix_slab(slab0) == 3
    assert b.prefill_into_cache(prompt) == 6
    slab1 = b.export_prefix_slab(prompt, start_page=3)
    dec = ff.make_serving_engine(**kw)
    assert dec.import_prefix_slab(slab0) == 3
    assert dec.import_prefix_slab(slab1) == 3
    rpath = ref.prefix_cache.match(prompt, 6)
    dpath = dec.prefix_cache.match(prompt, 6)
    assert len(rpath) == len(dpath) == 6
    for op in ref.gen.attn_ops:
        for plane in ("k", "v"):
            want = np.stack([np.asarray(ref.kv.pool[op.name][plane][n.page])
                             for n in rpath])
            got = np.stack([np.asarray(dec.kv.pool[op.name][plane][n.page])
                            for n in dpath])
            assert (want == got).all(), \
                f"sharded merge diverged at {op.name}/{plane}"
    assert dec.stats()["partial_slab_imports"] == 1
    print("longctx_smoke[seq_parallel]: 2-shard merge bitwise identical "
          "to single-replica prefill")

    # fleet leg: sharded handoff, token identity vs solo generate
    prompts = [rs.randint(1, VOCAB, (int(n),)).astype(np.int32)
               for n in (48, 50, 52, 11)]
    router = ff.make_serving_router(
        replicas=3, roles=["prefill", "prefill", "decode"],
        seq_parallel_shards=2, handoff_min_pages=2,
        serve_slots=2, kv_page_size=PS, max_seq_len=96)
    try:
        reqs = router.run(prompts, max_new_tokens=6, timeout=600)
        assert all(r.state == "done" for r in reqs), \
            [r.state for r in reqs]
        for r in reqs:
            solo = ff.generate(r.prompt[None, :], max_new_tokens=6)
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), solo[0, r.prompt.size:],
                err_msg=f"request {r.rid} diverged from its solo run")
        fleet = router.stats()["fleet"]
        assert fleet["seq_parallel_prefills"] == 3, \
            f"seq_parallel_prefills={fleet['seq_parallel_prefills']}"
        assert fleet["partial_slab_imports"] >= 3
        print(f"longctx_smoke[seq_parallel]: fleet ran "
              f"{fleet['seq_parallel_prefills']} sharded prefills, "
              f"{fleet['partial_slab_imports']} partial-slab merges, "
              f"streams identical to solo generate")
    finally:
        router.close()


def main():
    flood_tokens = int(sys.argv[1]) if len(sys.argv) > 1 else 32
    ff = build_model()
    interleave_phase(ff, flood_tokens)
    seq_parallel_phase(ff)

    if os.environ.get("FF_SANITIZE"):
        from flexflow_tpu.runtime import locks

        assert locks.mode() != "off", "FF_SANITIZE set but sanitizer off"
        assert locks.violations() == [], (
            "lock-order violations under FF_SANITIZE:\n"
            + "\n".join(f"{v['outer']} -> {v['inner']}\n{v['inner_stack']}"
                        for v in locks.violations()))
        assert locks.retrace_log() == [], (
            "post-warmup retraces under FF_SANITIZE:\n"
            + "\n".join(f"{r['program']} {r['signature']}\n{r['stack']}"
                        for r in locks.retrace_log()))
        print("longctx_smoke[sanitize]: zero violations, zero retraces")

    print("longctx_smoke: PASSED")


if __name__ == "__main__":
    main()
