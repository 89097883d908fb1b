"""Open-loop serving traffic over RESIDENT DOCUMENTS: a few long documents are
prefilled once, before the window, and stay published in the engine's prefix
cache; every request of the window is one of them plus a fresh short question
(kind "shared_doc_serving" in the traffic file). Document question-answering
and agent back ends send this: the long context is shared and asked again,
only the tail is new.

The traffic file fixes everything but the tokens:

  * `documents`: groups of `count` documents of `tokens` tokens, each a whole
    number of pages, so a request's prompt hits exactly its document's pages
    and prefills the page of its question (one prefix-hit program per
    document length);
  * the ARRANGEMENT (when each request is due, which document it asks, how
    long its question and its answer are) is drawn from `arrangement_seed`
    and is the same in every run: the count is round(rate x seconds), due
    times are jittered ((i + u_i) / rate), documents uniform, questions
    uniform in `question_tokens`, answers the stratified lognormal of
    `open_loop_serving`;
  * `--seed` draws the documents' and the questions' tokens only.

Set-up seats the documents (`ServingEngine.prefill_into_cache`: the normal
cold prefill programs, pages published to the trie and never flushed), then
warms one request per document length (the hit programs and the decode
program). `drive` seats whatever document is not resident before its clock
starts, so `knee_sweep.py`, which flushes the prefix cache between rates,
offers every rate the same resident set. The engine, the loop and the
statistics are `open_loop_serving`'s, imported as its `_ref` twin does;
`correct` comes from the configuration's own reference
(`reference/serve_check_ref.py`'s two checks): (a) `ff.predict` against it on
one seeded sequence, (b) the shortest completed request OF EACH DOCUMENT
LENGTH rescored in one pass (a request's MEAN margin under the
configuration's `emitted_margin_mean`: with this model's seeded weights single
margins are wide, PERF.md section 6, and the mean is what tells a wrong page
from rounding; benchmark/dsa_controls.py plants faults in the timed path and
reads what this check says of each), after the engine's pool has been dropped
so that a 33 k-token float32 pass fits beside the weights.
"""

import dataclasses
import gc
import time

import numpy as np

from benchmark import spec
from benchmark.generators import open_loop_serving as base
from benchmark.generators.open_loop_serving import (  # noqa: F401
    attainment, build_engine, latency_metrics)
from benchmark.reference.serve_check_ref import PAD_TO

COUNTERS = ("requests", "completed", "failed", "timeouts", "tokens_generated",
            "decode_steps", "occupied_slot_steps", "recompiles",
            "prefix_hits", "prefix_lookups")
# what a program that has them adds (a checkout without them reports none,
# and their readers then return nothing)
EXTRA = ("moe_assignments", "moe_experts_hit", "moe_streamed_dispatches",
         "dsa_selected_tokens", "dsa_context_tokens", "index_read_bytes",
         "prefix_hit_tokens", "prefix_prompt_tokens")
FULL_FROM_S = 10.0      # the ramp: from here on the queue should never empty


@dataclasses.dataclass
class Schedule(base.Schedule):
    docs: list = None           # the documents (int32 arrays)
    doc_of: np.ndarray = None   # (n,) which document each request asks

    def describe(self):
        out = super().describe()
        out["documents"] = sorted({int(d.size) for d in self.docs})
        out["requests_by_document_tokens"] = {
            int(t): int(sum(self.docs[d].size == t for d in self.doc_of))
            for t in out["documents"]}
        return out


def document_lengths(traffic, scale=1):
    return [max(1, g["tokens"] // scale) for g in traffic["documents"]
            for _ in range(g["count"])]


def generate(traffic, seed, seconds, vocab, scale=1):
    """The schedule of one window (`scale` > 1 divides every length: the CPU
    rehearsal)."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    arr = np.random.default_rng([int(traffic["arrangement_seed"]), 0xD0C5])
    if traffic["arrivals"] != "jittered":
        raise ValueError(f"arrivals {traffic['arrivals']!r}: jittered")
    due = (np.arange(n) + arr.uniform(0.0, 1.0, n)) / rate
    due = np.minimum(due, np.nextafter(seconds, 0.0))
    lens = document_lengths(traffic, scale)
    doc_of = arr.integers(0, len(lens), n)
    q = traffic["question_tokens"]
    qlen = arr.integers(max(1, q["min"] // scale),
                        max(1, q["max"] // scale) + 1, n)
    out = traffic["output_tokens"]
    olen = base.stratified_lognormal(
        n, {**out, **{k: max(1, out[k] // scale)
                      for k in ("median", "min", "max")}}, arr)
    tok = np.random.default_rng([int(seed), 0x70CE])
    docs = [tok.integers(1, vocab, size=k, dtype=np.int32) for k in lens]
    prompts = [np.concatenate(
        [docs[d], tok.integers(1, vocab, size=int(k), dtype=np.int32)])
        for d, k in zip(doc_of, qlen)]
    return Schedule(due=due, prompts=prompts, max_new=olen, docs=docs,
                    doc_of=doc_of)


def pool_arithmetic(traffic, page_size, slots, scale=1):
    """Pages the resident documents hold, and the most the live requests
    add: per slot the question's page, the answer's pages and the bucket's
    padding, none of which is ever published (a prompt's only full pages are
    its document's)."""
    lens = document_lengths(traffic, scale)
    if any(k % page_size for k in lens):
        raise ValueError(f"documents of {sorted(set(lens))} tokens are not "
                         f"whole pages of {page_size}")
    resident = sum(k // page_size for k in lens)
    qmax = max(1, traffic["question_tokens"]["max"] // scale)
    omax = max(1, traffic["output_tokens"]["max"] // scale)
    buckets = [_pow2(k + qmax) for k in lens]
    live = slots * max(-(-(b + omax) // page_size) - k // page_size
                       for b, k in zip(buckets, lens))
    return {"resident_pages": resident, "live_pages_most": live,
            "largest_bucket": max(buckets)}


def _pow2(n):
    b = 8
    while b < n:
        b *= 2
    return b


def seat_documents(log, eng, docs):
    """Prefill every document that is not resident into the prefix cache
    (cold, through the engine's normal programs); returns how many it had
    to seat."""
    ns = eng._cache_ns(None)
    seated = 0
    for d in docs:
        pages = d.size // eng.page_size
        if len(eng.prefix_cache.match(d, pages, ns=ns)) == pages:
            continue
        t0 = time.perf_counter()
        got = eng.prefill_into_cache(d)
        if got != pages:
            raise RuntimeError(f"document of {d.size} tokens: "
                               f"prefill_into_cache published {got} of "
                               f"{pages} pages")
        seated += 1
        log(f"document {d.size} tokens seated in "
            f"{time.perf_counter() - t0:.2f} s ({pages} pages)")
    return seated


def warm(h, eng, traffic):
    """The resident set of THIS run's seed and, per document length, one
    request of the longest question (the hit program and the decode
    program). Nothing is flushed: the documents stay."""
    t0 = time.perf_counter()
    before = eng.recompile_count
    sched = generate(traffic, h.args.seed, 1.0, h.vocab, h.scale)
    seat_documents(h.log, eng, sched.docs)
    rng = np.random.default_rng([int(h.args.seed), 0xC01D])
    qmax = max(1, traffic["question_tokens"]["max"] // h.scale)
    firsts = {}
    for d in sched.docs:
        firsts.setdefault(d.size, d)
    eng.run([np.concatenate([d, rng.integers(1, h.vocab, size=qmax,
                                             dtype=np.int32)])
             for d in firsts.values()],
            max_new_tokens=max(2, eng.decode_chunk + 1))
    st = eng.stats()
    h.log(f"warm-up: {len(sched.docs)} documents resident "
          f"({st['kv_pages_cached']} pages cached, {st['free_pages']} free), "
          f"{eng.recompile_count - before} programs in "
          f"{time.perf_counter() - t0:.1f} s")


class _Occupancy:
    """What the loop saw of the engine's queue once per turn (one turn = one
    tick: the admissions, then a dispatch of decode steps), from FULL_FROM_S
    to the window's end. A turn that finds the queue empty is one whose
    freed slots were not refilled."""

    def __init__(self, eng, seconds, inner):
        self.eng, self.seconds, self.inner = eng, seconds, inner
        self.turns = self.empty = 0
        self.last_empty = None

    def __call__(self, now):
        self.inner(now)
        if FULL_FROM_S <= now < self.seconds:
            self.turns += 1
            if self.eng.load()["queued"] == 0:
                self.empty += 1
                self.last_empty = now

    def line(self):
        last = ("never" if self.last_empty is None
                else f"last at {self.last_empty:.2f} s")
        return (f"queue per loop turn, t = {FULL_FROM_S:.0f} s to the "
                f"window's end: {self.turns} turns, empty in {self.empty} "
                f"({last})")


def drive(eng, sched, seconds, grace_s, annotate, poll=lambda now: None):
    """`open_loop_serving.drive` over a resident set: documents that are not
    in the prefix cache (a sweep flushed it) are seated before the clock
    starts."""
    seat_documents(lambda msg: print(f"[shared_doc] {msg}", flush=True),
                   eng, sched.docs)
    return base.drive(eng, sched, seconds, grace_s, annotate, poll)


def check_predict(h, ff, reference, z, params):
    """Check (a): `ff.predict` on `graph_seq_len` seeded tokens against the
    reference given `params`, with the reference's own log of where the
    program selects and routes otherwise. (ok, relative RMS, the log)."""
    import jax

    tol = h.config["tolerances"]
    seq = h.cut["graph_seq_len"] // h.scale
    rng = np.random.default_rng([int(h.args.seed), 0xD15E])
    toks = rng.integers(1, z["vocab_size"], size=(1, seq), dtype=np.int32)
    got = np.asarray(jax.block_until_ready(
        ff.predict({"input": toks})), np.float32)[0]
    trace = {}
    want = np.asarray(reference.forward(params, toks[0], z, trace=trace))
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    h.log(f"check (a) predict vs reference on {seq} tokens: relative RMS "
          f"error {rel:.5f} (tolerance {tol['predict_rel_rms']}), logit std "
          f"{want.std():.4f}")
    differs = reference.program_disagreement(ff, toks[0], z, trace)
    h.log(f"check (a) where the program chooses otherwise than the float32 "
          f"reference (near-ties; logged, not judged): "
          f"{ {k: round(100 * v, 3) for k, v in differs.items()} } % of the "
          f"(token, layer) rows")
    return rel <= tol["predict_rel_rms"], rel, differs


def check_emitted(h, reference, z, params, records, sched, sizes=None):
    """Check (b): the shortest completed request of each document length
    (of `sizes`, when given) rescored by the reference in one pass. Judged:
    the MEAN, over a request's emitted tokens, of how far the token's
    reference logit lies below the reference's maximum at its position
    (`emitted_margin_mean`). The largest single margin is logged and not
    judged: a token drawn at random reads about 3.0 and sound requests have
    read up to 2.85 (PERF.md section 6), so no limit on one token tells them
    apart."""
    import jax.numpy as jnp

    tol = h.config["tolerances"]
    done = sorted((r for r in records if r["state"] == "done"),
                  key=lambda r: r["prompt_tokens"] + r["tokens"])
    ok, worst, worst_mean, scored = True, 0.0, 0.0, []
    for size in sorted({int(d.size) for d in sched.docs}):
        if sizes is not None and size not in sizes:
            continue
        mine = [r for r in done
                if sched.docs[sched.doc_of[r["index"]]].size == size]
        if not mine:
            h.log(f"check (b): no completed request of the {size}-token "
                  f"documents to rescore")
            ok = False
            continue
        req = mine[0]["request"]
        full = np.asarray(req.output, np.int32)
        padded = np.zeros((-(-full.size // PAD_TO) * PAD_TO,), np.int32)
        padded[:full.size] = full       # causal: trailing pads change nothing
        p = req.prompt.size
        rows = reference.forward(params, padded, z,
                                 rows=(p - 1, full.size - 1))
        emitted = jnp.asarray(full[p:])
        margins = np.asarray(rows.max(axis=-1) - jnp.take_along_axis(
            rows, emitted[:, None], axis=-1)[:, 0])
        worst = max(worst, float(margins.max()))
        worst_mean = max(worst_mean, float(margins.mean()))
        scored.append(size)
        h.log(f"check (b) document {size} tokens, request prompt={p} "
              f"emitted={emitted.size}: reference margin of the emitted "
              f"tokens mean {margins.mean():.5f} max {margins.max():.5f} "
              f"(the first, which the prefix-hit prefill emits, "
              f"{margins[0]:.5f}), {int((margins == 0).sum())}/"
              f"{emitted.size} are the reference's own argmax")
    h.log(f"check (b) worst mean margin of a request {worst_mean:.5f} "
          f"(tolerance {tol['emitted_margin_mean']}); largest single margin "
          f"{worst:.5f} (logged); document lengths rescored {scored}")
    ok &= worst_mean <= tol["emitted_margin_mean"]
    return {"ok": bool(ok), "worst_margin": worst,
            "worst_mean_margin": worst_mean,
            "rescored_document_tokens": scored}


def check(h, ff, records, sched, reference_params=None):
    """`serve_check_ref`'s two checks with the configuration's reference,
    which takes the program's weights by name (`reference_params` when the
    program under test was given others: benchmark/dsa_controls.py)."""
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    params = ff.params if reference_params is None else reference_params
    ok_a, rel, differs = check_predict(h, ff, reference, z, params)
    b = check_emitted(h, reference, z, params, records, sched)
    return {**b, "ok": bool(ok_a and b["ok"]), "predict_rel_rms": rel,
            **differs}


def run(h):
    traffic = h.traffic
    seconds = h.seconds
    sched = generate(traffic, h.args.seed, seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = build_engine(h)
    h.log(f"pool: {pool_arithmetic(traffic, eng.page_size, eng.slots, h.scale)}"
          f" of {eng.num_pages} pages")
    warm(h, eng, traffic)

    stats0 = eng.stats()
    occ = _Occupancy(eng, seconds, h.trace_poll)
    h.setup_done()
    records, lateness, t_end = drive(
        eng, sched, seconds, float(traffic["drain_grace_s"]), h.annotate,
        occ)
    h.window_done()
    stats1 = eng.stats()
    h.log(f"generator lateness: median {lateness['median_s'] * 1e3:.3f} ms, "
          f"max {lateness['max_s'] * 1e3:.3f} ms; loop ended at "
          f"{t_end:.2f} s of a {seconds} s window (grace "
          f"{traffic['drain_grace_s']} s)")
    h.log(occ.line())
    for k, r in enumerate(records):
        r["index"] = k

    e2e = latency_metrics(records, seconds)
    delta = {k: stats1[k] - stats0[k] for k in COUNTERS + EXTRA
             if k in stats1}
    h.log(f"engine stats delta: {delta}")
    h.log(f"window: {e2e}")
    limits = traffic.get("limits")
    if limits:
        h.log(f"share meeting TTFT <= {limits['ttft_s']} s and TPOT <= "
              f"{limits['tpot_s']} s (logged, not judged: above the knee "
              f"TTFT grows by design): "
              f"{attainment(records, limits['ttft_s'], limits['tpot_s']):.3f}")
    hit, asked = (delta.get("prefix_hit_tokens"),
                  delta.get("prefix_prompt_tokens"))
    if asked:
        h.log(f"prefix hits: {hit} of {asked} prompt tokens "
              f"({100.0 * hit / asked:.2f} %)")
        if hit < 0.99 * asked:
            cold = sorted({int(sched.docs[sched.doc_of[k]].size)
                           for k, r in enumerate(records)
                           if r.get("request") is not None
                           and r["request"].prefix_tokens
                           < sched.docs[sched.doc_of[k]].size})
            h.log(f"a document was evicted: requests of documents of "
                  f"{cold} tokens prefilled cold")

    # the reference's float32 pass over 33 k tokens needs the pool's room
    slots = eng.slots
    eng.kv.pool = eng.kv.draft_pool = None
    del eng
    gc.collect()
    checks = check(h, ff, records, sched)
    compiles = max(delta["recompiles"], h.compiles_in_window())
    correct = (checks["ok"] and compiles == 0 and e2e["failed"] == 0
               and delta["failed"] == 0)
    return {
        "correct": bool(correct), "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        "end_to_end": {name: e2e[name] for name in traffic["end_to_end"]
                       if name in e2e},
        "ctx": {"mode": "serve", "stats_delta": delta, "slots": slots,
                "records": records, "window": e2e,
                "compiles_in_window": compiles, "lateness": lateness},
    }
