"""Open-loop serving traffic: independent users send on a schedule whether or
not earlier requests have finished.

`generate` is the one general generator: a traffic file (benchmark/traffic/
<mix>.json, kind "open_loop_serving") gives it a rate, an arrival process and
two length distributions, and `--seed` gives it the rest. The amount of work
is FIXED by the file and the window, and only its arrangement is drawn from
the seed, so that runs with different seeds offer the same load:

  * the number of requests is round(rate x seconds);
  * prompt and output lengths are the n mid-quantiles of a clipped lognormal
    (stratified: every run holds the same multiset of lengths), each
    shuffled by the seed;
  * arrivals: "poisson" places the n requests uniformly at random in the
    window (a Poisson process given its count); "jittered" puts request i
    at (i + u_i) / rate, u_i uniform in [0, 1) (no bursts);
  * prompt tokens are uniform over the vocabulary and unshared: the chance
    that two prompts share a page is nil, so the prefix cache is bypassed.

`run` drives a ServingEngine with one thread: submit every request now due,
call step(), and sleep only when nothing is pending. A request is timed from
when it was DUE, so a stall delays the requests behind it visibly.
"""

import dataclasses
import math
import statistics
import time

import numpy as np

from benchmark import stats

NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Schedule:
    due: np.ndarray             # (n,) seconds from the start of the window
    prompts: list               # n int32 arrays
    max_new: np.ndarray         # (n,) output tokens (greedy, no eos)

    def describe(self):
        plen = [p.size for p in self.prompts]
        return {
            "requests": len(self.prompts),
            "prompt_tokens": {"min": int(min(plen)), "p50": stats.median(plen),
                              "p90": stats.percentile(plen, 90),
                              "max": int(max(plen)), "sum": int(sum(plen))},
            "output_tokens": {"min": int(self.max_new.min()),
                              "p50": stats.median(self.max_new),
                              "p90": stats.percentile(self.max_new, 90),
                              "max": int(self.max_new.max()),
                              "sum": int(self.max_new.sum())},
            "last_due_s": float(self.due[-1]),
        }


def stratified_lognormal(n, dist, rng):
    """n lengths: the (i + 0.5) / n quantiles of lognormal(median, sigma),
    clipped to [min, max], in an order drawn from `rng`."""
    mu = math.log(dist["median"])
    q = [math.exp(mu + dist["sigma"] * NORMAL.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    lens = np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)
    return rng.permutation(lens)


def generate(traffic, seed, seconds, vocab, scale=1):
    """The schedule of one window. `scale` > 1 divides every length (the CPU
    rehearsal); a cell runs at scale 1."""
    rate = float(traffic["rate_per_s"])
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng([int(seed), 0x5EED])
    if traffic["arrivals"] == "poisson":
        due = np.sort(rng.uniform(0.0, seconds, n))
    elif traffic["arrivals"] == "jittered":
        due = (np.arange(n) + rng.uniform(0.0, 1.0, n)) / rate
        due = np.minimum(due, np.nextafter(seconds, 0.0))
    else:
        raise ValueError(f"arrivals {traffic['arrivals']!r}: poisson or "
                         f"jittered")

    def scaled(dist):
        return {**dist, **{k: max(1, dist[k] // scale)
                           for k in ("median", "min", "max")}}

    plen = stratified_lognormal(n, scaled(traffic["prompt_tokens"]), rng)
    olen = stratified_lognormal(n, scaled(traffic["output_tokens"]), rng)
    prompts = [rng.integers(1, vocab, size=int(k), dtype=np.int32)
               for k in plen]
    return Schedule(due=due, prompts=prompts, max_new=olen)


def buckets_reached(traffic, scale=1):
    """The engine's default power-of-two prompt buckets between the clipped
    minimum and maximum prompt length: the shapes a warm-up has to drive."""
    lo = max(1, traffic["prompt_tokens"]["min"] // scale)
    hi = max(1, traffic["prompt_tokens"]["max"] // scale)
    out, b = [], 8
    while b < lo:
        b *= 2
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def warm_prompts(traffic, seed, vocab, scale=1):
    """One prompt per bucket (as long as the bucket allows, within the mix's
    range), seeded apart from the measured prompts."""
    rng = np.random.default_rng([int(seed), 0xC01D])
    hi = max(1, traffic["prompt_tokens"]["max"] // scale)
    return [rng.integers(1, vocab, size=min(b, hi), dtype=np.int32)
            for b in buckets_reached(traffic, scale)]


def drive(eng, sched, seconds, grace_s, annotate, poll=lambda now: None):
    """Offer `sched` to the engine and step it until everything due in the
    window has finished or `grace_s` past the window has gone. Returns the
    per-request records and the generator's lateness.

    `annotate(name)` gives a context manager (a profiler annotation);
    `poll(now)` is called once per loop turn (the trace slice's switch)."""
    n = len(sched.prompts)
    reqs, late = [None] * n, np.zeros(n)
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        poll(now)
        if i < n and sched.due[i] <= now:
            with annotate("bench.submit"):
                while i < n and sched.due[i] <= now:
                    reqs[i] = eng.submit(sched.prompts[i],
                                         int(sched.max_new[i]))
                    late[i] = (reqs[i].t_submit - t0) - sched.due[i]
                    i += 1
        if eng.pending():
            with annotate("bench.engine_step"):
                eng.step()
        elif i < n:
            with annotate("bench.generator_wait"):
                time.sleep(max(0.0, sched.due[i]
                               - (time.perf_counter() - t0)))
        else:
            break
        if now > seconds + grace_s:
            break
    t_end = time.perf_counter() - t0
    records = []
    for k, r in enumerate(reqs):
        if r is None:       # never submitted: the loop fell behind for good
            records.append({"due": float(sched.due[k]), "state": "unsent",
                            "prompt_tokens": int(sched.prompts[k].size),
                            "tokens": 0})
            continue
        first = (r.t_submit + r.ttft - t0) if r.ttft else None
        done = (r.t_done - t0) if r.state == "done" else None
        records.append({
            "due": float(sched.due[k]), "state": r.state,
            "prompt_tokens": int(r.prompt.size), "bucket": int(r.bucket),
            "tokens": len(r.tokens), "asked": int(r.max_new_tokens),
            "t_first": first, "t_done": done, "request": r})
    return records, {"median_s": float(np.median(late)),
                     "max_s": float(late.max())}, t_end


def _finished(records):
    return [r for r in records if r["state"] == "done"
            and r["tokens"] == r["asked"]]


def _ttft(r):
    return r["t_first"] - r["due"]


def _tpot(r):
    """Time per output token after the first; None for a one-token answer."""
    if r["tokens"] < 2:
        return None
    return (r["t_done"] - r["t_first"]) / (r["tokens"] - 1)


def latency_metrics(records, seconds):
    """The statistics of one window from its request records. Only requests
    that finished count towards a latency; the others are failures (and miss
    any limit)."""
    done = _finished(records)
    ttft = [_ttft(r) for r in done]
    tpot = [t for t in map(_tpot, done) if t is not None]
    out = {"attempted": len(records), "failed": len(records) - len(done),
           "completed_tokens": sum(r["tokens"] for r in done)}
    if ttft:
        out.update(ttft_p50_s=stats.percentile(ttft, 50),
                   ttft_p90_s=stats.percentile(ttft, 90))
    if tpot:
        out.update(tpot_p50_s=stats.percentile(tpot, 50),
                   tpot_p90_s=stats.percentile(tpot, 90))
    out["serve_tokens_per_s"] = out["completed_tokens"] / seconds
    return out


def attainment(records, ttft_limit_s, tpot_limit_s):
    """Share of ALL requests offered that met both limits."""
    ok = sum(_ttft(r) <= ttft_limit_s and (_tpot(r) or 0.0) <= tpot_limit_s
             for r in _finished(records))
    return ok / max(1, len(records))


def build_engine(h):
    """Model, engine and warm-up: everything before the first measured
    request. Returns (ff, eng)."""
    cut = h.cut
    ff, _, _ = h.builder.build(h.config, cut, h.rehearsal)
    kw = dict(cut["engine"])
    if h.rehearsal:
        # off-TPU `auto` resolves to einsum: the rehearsal names the kernel
        kw.update(h.builder.rehearsal_engine(kw), paged_attention_impl="pallas")
    eng = ff.make_serving_engine(**kw)
    st = eng.stats()
    h.log(f"engine: slots={eng.slots} page={eng.page_size} "
          f"kv_pages={eng.num_pages} max_seq_len={eng.max_seq_len} pool "
          f"{st['kv_pool_bytes'] / 1e9:.2f} GB "
          f"({st['kv_bytes_per_token']:.0f} B/token) decode impl="
          f"{st['paged_attention_impl']} prefill-write impl="
          f"{st['paged_prefill_impl']} kv dtype={st['kv_cache_dtype']}")
    if not h.rehearsal and (st["paged_attention_impl"] != "pallas"
                            or st["paged_prefill_impl"] != "pallas"):
        raise RuntimeError("paged impls did not resolve to the Pallas "
                           "kernels on the chip")
    return ff, eng


def warm(h, eng, traffic):
    """One prompt per bucket the mix reaches (cold prefill + decode), then
    the prefix cache is flushed: measured prompts are unshared, so no
    hit-prefill variant is reachable."""
    t0 = time.perf_counter()
    before = eng.recompile_count
    prompts = warm_prompts(traffic, h.args.seed, h.vocab, h.scale)
    eng.run(prompts, max_new_tokens=max(2, eng.decode_chunk + 1))
    eng.flush_prefix_cache()
    h.log(f"warm-up: buckets {[eng._bucket(p.size) for p in prompts]} -> "
          f"{eng.recompile_count - before} programs in "
          f"{time.perf_counter() - t0:.1f} s")


def run(h):
    from benchmark.reference import serve_check

    traffic = h.traffic
    seconds = h.seconds
    sched = generate(traffic, h.args.seed, seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = build_engine(h)
    warm(h, eng, traffic)

    stats0 = eng.stats()
    h.setup_done()
    records, lateness, t_end = drive(
        eng, sched, seconds, float(traffic["drain_grace_s"]), h.annotate,
        h.trace_poll)
    h.window_done()
    stats1 = eng.stats()
    h.log(f"generator lateness: median {lateness['median_s'] * 1e3:.3f} ms, "
          f"max {lateness['max_s'] * 1e3:.3f} ms; loop ended at "
          f"{t_end:.2f} s of a {seconds} s window")

    e2e = latency_metrics(records, seconds)
    delta = {k: stats1[k] - stats0[k] for k in
             ("requests", "completed", "failed", "timeouts",
              "tokens_generated", "decode_steps", "occupied_slot_steps",
              "recompiles", "prefix_hits", "prefix_lookups")}
    h.log(f"engine stats delta: {delta}")
    h.log(f"window: {e2e}")
    limits = traffic.get("limits")
    if limits:
        h.log(f"share meeting TTFT <= {limits['ttft_s']} s and TPOT <= "
              f"{limits['tpot_s']} s: "
              f"{attainment(records, limits['ttft_s'], limits['tpot_s']):.3f}")

    checks = serve_check.run(h, ff, records)
    compiles = max(delta["recompiles"], h.compiles_in_window())
    correct = (checks["ok"] and compiles == 0 and e2e["failed"] == 0
               and delta["failed"] == 0)
    return {
        "correct": bool(correct), "attempted": e2e["attempted"],
        "failed": e2e["failed"],
        # the traffic file says which statistics of the window this cell is
        # judged on; the others are for the per-layer readers
        "end_to_end": {name: e2e[name] for name in traffic["end_to_end"]
                       if name in e2e},
        "ctx": {"mode": "serve", "stats_delta": delta, "slots": eng.slots,
                "records": records, "window": e2e,
                "compiles_in_window": compiles, "lateness": lateness},
    }
