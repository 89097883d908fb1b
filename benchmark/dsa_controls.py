"""Controls for the limits of `dsa-docqa-saturated`'s `correct`: faults planted
in the TIMED path (the prefix-hit prefill, the paged decode and its two
kernels, the pool's pages) of one warm engine, each driven through a short
window at the cell's rate and judged by `generators/shared_doc_serving.py`'s
own `check_emitted` / `check`. A limit of the configuration file lies between
the largest reading the sound program gives and the smallest a control gives;
this script is where the second kind of reading comes from.

What is planted (one at a time, in this order; each undone before the next):

  sound          nothing
  selection_off  `ops/mla.py` `dsa_threshold` keeps every live token: the
                 decode and prefix-hit programs are built again with it, so
                 `mla_paged_core` and the hit prefill attend everything
  wrong_pages    two resident documents of one length hold each other's
                 rows: their requests read another document's pages
  fp8_cache      the resident documents' latent rows and index keys rounded
                 to float8_e4m3fn (an 8-bit cache), weights as stated
  fp8_all        that cache and every weight matrix rounded to float8_e4m3fn:
                 the timed path in the nearest precision below the bf16 the
                 configuration states. `check` then runs in full (also (a),
                 `ff.predict` with the rounded weights) against the reference
                 on the weights as stated, and must come out not correct.

Before them, one question is asked of a document that is NOT resident (cold
prefill, the prefix cache publishes it) and once more (prefix-hit prefill):
the cold answer is rescored like any other, and the two answers are compared
where they first differ.

The resident set is the traffic file's `rehearsal.documents` (2 + 1 documents,
both lengths, both hit programs) so that set-up is short; rate, slots, pool,
question and answer lengths are the cell's. Everything is written to
chiprun_out/dsa_controls.json as it is read.

    python3 benchmark/dsa_controls.py --seed 3000003301 [--seconds 8]
        [--only sound,wrong_pages] [--lengths 16256] [--rehearsal]
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmark import run as bench_run, spec  # noqa: E402

CELL = "dsa-docqa-saturated"
CONTROLS = ("sound", "selection_off", "wrong_pages", "fp8_cache", "fp8_all")
OUT = os.path.join(ROOT, "chiprun_out", "dsa_controls.json")


def document_pages(eng, docs):
    """Pool page ids of each resident document, in order."""
    ns = eng._cache_ns(None)
    out = []
    for d in docs:
        path = eng.prefix_cache.match(d, d.size // eng.page_size, ns=ns)
        assert len(path) == d.size // eng.page_size, "document not resident"
        out.append(np.asarray([n.page for n in path], np.int32))
    return out


def rewrite_pages(eng, dst, src, fn=None):
    """pool[dst] = fn(pool[src]) in every array of every attention op's
    pool, one array at a time, in place (donated). `fn` runs as dispatches
    of its own: inside one program XLA may keep the excess precision of a
    narrowing cast that is widened again at once, and on the chip it does
    (call 8: an 8-bit round trip inside the gather changed no value).
    Returns the share of the written values that `fn` changed."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda x, src: x[src])
    put = jax.jit(lambda x, dst, rows: x.at[dst].set(rows),
                  donate_argnums=(0,))
    dst, src = jnp.asarray(dst), jnp.asarray(src)
    changed = total = 0
    for pool in eng.kv.pool.values():
        for name in pool:
            rows = take(pool[name], src)
            if fn is not None:
                new = fn(rows).astype(rows.dtype)
                changed += int(jnp.sum(new != rows))
                total += rows.size
                rows = new
            pool[name] = put(pool[name], dst, rows)
    jax.block_until_ready(eng.kv.pool)
    return changed / total if total else 0.0


def to_fp8(x):
    import jax.numpy as jnp

    return x.astype(jnp.float8_e4m3fn)


def round_weights(params):
    """Every matrix of `params` rounded to float8_e4m3fn in place; returns
    the host copy of what they were."""
    import jax

    kept = {}
    for op in params:
        for name, w in list(params[op].items()):
            if w.ndim >= 2:
                kept[op, name] = np.asarray(jax.device_get(w))
                params[op][name] = to_fp8(w).astype(w.dtype)
                del w
    return kept


def restore_weights(params, kept):
    import jax

    for (op, name), w in kept.items():
        params[op][name] = jax.device_put(w)
    kept.clear()


@contextlib.contextmanager
def planted(name, eng, ff, docs, state):
    """The engine with control `name` planted; undone on exit (but for the
    two 8-bit ones, which come last: the pool is dropped after them and the
    weights are restored by `main` once check (a) has read them)."""
    from flexflow_tpu.ops import mla

    pages = document_pages(eng, docs)
    if name == "selection_off":
        import jax.numpy as jnp

        sound = mla.dsa_threshold
        mla.dsa_threshold = lambda scores, k: (
            jnp.full(scores.shape[:-1], -jnp.inf, jnp.float32),
            jnp.full(scores.shape[:-1], scores.shape[-1], jnp.int32))
        built = {k: eng._programs.pop(k) for k in list(eng._programs)
                 if k[0] in ("decode", "prefill_hit")}
        try:
            yield
        finally:
            mla.dsa_threshold = sound
            for k in [k for k in eng._programs
                      if k[0] in ("decode", "prefill_hit")]:
                del eng._programs[k]
            eng._programs.update(built)
    elif name == "wrong_pages":
        # each length's documents in a ring; a length with one document
        # keeps its own pages. (Moving pages WITHIN a document is no fault:
        # a cached key carries its rotary position, so attention and the
        # selection see the same set of rows; call 8 read 0.372 for it
        # beside the sound 0.342.)
        ids = np.concatenate(pages)
        by_len = {}
        for k, p in enumerate(pages):
            by_len.setdefault(p.size, []).append(k)
        nxt = {k: ring[(i + 1) % len(ring)]
               for ring in by_len.values() for i, k in enumerate(ring)}
        moved = np.concatenate([pages[nxt[k]] for k in range(len(pages))])
        rewrite_pages(eng, ids, moved)
        try:
            yield
        finally:
            rewrite_pages(eng, moved, ids)
    elif name in ("fp8_cache", "fp8_all"):
        if "cache_changed" not in state:
            ids = np.concatenate(pages)
            state["cache_changed"] = rewrite_pages(eng, ids, ids, to_fp8)
        if name == "fp8_all":
            state["weights"] = round_weights(ff.params)
        yield
    else:
        yield


def cold_then_hit(h, eng, rng, size, vocab, new_tokens):
    """One question of a fresh document of `size` tokens, answered after a
    cold prefill and again after a prefix-hit prefill of the same prompt."""
    qmin = max(1, h.traffic["question_tokens"]["min"] // h.scale)
    prompt = rng.integers(1, vocab, size=size + qmin, dtype=np.int32)
    cold = eng.run([prompt], max_new_tokens=new_tokens)[0]
    hit = eng.run([prompt], max_new_tokens=new_tokens)[0]
    a, b = list(cold.tokens), list(hit.tokens)
    same = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), len(a))
    h.log(f"cold then hit, document {size}: prefix tokens cold "
          f"{cold.prefix_tokens} hit {hit.prefix_tokens}; the answers agree "
          f"in their first {same} of {len(a)} tokens")
    return {"size": size, "cold": cold, "hit": hit, "agree": same}


def rescore_cold_hit(h, reference, z, params, pair):
    """Reference margins of the cold answer, and at the first position where
    the hit answer differs, of both candidates."""
    import jax.numpy as jnp
    from benchmark.reference.serve_check_ref import PAD_TO

    cold, hit, same = pair["cold"], pair["hit"], pair["agree"]
    full = np.asarray(cold.output, np.int32)
    padded = np.zeros((-(-full.size // PAD_TO) * PAD_TO,), np.int32)
    padded[:full.size] = full
    p = cold.prompt.size
    rows = reference.forward(params, padded, z, rows=(p - 1, full.size - 1))
    top = np.asarray(rows.max(axis=-1))
    m = top - np.asarray(jnp.take_along_axis(
        rows, jnp.asarray(full[p:])[:, None], axis=-1)[:, 0])
    out = {"document_tokens": pair["size"], "emitted": int(m.size),
           "cold_mean_margin": float(m.mean()),
           "cold_max_margin": float(m.max()),
           "cold_argmax": int((m == 0).sum()), "agree_tokens": same}
    if same < m.size:
        other = float(top[same] - rows[same, int(hit.tokens[same])])
        out.update(first_difference={"at": same,
                                     "cold_margin": float(m[same]),
                                     "hit_margin": other})
    h.log(f"cold then hit, document {pair['size']}: {out}")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000003301)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--lengths", default="16256",
                    help="document lengths whose requests are rescored "
                         "(a 33 k pass takes twice a 16 k pass)")
    ap.add_argument("--cold", default="16256",
                    help="document lengths of the cold-then-hit question")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    wanted = [c for c in CONTROLS if c in args.only.split(",")]

    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from flexflow_tpu import _env

    if args.rehearsal:
        _env.force_cpu_devices(1)
    else:
        bench_run.place_compile_cache()
    from benchmark.generators import shared_doc_serving as gen

    h = bench_run.load_cell(spec.load_benchmark(ROOT), CELL, args.seed,
                            args.seconds, 0, args.rehearsal)
    h.traffic = traffic = {**h.traffic,
                           "documents": h.traffic["rehearsal"]["documents"]}
    lengths = {int(s) // h.scale for s in args.lengths.split(",") if s}
    cold_sizes = [int(s) // h.scale for s in args.cold.split(",") if s]
    results = {"seed": args.seed, "seconds": args.seconds,
               "rate_per_s": traffic["rate_per_s"],
               "device": jax.devices()[0].device_kind, "controls": {}}

    def save():
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)

    sched = gen.generate(traffic, args.seed, h.seconds, h.vocab, h.scale)
    h.log(f"schedule: {sched.describe()}")
    ff, eng = gen.build_engine(h)
    gen.warm(h, eng, traffic)
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    reference = spec.load_module("reference", h.config["reference"])

    rng = np.random.default_rng([args.seed, 0xC01D, 2])
    pairs = [cold_then_hit(h, eng, rng, size, h.vocab,
                           max(2, 47 // h.scale)) for size in cold_sizes]

    windows, state = {}, {}
    grace = float(traffic["drain_grace_s"])
    for name in wanted:
        t0 = time.perf_counter()
        with planted(name, eng, ff, sched.docs, state):
            if name == "selection_off":
                # its programs compile before the window, as the cell's do
                gen.warm(h, eng, traffic)
            records, _, t_end = gen.drive(eng, sched, h.seconds, grace,
                                          h.annotate)
        for k, r in enumerate(records):
            r["index"] = k
        windows[name] = records
        done = sum(r["state"] == "done" for r in records)
        if name in ("fp8_cache", "fp8_all"):
            results["controls"].setdefault(name, {})["cache_values_changed"] \
                = state["cache_changed"]
            h.log(f"control {name}: rounding changed "
                  f"{100 * state['cache_changed']:.1f} % of the resident "
                  f"documents' cached values")
        h.log(f"control {name}: window of {h.seconds} s ended at "
              f"{t_end:.2f} s, {done} of {len(records)} requests done "
              f"({time.perf_counter() - t0:.1f} s with planting)")

    # the reference's float32 pass needs the pool's room
    eng.kv.pool = eng.kv.draft_pool = None
    del eng
    gc.collect()

    sound = ff.params
    if "weights" in state:
        # check (a) of the program on the rounded weights, against the
        # reference on the weights as stated (host copy put back after)
        kept = state.pop("weights")
        stated = {op: dict(ws) for op, ws in ff.params.items()}
        ok_a, rel, _ = gen.check_predict(
            h, ff, reference, z, _stated(stated, kept))
        del stated
        results["controls"]["fp8_all"] = {"predict_ok": bool(ok_a),
                                          "predict_rel_rms": rel}
        save()
        restore_weights(ff.params, kept)
    for pair in pairs:
        results.setdefault("cold_then_hit", []).append(
            rescore_cold_hit(h, reference, z, sound, pair))
        save()
    for name in wanted:
        got = gen.check_emitted(h, reference, z, sound, windows[name], sched,
                                lengths)
        entry = results["controls"].setdefault(name, {})
        entry.update(emitted_ok=got["ok"],
                     mean_margin=got["worst_mean_margin"],
                     max_margin=got["worst_margin"],
                     rescored=got["rescored_document_tokens"])
        if "predict_ok" in entry:
            entry["correct_by_check"] = bool(entry["predict_ok"]
                                             and entry["emitted_ok"])
        h.log(f"RESULT control {name}: {entry}")
        save()
    tol = h.config["tolerances"]
    h.log(f"limits: {tol}; not correct by check (b): "
          f"{[n for n in wanted if not results['controls'][n]['emitted_ok']]}")
    return 0


def _stated(params, kept):
    """`params` with the host copies of `kept` as its rounded leaves: what
    the reference reads while the program still holds the rounded ones. The
    reference casts one matrix at a time (`jnp.asarray(w).astype(float32)`),
    so a host array costs a transfer and never a second copy of the weights
    on the device."""
    for (op, name), w in kept.items():
        params[op][name] = w
    return params


if __name__ == "__main__":
    sys.exit(main())
