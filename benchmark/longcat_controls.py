"""Controls for the limits of `mla-zeromoe-docqa-saturated`'s `correct`: faults
planted in the TIMED path (the prefix-hit prefill, the paged decode and its
kernel `mla_paged_core_dense`, the pool's pages) of one warm engine, each
driven through a short window at the cell's rate and judged by
the cell's generator's own `check_emitted` / `check_predict`
(`generators/shared_doc_serving.py`'s). A
limit of the configuration file lies between the largest reading the sound
program gives and the smallest a control gives; this script is where the
second kind of reading comes from.

What is planted (one at a time, in this order; each undone before the next;
a control that changes a program builds the decode and prefix-hit programs
again, which compile before its window, as the cell's do):

  sound            nothing
  identity_off     the identity term left out: ops/moe.py's dropless forward
                   less the zero-computation picks' gates times the token
  held_off         the held experts left out: their down-projections zeroed
  shortcut_early   the expert layer's output added after the FIRST sub-block
                   (at `res_ffn_<l>_0`) instead of at the layer's end
  skv_off          `kv_lora_scale` 1 in the decode and prefix-hit programs,
                   and the resident documents' cached latents divided by skv
                   (what such a program would have cached, to first order)
  gates_biased     gates taken from p + b instead of p
  wrong_pages      two resident documents of one length hold each other's
                   rows: their requests read another document's pages
  fp8_weights      every weight matrix rounded to float8_e4m3fn: the timed
                   path in the nearest precision below the bf16 the
                   configuration states. Check (a), `ff.predict` with the
                   rounded weights against the reference on the weights as
                   stated, runs too, and the control must come out not
                   correct by one of the cell's limits.
  fp8_cache        the resident documents' latent rows rounded to
                   float8_e4m3fn (an 8-bit cache), weights as stated; last,
                   because it is not undone

Before them, one question is asked of a document that is NOT resident (cold
prefill, the prefix cache publishes it) and once more (prefix-hit prefill):
the cold answer is rescored like any other, and the two answers are compared
where they first differ.

The resident set is the traffic file's `rehearsal.documents` (2 + 1 documents,
both lengths, both hit programs) so that set-up is short; rate, slots, pool,
question and answer lengths are the cell's. Everything is written to
chiprun_out/longcat_controls.json as it is read. The page movers, the 8-bit
rounding and the cold-then-hit question are `dsa_controls.py`'s, imported.

    python3 benchmark/longcat_controls.py --seed 5300005301 [--seconds 8]
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
from benchmark.dsa_controls import (  # noqa: E402
    _stated, cold_then_hit, document_pages, rescore_cold_hit,
    restore_weights, rewrite_pages, round_weights, to_fp8)

CELL = "mla-zeromoe-docqa-saturated"
CONTROLS = ("sound", "identity_off", "held_off", "shortcut_early", "skv_off",
            "gates_biased", "wrong_pages", "fp8_weights", "fp8_cache")
REBUILT = ("identity_off", "held_off", "shortcut_early", "skv_off",
           "gates_biased")
OUT = os.path.join(ROOT, "chiprun_out", "longcat_controls.json")


@contextlib.contextmanager
def rebuilt_programs(eng):
    """The decode and prefix-hit programs are traced again inside, and the
    sound ones put back after."""
    def mine():
        return [k for k in eng._programs
                if k[0] in ("decode", "prefill_hit")]

    built = {k: eng._programs.pop(k) for k in mine()}
    try:
        yield
    finally:
        for k in mine():
            del eng._programs[k]
        eng._programs.update(built)


@contextlib.contextmanager
def planted(name, eng, ff, docs, state):
    """The engine with control `name` planted; undone on exit (but for
    `fp8_cache`, which comes last: the pool is dropped after it; the rounded
    weights are restored by `main` once check (a) has read them)."""
    import jax.numpy as jnp
    from flexflow_tpu.ops.moe import MoE

    pages = document_pages(eng, docs)
    moes = [op for op in ff.ops if isinstance(op, MoE)]
    attns = [op for op in ff.ops if op.name.startswith("attn_")]
    with contextlib.ExitStack() as stack:
        if name in REBUILT:
            stack.enter_context(rebuilt_programs(eng))
        if name == "identity_off":
            sound = MoE._forward_dropless

            def without_identity(self, params, t, *a, **kw):
                y, aux = sound(self, params, t, *a, **kw)
                _, top_g, top_e = self._route(params, t)
                zero = jnp.sum(jnp.where(top_e >= self.num_experts, top_g,
                                         0.0), axis=-1, keepdims=True)
                return [(y.reshape(t.shape).astype(jnp.float32)
                         - zero * t.astype(jnp.float32))
                        .astype(y.dtype).reshape(y.shape), aux]

            MoE._forward_dropless = without_identity
            stack.callback(setattr, MoE, "_forward_dropless", sound)
        elif name == "held_off":
            sound = MoE._forward_dropless
            MoE._forward_dropless = lambda self, params, *a, **kw: sound(
                self, {**params, "w_down": jnp.zeros_like(params["w_down"])},
                *a, **kw)
            stack.callback(setattr, MoE, "_forward_dropless", sound)
        elif name == "shortcut_early":
            tail = set(eng.gen._tail_ops)
            for moe in moes:
                layer = moe.name.split("_")[1]
                first = ff.get_op_by_name(f"res_ffn_{layer}_0")
                last = ff.get_op_by_name(f"res_moe_{layer}")
                for op, fwd, inputs in (
                        (first, lambda p, xs, **kw: [xs[0] + xs[1] + xs[2]],
                         first.inputs + [moe.outputs[0]]),
                        (last, lambda p, xs, **kw: [xs[0]], last.inputs)):
                    stack.callback(setattr, op, "inputs", op.inputs)
                    stack.callback(op.__dict__.pop, "forward", None)
                    op.inputs, op.forward = inputs, fwd
            # the last layer's experts now feed its second attention
            eng.gen._tail_ops = tail - set(moes)
            stack.callback(setattr, eng.gen, "_tail_ops", tail)
        elif name == "skv_off":
            skv = attns[0].kv_lora_scale
            c = attns[0].kv_lora_rank
            ids = np.concatenate(pages)

            def scaled(by):
                return lambda rows: jnp.concatenate(
                    [(rows[..., :c].astype(jnp.float32) * by)
                     .astype(rows.dtype), rows[..., c:]], axis=-1)

            for op in attns:
                op.kv_lora_scale = 1.0
                stack.callback(setattr, op, "kv_lora_scale", skv)
            rewrite_pages(eng, ids, ids, scaled(1.0 / skv))
            stack.callback(rewrite_pages, eng, ids, ids, scaled(skv))
        elif name == "gates_biased":
            sound = MoE._route

            def biased(self, params, t):
                scores, _, top_e = sound(self, params, t)
                sel = scores + params["score_bias"].astype(jnp.float32)
                return scores, self.routed_scaling * jnp.take_along_axis(
                    sel, top_e, axis=-1), top_e

            MoE._route = biased
            stack.callback(setattr, MoE, "_route", sound)
        elif name == "wrong_pages":
            # each length's documents in a ring; a length with one document
            # keeps its own pages
            ids = np.concatenate(pages)
            by_len = {}
            for k, p in enumerate(pages):
                by_len.setdefault(p.size, []).append(k)
            nxt = {k: ring[(i + 1) % len(ring)]
                   for ring in by_len.values() for i, k in enumerate(ring)}
            moved = np.concatenate([pages[nxt[k]] for k in range(len(pages))])
            rewrite_pages(eng, ids, moved)
            stack.callback(rewrite_pages, eng, moved, ids)
        elif name == "fp8_weights":
            state["weights"] = round_weights(ff.params)
        elif name == "fp8_cache":
            ids = np.concatenate(pages)
            state["cache_changed"] = rewrite_pages(eng, ids, ids, to_fp8)
        yield


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=5300005301)
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
    h = bench_run.load_cell(spec.load_benchmark(ROOT), CELL, args.seed,
                            args.seconds, 0, args.rehearsal)
    gen = spec.load_module("generators", h.traffic["kind"])
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
            if name in REBUILT:
                gen.warm(h, eng, traffic)
            records, _, t_end = gen.drive(eng, sched, h.seconds, grace,
                                          h.annotate)
        for k, r in enumerate(records):
            r["index"] = k
        windows[name] = records
        done = sum(r["state"] == "done" for r in records)
        if name == "fp8_cache":
            results["controls"].setdefault(name, {})["cache_values_changed"] \
                = state["cache_changed"]
        h.log(f"control {name}: window of {h.seconds} s ended at "
              f"{t_end:.2f} s, {done} of {len(records)} requests done "
              f"({time.perf_counter() - t0:.1f} s with planting)")
        if name == "fp8_weights":
            # check (a) of the program on the rounded weights, against the
            # reference on the weights as stated (host copy put back after)
            kept = state.pop("weights")
            stated = {op: dict(ws) for op, ws in ff.params.items()}
            ok_a, rel, _ = gen.check_predict(
                h, ff, reference, z, _stated(stated, kept))
            del stated
            results["controls"][name] = {"predict_ok": bool(ok_a),
                                         "predict_rel_rms": rel}
            save()
            restore_weights(ff.params, kept)

    # the reference's float32 pass needs the pool's room
    eng.kv.pool = eng.kv.draft_pool = None
    del eng
    gc.collect()

    for pair in pairs:
        results.setdefault("cold_then_hit", []).append(
            rescore_cold_hit(h, reference, z, ff.params, pair))
        save()
    for name in wanted:
        got = gen.check_emitted(h, reference, z, ff.params, windows[name],
                                sched, lengths)
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


if __name__ == "__main__":
    sys.exit(main())
