"""Controls for the limits of `swa-sink-docqa-saturated`'s `correct`: faults
planted in the TIMED path, each driven through a short window at the cell's
rate on an engine of its own and judged by the cell's own checks
(`reference/serve_check_ring.py`: predict, the emitted tokens' margins, the
rings' rows through a hit against a cold prefill). A limit of the
configuration file lies between the largest reading the sound program gives
and the smallest a control gives; this script is where the second kind of
reading comes from.

What is planted (one at a time; every control builds its own engine over the
one model, because the reference's 17 k-token passes need the room of the
engine's pools; the resident set is CONTROL_DOCUMENTS documents of the shorter
length, so that a control compiles one cold program and not two):

  sound              nothing
  sink_off           the window layers' softmax without its sink
  sink_on_global     the global layers' softmax WITH a sink (a window
                     layer's logits)
  rotary_all         rotary over all 192 entries of a head, not the first 64
  bases_swapped      rotary base 1e4 on the global layers, 5e6 on the window
                     layers
  value_scale_out    v = Wv x without the 0.707
  four_kv_heads      a window layer reads 4 KV heads: heads 4-7 of its keys
                     and values are copies of heads 0-3
  page_before        a document's snapshot holds, of every window layer, the
                     page BEFORE the match point's: every hit seats rows 128
                     positions early
  snapshot_8bit      a window layer's SNAPSHOT alone held in 8 bits (its rows
                     rounded to 3 mantissa bits where the prefill copies them
                     to the node's page; pool and rings as stated): what
                     check (c) is for, since it compares rows that went
                     through the snapshot with rows that did not
  cache_8bit         every key and value rounded to 3 mantissa bits where it
                     is written into the pool or a snapshot (prefill's page
                     writes, the seat of the rings, decode's appends; both
                     kinds of layer): the nearest precision below bf16
  weights_8bit       no window: `ff.predict` on every weight matrix rounded
                     to 3 mantissa bits at bf16's exponent range against the
                     reference on the weights as stated

Everything is written to chiprun_out/mimo_controls.json as it is read.

    python3 benchmark/mimo_controls.py --seed 3000004601 [--seconds 8]
        [--only sound,sink_off] [--rehearsal]
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run, spec  # noqa: E402
from benchmark.exaone_controls import _round, cache_8bit  # noqa: E402

CELL = "swa-sink-docqa-saturated"
CONTROLS = ("sound", "sink_off", "sink_on_global", "rotary_all",
            "bases_swapped", "value_scale_out", "four_kv_heads",
            "page_before", "snapshot_8bit", "cache_8bit", "weights_8bit")
CONTROL_DOCUMENTS = 2
OUT = os.path.join(ROOT, "chiprun_out", "mimo_controls.json")


def _ops(ff, kind):
    """The attention ops of one kind ("window" | "global")."""
    return [op for op in ff.ops if op.name.startswith(f"attn_{kind}_")]


@contextlib.contextmanager
def _attrs(ops, **values):
    """Each op's attributes set for the block: a program traced inside it is
    built otherwise."""
    kept = [{k: getattr(op, k) for k in values} for op in ops]
    for op in ops:
        for k, v in values.items():
            setattr(op, k, v(op) if callable(v) else v)
    try:
        yield
    finally:
        for op, old in zip(ops, kept):
            for k, v in old.items():
                setattr(op, k, v)


def sink_off(ff, eng):
    return _attrs(_ops(ff, "window"), sink=None)


@contextlib.contextmanager
def sink_on_global(ff, eng):
    logits = ff.params[_ops(ff, "window")[0].name]["sink"]
    with _attrs(_ops(ff, "global"), sink=1.0):
        for op in _ops(ff, "global"):
            ff.params[op.name]["sink"] = logits
        try:
            yield
        finally:
            for op in _ops(ff, "global"):
                del ff.params[op.name]["sink"]


def rotary_all(ff, eng):
    return _attrs(_ops(ff, "window") + _ops(ff, "global"), rope_dim=0)


def bases_swapped(ff, eng):
    win, glob = _ops(ff, "window"), _ops(ff, "global")
    a, b = win[0].rope_theta, glob[0].rope_theta
    return _attrs(win + glob,
                  rope_theta=lambda op: b if op.window else a)


def value_scale_out(ff, eng):
    return _attrs(_ops(ff, "window") + _ops(ff, "global"), value_scale=1.0)


@contextlib.contextmanager
def four_kv_heads(ff, eng):
    import jax.numpy as jnp

    def halved(op):
        project = op._project_qkv

        def four(params, q, k, v, rope_offset=0):
            qh, kh, vh = project(params, q, k, v, rope_offset=rope_offset)
            n = kh.shape[2] // 2
            return (qh, jnp.concatenate([kh[:, :, :n]] * 2, axis=2),
                    jnp.concatenate([vh[:, :, :n]] * 2, axis=2))
        return four

    with _attrs(_ops(ff, "window"), _project_qkv=halved):
        yield


@contextlib.contextmanager
def page_before(ff, eng):
    from flexflow_tpu.ops.attention import MultiHeadAttention as Attn

    take = Attn.take_window_snapshot

    def early(self, snaps, contiguous, length, snap, impl="einsum"):
        return take(self, snaps, contiguous,
                    length - snaps["k"].shape[1], snap, impl=impl)

    Attn.take_window_snapshot = early
    try:
        yield
    finally:
        Attn.take_window_snapshot = take


@contextlib.contextmanager
def snapshot_8bit(ff, eng):
    from flexflow_tpu.ops.attention import MultiHeadAttention as Attn

    take = Attn.take_window_snapshot

    def rounded(self, snaps, contiguous, length, snap, impl="einsum"):
        return take(self, snaps, {n: _round(x, 3)
                                  for n, x in contiguous.items()},
                    length, snap, impl=impl)

    Attn.take_window_snapshot = rounded
    try:
        yield
    finally:
        Attn.take_window_snapshot = take


PLANT = {"sink_off": sink_off, "sink_on_global": sink_on_global,
         "rotary_all": rotary_all, "bases_swapped": bases_swapped,
         "value_scale_out": value_scale_out, "four_kv_heads": four_kv_heads,
         "page_before": page_before, "snapshot_8bit": snapshot_8bit,
         "cache_8bit": cache_8bit}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000004601)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from benchmark.reference import serve_check_ring as check

    bench = spec.load_benchmark(ROOT)
    h = bench_run.load_cell(bench, CELL, args.seed, args.seconds,
                            rehearsal=args.rehearsal)
    if not args.rehearsal:
        if jax.devices()[0].platform != "tpu":
            print("mimo_controls: not a TPU: nothing is read",
                  file=sys.stderr)
            return 2
        bench_run.place_compile_cache()
    short = min(g["tokens"] for g in h.traffic["documents"])
    h.traffic = {**h.traffic, "documents": [
        {"count": CONTROL_DOCUMENTS, "tokens": short}]}
    gen = spec.load_module("generators", h.traffic["kind"])
    ff, _, _ = h.builder.build(h.config, h.cut, h.rehearsal)
    kw = dict(h.cut["engine"])
    if h.rehearsal:
        kw.update(h.builder.rehearsal_engine(kw),
                  paged_attention_impl="pallas")
    tol = h.config["tolerances"]
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    out = {"cell": CELL, "seed": args.seed, "seconds": args.seconds,
           "rehearsal": args.rehearsal, "tolerances": tol, "controls": {}}

    def record(name, row, t0):
        row["seconds"] = round(time.perf_counter() - t0, 1)
        out["controls"][name] = row
        h.log(f"control {name}: {row}")
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)

    for i, name in enumerate(args.only.split(",")):
        t0 = time.perf_counter()
        h.args.seed = args.seed + i          # every window its own tokens
        if name == "weights_8bit":
            toks = np.random.default_rng([h.args.seed, 0xD15E]).integers(
                1, z["vocab_size"], dtype=np.int32,
                size=(1, h.cut["graph_seq_len"] // h.scale))
            want = np.asarray(reference.forward(ff.params, toks[0], z))
            kept = {(op, w): v for op, ws in ff.params.items()
                    for w, v in ws.items() if v.ndim >= 2}
            to8 = jax.jit(lambda w: _round(w, 3))
            for (op, w), v in kept.items():
                ff.params[op][w] = to8(v)
            got = np.asarray(jax.device_get(ff.predict({"input": toks})),
                             np.float32)[0]
            for (op, w), v in kept.items():
                ff.params[op][w] = v
            rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
            record(name, {"predict_rel_rms_8bit": rel, "fails": (
                ["predict_rel_rms"] if rel > tol["predict_rel_rms"]
                else [])}, t0)
            continue
        sched = gen.generate(h.traffic, h.args.seed, h.seconds, h.vocab,
                             h.scale)
        plant = PLANT.get(name, lambda ff, eng: contextlib.nullcontext())
        eng = ff.make_serving_engine(**kw)
        with plant(ff, eng):
            # every program is traced inside the plant, at its first call
            gen.warm(h, eng, h.traffic)
            records, _, _ = gen.drive(
                eng, sched, h.seconds, float(h.traffic["drain_grace_s"]),
                h.annotate, h.trace_poll)
            for k, r in enumerate(records):
                r["index"] = k
            probed = check.probe(h, eng, sched.docs[0])
        eng.kv.pool = eng.kv.snapshots = None
        del eng
        gc.collect()
        # judged after the fault is undone: (a) reads the sound program, (b)
        # rescores what the faulted window emitted, (c) holds the rows the
        # faulted probe left against its own cold prefill
        checks = check.run(h, ff, records, sched, probed)
        checks.pop("ring_errors")
        done = sum(r["state"] == "done" for r in records)
        record(name, {**checks, "completed": done, "offered": len(records),
                      "fails": sorted(
                          k for k, v in (
                              ("predict_rel_rms", checks["predict_rel_rms"]),
                              ("emitted_margin_mean",
                               checks["worst_mean_margin"]),
                              ("ring_rel_rms", checks["ring_rel_rms"]))
                          if v > tol[k])}, t0)
    return 64 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
