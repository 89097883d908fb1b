"""Controls for the limits of `retention-docqa-saturated`'s `correct`: faults
planted in the TIMED path, each driven through a short window at the cell's
rate on an engine of its own and judged by the cell's own checks
(`reference/serve_check_retention.py`: predict, the emitted tokens' margins,
the probe request's state through a hit). A limit of the configuration file
lies between the largest reading the sound program gives and the smallest a
control gives; this script is where the second kind of reading comes from.

What is planted (one at a time; every control builds its own engine, because
the reference's 32 k-token passes need the room of the engine's pools):

  sound          nothing
  state_bf16     S and z rounded to bfloat16's 8 mantissa bits wherever they
                 are written: seated in a slot or in a SNAPSHOT by a prefill,
                 advanced by a decode step (the nearest precision below the
                 float32 the configuration states)
  decay_dropped  the gate's log-decay forced to 0 in every path of the
                 program (a state that never forgets) against the reference's
                 gated one
  weights_8bit   no window: `ff.predict` on every weight matrix rounded to 3
                 mantissa bits at bf16's exponent range (the nearest precision
                 below the bf16 the configuration states) against the
                 reference on the weights as stated

Everything is written to chiprun_out/brumby_controls.json as it is read.

    python3 benchmark/brumby_controls.py --seed 3000005001 [--seconds 8]
        [--only sound,state_bf16] [--rehearsal]
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
from benchmark.nemotron_controls import _forget, _round  # noqa: E402

CELL = "retention-docqa-saturated"
CONTROLS = ("sound", "state_bf16", "decay_dropped", "weights_8bit")
OUT = os.path.join(ROOT, "chiprun_out", "brumby_controls.json")


@contextlib.contextmanager
def state_bf16(ff, eng):
    from flexflow_tpu.ops import pallas_kernels, retention

    update, seat = (retention.retention_state_update,
                    retention.PowerRetention.seat_state)
    kernel = pallas_kernels.retention_state_update_pallas

    def rounded(fn):
        def run(*a):
            num, den, st, z = fn(*a)
            return num, den, _round(st, 7), _round(z, 7)
        return run

    def rounded_seat(self, pool, state, slot):
        return seat(self, pool, {**state, "s": _round(state["s"], 7),
                                 "z": _round(state["z"], 7)}, slot)

    retention.retention_state_update = rounded(update)
    pallas_kernels.retention_state_update_pallas = rounded(kernel)
    retention.PowerRetention.seat_state = rounded_seat
    _forget(eng, "prefill", "prefill_hit", "decode")
    try:
        yield
    finally:
        retention.retention_state_update = update
        pallas_kernels.retention_state_update_pallas = kernel
        retention.PowerRetention.seat_state = seat
        _forget(eng, "prefill", "prefill_hit", "decode")


@contextlib.contextmanager
def decay_dropped(ff, eng):
    from flexflow_tpu.ops import retention

    project = retention.PowerRetention._project

    def flat(self, params, u, offset):
        q, k, v, l = project(self, params, u, offset)
        return q, k, v, l * 0.0

    retention.PowerRetention._project = flat
    _forget(eng, "prefill", "prefill_hit", "decode")
    try:
        yield
    finally:
        retention.PowerRetention._project = project
        _forget(eng, "prefill", "prefill_hit", "decode")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000005001)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from benchmark.reference import serve_check_retention as check

    bench = spec.load_benchmark(ROOT)
    h = bench_run.load_cell(bench, CELL, args.seed, args.seconds,
                            rehearsal=args.rehearsal)
    if not args.rehearsal:
        if jax.devices()[0].platform != "tpu":
            print("brumby_controls: not a TPU: nothing is read",
                  file=sys.stderr)
            return 2
        bench_run.place_compile_cache()
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
        plant = {"state_bf16": state_bf16,
                 "decay_dropped": decay_dropped}.get(
            name, lambda ff, eng: contextlib.nullcontext())
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
        # rescores what the faulted window emitted, (c) holds the state the
        # faulted probe left
        checks = check.run(h, ff, records, sched, probed)
        checks.pop("state_errors")
        done = sum(r["state"] == "done" for r in records)
        record(name, {**checks, "completed": done, "offered": len(records),
                      "fails": sorted(
                          k for k, v in (
                              ("predict_rel_rms", checks["predict_rel_rms"]),
                              ("emitted_margin", checks["worst_margin"]),
                              ("state_rel_rms", checks["state_rel_rms"]),
                              ("state_rel_rms_last",
                               checks["state_rel_rms_last"]))
                          if v > tol[k])}, t0)
    return 64 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
