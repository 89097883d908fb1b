"""Controls for the limits of `ssm-latentmoe-chat-saturated`'s `correct`:
faults planted in the TIMED path of one warm engine, each driven through a
short window at the cell's rate and judged by the cell's own checks
(`reference/serve_check_state.py`: predict, the emitted tokens' margins, the
probe request's state). A limit of the configuration file lies between the largest
reading the sound program gives and the smallest a control gives; this script
is where the second kind of reading comes from.

What is planted (one at a time, in this order; each undone before the next):

  sound           nothing
  state_bf16      the recurrent state H rounded to bfloat16's 8 mantissa bits
                  wherever it is written (seated by a prefill, advanced by a
                  decode step): a pool that held H in the compute dtype
  weights_8bit    every weight matrix rounded to 3 mantissa bits at bf16's
                  own exponent range (an 8-bit float with a scale that
                  loses no small weight; float8_e4m3fn's 4 exponent bits
                  would flush what lies under 2^-6, most of a glorot matrix
                  of this width, to zero): the nearest precision below the
                  bf16 the configuration states. The window runs on them, and
                  `ff.predict` is read on them against the reference on the
                  weights as stated (`predict_rel_rms_8bit`); check (b)
                  rescores what the window emitted under the weights as
                  stated
  conv_pad_tail   the conv tail taken from the END of the prompt's bucket (its
                  padding rows) instead of the last live rows
  state_swapped   before every fourth decode dispatch the slots' recurrent
                  states move one slot on: each request decodes from a
                  neighbour's state

Everything is written to chiprun_out/nemotron_controls.json as it is read.

    python3 benchmark/nemotron_controls.py --seed 3000003301 [--seconds 8]
        [--only sound,state_swapped] [--rehearsal]
"""

import argparse
import contextlib
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

CELL = "ssm-latentmoe-chat-saturated"
CONTROLS = ("sound", "state_bf16", "weights_8bit", "conv_pad_tail",
            "state_swapped")
OUT = os.path.join(ROOT, "chiprun_out", "nemotron_controls.json")


def _round(x, mantissa_bits, exponent_bits=8):
    """x rounded to a narrower float's grid and kept in its own dtype
    (`reduce_precision` is never folded away, as a cast there and back can
    be)."""
    import jax

    return jax.lax.reduce_precision(x, exponent_bits, mantissa_bits)


def _forget(eng, *kinds):
    """Drop the engine's compiled programs of these kinds: the next dispatch
    builds them again, with whatever is planted."""
    for key in [k for k in eng._programs if k[0] in kinds]:
        del eng._programs[key]
        eng._registered.pop(key, None)


@contextlib.contextmanager
def state_bf16(ff, eng):
    from flexflow_tpu.ops import mamba

    from flexflow_tpu.ops import pallas_kernels

    update, seat = mamba.mamba_state_update, mamba.Mamba2Mixer.seat_state
    kernel = pallas_kernels.mamba_state_update_pallas

    def rounded(fn):
        def run(*a):
            y, h = fn(*a)
            return y, _round(h, 7)
        return run

    def rounded_seat(self, pool, state, slot):
        return seat(self, pool, {**state, "h": _round(state["h"], 7)}, slot)

    mamba.mamba_state_update = rounded(update)
    pallas_kernels.mamba_state_update_pallas = rounded(kernel)
    mamba.Mamba2Mixer.seat_state = rounded_seat
    _forget(eng, "prefill", "decode")
    try:
        yield
    finally:
        mamba.mamba_state_update = update
        pallas_kernels.mamba_state_update_pallas = kernel
        mamba.Mamba2Mixer.seat_state = seat
        _forget(eng, "prefill", "decode")


@contextlib.contextmanager
def weights_8bit(ff, eng):
    import jax

    kept = {}
    to8 = jax.jit(lambda w: _round(w, 3))
    for op in ff.params:
        for name, w in list(ff.params[op].items()):
            if w.ndim >= 2:
                kept[op, name] = jax.device_get(w)
                ff.params[op][name] = to8(w)
                del w
    try:
        yield
        # `ff.predict` on the rounded weights, for main() to hold against
        # the reference on the weights as stated
        eng.predict_8bit = jax.device_get(ff.predict(eng.check_batch))
    finally:
        for (op, name), w in kept.items():
            ff.params[op][name] = jax.device_put(
                w, ff.params[op][name].sharding)


@contextlib.contextmanager
def conv_pad_tail(ff, eng):
    from flexflow_tpu.ops.mamba import Mamba2Mixer

    scan = Mamba2Mixer._scan

    def tail_from_the_end(self, params, xs, state, start, row_lengths):
        out, new = scan(self, params, xs, state, start, row_lengths)
        _, xbc, _ = self._project(params, xs[0])
        return out, {**new, "conv": xbc[:, 1 - self.conv_kernel:].astype(
            new["conv"].dtype)}

    Mamba2Mixer._scan = tail_from_the_end
    _forget(eng, "prefill")
    try:
        # the prefill programs are traced inside this block, at their first
        # dispatch
        yield
    finally:
        Mamba2Mixer._scan = scan
        _forget(eng, "prefill")


@contextlib.contextmanager
def state_swapped(ff, eng):
    import jax
    import jax.numpy as jnp

    step, n = eng._decode_step, [0]
    roll = jax.jit(lambda v: jnp.roll(v, 1, axis=0), donate_argnums=(0,))

    def swapping(sampled):
        n[0] += 1
        if n[0] % 4 == 0:
            for op in eng.gen.state_ops:
                eng.kv.pool[op.name] = {
                    k: roll(v) for k, v in eng.kv.pool[op.name].items()}
        return step(sampled)

    eng._decode_step = swapping
    try:
        yield
    finally:
        eng._decode_step = step


PLANT = {"sound": lambda ff, eng: contextlib.nullcontext(),
         "state_bf16": state_bf16, "weights_8bit": weights_8bit,
         "conv_pad_tail": conv_pad_tail, "state_swapped": state_swapped}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000003301)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
    import jax

    from benchmark.reference import serve_check_state

    bench = spec.load_benchmark(ROOT)
    h = bench_run.load_cell(bench, CELL, args.seed, args.seconds,
                            rehearsal=args.rehearsal)
    if not args.rehearsal:
        if jax.devices()[0].platform != "tpu":
            print("nemotron_controls: not a TPU: nothing is read",
                  file=sys.stderr)
            return 2
        bench_run.place_compile_cache()
    gen = spec.load_module("generators", h.traffic["kind"])
    ff, eng = gen.build_engine(h)
    gen.warm(h, eng, h.traffic)
    tol = h.config["tolerances"]
    # check (a)'s own sequence, for the 8-bit control's reading of predict
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    toks = np.random.default_rng([args.seed, 0xD15E]).integers(
        1, z["vocab_size"], size=(1, h.cut["graph_seq_len"] // h.scale),
        dtype=np.int32)
    eng.check_batch = {"input": toks}
    out = {"cell": CELL, "seed": args.seed, "seconds": args.seconds,
           "rehearsal": args.rehearsal, "tolerances": tol, "controls": {}}
    for i, name in enumerate(args.only.split(",")):
        t0 = time.perf_counter()
        h.args.seed = args.seed + i          # every window its own prompts
        sched = gen.generate(h.traffic, h.args.seed, h.seconds, h.vocab,
                             h.scale)
        with PLANT[name](ff, eng):
            # whatever the plant made the engine forget compiles here, not
            # inside the window
            gen.warm(h, eng, h.traffic)
            records, _, _ = gen.drive(
                eng, sched, h.seconds, float(h.traffic["drain_grace_s"]),
                h.annotate, h.trace_poll)
            probed = serve_check_state.probe(h, eng)
        # judged after the fault is undone: (a) reads the sound program, (b)
        # rescores what the faulted window emitted, (c) holds the state the
        # faulted probe left, and the reference sees the weights as stated
        checks = serve_check_state.run(h, ff, eng, records, probed)
        checks.pop("state_errors")
        if name == "weights_8bit":
            want = np.asarray(reference.forward(ff.params, toks[0], z))
            got = np.asarray(eng.predict_8bit, np.float32)[0]
            checks["predict_rel_rms_8bit"] = float(
                np.linalg.norm(got - want) / np.linalg.norm(want))
        done = sum(r["state"] == "done" for r in records)
        row = {**checks, "completed": done, "offered": len(records),
               "fails": sorted(
                   k for k, v in (
                       ("predict_rel_rms", checks.get(
                           "predict_rel_rms_8bit",
                           checks["predict_rel_rms"])),
                       ("emitted_margin", checks["worst_margin"]),
                       ("state_rel_rms", checks["state_rel_rms"]))
                   if v > tol[k]),
               "seconds": round(time.perf_counter() - t0, 1)}
        out["controls"][name] = row
        h.log(f"control {name}: {row}")
        os.makedirs(os.path.dirname(OUT), exist_ok=True)
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)
    return 64 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
