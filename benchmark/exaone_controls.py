"""Controls for the limits of `swa-mixed-lengths-saturated`'s `correct`:
faults planted in the TIMED path of one warm engine, each driven through a
short window at the cell's rate and judged by the cell's own checks
(`reference/serve_check_window.py`: predict, and the mean margin of the
three shortest completed requests and of the shortest one with a prompt of
at least 8192 tokens). A limit of the configuration file lies between the
largest reading the sound program gives and the smallest a control gives;
this script is where the second kind of reading comes from.

What is planted (one at a time, in this order; each undone before the next):

  sound             nothing; after the window ONE request of LONG_PROBE
                    prompt tokens goes through the warm engine (the 32768-row
                    bucket's prefill in 16 chunks, the seat of the rings,
                    decode) and is rescored by the blocked reference through
                    the same function (`long24k_margin_mean`)
  window_off        the paged kernel's window mask off in the decode program:
                    a window layer sees every row its ring of pages holds up
                    to the frontier (129-256 keys where 128 are its window)
  window_page_shift every other decode dispatch is handed every slot's ring
                    turned by one column: the window's pages are read (and
                    the new rows written) one page off
  other_slots_ring  the decode program is handed every slot's ring as
                    another slot's (one, two or three slots on, by the
                    dispatch): a window layer reads another slot's pages
  weights_8bit      every weight matrix rounded to 3 mantissa bits at bf16's
                    own exponent range (an 8-bit float with a scale that
                    loses no small weight: float8_e4m3fn's 4 exponent bits
                    would flush what lies under 2^-6, most of a glorot
                    matrix of this width, to zero, as PR 37 found): the
                    nearest precision below the bf16 the configuration
                    states. The window runs on them, `ff.predict` is read on
                    them against the reference on the weights as stated
                    (`predict_rel_rms_8bit`); check (b) rescores what the
                    window emitted under the weights as stated
  cache_8bit        every key and value rounded to 3 mantissa bits where it
                    is written into the pool (prefill's page writes, the
                    seat of the rings, decode's appends), both kinds of layer

Everything is written to chiprun_out/exaone_controls.json as it is read.

    python3 benchmark/exaone_controls.py --seed 3000003901 [--seconds 8]
        [--only sound,window_off] [--rehearsal]
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

CELL = "swa-mixed-lengths-saturated"
CONTROLS = ("sound", "window_off", "window_page_shift", "other_slots_ring",
            "weights_8bit", "cache_8bit")
LONG_PROBE = 24576 + 419    # prompt tokens: inside the 32768-row bucket
LONG_PROBE_OUT = 160        # emitted: wraps a window layer's ring
OUT = os.path.join(ROOT, "chiprun_out", "exaone_controls.json")


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
def window_off(ff, eng):
    from flexflow_tpu.ops import pallas_kernels

    kernel = pallas_kernels._paged_attn_kernel

    def unmasked(*refs, window=None, **kw):
        # the loop still starts at the window's first page (the prefetched
        # first-page operand is computed outside): only the mask goes
        return kernel(*refs, window=None if window is None else 1 << 30,
                      **kw)

    pallas_kernels._paged_attn_kernel = unmasked
    _forget(eng, "decode")
    try:
        yield
    finally:
        pallas_kernels._paged_attn_kernel = kernel
        _forget(eng, "decode")


@contextlib.contextmanager
def _decode_tables(eng, turn):
    """The ring tables the DECODE dispatch is handed (`window_tables()` of
    every slot) through `turn`; a prefill's own slot row stays as it is."""
    tables, n = eng.kv.window_tables, [0]

    def turned(slot=None):
        out = tables(slot)
        if slot is not None:
            return out
        n[0] += 1
        return {w: turn(t, n[0]) for w, t in out.items()}

    eng.kv.window_tables = turned
    try:
        yield
    finally:
        eng.kv.window_tables = tables


# a turn that stayed the same from dispatch to dispatch would heal itself:
# after a window's worth of steps a slot would have written every row it
# reads, into the wrong place and at the right position. So the turn changes
# with the dispatch.


def window_page_shift(ff, eng):
    return _decode_tables(eng, lambda t, n: np.roll(t, n % 2, axis=1))


def other_slots_ring(ff, eng):
    return _decode_tables(eng, lambda t, n: np.roll(t, 1 + n % 3, axis=0))


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
def cache_8bit(ff, eng):
    from flexflow_tpu.ops.attention import MultiHeadAttention as Attn

    append, write = Attn._paged_append, Attn.paged_prefill_write

    def append8(self, cache, kh, vh, page_ids, offs):
        return append(self, cache, _round(kh, 3), _round(vh, 3), page_ids,
                      offs)

    def write8(self, cache, kh, vh, pages, impl="einsum"):
        return write(self, cache, _round(kh, 3), _round(vh, 3), pages,
                     impl=impl)

    Attn._paged_append, Attn.paged_prefill_write = append8, write8
    _forget(eng, "prefill", "decode")
    try:
        yield
    finally:
        Attn._paged_append, Attn.paged_prefill_write = append, write
        _forget(eng, "prefill", "decode")


PLANT = {"sound": lambda ff, eng: contextlib.nullcontext(),
         "window_off": window_off, "window_page_shift": window_page_shift,
         "other_slots_ring": other_slots_ring, "weights_8bit": weights_8bit,
         "cache_8bit": cache_8bit}


def long_probe(h, ff, eng):
    """One request of LONG_PROBE prompt tokens through the warm engine, its
    mean margin by the cell's own rescoring."""
    from benchmark.reference import serve_check_window as check

    rng = np.random.default_rng([int(h.args.seed), 0x10E6])
    prompt = rng.integers(1, h.vocab, size=max(8, LONG_PROBE // h.scale),
                          dtype=np.int32)
    before = eng.recompile_count
    req = eng.submit(prompt, max(eng.decode_chunk, LONG_PROBE_OUT // h.scale))
    while eng.pending():
        eng.step()
    if req.state != "done" or eng.recompile_count != before:
        raise RuntimeError(f"the long probe: {req.state} {req.error}, "
                           f"{eng.recompile_count - before} programs "
                           f"compiled")
    reference = spec.load_module("reference", h.config["reference"])
    z = h.builder.sizes_of(h.config, h.cut, h.rehearsal)
    t0 = time.perf_counter()
    m = check.margins_of(reference, ff.params, z, req, check.PAD_LONG)
    h.log(f"long probe prompt={prompt.size} emitted={m.size}: margin mean "
          f"{m.mean():.5f} max {m.max():.5f}, {int((m == 0).sum())}/{m.size} "
          f"the reference's own argmax; the blocked reference took "
          f"{time.perf_counter() - t0:.1f} s")
    return {"long24k_prompt_tokens": int(prompt.size),
            "long24k_margin_mean": float(m.mean()),
            "long24k_margin_max": float(m.max())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000003901)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from benchmark.reference import serve_check_window

    bench = spec.load_benchmark(ROOT)
    h = bench_run.load_cell(bench, CELL, args.seed, args.seconds,
                            rehearsal=args.rehearsal)
    if not args.rehearsal:
        if jax.devices()[0].platform != "tpu":
            print("exaone_controls: not a TPU: nothing is read",
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
            extra = long_probe(h, ff, eng) if name == "sound" else {}
        # judged after the fault is undone: (a) reads the sound program, (b)
        # rescores what the faulted window emitted, and the reference sees
        # the weights as stated
        checks = {**serve_check_window.run(h, ff, records), **extra}
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
                       ("emitted_margin_mean",
                        checks["emitted_margin_mean"]),
                       ("emitted_margin_pooled",
                        checks["emitted_margin_pooled"]))
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
