"""Controls for the limits of `hybrid-ssm-docqa-saturated`'s `correct`: faults
planted in the TIMED path, each driven through a short window at the cell's
rate on an engine of its own and judged by the cell's own checks
(`reference/serve_check_snapshot.py`: predict, the emitted tokens' margins,
the probe request's state through a hit). A limit of the configuration file
lies between the largest reading the sound program gives and the smallest a
control gives; this script is where the second kind of reading comes from.

What is planted (one at a time; every control builds its own engine, because
the reference's 17 k-token passes need the room of the engine's pools):

  sound             nothing
  state_bf16        the recurrent state H rounded to bfloat16's 8 mantissa
                    bits wherever it is written: seated in a slot or in a
                    SNAPSHOT by a prefill, advanced by a decode step: pools
                    that held H in the compute dtype (the nearest precision
                    below the float32 the configuration states)
  snapshot_swapped  after the documents are seated the snapshot rows move one
                    document on: every admission resumes from ANOTHER
                    document's state under its own document's pages
  snapshot_early    every document's snapshot holds the state one page EARLY
                    (after its first N - 1 pages) under the node of its N-th
  state_rolled      before every fourth decode dispatch the slots' recurrent
                    states move one slot on: each request decodes from a
                    neighbour's state (PR 37's `state_swapped`): the fault
                    the emitted tokens' margins are there to see
  weights_8bit      no window: `ff.predict` on every weight matrix rounded to
                    3 mantissa bits at bf16's exponent range (the nearest
                    precision below the bf16 the configuration states)
                    against the reference on the weights as stated

Everything is written to chiprun_out/granite_controls.json as it is read.

    python3 benchmark/granite_controls.py --seed 3000004301 [--seconds 8]
        [--only sound,snapshot_swapped] [--rehearsal]
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
from benchmark.nemotron_controls import (  # noqa: E402
    _round, state_bf16, state_swapped as state_rolled)

CELL = "hybrid-ssm-docqa-saturated"
CONTROLS = ("sound", "state_bf16", "snapshot_swapped", "snapshot_early",
            "state_rolled", "weights_8bit")
OUT = os.path.join(ROOT, "chiprun_out", "granite_controls.json")


def swap_snapshots(eng):
    """Rows 1..n of the snapshot arrays move one on (row 0 is scratch)."""
    import jax.numpy as jnp

    n = eng.stats()["state_snapshots_held"]
    for name, arrays in eng.kv.snapshots.items():
        eng.kv.snapshots[name] = {
            k: v.at[1:n + 1].set(jnp.roll(v[1:n + 1], 1, axis=0))
            for k, v in arrays.items()}


def seat_early(h, eng, docs):
    """Every document seated in two prefills, its first N - 1 pages and then
    all N; the state after N - 1 is then copied over the snapshot of N and
    its own id handed back (the node of N - 1 stays, as an interior page)."""
    trie, ps = eng.prefix_cache, eng.page_size
    ns = eng._cache_ns(None)
    for d in docs:
        eng.prefill_into_cache(d[:d.size - ps])
        early = trie.match(d, d.size // ps - 1, ns=ns)[-1]
        eng.prefill_into_cache(d)
        last = trie.match(d, d.size // ps, ns=ns)[-1]
        assert early.snap and last.snap and early is last.parent
        for name, arrays in eng.kv.snapshots.items():
            eng.kv.snapshots[name] = {
                k: v.at[last.snap].set(v[early.snap])
                for k, v in arrays.items()}
        trie.release_snapshot_id(early.snap)
        early.snap = 0
    h.log(f"snapshot_early: {len(docs)} documents seated, each snapshot one "
          f"page early")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3000004301)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--only", default=",".join(CONTROLS))
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearsal:
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from benchmark.reference import serve_check_snapshot as check

    bench = spec.load_benchmark(ROOT)
    h = bench_run.load_cell(bench, CELL, args.seed, args.seconds,
                            rehearsal=args.rehearsal)
    if not args.rehearsal:
        if jax.devices()[0].platform != "tpu":
            print("granite_controls: not a TPU: nothing is read",
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
        plant = {"state_bf16": state_bf16, "state_rolled": state_rolled}.get(
            name, lambda ff, eng: contextlib.nullcontext())
        eng = ff.make_serving_engine(**kw)
        with plant(ff, eng):
            # every program is traced inside the plant, at its first call
            if name == "snapshot_early":
                seat_early(h, eng, sched.docs)
            gen.warm(h, eng, h.traffic)
            if name == "snapshot_swapped":
                swap_snapshots(eng)
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
                              ("state_rel_rms", checks["state_rel_rms"]))
                          if v > tol[k])}, t0)
    return 64 if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
