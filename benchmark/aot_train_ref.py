#!/usr/bin/env python3
"""`aot_check.py` for a cell of kind "train_job_ref": compile the train step
and the named reference's gradient of one sequence at the REAL widths for a
TPU v5e without a chip, and print each program's memory, its Mosaic calls by
name and how many ops of the step lie under each graph op's name.

    JAX_PLATFORMS=cpu python3 benchmark/aot_train_ref.py --workload <cell>

`aot_check.check_train` compiles the step as it is; its reference part calls
`reference/decoder.py` by name, so this file hands it the cut without
`update_check_weights` and lowers the configuration's own reference module
(`mean_loss_and_grads`'s jitted `_sequence_loss_and_grads`) on the shapes of
the program's parameters instead. Exit codes as `aot_check.py`.
"""

import argparse
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.pop("FF_PALLAS_INTERPRET", None)
    os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import aot_check, run as bench_run, spec, train_trace

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=aot_check.TOPOLOGY)
    except Exception as e:  # whatever libtpu's absence raises here
        print(f"aot_train_ref: SKIPPED - cannot describe "
              f"{aot_check.TOPOLOGY} ({type(e).__name__}: {e})")
        return aot_check.SKIPPED
    one_chip = SingleDeviceSharding(topo.devices[0])
    h = bench_run.load_cell(spec.load_benchmark(ROOT), args.workload)
    print(f"{args.workload} ({h.workload['config']}):", flush=True)

    # check_train builds the model itself and keeps it: listen in
    built, compiled = [], []
    build, report = h.builder.build, aot_check.report
    h.builder.build = lambda *a, **k: built.append(build(*a, **k)) \
        or built[-1]
    aot_check.report = lambda name, c, *a, **k: compiled.append(c) \
        or report(name, c, *a, **k)
    wrt = [tuple(w) for w in h.cut["update_check_weights"]]
    h.cut = {k: v for k, v in h.cut.items() if k != "update_check_weights"}
    try:
        ok = aot_check.check_train(h, topo)
    finally:
        h.builder.build, aot_check.report = build, report
    text = compiled[0].as_text()
    calls = {}
    for head in re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                           r'"tpu_custom_call"', text):
        kind = train_trace.kernel_of(head) or re.sub(r"[._]\d+", "", head)
        calls[kind] = calls.get(kind, 0) + 1
    print(f"  Mosaic calls of the step by kind: {calls}")
    ff = built[0][0]
    scopes = train_trace.scopes_of(text, [op.name for op in ff.ops])
    by = {}
    for s in scopes.values():
        by[s] = by.get(s, 0) + 1
    print(f"  instructions of the step under a graph op's name: {by}")
    ok &= all(calls.get(k) for k in train_trace.FLASH)

    seq = built[0][1].dims[1]
    ref = spec.load_module("reference", h.config["reference"])
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        ff.params)
    subset = {}
    for op, w in wrt:
        subset.setdefault(op, {})[w] = params[op][w]
    ids = jax.ShapeDtypeStruct((seq,), np.int32, sharding=one_chip)
    sizes = ref._sizes(h.builder.sizes_of(h.config, h.cut))
    t0 = time.perf_counter()
    c = ref._sequence_loss_and_grads.lower(subset, params, ids, ids,
                                           z=sizes).compile()
    m = c.memory_analysis()
    sub_b = sum(np.prod(a.shape) * 4 for a in jax.tree.leaves(subset))
    beside = (2 * m.argument_size_in_bytes      # the two moments
              + 2 * sub_b)          # summed gradient, copy of the weights
    print(f"  reference gradient compiled in {time.perf_counter() - t0:.0f} "
          f"s; beside it {beside / 1e9:.2f} GB (moments, summed gradient, "
          f"copy of the {len(wrt)} checked weights)")
    ok &= aot_check.report("reference_gradient", c)
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + beside)
    print(f"    with what lies beside it: {peak / 1e9:.2f} GB of "
          f"{aot_check.HBM_LIMIT / 1e9:.1f}")
    ok &= peak <= aot_check.HBM_LIMIT
    print("aot_train_ref: every program compiled and fits" if ok
          else "aot_train_ref: FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
