#!/usr/bin/env python3
"""Compile every program of a cell at its REAL widths for a TPU v5e without a
chip, and print each program's memory. Run before any chip call:

    JAX_PLATFORMS=cpu python3 benchmark/aot_check.py [--workload <name>]

libtpu describes a topology it is not attached to (`v5e:2x2`, compile-only);
lowering a jitted program against a device of it and calling `.compile()`
runs the real XLA TPU and Mosaic compilers. What the compiler refuses here
(a kernel's block shape, VMEM, a program that does not fit 16 GB) costs no
chip time. Nothing runs: this proves a program compiles and how much memory
ONE program needs, never a result or a time.

How the programs are found: the model is built on the CPU at the real size
through the cell's own builder, and
  * a training cell lowers `ff._train_step` on the shapes of its own
    arguments;
  * a serving cell drives the engine through the SAME warm-up prompts the
    benchmark uses, with the engine's program table intercepted: each
    program the warm-up reaches is lowered on the shapes of the arguments
    the engine really passes, compiled for the described chip, and answered
    with dummy tokens so that the engine's loop goes on to the next one.
Off-TPU the program's selectors would route around the kernels, so this
script names them (`FF_FORCE_FLASH_ATTENTION=1`, `paged_attention_impl=
"pallas"`), as the chip run resolves them by itself.

Exit 0: everything compiled and fits. Exit 1: a refusal. Exit 77: no
compile-only topology can be described here (nothing was checked).
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TOPOLOGY = "v5e:2x2"
SKIPPED = 77
HBM_LIMIT = 16.9e9      # memory_stats()["bytes_limit"] of one v5e chip


def sds_tree(tree, sharding):
    import jax
    import jax.numpy as jnp
    import numpy as np

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                       sharding=sharding), tree)


def report(name, compiled, text_needed=()):
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    text = compiled.as_text()
    mosaic = text.count('custom_call_target="tpu_custom_call"')
    print(f"  {name}: compiled; arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.2f} GB (aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f}), temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB -> this program needs "
          f"{peak / 1e9:.2f} GB of {HBM_LIMIT / 1e9:.1f}; {mosaic} Mosaic "
          f"calls", flush=True)
    ok = peak <= HBM_LIMIT
    for needle in text_needed:
        if needle not in text:
            print(f"    MISSING in the compiled program: {needle}")
            ok = False
    return ok


def check_train(h, topo):
    """The train step is bound to the model's mesh by sharding constraints,
    so the model is built on a mesh of the DESCRIBED chip, with parameters
    and optimizer state as shapes only (`jax.eval_shape` around the program's
    own initialisers): nothing can be placed on a chip that is not there. A
    four-chip cut needs four devices here to build its mesh on
    (`jax_num_cpu_devices`, set in main), and runs the repo's search."""
    import functools

    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    import flexflow_tpu.model as ffmodel
    from flexflow_tpu.runtime.executor import GraphExecutor
    from flexflow_tpu.runtime.optimizer import AdamOptimizer

    make_mesh, init_params = ffmodel.make_mesh, GraphExecutor.init_params
    init_state = AdamOptimizer.init_state

    def described_mesh(shape):
        m = make_mesh(shape)
        devs = np.array(topo.devices[:m.devices.size]).reshape(
            m.devices.shape)
        return Mesh(devs, m.axis_names)

    ffmodel.make_mesh = described_mesh
    GraphExecutor.init_params = lambda self, key: jax.eval_shape(
        functools.partial(init_params, self), key)
    AdamOptimizer.init_state = lambda self, params: jax.eval_shape(
        functools.partial(init_state, self), params)
    try:
        ff, tokens, _ = h.builder.build(h.config, h.cut)
    finally:
        ffmodel.make_mesh = make_mesh
        GraphExecutor.init_params = init_params
        AdamOptimizer.init_state = init_state
    if getattr(ff.executor, "jits_per_group", False):
        print("  the strategy places ops on device blocks: one program per "
              "block, no single step to compile here")
        return False
    batch, seq = ff.config.batch_size, tokens.dims[1]
    mesh = ff.mesh
    repl = NamedSharding(mesh, PartitionSpec())

    def sds(tree, shardings):
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, shardings)

    # every argument under the sharding the program itself gives it: weights
    # and Adam moments by the strategy, the batch over `data`
    wsh = ff.executor.param_shardings()
    wsh = {op: {w: wsh[op][w] for w in ws} for op, ws in ff.params.items()}
    params = sds(ff.params, wsh)
    opt = {"m": sds(ff.opt_state["m"], wsh), "v": sds(ff.opt_state["v"], wsh),
           "t": jax.ShapeDtypeStruct((), ff.opt_state["t"].dtype,
                                     sharding=repl)}
    data = {"input": jax.ShapeDtypeStruct(
                (batch, seq), np.int32,
                sharding=ff.executor.input_sharding(tokens)),
            "label": jax.ShapeDtypeStruct(
                (batch, seq, 1), np.int32,
                sharding=ff.executor.input_sharding(ff.label_tensor))}
    key = jax.random.split(ff._rng)[1]
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl)
    summary = getattr(ff, "_search_summary", None)
    if summary:
        print(f"  search: simulator {summary.get('simulator')}, predicted "
              f"step {summary.get('predicted_step_s')} s, predicted peak "
              f"{(summary.get('peak_hbm_bytes') or 0) / 1e9:.2f} GB a chip")
    t0 = time.perf_counter()
    compiled = ff._train_step.lower(params, opt, ff.bn_state, data,
                                    key).compile()
    print(f"  train step lowered + compiled for {mesh.devices.size} chip(s) "
          f"in {time.perf_counter() - t0:.0f} s")
    text = compiled.as_text()
    for coll in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                 "collective-permute"):
        n = text.count(f" {coll}(") + text.count(f" {coll}-start(")
        if n:
            print(f"    {n} x {coll}")
    # flash forward, dq and dkv per layer are Mosaic calls in the step;
    # memory_analysis() counts one chip's share
    ok = report("train_step", compiled, ("tpu_custom_call",))
    wrt = h.cut.get("update_check_weights")
    if wrt:
        # the reference's gradient of one sequence runs BESIDE the weights
        # and the optimizer's moments (its arguments hold only the weights)
        from benchmark.reference import decoder, train_check

        subset = {}
        for op, w in wrt:
            subset.setdefault(op, {})[w] = params[op][w]
        ids = jax.ShapeDtypeStruct((seq,), np.int32, sharding=repl)
        t0 = time.perf_counter()
        compiled = decoder.sequence_loss_and_grads.lower(
            subset, params, ids, ids, **train_check.sizes(h)).compile()
        m = compiled.memory_analysis()
        sub_b = sum(np.prod(a.shape) * 4 for a in jax.tree.leaves(subset))
        beside = (2 * m.argument_size_in_bytes      # the two moments
                  + 2 * sub_b)          # summed gradient, copy of the weights
        print(f"  reference gradient compiled in "
              f"{time.perf_counter() - t0:.0f} s; beside it "
              f"{beside / 1e9:.2f} GB (moments, summed gradient, copy of "
              f"the {len(wrt)} checked weights)")
        ok &= report("reference_gradient", compiled)
        m_peak = (m.argument_size_in_bytes + m.output_size_in_bytes
                  + m.temp_size_in_bytes + beside)
        print(f"    with what lies beside it: {m_peak / 1e9:.2f} GB of "
              f"{HBM_LIMIT / 1e9:.1f}")
        ok &= m_peak <= HBM_LIMIT
    return ok


class _Intercept:
    """Stands in for ServingEngine._compiled_call during the warm-up."""

    def __init__(self, eng, one_chip):
        self.eng, self.one_chip, self.ok, self.seen = eng, one_chip, True, []

    def __call__(self, key, build, *args):
        import numpy as np

        if key not in self.seen:
            self.seen.append(key)
            t0 = time.perf_counter()
            try:
                compiled = build().lower(
                    *sds_tree(args, self.one_chip)).compile()
                self.ok &= report(f"{key} ({time.perf_counter() - t0:.0f} s)",
                                  compiled, ("tpu_custom_call",))
            except Exception as e:     # the compiler's refusal is the finding
                self.ok = False
                print(f"  {key}: REFUSED {type(e).__name__}: "
                      f"{str(e)[:1500]}", flush=True)
        pool = args[4] if key[0] == "prefill" else args[2]
        if key[0] == "prefill":
            return np.ones((1,), np.int32), np.ones((1,), bool), pool
        if key[0] == "decode":
            shape = (key[1], self.eng.slots)
            return np.ones(shape, np.int32), np.ones(shape, bool), pool
        raise RuntimeError(f"aot_check has no stand-in for program {key}")


def check_serve(h, one_chip):
    from benchmark.generators import open_loop_serving as gen

    ff, _, _ = h.builder.build(h.config, h.cut)
    eng = ff.make_serving_engine(**h.cut["engine"],
                                 paged_attention_impl="pallas")
    hook = _Intercept(eng, one_chip)
    eng._compiled_call = hook
    prompts = gen.warm_prompts(h.traffic, 0, h.vocab)
    eng.run(prompts, max_new_tokens=eng.decode_chunk + 1)
    print(f"  programs reached by the warm-up: {hook.seen}")
    return hook.ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell of BENCHMARK.json (default: every cell)")
    args = ap.parse_args(argv)
    os.environ.pop("FF_PALLAS_INTERPRET", None)
    os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_num_cpu_devices", 4)     # a four-chip cut's mesh
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import run as bench_run
    from benchmark import spec

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as e:  # whatever libtpu's absence raises here
        print(f"aot_check: SKIPPED - cannot describe {TOPOLOGY} "
              f"({type(e).__name__}: {e})")
        return SKIPPED
    one_chip = SingleDeviceSharding(topo.devices[0])
    print(f"aot_check: compiling for {topo.devices[0].device_kind} "
          f"({TOPOLOGY}, compile-only); jax backend here is "
          f"{jax.default_backend()}")
    bench = spec.load_benchmark(ROOT)
    names = args.workload or [w["name"] for w in bench["workloads"]]
    ok = True
    for name in names:
        h = bench_run.load_cell(bench, name)
        print(f"{name} ({h.workload['config']}, mode {h.config['mode']}):",
              flush=True)
        ok &= (check_train(h, topo) if h.config["mode"] == "train"
               else check_serve(h, one_chip))
    print("aot_check: every program compiled and fits" if ok
          else "aot_check: FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
