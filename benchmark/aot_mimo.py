#!/usr/bin/env python3
"""`aot_granite.py` for a cell of kind "shared_doc_serving_window": compile
every program the cell's set-up reaches (the cold prefill that seats each
document length and takes each window layer's snapshot, the prefix-hit
prefill of each that resumes from one, the decode program) at the REAL widths
for a TPU v5e without a chip, and print each program's memory and compile
time.

    JAX_PLATFORMS=cpu python3 benchmark/aot_mimo.py \
        [--workload swa-sink-docqa-saturated]

The interception is `aot_check._Intercept`'s; the stand-ins differ because a
prefill program of an engine that holds snapshots returns the snapshot arrays
behind the pool, and where they are among the arguments depends on what the
model seats (`ServingEngine._seat_args`: here the slot's ring tables). Builds
3.43 B parameters and 2.6 GB of pools on the CPU: about 12 GB of host memory.
Off-TPU the dropless MoE op resolves to its grouped lowering (see
scripts/aot_moe_streamed.py for the streamed one). Exit codes as
`aot_check.py`.
"""

import argparse
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="swa-sink-docqa-saturated")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    os.environ.pop("FF_PALLAS_INTERPRET", None)
    os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import aot_check, run as bench_run, spec
    from benchmark.generators import shared_doc_serving_window as gen

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name=aot_check.TOPOLOGY)
    except Exception as e:  # whatever libtpu's absence raises here
        print(f"aot_mimo: SKIPPED - cannot describe "
              f"{aot_check.TOPOLOGY} ({type(e).__name__}: {e})")
        return aot_check.SKIPPED
    one_chip = SingleDeviceSharding(topo.devices[0])
    h = bench_run.load_cell(spec.load_benchmark(ROOT), args.workload,
                            seed=args.seed)
    t0 = time.perf_counter()
    ff, _, _ = h.builder.build(h.config, h.cut)
    eng = ff.make_serving_engine(**h.cut["engine"],
                                 paged_attention_impl="pallas")
    print(f"  model and engine built in {time.perf_counter() - t0:.0f} s",
          flush=True)
    seated = len(eng._seat_args(0))

    class Hook(aot_check._Intercept):
        """`aot_check._Intercept` for programs that hand the snapshot arrays
        back behind the pool (cold: argument 13 + what is seated, hit: 15 +
        that)."""

        def __call__(self, key, build, *a):
            if key[0] not in ("prefill", "prefill_hit"):
                return super().__call__(key, build, *a)
            if key not in self.seen:
                self.seen.append(key)
                t0 = time.perf_counter()
                try:
                    compiled = build().lower(
                        *aot_check.sds_tree(a, self.one_chip)).compile()
                    self.ok &= aot_check.report(
                        f"{key} ({time.perf_counter() - t0:.0f} s)",
                        compiled, ("tpu_custom_call",))
                except Exception as e:  # the compiler's refusal is the finding
                    self.ok = False
                    print(f"  {key}: REFUSED {type(e).__name__}: "
                          f"{str(e)[:1500]}", flush=True)
            pool, snaps = (a[4], a[13 + seated]) if key[0] == "prefill" \
                else (a[5], a[15 + seated])
            return (np.ones((1,), np.int32), np.ones((1,), bool), pool,
                    snaps)

    hook = Hook(eng, one_chip)
    eng._compiled_call = hook
    gen.warm(h, eng, h.traffic)
    print(f"  programs reached by the set-up: {hook.seen}")
    print("aot_mimo: every program compiled and fits" if hook.ok
          else "aot_mimo: FAILED")
    return 0 if hook.ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
