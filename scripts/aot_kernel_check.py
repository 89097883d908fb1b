#!/usr/bin/env python
"""Compile every Pallas kernel x shape class of the chip_smoke.py sweep for a
TPU v5e WITHOUT a chip, so a Mosaic refusal is caught in the sandbox before
chip time is spent.

libtpu can describe a topology it is not attached to
(jax.experimental.topologies); lowering a jitted function against a device
of that topology and calling .compile() runs the real Mosaic + XLA TPU
compilers. A compile-only topology is not a chip: this proves a kernel
lowers, not that it is right — chip_smoke.py compares results on the device.

Usage: JAX_PLATFORMS=cpu python scripts/aot_kernel_check.py
Exit 0: every class compiled (or its selector refused it, with the reason).
Exit 1: a class failed to compile. Exit 77: no v5e topology could be created
(no libtpu in this installation) — nothing was checked.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

TOPOLOGY = "v5e:2x2"
SKIPPED = 77


def main():
    # the kernels must compile here, whatever the caller's environment (the
    # CPU test suite and CI export FF_PALLAS_INTERPRET=1 for everything else)
    os.environ.pop("FF_PALLAS_INTERPRET", None)
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import chip_smoke

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=TOPOLOGY)
    except Exception as e:  # whatever libtpu's absence raises here
        print(f"aot_kernel_check: SKIPPED — cannot create the compile-only "
              f"{TOPOLOGY} topology ({type(e).__name__}: {e})")
        return SKIPPED
    on_chip = SingleDeviceSharding(topo.devices[0])
    print(f"aot_kernel_check: compiling for {topo.devices[0].device_kind} "
          f"({TOPOLOGY}, compile-only)")

    failed = []
    for i, case in enumerate(chip_smoke.kernel_cases(chip_smoke.FULL)):
        reason = case.refusal()
        if reason is not None:
            print(f"  {case.name}: refused({reason})")
            continue
        specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip)
                 for a in case.make_args(np.random.RandomState(i))]
        try:
            text = jax.jit(case.kernel).lower(*specs).compile().as_text()
        except Exception as e:  # a Mosaic/XLA refusal of any kind is the finding
            failed.append(case.name)
            print(f"  {case.name}: FAILED {type(e).__name__}: "
                  f"{str(e)[:600]}")
            continue
        calls = text.count('custom_call_target="tpu_custom_call"')
        if not calls:
            failed.append(case.name)
        print(f"  {case.name}: compiled ({calls} Mosaic calls)")
    if failed:
        print(f"aot_kernel_check: {len(failed)} FAILED: {failed}")
        return 1
    print("aot_kernel_check: every kernel x class compiles for v5e")
    return 0


if __name__ == "__main__":
    sys.exit(main())
