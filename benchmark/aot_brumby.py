#!/usr/bin/env python3
"""`aot_granite.py` for the cell `retention-docqa-saturated`: compile every
program the cell's set-up reaches (the cold prefill that seats both document
lengths and takes their snapshots, the prefix-hit prefill of each that resumes
from one, the decode program) at the REAL widths for a TPU v5e without a chip,
and print each program's memory and compile time.

    JAX_PLATFORMS=cpu python3 benchmark/aot_brumby.py

It IS `aot_granite.py` (the set-up's `warm` is `shared_doc_serving`'s for both
kinds), with ONE expectation taken away: that file asks every prefill program
for a Mosaic call (a page writer), and a model whose cached ops are all states
writes no page: its chunked scan is `jax.numpy`. The decode program still has
to hold its kernels (five `retention_state_update` calls). Builds 3.21 B
parameters and 6.4 GB of states and snapshots on the CPU: about 13 GB of host
memory. Exit codes as `aot_check.py`.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None):
    from benchmark import aot_check, aot_granite

    report = aot_check.report

    def prefill_needs_no_kernel(name, compiled, text_needed=()):
        if name.startswith(("('prefill'", "('prefill_hit'")):
            text_needed = ()
        return report(name, compiled, text_needed)

    aot_check.report = prefill_needs_no_kernel
    try:
        return aot_granite.main(
            ["--workload", "retention-docqa-saturated", *(argv or [])])
    finally:
        aot_check.report = report


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
