#!/usr/bin/env python3
"""`aot_shared_doc.py` for the cell `mla-zeromoe-docqa-saturated`: compile
every program the cell's set-up reaches (the cold prefill of each document
length, the prefix-hit prefill of each, the decode program with the kernel
`mla_paged_core_dense`) at the REAL widths for a TPU v5e without a chip, and
print each program's memory and compile time.

    JAX_PLATFORMS=cpu python3 benchmark/aot_longcat.py

It IS `aot_shared_doc.py` called with this cell's name. Builds 5.17 B
parameters (10.3 GB) and a 3.2 GB pool on the CPU: about 14 GB of host memory.
Off-TPU the dropless MoE op resolves to its grouped lowering (see
scripts/aot_moe_streamed.py for the streamed one). Exit codes as
`aot_check.py`.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

CELL = "mla-zeromoe-docqa-saturated"


def main(argv=None):
    from benchmark import aot_shared_doc

    return aot_shared_doc.main(["--workload", CELL, *(argv or [])])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
