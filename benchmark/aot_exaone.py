#!/usr/bin/env python3
"""Compile a K-EXAONE serving cell's programs at their REAL widths for a TPU
v5e without a chip, with the expert ops lowered as they are ON the chip.

    JAX_PLATFORMS=cpu python3 benchmark/aot_exaone.py \
        [--workload swa-mixed-lengths-saturated]

`aot_check.py`'s serving check as it is, with the one thing it cannot do for
this cell: from a CPU process ops/moe.py `dropless_lowering` reads the backend
`cpu` and keeps `ragged_dot`, so this file tells the rule the backend is a TPU
(the one fact it cannot observe here), which puts the expert-stream kernel
(SwiGLU's three matrices) into the decode program. The 32 k prefill's
temporaries beside 9.7 GB of weights and pools are the risk. Prints each
program's memory and the Mosaic calls it holds.

Exit code: aot_check's (0 fits, 1 a refusal, 77 no compile-only topology).
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None):
    from benchmark import aot_check
    from flexflow_tpu.ops import moe

    moe._backend = lambda: "tpu"
    return aot_check.main(
        argv or ["--workload", "swa-mixed-lengths-saturated"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
