#!/usr/bin/env python
"""Compile `moe-chat-steady`'s serve programs for a TPU v5e WITHOUT a chip,
with the MoE ops lowered as they are ON the chip.

benchmark/aot_check.py compiles a cell's programs against libtpu's
compile-only topology from a CPU process, where ops/moe.py
`dropless_lowering` reads the backend `cpu` and keeps `ragged_dot`: its
figures for an expert model are those of the grouped lowering. This script
tells the rule the backend is a TPU (the one fact it cannot observe here)
and runs that check unchanged, so the decode program and the short prefill
buckets hold the expert-stream kernel and their HBM need is the chip's.

Usage: JAX_PLATFORMS=cpu python scripts/aot_moe_streamed.py
Exit code: benchmark/aot_check.py's.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    from benchmark import aot_check
    from flexflow_tpu.ops import moe

    moe._backend = lambda: "tpu"
    return aot_check.main(["--workload", "moe-chat-steady"])


if __name__ == "__main__":
    sys.exit(main())
