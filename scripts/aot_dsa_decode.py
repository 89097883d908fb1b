#!/usr/bin/env python
"""benchmark/aot_shared_doc.py for `dsa-docqa-saturated` (every program of the
cell compiled for a TPU v5e without a chip, ~6 min, each program's HBM need
printed), which also prints the names of each program's `mla_paged_core*`
custom-calls: benchmark/dsa_trace.py counts the core by that prefix, and the
decode program has to hold one a layer.

Usage: JAX_PLATFORMS=cpu python scripts/aot_dsa_decode.py
Exit codes as benchmark/aot_check.py, and 1 if the decode program holds no
such call.
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import aot_check, aot_shared_doc  # noqa: E402

CORE = re.compile(r"%(mla_paged_core[\w.\-]*) = ")


def main():
    report, decode_cores = aot_check.report, []

    def report_with_cores(name, compiled, text_needed=()):
        cores = CORE.findall(compiled.as_text())
        if name.startswith("('decode'"):
            decode_cores.extend(cores)
        print(f"  {name}: mla_paged_core* custom-calls: {cores}", flush=True)
        return report(name, compiled, text_needed)

    aot_check.report = report_with_cores
    rc = aot_shared_doc.main(["--workload", "dsa-docqa-saturated"])
    if rc == 0 and not decode_cores:
        print("aot_dsa_decode: FAILED - the decode program holds no "
              "mla_paged_core* custom-call")
        return 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
