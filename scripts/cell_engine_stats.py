#!/usr/bin/env python3
"""Run one cell of the benchmark as `benchmark/run.py` runs it and then print
what its engine counted that the result line does not carry: the paged
kernel's shared-page counters (`shared_groups`, `shared_pages_saved`, the
three `kv_*_bytes`), the decode programs it built, and the last decode
dispatch span's own counts. One JSON line, `[engine_stats] ...`, after the
benchmark's result line.

    chiprun -- python3 scripts/cell_engine_stats.py --workload <cell> \\
        --seed <n> --seconds 51 --trace 0
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

KEYS = ("shared_groups", "shared_pages_saved", "shared_members_cap",
        "kv_attended_bytes", "kv_read_bytes", "kv_streamed_bytes",
        "decode_steps", "prefix_hits")
SPAN = ("program", "slots", "context_tokens", "context_tokens_attended",
        "context_tokens_global", "context_tokens_attended_global",
        "shared_groups", "shared_pages_saved")


def main():
    from benchmark import run as bench_run
    from flexflow_tpu.runtime import telemetry
    from flexflow_tpu.runtime.serving import ServingEngine

    engines, build = [], ServingEngine.__init__

    def kept(self, *a, **kw):
        build(self, *a, **kw)
        engines.append(self)

    ServingEngine.__init__ = kept
    rc = bench_run.main(sys.argv[1:])
    for eng in engines:
        st = eng.stats()
        spans = telemetry.tracer().events(name="decode_dispatch")
        print("[engine_stats] " + json.dumps({
            **{k: st.get(k) for k in KEYS},
            "decode_programs": sorted(
                str(k) for k in eng._programs if k[0] == "decode"),
            "last_dispatch": {k: spans[-1]["args"].get(k) for k in SPAN}
            if spans else None}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
