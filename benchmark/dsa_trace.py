"""A traced run's device time inside the sparse latent attention's kernels and
the held experts' kernel, beside the counts their dispatches carried.

Which device op is which (TPU v5e, jax 0.9.0): a `pallas_call` given `name=`
keeps it as its HLO instruction's name, so the two decode kernels read as
`dsa_index_scores[.n]` and `mla_paged_core[.n]` whatever layer they run in;
the expert-stream kernel has no name of its own and reads under its jax scope,
`moe_<i>.<n>` (benchmark/moe_trace.py `is_grouped_matmul`). What the trace
CANNOT attribute: the selection between the two kernels (`dsa_threshold`: 12
anonymous compare-and-count passes inside two while loops a layer) and, in a
prefill program, the whole attention (blocked XLA einsums): a device event
carries its HLO instruction and no jax scope. `dsa_device_share` therefore
reads low by the selection's time in decode and sees no prefill attention;
`reduce_dsa` prints the decode programs' time outside every named kernel,
which bounds what is missed.

`reduce_dsa` works on `span_reduce.load`'s structure. Decode programs that ran
wholly inside the traced window are paired with the last `ff.decode_dispatch`
that began before each (its `dsa_context_tokens`, `k` steps and `slots` live
rows) and with the `ff.record_tokens` that follows (its `experts_hit`). A trace without `ff.engine_step`, or whose spans carry none of
these counts (a program without the op), gives None for them: the readers
then leave their metrics out.

By hand, after a traced run: python3 benchmark/dsa_trace.py .bench_trace/<cell>
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import moe_trace as mt, span_reduce as sr  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

INDEX, CORE = "dsa_index_scores", "mla_paged_core"
DISPATCH_COUNTS = ("dsa_context_tokens", "k", "slots")


def kernel_of(name):
    """'index' | 'core' | 'expert' | None for a device op's name."""
    if not tr.is_custom_call(name):
        return None
    head = name.split(" = ", 1)[0].lstrip("%")
    if head.startswith(INDEX):
        return "index"
    if head.startswith(CORE):
        return "core"
    return "expert" if mt.is_grouped_matmul(name) else None


def _spans_before(spans, name, starts):
    """For each time in `starts` the stats of the last span called `name`
    that began at or before it."""
    named = sorted((s, st) for n, s, _, st in spans if n == name)
    out, j = [], -1
    for t in starts:
        while j + 1 < len(named) and named[j + 1][0] <= t:
            j += 1
        out.append(named[j][1] if j >= 0 else {})
    return out


def _total(stats, key):
    vals = [st.get(key) for st in stats]
    return (None if not vals or None in vals
            else float(sum(float(v) for v in vals)))


def _row_steps(stats):
    """Sum over dispatches of live rows x steps (None where a span lacks
    either)."""
    pairs = [(st.get("slots"), st.get("k")) for st in stats]
    if not pairs or any(None in p for p in pairs):
        return None
    return float(sum(float(a) * float(b) for a, b in pairs))


def reduce_dsa(planes):
    """None without `ff.engine_step`; else
      window_s, busy_s
      kernels_s      {kind: own seconds in the window} of the three kernels
      decode         {"programs", "program_s", "index_s", "core_s",
                      "expert_s", "other_s", the dispatch counts,
                      "row_steps", "experts_hit"} over decode programs wholly inside the
                     window (None without device programs)"""
    spans = sr._tick_line(planes)
    if spans is None:
        return None
    ops, busy, programs = sr._device(planes)
    t0, t1 = sr._window(planes, ops)
    mine = [e for e in ops if kernel_of(e[0])]
    kernels = dict.fromkeys(("index", "core", "expert"), 0.0)
    for n, s in mt._own_inside(mine, [(t0, t1)]).items():
        kernels[kernel_of(n)] += s
    out = {"window_s": (t1 - t0) / 1e9,
           "busy_s": sum(min(e, t1) - max(s, t0) for s, e in busy
                         if e > t0 and s < t1) / 1e9,
           "kernels_s": kernels, "decode": None}
    if programs is None:
        return out
    inside = sorted((s, s + d) for n, s, d in programs
                    if sr.program_kind(n) == "decode" and s >= t0
                    and s + d <= t1)
    disp = _spans_before(spans, sr.DISPATCH, [s for s, _ in inside])
    rec = mt._spans_after(spans, mt.RECORD, [s for s, _ in inside])
    per = dict.fromkeys(("index", "core", "expert"), 0.0)
    for n, s in mt._own_inside(mine, inside).items():
        per[kernel_of(n)] += s
    program_s = sum(e - s for s, e in inside) / 1e9
    out["decode"] = {
        "programs": len(inside), "program_s": program_s,
        "index_s": per["index"], "core_s": per["core"],
        "expert_s": per["expert"],
        "other_s": program_s - sum(per.values()),
        **{k: _total(disp, k) for k in DISPATCH_COUNTS},
        "row_steps": _row_steps(disp),
        "experts_hit": _total(rec, "experts_hit")}
    return out


def table(red):
    k = red["kernels_s"]
    rows = [f"window {red['window_s']:.3f} s, busy {red['busy_s']:.3f} s; "
            f"own seconds: {INDEX} {k['index']:.4f}, {CORE} {k['core']:.4f},"
            f" expert stream {k['expert']:.4f}"]
    if red["decode"]:
        rows.append(f"decode programs inside the window: {red['decode']}")
    return rows


def for_ctx(ctx):
    """The reduction of THIS run's trace, made once per run (kept in `ctx`)
    and printed; None where the run was not traced on a device, the newest
    trace on disk is not this run's, or it holds no `ff.engine_step`."""
    trace = ctx.get("trace")
    if not trace:
        return None
    if "dsa_trace" not in ctx:
        path = sr.newest_xplane()
        red = reduce_dsa(sr.load(path)) if path else None
        if red and abs(red["window_s"] - trace["window_s"]) > 1e-6:
            red = None
        for row in table(red) if red else ["no DSA reduction of this run"]:
            print(f"[dsa_trace] {row}", flush=True)
        ctx["dsa_trace"] = red
    return ctx["dsa_trace"]


if __name__ == "__main__":
    red = reduce_dsa(sr.load(tr.find_xplane(sys.argv[1])))
    print("\n".join(table(red)) if red else
          "no ff.engine_step span in this trace")
