"""A traced run's device time inside the grouped expert matmuls of the
mixture-of-experts ops, and inside decode and prefill programs beside the
routing counts those programs carried.

Which device op is the MoE op's (TPU v5e, jax 0.9.0, read from a trace by
hand, PR 25): the grouped matmuls are XLA's own Mosaic kernel for
`jax.lax.ragged_dot`, custom-calls named `ragged-dot-none[.n]`, each group of
three preceded by a small `ragged-dot-metadata` call that lays out the
groups. XLA names them itself and drops the jax scope, so
`breakdown.device_ops` shows them under that name and not under `moe_<i>`.
A Pallas kernel does keep its jax scope in its instruction's name (the paged
kernel is `attn_<i>.<n>`), so a Mosaic call named after a `moe_<i>` scope is
booked to the MoE op too: a Pallas grouped matmul that takes `ragged_dot`'s
place is then read by the same metrics, with no change here.
Every OTHER op of the MoE op (router matmul, softmax, the top-k's sort of
`[rows, experts]`, the gathers into expert order and back, the gate-weighted
sum) is an anonymous fusion or `sort`: a device event carries its HLO
instruction and no jax scope (its stats are `device_offset_ps`,
`device_duration_ps`, `Time Scale Multiplier`), so a `jax.named_scope` reaches
the HLO's metadata but not the trace. Those ops were under 2 % of the busy time in the op dump of PR 25's traced
runs; "MoE time" here is the grouped matmuls and their layout calls, which
reads low by that much.

`reduce_moe` works on `span_reduce.load`'s structure: own seconds of those
calls inside the traced window, and for the decode programs that ran wholly
inside it their own seconds beside the `experts_hit` / `assignments` counts
of the `ff.record_tokens` span that follows each (the engine counts them on
the device: `ServingEngine.stats()`); prefill programs likewise, with the
counts of the `ff.prefill` span they ran under.

A trace without an `ff.engine_step` line gives None, and a program whose
spans carry no counts gives None for them: the readers then leave their
metrics out.

By hand, after a traced run: python3 benchmark/moe_trace.py .bench_trace/<cell>
"""

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import span_reduce as sr, trace_reduce as tr  # noqa: E402

GROUPED = "ragged-dot"
MOE_SCOPE = re.compile(r"(?:^|_)moe_\d")      # `moe_3.7`, `jvp_moe_3_.7`
RECORD, PREFILL = sr.FF + "record_tokens", sr.FF + "prefill"
COUNTS = ("experts_hit", "assignments")


def is_grouped_matmul(name):
    """A Mosaic call of the MoE op: XLA's grouped-matmul kernel or its
    group-layout call, or a Pallas kernel named after a `moe_<i>` scope."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return tr.is_custom_call(name) and (GROUPED in head
                                        or bool(MOE_SCOPE.search(head)))


def _own_inside(ops, intervals):
    """{op name: own seconds inside the sorted disjoint `intervals`}."""
    out = {}
    for t0, t1 in intervals:
        inside = [e for e in ops if e[1] + e[2] > t0 and e[1] < t1]
        for name, sec in tr._self_times(inside, t0, t1).items():
            out[name] = out.get(name, 0.0) + sec
    return out


def _spans_after(spans, name, starts):
    """For each time in `starts` the stats of the first span called `name`
    that begins at or after it."""
    named = sorted((s, st) for n, s, _, st in spans if n == name)
    out, j = [], 0
    for t in starts:
        while j < len(named) and named[j][0] < t:
            j += 1
        out.append(named[j][1] if j < len(named) else {})
    return out


def reduce_moe(planes):
    """None without `ff.engine_step`; else a dict with
      window_s, busy_s
      moe_s          own seconds of the grouped matmuls in the window
      by_op          {label: own seconds} of them, largest first
      decode / prefill: {"programs", "grouped_s", "experts_hit",
                      "assignments"} over the programs of that kind that ran
                      wholly inside the window (counts None where the
                      program's spans carry none)"""
    spans = sr._tick_line(planes)
    if spans is None:
        return None
    ops, busy, programs = sr._device(planes)
    t0, t1 = sr._window(planes, ops)
    moe = {n: s for n, s in _own_inside(ops, [(t0, t1)]).items()
           if is_grouped_matmul(n)}
    by_op = {}
    for n, s in moe.items():
        by_op[tr.label(n)] = by_op.get(tr.label(n), 0.0) + s
    out = {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(min(e, t1) - max(s, t0) for s, e in busy
                      if e > t0 and s < t1) / 1e9,
        "moe_s": sum(moe.values()),
        "by_op": dict(sorted(by_op.items(), key=lambda kv: -kv[1])),
        "decode": None, "prefill": None,
    }
    if programs is None:
        return out
    for kind in ("decode", "prefill"):
        inside = sorted((s, s + d) for n, s, d in programs
                        if sr.program_kind(n) == kind and s >= t0
                        and s + d <= t1)
        if kind == "decode":
            # the tick is serial: the record_tokens after a decode program
            # began is that dispatch's
            stats = _spans_after(spans, RECORD, [s for s, _ in inside])
        else:
            # a prefill program runs inside its own ff.prefill span
            stats = [next((st for n, s, e, st in spans if n == PREFILL
                           and s <= ps <= e), {}) for ps, _ in inside]
        counts = {}
        for key in COUNTS:
            vals = [st.get(key) for st in stats]
            counts[key] = (None if not vals or None in vals
                           else float(sum(float(v) for v in vals)))
        out[kind] = {"programs": len(inside),
                     "grouped_s": sum(_own_inside(
                         [e for e in ops if is_grouped_matmul(e[0])],
                         inside).values()), **counts}
    return out


def table(red):
    rows = [f"window {red['window_s']:.3f} s, busy {red['busy_s']:.3f} s, "
            f"grouped expert matmuls {red['moe_s']:.4f} s in "
            f"{len(red['by_op'])} instructions; the largest:"]
    rows += [f"{v:9.4f} s  {k}" for k, v in list(red["by_op"].items())[:4]]
    for kind in ("decode", "prefill"):
        if red[kind]:
            rows.append(f"{kind} programs inside the window: {red[kind]}")
    return rows


def for_ctx(ctx):
    """The reduction of THIS run's trace, made once per run (kept in `ctx`)
    and printed; None where the run was not traced on a device, the newest
    trace on disk is not this run's, or it holds no `ff.engine_step`."""
    trace = ctx.get("trace")
    if not trace:
        return None
    if "moe_trace" not in ctx:
        path = sr.newest_xplane()
        red = reduce_moe(sr.load(path)) if path else None
        if red and abs(red["window_s"] - trace["window_s"]) > 1e-6:
            red = None
        for row in table(red) if red else ["no MoE reduction of this run"]:
            print(f"[moe_trace] {row}", flush=True)
        ctx["moe_trace"] = red
    return ctx["moe_trace"]


if __name__ == "__main__":
    red = reduce_moe(sr.load(tr.find_xplane(sys.argv[1])))
    print("\n".join(table(red)) if red else
          "no ff.engine_step span in this trace")
