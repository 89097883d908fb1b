"""A traced run's device seconds by (kind of program, graph op, phase): every
device op booked to the scope its HLO instruction was traced under.

A device event carries its HLO instruction's name (`fusion.1258`) and no jax
scope. The scope is in the compiled program's text, and the PROGRAM keeps
that: `flexflow_tpu/runtime/profiler.py` registers every program a serving
engine or a model runs, and `program_scopes(names)` lowers the ones asked for
again (the executable comes from the compile cache) and reads each text into
{instruction: (op, phase)} (`scope_table`: `op` the graph op's name or one of
`sampler`, `loss`, `optimizer`, `grad_sync`; `phase` one of the words the ops
declare, `project` / `index` / `select` / `gather` / `core` / `out`, `route` /
`experts` / `shared`, or ""). This file asks, after the window, for the
programs that ran inside the traced slice and for no other.

Which table a device op belongs to (instruction names repeat across programs):
the op ran inside one event of the device plane's `XLA Modules` line, named
after the jitted function (`jit_decode(..)`); the engine's
`ff.decode_dispatch`, `ff.prefill`, `ff.prefill_chunk` and `ff.compile` spans
carry `program`, the registry's name of what they dispatched (`decode_k8`,
`prefill_b2048`, `prefill_hit_b128_m255`). A module belongs to the last such
span that began
before it (the tick is serial, as `span_reduce` pairs decode dispatches), if
that program's function is the module's; else, and in a run without those
spans (training: one module a step), to the one registered program whose
function has the module's name; else to none. (The module event's name holds
an id the compiled text does not, so it cannot name the executable outright.)

`reduce_scopes` works on `span_reduce.load`'s structure, chip by chip: own
seconds inside the traced window by (kind, op without its layer index, phase),
and the seconds of ops no table names (`unscoped`, by instruction). Rows are
averaged over the chips, as `trace_reduce`'s `busy_s` is; a share is the
worst chip's. A program without the registry (the parent of PR 34) or a run
that was not traced gives None, and the readers leave their metrics out.

Per-layer metrics that read this: `mla_core_gather_roofline_share`,
`dsa_sparse_device_share`, `sampler_device_share`, `attn_train_device_share`,
`optimizer_device_share`, `serve_unscoped_share`, `train_unscoped_share`.

By hand, after a traced run (no tables: seconds by instruction and module):
python3 benchmark/scope_reduce.py .bench_trace/<cell>
"""

import bisect
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import span_reduce as sr, trace_reduce as tr  # noqa: E402
from benchmark.train_trace import head_of  # noqa: E402

DISPATCHING = tuple(sr.FF + n for n in (
    "decode_dispatch", "prefill", "prefill_chunk", "compile"))
ROW_SHARE = 0.005       # a (kind, op, phase) under this share of busy time
                        # is summed into one row


def module_of(event_name):
    """`jit_decode(1234)` -> `jit_decode`."""
    return event_name.split("(", 1)[0]


def kind_of(module, program):
    """prefill | decode as `span_reduce.program_kind`; `train` for a model's
    train programs; else `other`."""
    kind = sr.program_kind(module)
    if kind == "other" and (program or "").startswith("train"):
        return "train"
    return kind


def _sole_programs(modules):
    """{`jit_<function>`: program} for each function that ONE registered
    program has."""
    by = {}
    for prog, mod in modules.items():
        by.setdefault(mod, []).append(prog)
    return {mod: progs[0] for mod, progs in by.items() if len(progs) == 1}


def _own_events(ops, t0, t1):
    """[(name, start_ns, own_ns)] of the events inside [t0, t1): an event's
    duration there minus what its nested children cover (events of one line
    nest or follow each other)."""
    out, stack = [], []     # stack: [name, start, end, own]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, _, own = stack.pop()
            out.append((name, start, own))

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        s, e = max(start, t0), min(start + dur, t1)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][3] -= e - s
        stack.append([name, s, e, e - s])
    close(float("inf"))
    return out


def _dispatches(planes):
    """[(start_ns, program)] of the driving thread's dispatching spans that
    say which program they ran, by start."""
    spans = sr._tick_line(planes) or []
    return sorted((s, str(st["program"])) for name, s, _, st in spans
                  if name in DISPATCHING and st.get("program"))


def programs_in(planes, modules):
    """The registered programs a trace shows running: those its dispatching
    spans name, and each one that alone has the function of a module on a
    device's per-program line. `modules`: {program: `jit_<function>`}."""
    names = {p for _, p in _dispatches(planes)}
    seen = {module_of(e[0]) for pl in planes
            if tr.DEVICE_PLANE.match(pl["name"]) for ln in pl["lines"]
            if ln["name"] == sr.MODULES_LINE for e in ln["events"]}
    sole = _sole_programs(modules)
    names.update(sole[mod] for mod in seen if mod in sole)
    return names & set(modules)


def _chip(ops, programs, dispatches, tables, modules, t0, t1):
    """One chip's {"busy_s", "rows", "whole", "unscoped", "by_program"}."""
    progs = sorted((s, s + d, module_of(n)) for n, s, d in programs or ()
                   if s + d > t0 and s < t1)
    starts = [s for s, _, _ in progs]
    d_starts = [s for s, _ in dispatches]
    sole = _sole_programs(modules)
    named = []
    for s, _, mod in progs:
        j = bisect.bisect_right(d_starts, s) - 1
        prog = dispatches[j][1] if j >= 0 else None
        if modules.get(prog) != mod:
            prog = sole.get(mod)
        named.append(prog)
    rows, whole, unscoped, by_program = {}, {}, {}, {}
    busy = 0.0
    for name, start, own in _own_events(ops, t0, t1):
        sec = own / 1e9
        busy += sec
        i = bisect.bisect_right(starts, start) - 1
        inside = i >= 0 and start < progs[i][1]
        prog = named[i] if inside else None
        mod = progs[i][2] if inside else ""
        kind = kind_of(mod, prog) if inside else "none"
        by_program[prog or mod or "(no module)"] = by_program.get(
            prog or mod or "(no module)", 0.0) + sec
        scope = tables.get(prog, {}).get(head_of(name)) if prog else None
        if scope is None:
            key = (kind, tr.label(name, 72))
            unscoped[key] = unscoped.get(key, 0.0) + sec
            continue
        key = (kind, re.sub(r"_\d+$", "", scope[0]), scope[1])
        rows[key] = rows.get(key, 0.0) + sec
        if progs[i][0] >= t0 and progs[i][1] <= t1:
            whole[key] = whole.get(key, 0.0) + sec
    return {"busy_s": busy, "rows": rows, "whole": whole,
            "unscoped": unscoped, "by_program": by_program}


def reduce_scopes(planes, tables, modules):
    """The reduction. `tables`: {program: {instruction: (op, phase)}};
    `modules`: {program: `jit_<function>`} of every registered program.
      window_s, busy_s  (busy: own seconds inside the window, mean of chips)
      rows        {(kind, op, phase): own seconds, mean of the chips}
      whole       the same over programs that ran wholly inside the window
      unscoped_s  own seconds of ops no table names, mean of the chips
      unscoped    {(kind, instruction label): seconds}, largest first
      by_program  {program or module: seconds}
      chips       [one chip's {"busy_s", "rows", "whole", "unscoped"}]"""
    dispatches = _dispatches(planes)
    chips, t0, t1 = [], None, None
    for plane in planes:
        if not tr.DEVICE_PLANE.match(plane["name"]):
            continue
        by = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = by.get(tr.OPS_LINE) or []
        if not ops:
            continue
        if t0 is None:
            t0, t1 = sr._window(planes, ops)
        chips.append(_chip(ops, by.get(sr.MODULES_LINE), dispatches, tables,
                           modules, t0, t1))
    if not chips:
        raise ValueError("no operation ran on a device in the trace")

    def mean(field):
        out = {}
        for chip in chips:
            for k, v in chip[field].items():
                out[k] = out.get(k, 0.0) + v / len(chips)
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    unscoped = mean("unscoped")
    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(c["busy_s"] for c in chips) / len(chips),
            "rows": mean("rows"), "whole": mean("whole"),
            "unscoped_s": sum(unscoped.values()), "unscoped": unscoped,
            "by_program": mean("by_program"), "chips": chips}


def share(red, pick, field="rows"):
    """Percent of busy time, on the chip where it is largest, of the
    (kind, op, phase) rows `pick(kind, op, phase)` accepts; `field`
    "unscoped" picks by (kind, label) instead."""
    if not red:
        return None
    best = None
    for chip in red["chips"]:
        if chip["busy_s"]:
            own = sum(v for k, v in chip[field].items() if pick(*k))
            best = max(best or 0.0, 100.0 * own / chip["busy_s"])
    return best


def table(red, top_unscoped=8):
    """The reduction as log lines: one row a (kind, op, phase) over 0.5 % of
    busy time, the rest in one row, the unscoped seconds and what they
    are."""
    busy = red["busy_s"]
    out = [f"window {red['window_s']:.3f} s, busy {busy:.4f} s (mean of "
           f"{len(red['chips'])} chip(s)); own seconds by kind of program, "
           f"graph op, phase:"]
    rest = 0.0
    for (kind, op, phase), sec in red["rows"].items():
        if sec < ROW_SHARE * busy:
            rest += sec
            continue
        out.append(f"{sec:9.4f} s {100 * sec / busy:5.1f} %  {kind:8s} "
                   f"{op:14s} {phase}")
    out.append(f"{rest:9.4f} s {100 * rest / busy:5.1f} %  (rows under "
               f"{100 * ROW_SHARE:.1f} %)")
    out.append(f"{red['unscoped_s']:9.4f} s "
               f"{100 * red['unscoped_s'] / busy:5.1f} %  unscoped (no table "
               f"names the instruction); the largest:")
    for (kind, label), sec in list(red["unscoped"].items())[:top_unscoped]:
        out.append(f"{sec:9.4f} s {100 * sec / busy:5.1f} %    {kind:8s} "
                   f"{label}")
    total = sum(red["rows"].values()) + red["unscoped_s"]
    out.append(f"rows + unscoped = {total:.4f} s of busy {busy:.4f} s; "
               f"seconds by program: " + ", ".join(
                   f"{k} {v:.4f}" for k, v in red["by_program"].items()))
    return out


def for_ctx(ctx):
    """The scope reduction of THIS run's trace, made once per run (kept in
    `ctx`) and printed; None where the run was not traced on a device, the
    newest trace on disk is not this run's, or the program keeps no registry
    of its programs. The tables are asked for here: after the window, and
    only for the programs the trace shows."""
    trace = ctx.get("trace")
    if not trace:
        return None
    if "scope_reduce" not in ctx:
        ctx["scope_reduce"] = red = _reduce_run(trace)
        for row in table(red) if red else ["no scope reduction of this run"]:
            print(f"[scope_reduce] {row}", flush=True)
    return ctx["scope_reduce"]


def _reduce_run(trace):
    from flexflow_tpu.runtime import profiler

    if not hasattr(profiler, "program_scopes"):
        return None
    path = sr.newest_xplane()
    if not path:
        return None
    planes = sr.load(path)
    modules = {p.name: p.module for p in profiler.live_programs()}
    names = programs_in(planes, modules)
    t0 = time.perf_counter()
    tables = profiler.program_scopes(names)
    print(f"[scope_reduce] program_scopes() read {len(tables)} of "
          f"{len(modules)} registered programs in "
          f"{time.perf_counter() - t0:.2f} s after the window: "
          f"{sorted(tables)}", flush=True)
    red = reduce_scopes(planes, tables, modules)
    if abs(red["window_s"] - trace["window_s"]) > 1e-6:
        return None
    return red


if __name__ == "__main__":
    for line in table(reduce_scopes(
            sr.load(tr.find_xplane(sys.argv[1])), {}, {})):
        print(line)
