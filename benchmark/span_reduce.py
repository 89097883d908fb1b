"""The serving tick's spans laid against the device: idle seconds by the
innermost span open at the time, self time by span, device seconds by kind of
program, the device's idle seconds inside each tick, and the counts the engine
wrote on its decode dispatches summed over the programs that ran.

`trace_reduce.py` reads what the harness annotates (`bench.*`) and books every
idle second of a serving cell to `bench.engine_step`. The program's own spans
(`flexflow_tpu/runtime/telemetry.py` `Tracer.span`, one
`jax.profiler.TraceAnnotation` named `ff.<span>` per ring span, its counts as
the event's stats) lie in the same host plane on the same clock; this file
reads them, WITH their stats, beside the `bench.*` ones, and the per-program
line of the device plane. It works on the plain structure of `trace_reduce`,

    [{"name": plane, "lines": [{"name": line, "events": [(name, start_ns,
                                                          duration_ns[,
                                                          {stat: value}])]}]}]

which `load` fills from the newest `.xplane.pb` under `<checkout>/.bench_trace`
and benchmark/tests/test_span_reduce.py fills by hand.

What is read (TPU v5e, jax 0.9.0):
  * /host:CPU, the line (thread) that holds the `ff.engine_step` events: the
    engine's driving thread. Spans of one thread nest or follow each other. A
    span's SELF time is its duration minus what its children cover. Idle time
    of the device is cut at span boundaries and each piece goes to the
    innermost span open there: `ff.token_fetch` is the host waiting for the
    chip, every other name is the chip waiting for the host.
  * /device:TPU:<n> line "XLA Modules": one event per executed program, named
    after the jitted function (`jit_decode(...)`, `jit_prefill(...)`). A
    program's KIND is `prefill` or `decode` if its name says so, else `other`.
    Line "XLA Ops" as in trace_reduce: busy time is the union of its events.
  * the n-th `ff.decode_dispatch` is the n-th decode program: the tick is
    serial (dispatch, then `ff.token_fetch` blocks until the program is done),
    so a program belongs to the last dispatch span that began before it. At
    the slice's edges a program whose dispatch the trace did not see, and a
    dispatch whose program it did not see, stay unpaired; sums are over pairs
    whose program ran wholly inside the slice.

Per-layer metrics that read this: `tick_idle_p50_s`, `paged_attn_hbm_share`,
`prefill_device_share` (benchmark/layer_metrics/). Where the trace holds no
`ff.engine_step` (a program without the spans, a training cell) `for_ctx`
returns None and those readers leave their metric out.

By hand, after `benchmark/run.py --workload <cell> --trace 1 ...`:

    python3 benchmark/span_reduce.py .bench_trace/<cell>
"""

import glob
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import stats, trace_reduce as tr  # noqa: E402

MODULES_LINE = "XLA Modules"
FF = "ff."
TICK = FF + "engine_step"
DISPATCH = FF + "decode_dispatch"
COUNTS = ("k", "slots", "context_tokens", "kv_read_bytes")
LONG_GAP_S = 0.020
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")


def program_kind(name):
    """`jit_prefill(123)` -> prefill, `jit_decode(7)` -> decode: the engine's
    jitted functions say their kind in their name."""
    low = name.lower()
    if "prefill" in low:
        return "prefill"
    if "decode" in low:
        return "decode"
    return "other"


def newest_xplane(root=TRACE_ROOT):
    """The newest .xplane.pb under any cell's trace directory."""
    found = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path):
    """ProfileData -> the plain structure: host events `ff.*` and `bench.*`
    with their stats, the device's op line and its per-program line."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        host = plane.name == tr.HOST_PLANE
        if not host and not tr.DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if host:
                events = [(e.name, float(e.start_ns), float(e.duration_ns),
                           dict(e.stats))
                          for e in line.events
                          if e.name.startswith((FF, tr.PREFIX))]
            elif line.name in (tr.OPS_LINE, MODULES_LINE):
                events = [(e.name, float(e.start_ns), float(e.duration_ns))
                          for e in line.events]
            else:
                events = []
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def _tick_line(planes):
    """Events (name, start, end, stats) of the host thread that drives the
    engine: the line with the most `ff.engine_step` events. None without."""
    best, most = None, 0
    for plane in planes:
        if plane["name"] != tr.HOST_PLANE:
            continue
        for line in plane["lines"]:
            n = sum(1 for e in line["events"] if e[0] == TICK)
            if n > most:
                best, most = line["events"], n
    if best is None:
        return None
    return [(e[0], e[1], e[1] + e[2], e[3] if len(e) > 3 else {})
            for e in best if e[0] != tr.WINDOW]


def _window(planes, ops):
    spans = [(e[1], e[1] + e[2]) for p in planes
             if p["name"] == tr.HOST_PLANE
             for ln in p["lines"] for e in ln["events"] if e[0] == tr.WINDOW]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    return min(s for _, s, _ in ops), max(s + d for _, s, d in ops)


def _segments(spans, t0, t1):
    """The thread's time in [t0, t1) cut at span boundaries:
    [(start, end, innermost span name)], `tr.UNATTRIBUTED` where none is
    open. Spans of one thread nest or follow; a child cuts its parent."""
    out, stack, cursor = [], [], t0      # stack: [name, end]

    def emit(upto):
        nonlocal cursor
        upto = min(upto, t1)
        if upto > cursor:
            out.append((cursor, upto, stack[-1][0] if stack
                        else tr.UNATTRIBUTED))
            cursor = upto

    for name, s, e, _ in sorted(spans, key=lambda x: (x[1], -x[2])):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append([name, e])
    while stack:
        emit(stack[-1][1])
        stack.pop()
    if t1 > cursor:
        out.append((cursor, t1, tr.UNATTRIBUTED))
    return out


def _overlap(intervals, s, e):
    """Seconds of [s, e) covered by sorted disjoint `intervals` (ns)."""
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in intervals
               if b > s and a < e) / 1e9


def _device(planes):
    """(ops, busy, programs) of the chip that idled most: its `XLA Ops`
    events, their union as sorted [start, end] intervals, and its
    `XLA Modules` events (None where the line is missing)."""
    worst = None
    for plane in planes:
        if not tr.DEVICE_PLANE.match(plane["name"]):
            continue
        by = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = by.get(tr.OPS_LINE) or []
        if not ops:
            continue
        busy = tr._union((s, s + d) for _, s, d in ops)
        total = sum(e - s for s, e in busy)
        if worst is None or total < worst[0]:
            worst = (total, ops, busy, by.get(MODULES_LINE))
    if worst is None:
        raise ValueError("no operation ran on a device in the trace")
    return worst[1:]


def reduce_spans(planes):
    """The reduction; None where the trace holds no `ff.engine_step`. Keys:
      window_s, busy_s, idle_s
      idle_by_span    {span: idle seconds of the device under it, innermost}
      fetch_idle      {`*_fetch` span: [idle seconds before the chip began
                      what the host waits for, in between, after it was done]}
      leaf_idle_share share of idle_s under an `ff.` span other than
                      `ff.engine_step` (0..1; 1.0 where nothing idled)
      self_by_span    {span: self seconds inside the window}
      spans           {span: how many began inside the window}
      long_gaps       [(seconds, {span: seconds})] single gaps over 20 ms
      tick_idle_s     [device-idle seconds inside each whole `ff.engine_step`]
      device_by_kind  {prefill|decode|other: device seconds of programs}, or
                      None where the device plane has no per-program line
      dispatch        {"pairs", "programs", "dispatches", "paged_attn_s"} +
                      the sums of COUNTS over paired dispatches, or None"""
    spans = _tick_line(planes)
    if spans is None:
        return None
    ops, busy, programs = _device(planes)
    t0, t1 = _window(planes, ops)
    busy = [(max(s, t0), min(e, t1)) for s, e in busy if e > t0 and s < t1]
    busy_s = sum(e - s for s, e in busy) / 1e9
    gaps, cursor = [], t0
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t1 > cursor:
        gaps.append((cursor, t1))

    segments = _segments(spans, t0, t1)
    idle_by, fetch_idle, long_gaps = {}, {}, []
    self_by = {name: 0.0 for name, s, e, _ in spans if e > t0 and s < t1}
    for s, e, name in segments:
        if name != tr.UNATTRIBUTED:
            self_by[name] += (e - s) / 1e9
    i = 0
    for gs, ge in gaps:
        parts = {}
        while i < len(segments) and segments[i][1] <= gs:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < ge:
            s, e, name = segments[j]
            sec = (min(e, ge) - max(s, gs)) / 1e9
            parts[name] = parts.get(name, 0.0) + sec
            if name.endswith("_fetch"):
                # the chip idle while the host waits for it: not yet begun
                # (the gap was open when the wait started), already done
                # (the gap outlasts the wait), or a bubble in between
                when = 0 if gs <= s else 2 if ge >= e else 1
                fetch_idle.setdefault(name, [0.0, 0.0, 0.0])[when] += sec
            j += 1
        for name, sec in parts.items():
            idle_by[name] = idle_by.get(name, 0.0) + sec
        if (ge - gs) / 1e9 > LONG_GAP_S:
            long_gaps.append(((ge - gs) / 1e9, parts))
    idle_s = sum(idle_by.values())
    leaf = sum(sec for name, sec in idle_by.items()
               if name.startswith(FF) and name != TICK)
    counts = {}
    for name, s, _, _ in spans:
        if t0 <= s < t1:
            counts[name] = counts.get(name, 0) + 1

    out = {
        "window_s": (t1 - t0) / 1e9, "busy_s": busy_s, "idle_s": idle_s,
        "idle_by_span": idle_by, "fetch_idle": fetch_idle,
        "leaf_idle_share": leaf / idle_s if idle_s else 1.0,
        "self_by_span": self_by, "spans": counts,
        "long_gaps": sorted(long_gaps, key=lambda g: -g[0]),
        "tick_idle_s": [(e - s) / 1e9 - _overlap(busy, s, e)
                        for name, s, e, _ in spans
                        if name == TICK and s >= t0 and e <= t1],
        "device_by_kind": None, "dispatch": None,
    }
    if programs is None:
        return out
    kinds = {}
    for name, s, d in programs:
        sec = (min(s + d, t1) - max(s, t0)) / 1e9
        if sec > 0:
            k = program_kind(name)
            kinds[k] = kinds.get(k, 0.0) + sec
    out["device_by_kind"] = kinds
    out["dispatch"] = _pair_dispatches(spans, programs, ops, t0, t1)
    return out


def _pair_dispatches(spans, programs, ops, t0, t1):
    """Each decode program with the last `ff.decode_dispatch` that began
    before it (one program per dispatch); the sums over the pairs whose
    program ran wholly inside [t0, t1)."""
    disp = sorted((s, st) for name, s, _, st in spans if name == DISPATCH)
    progs = sorted((s, s + d) for name, s, d in programs
                   if program_kind(name) == "decode")
    sums = dict.fromkeys(COUNTS, 0.0)
    inside, taken = [], set()
    j = -1
    for ps, pe in progs:
        while j + 1 < len(disp) and disp[j + 1][0] <= ps:
            j += 1
        if j < 0 or j in taken:
            continue        # dispatched before the trace began
        taken.add(j)
        if ps < t0 or pe > t1:
            continue
        inside.append((ps, pe))
        for key in COUNTS:
            sums[key] += float(disp[j][1].get(key, 0))
    calls = sorted((s, d) for name, s, d in ops if tr.is_custom_call(name))
    attn_ns, c = 0.0, 0
    for ps, pe in inside:
        while c < len(calls) and calls[c][0] < ps:
            c += 1
        while c < len(calls) and calls[c][0] < pe:
            attn_ns += calls[c][1]      # a Mosaic call holds no other op
            c += 1
    return {"pairs": len(inside), "programs": len(progs),
            "dispatches": len(disp), "paged_attn_s": attn_ns / 1e9, **sums}


def table(red):
    """The whole reduction as log lines."""
    def rows(d):
        return [f"{v:9.4f} s  {k}" for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]

    out = [f"window {red['window_s']:.3f} s, busy {red['busy_s']:.3f} s, "
           f"idle {red['idle_s']:.4f} s, of which "
           f"{100 * red['leaf_idle_share']:.1f} % under a leaf ff. span",
           "idle seconds by innermost span:"] + rows(red["idle_by_span"])
    for name, (head, mid, tail) in sorted(red["fetch_idle"].items()):
        out.append(f"  under {name}: {head:.4f} s before the chip began, "
                   f"{mid:.4f} s in between, {tail:.4f} s after it was done")
    out += ["self seconds by span (count):"] + [
        f"{v:9.4f} s  {k} ({red['spans'].get(k, 0)})" for k, v in
        sorted(red["self_by_span"].items(), key=lambda kv: -kv[1])]
    if red["device_by_kind"] is not None:
        out += ["device seconds by kind of program:"] + rows(
            red["device_by_kind"])
    ticks = red["tick_idle_s"]
    if ticks:
        out.append(f"device idle inside a tick: p50 "
                   f"{stats.median(ticks) * 1e3:.3f} ms, max "
                   f"{max(ticks) * 1e3:.3f} ms over {len(ticks)} whole ticks")
    if red["dispatch"]:
        out.append(f"decode dispatches: {red['dispatch']}")
    for sec, parts in red["long_gaps"]:
        out.append(f"gap of {sec * 1e3:.1f} ms: " + ", ".join(
            f"{k} {v * 1e3:.1f} ms" for k, v in
            sorted(parts.items(), key=lambda kv: -kv[1])))
    return out


def for_ctx(ctx):
    """The span reduction of THIS run's trace, made once per run (kept in
    `ctx`) and printed in full; None where the run was not traced on a
    device, the newest trace on disk is not the one `run.py` reduced, or it
    holds no `ff.engine_step`."""
    trace = ctx.get("trace")
    if not trace:
        return None
    if "span_reduce" not in ctx:
        path = newest_xplane()
        red = reduce_spans(load(path)) if path else None
        if red and abs(red["window_s"] - trace["window_s"]) > 1e-6:
            print(f"[span_reduce] {path} is not this run's trace (window "
                  f"{red['window_s']:.6f} s against {trace['window_s']:.6f} "
                  f"s): not read", flush=True)
            red = None
        for row in table(red) if red else [
                "no ff.engine_step span in this run's trace: span metrics "
                "left out"]:
            print(f"[span_reduce] {row}", flush=True)
        ctx["span_reduce"] = red
    return ctx["span_reduce"]


if __name__ == "__main__":
    red = reduce_spans(load(tr.find_xplane(sys.argv[1])))
    print("\n".join(table(red)) if red else
          "no ff.engine_step span in this trace")
