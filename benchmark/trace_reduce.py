"""From the profiler's trace to numbers: device busy intervals, idle share,
time by op name, and the longest idle gaps by what the host was doing.

The reduction is the benchmark's own, so that every PR computes the same
number in the same way. It works on a plain structure,

    [{"name": plane, "lines": [{"name": line, "events": [(name, start_ns,
                                                          duration_ns)]}]}]

which `load_xplane` fills from `jax.profiler.ProfileData` and which
benchmark/tests fills by hand.

What is read (TPU v5e, jax 0.9.0; see PERF.md section 3 "Device"):
  * planes named /device:TPU:<n> are chips; their line "XLA Ops" holds one
    event per executed HLO op, nested where an op (while, conditional, call)
    contains others. Busy time is the union of those events; an op's own
    time is its duration minus its children's. An event's name is the whole
    HLO instruction (`%fusion.3 = bf16[..] fusion(...), kind=...`): `label`
    cuts it to the instruction's name and the start of what it computes. A
    Pallas kernel is a `custom-call` there (Mosaic's `tpu_custom_call`; the
    instruction is named after the jax scope that called it, e.g.
    `jvp_attn_0_`), which is all today's trace tells of it: per-kernel
    names wait for a stable `name=` on every pallas_call (PERF.md).
  * plane /host:CPU holds the host threads; `jax.profiler.TraceAnnotation`
    spans of the harness appear there under their own names (`bench.*`).
    `bench.trace_window` is the traced slice; the others say what the host
    was doing when the device idled.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.trace_window"
PREFIX = "bench."
UNATTRIBUTED = "(no bench annotation)"


def find_xplane(trace_dir):
    """The one .xplane.pb under a jax.profiler trace directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path, keep_host=lambda name: name.startswith(PREFIX)):
    """ProfileData -> the plain structure. Of the host plane only events
    `keep_host` accepts are kept (the harness's annotations): host lines
    carry every runtime call and are large."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        host = plane.name == HOST_PLANE
        if not host and not DEVICE_PLANE.match(plane.name):
            continue
        lines = []
        for line in plane.lines:
            if not host and line.name != OPS_LINE:
                lines.append({"name": line.name, "events": []})
                continue
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events
                      if not host or keep_host(e.name)]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def label(name, width=96):
    """`%attn_7.10 = bf16[2,4096]{..} custom-call(...)` -> `attn_N.10 =
    bf16[2,4096] custom-call(...` cut to `width`: short enough for a result
    line, still the name the trace prints, and the same for every layer (a
    jax scope's layer index `_7` becomes `_N`, so that the same op of 24
    layers is one entry of `device_ops`)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    rest = re.sub(r"\{[^{}]*\}", "", rest)      # layouts say nothing here
    text = f"{head.lstrip('%')} = {rest}"
    return re.sub(r"(?<=[a-z])_\d+(?=[_.\s,)])", "_N", text)[:width]


def is_custom_call(name):
    """A Pallas kernel: a custom-call whose target is Mosaic's (XLA's own
    custom-calls, such as ConcatBitcast, are not kernels)."""
    return " custom-call(" in name and 'target="tpu_custom_call"' in name


COLLECTIVE = re.compile(r"[\s)](all-reduce|all-gather|reduce-scatter|all-to-all"
                        r"|collective-permute)(-start|-done)?\(")


def is_collective(name):
    """A cross-chip collective on the ops line. Ops of one line run one
    after another, so its own time there is EXPOSED: no compute op of that
    chip runs meanwhile (an asynchronous collective that is hidden runs on
    another line, and only its `-done` wait shows here)."""
    return bool(COLLECTIVE.search(name))


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(events, t0, t1):
    """{op name: seconds of its own time inside [t0, t1)}: an event's
    duration minus the part its nested children cover. Events of one line
    nest or follow each other; they never partly overlap."""
    out = {}
    stack = []      # [name, end, own_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        s, e = max(start, t0), min(start + dur, t1)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def _annotations(planes):
    """Host annotations as (name, start, end), and the traced window."""
    spans = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            spans += [(n, s, s + d) for n, s, d in line["events"]
                      if n.startswith(PREFIX)]
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    return [a for a in spans if a[0] != WINDOW], windows


def _open_at(spans, t):
    """Name of the innermost annotation open at time t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else UNATTRIBUTED


def reduce_trace(planes, top_ops=10, top_gaps=5):
    """The reduction. Returns a dict with
      window_s        length of the traced window
      busy_s          seconds an op ran, averaged over the chips traced
      idle_share      1 - busy / window on the WORST chip (0..1)
      per_device      {plane: {"busy_s", "idle_share", "collective_s"}}
      collective_exposed_share  exposed collective seconds / window on the
                      worst chip (0..1)
      op_seconds      {op name: own seconds, averaged over chips}
      custom_call_s   own seconds of custom-call ops (Pallas kernels),
                      averaged over chips
      device_ops      the `top_ops` of op_seconds as [[label, seconds]]
      idle_by_host    {annotation: idle seconds under it, worst chip}
      idle_gaps       `top_gaps` sums "sum:<annotation>" then the `top_gaps`
                      longest single gaps "<annotation>", as [[name, seconds]]
    Raises when the trace has no device plane or no op ran on a device."""
    device_planes = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not device_planes:
        raise ValueError(f"trace has no /device:TPU plane (planes: "
                         f"{[p['name'] for p in planes]})")
    spans, windows = _annotations(planes)
    ops = {}
    for plane in device_planes:
        lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
        if not lines:
            raise ValueError(
                f"{plane['name']} has no {OPS_LINE!r} line (lines: "
                f"{[ln['name'] for ln in plane['lines']]})")
        ops[plane["name"]] = [e for ln in lines for e in ln["events"]]
    every = [e for evs in ops.values() for e in evs]
    if not every:
        raise ValueError("no operation ran on a device in the trace")
    if windows:
        t0, t1 = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        t0 = min(s for _, s, _ in every)
        t1 = max(s + d for _, s, d in every)
    window_s = (t1 - t0) / 1e9

    per_device, op_seconds = {}, {}
    worst = None
    for name, events in ops.items():
        busy = _union((max(s, t0), min(s + d, t1)) for _, s, d in events)
        busy_s = sum(e - s for s, e in busy) / 1e9
        idle = 1.0 - busy_s / window_s
        own = _self_times(events, t0, t1)
        per_device[name] = {
            "busy_s": busy_s, "idle_share": idle,
            "collective_s": sum(s for n, s in own.items()
                                if is_collective(n))}
        for op, sec in own.items():
            op_seconds[op] = op_seconds.get(op, 0.0) + sec / len(ops)
        if worst is None or idle > worst[0]:
            worst = (idle, busy)
    gaps, cursor = [], t0
    for s, e in worst[1]:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if t1 > cursor:
        gaps.append((cursor, t1))
    idle_by_host = {}
    named = []
    for s, e in gaps:
        host = _open_at(spans, (s + e) / 2.0)
        idle_by_host[host] = idle_by_host.get(host, 0.0) + (e - s) / 1e9
        named.append((host, (e - s) / 1e9))
    by_label = {}
    for op, sec in op_seconds.items():
        by_label[label(op)] = by_label.get(label(op), 0.0) + sec
    sums = sorted(idle_by_host.items(), key=lambda kv: -kv[1])[:top_gaps]
    longest = sorted(named, key=lambda kv: -kv[1])[:top_gaps]
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in per_device.values())
        / len(per_device),
        "idle_share": worst[0],
        "per_device": per_device,
        "collective_exposed_share": max(
            d["collective_s"] for d in per_device.values()) / window_s,
        "op_seconds": op_seconds,
        "custom_call_s": sum(s for n, s in op_seconds.items()
                             if is_custom_call(n)),
        "device_ops": [[n, s] for n, s in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top_ops]],
        "idle_by_host": idle_by_host,
        "idle_gaps": [[f"sum:{n}", s] for n, s in sums]
        + [[n, s] for n, s in longest],
    }


def describe(path, limit=40):
    """What a trace holds, for reading one by hand: planes, lines, event
    counts and the most frequent names of each device line."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(events)} events")
            if DEVICE_PLANE.match(plane.name):
                total = {}
                for e in events:
                    total[e.name] = total.get(e.name, 0.0) + e.duration_ns
                for n, ns in sorted(total.items(),
                                    key=lambda kv: -kv[1])[:limit]:
                    out.append(f"      {ns / 1e6:10.3f} ms  {label(n, 110)}")
                targets = {}
                for e in events:        # how are custom-calls named?
                    if " custom-call(" in e.name:
                        t = re.findall(r'custom_call_target="([^"]+)"',
                                       e.name) or ["(no target in the text)"]
                        n, ns = targets.get(t[0], (0, 0.0))
                        targets[t[0]] = (n + 1, ns + e.duration_ns)
                for t, (n, ns) in targets.items():
                    out.append(f"    CUSTOM-CALL TARGET {t}: {n} events, "
                               f"{ns / 1e6:.3f} ms")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(describe(find_xplane(sys.argv[1])))
