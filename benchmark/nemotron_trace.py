"""A traced run's counts for the state-space readers: what the decode and
prefill programs that ran WHOLLY inside the traced window were asked to do,
from the spans that dispatched them, beside `scope_reduce`'s device seconds of
the same programs (its `whole` rows).

  * a decode program belongs to the last `ff.decode_dispatch` span that began
    before it (`span_reduce._pair_dispatches`' rule); the span says its `k`
    steps and its live `slots`, and, where the model keeps a recurrent state,
    `state_bytes`;
  * a prefill program runs inside its own `ff.prefill` span, which says
    `scan_rows` (the bucket's rows x the recurrent ops) where the model has
    such ops.

A trace without `ff.engine_step`, a program whose spans carry none of these
counts (every model but a state-space one; the parent of PR 37) or a run that
was not traced gives None, and the readers leave their metrics out.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import span_reduce as sr  # noqa: E402

PREFILL = sr.FF + "prefill"


def reduce_state(planes):
    """{"decode": {"programs", "slot_steps", "state_bytes"}, "prefill":
    {"programs", "scan_rows"}} over the programs wholly inside the window;
    None without `ff.engine_step` or where no span carries a state count."""
    spans = sr._tick_line(planes)
    if spans is None:
        return None
    ops, _, programs = sr._device(planes)
    if programs is None:
        return None
    t0, t1 = sr._window(planes, ops)
    disp = sorted((s, st) for name, s, _, st in spans if name == sr.DISPATCH)
    dec = {"programs": 0, "slot_steps": 0.0, "state_bytes": 0.0}
    j, taken = -1, set()
    for ps, pe in sorted((s, s + d) for n, s, d in programs
                         if sr.program_kind(n) == "decode"):
        while j + 1 < len(disp) and disp[j + 1][0] <= ps:
            j += 1
        if j < 0 or j in taken:
            continue
        taken.add(j)
        st = disp[j][1]
        if ps < t0 or pe > t1 or "state_bytes" not in st:
            continue
        dec["programs"] += 1
        dec["slot_steps"] += float(st.get("k", 0)) * float(st.get("slots", 0))
        dec["state_bytes"] += float(st["state_bytes"])
    pre = {"programs": 0, "scan_rows": 0.0}
    for ps, pe in sorted((s, s + d) for n, s, d in programs
                         if sr.program_kind(n) == "prefill"):
        if ps < t0 or pe > t1:
            continue
        st = next((st for n, s, e, st in spans
                   if n == PREFILL and s <= ps <= e), {})
        if "scan_rows" in st:
            pre["programs"] += 1
            pre["scan_rows"] += float(st["scan_rows"])
    if not dec["programs"] and not pre["programs"]:
        return None
    return {"decode": dec, "prefill": pre}


def for_ctx(ctx):
    """{"state": reduce_state's dict, "scopes": scope_reduce's reduction} of
    THIS run's trace, made once per run (kept in `ctx`) and printed; None
    where either is missing."""
    from benchmark import scope_reduce

    trace = ctx.get("trace")
    if not trace:
        return None
    if "nemotron_trace" not in ctx:
        scopes = scope_reduce.for_ctx(ctx)
        path = sr.newest_xplane()
        state = reduce_state(sr.load(path)) if (scopes and path) else None
        ctx["nemotron_trace"] = ({"state": state, "scopes": scopes}
                                 if state else None)
        print(f"[nemotron_trace] programs wholly inside the window: "
              f"{state or 'no state counts on the spans of this run'}",
              flush=True)
    return ctx["nemotron_trace"]


def whole_seconds(scopes, kind, op, phase):
    """Mean-of-chips own seconds of (kind, op, phase) over the programs that
    ran wholly inside the window."""
    return scopes["whole"].get((kind, op, phase), 0.0)


def pattern_count(ctx, char):
    cfg = ctx.get("config") or {}
    cut = ctx.get("cut") or {}
    pattern = cut.get("model", {}).get(
        "hybrid_override_pattern", cfg.get("hybrid_override_pattern", ""))
    return pattern.count(char)
