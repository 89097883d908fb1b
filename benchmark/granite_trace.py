"""A traced run's counts for the hybrid (Granite 4.0-H) readers: what the
decode programs that ran WHOLLY inside the traced window were asked to do,
from the `ff.decode_dispatch` spans that dispatched them, beside
`scope_reduce`'s device seconds of the same programs (its `whole` rows).

A decode program belongs to the last dispatch span that began before it
(`span_reduce._pair_dispatches`' rule). The span says its `k` steps, its live
`slots`, `context_tokens` (the live slots' contexts at the dispatch's FIRST
step, summed) and, where the model keeps a recurrent state, `state_bytes`
(steps x live slots x the bytes a slot holds, there and back).

A trace without `ff.engine_step`, a run whose decode spans carry no
`state_bytes` (every model without a recurrent state) or a run that was not
traced gives None, and the readers leave their metrics out.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import span_reduce as sr  # noqa: E402


def reduce_decode(planes):
    """{"programs", "slot_steps", "state_bytes", "context_token_steps"} over
    the decode programs wholly inside the window; None without
    `ff.engine_step` or where no such program's span carries `state_bytes`."""
    spans = sr._tick_line(planes)
    if spans is None:
        return None
    ops, _, programs = sr._device(planes)
    if programs is None:
        return None
    t0, t1 = sr._window(planes, ops)
    disp = sorted((s, st) for name, s, _, st in spans if name == sr.DISPATCH)
    out = {"programs": 0, "slot_steps": 0.0, "state_bytes": 0.0,
           "context_token_steps": 0.0}
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
        k = float(st.get("k", 0))
        out["programs"] += 1
        out["slot_steps"] += k * float(st.get("slots", 0))
        out["state_bytes"] += float(st["state_bytes"])
        out["context_token_steps"] += k * float(st.get("context_tokens", 0))
    return out if out["programs"] else None


def for_ctx(ctx):
    """{"decode": reduce_decode's dict, "scopes": scope_reduce's reduction}
    of THIS run's trace, made once per run (kept in `ctx`) and printed; None
    where either is missing or the configuration is no Granite hybrid."""
    from benchmark import scope_reduce

    trace = ctx.get("trace")
    if not trace or "layer_types" not in (ctx.get("config") or {}):
        return None
    if "granite_trace" not in ctx:
        scopes = scope_reduce.for_ctx(ctx)
        path = sr.newest_xplane()
        dec = reduce_decode(sr.load(path)) if (scopes and path) else None
        ctx["granite_trace"] = ({"decode": dec, "scopes": scopes}
                                if dec else None)
        print(f"[granite_trace] decode programs wholly inside the window: "
              f"{dec or 'no state counts on the spans of this run'}",
              flush=True)
    return ctx["granite_trace"]


def hbm_share(ctx, moved_bytes, op, phase):
    """Percent of the published HBM bandwidth that `moved_bytes` over the own
    seconds under (`decode`, `op`, `phase`) of the whole programs make."""
    from benchmark import peaks

    red = for_ctx(ctx)
    if not red:
        return None
    sec = red["scopes"]["whole"].get(("decode", op, phase), 0.0)
    moved = moved_bytes(red["decode"])
    if not sec or not moved:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * moved / (sec * peak)
