"""A traced run's counts for the retention (Brumby) readers: what the decode
programs that ran WHOLLY inside the traced window were asked to do, from the
`ff.decode_dispatch` spans that dispatched them (`granite_trace.reduce_decode`:
the spans carry `state_bytes` for every model that keeps a recurrent state),
beside `scope_reduce`'s device seconds of the same programs (its `whole`
rows).

A run that was not traced, a trace without `ff.engine_step`, a run whose
decode spans carry no `state_bytes`, or a configuration that is no Brumby
gives None, and the readers leave their metrics out.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def is_brumby(ctx):
    return (ctx.get("config") or {}).get("model_type") == "brumby"


def for_ctx(ctx):
    """{"decode": reduce_decode's dict, "scopes": scope_reduce's reduction}
    of THIS run's trace, made once per run (kept in `ctx`) and printed."""
    from benchmark import granite_trace, scope_reduce
    from benchmark import span_reduce as sr

    if not ctx.get("trace") or not is_brumby(ctx):
        return None
    if "brumby_trace" not in ctx:
        scopes = scope_reduce.for_ctx(ctx)
        path = sr.newest_xplane()
        dec = granite_trace.reduce_decode(sr.load(path)) \
            if (scopes and path) else None
        ctx["brumby_trace"] = ({"decode": dec, "scopes": scopes}
                               if dec else None)
        print(f"[brumby_trace] decode programs wholly inside the window: "
              f"{dec or 'no state counts on the spans of this run'}",
              flush=True)
    return ctx["brumby_trace"]


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
