"""A traced run's counts for the LongCat-Flash readers: what the decode
programs that ran WHOLLY inside the traced window were asked to do, from the
`ff.decode_dispatch` span that dispatched each (`k` steps, `slots` live rows,
`live_row_tokens`: the live rows' context tokens (prompt + emitted, no bucket
padding) summed over slots at the dispatch's first step, `live_pages_distinct`: the DISTINCT live pages of
its steps, what a stream that fetched a shared page once could at best read)
and the `ff.record_tokens` span that follows it (`experts_hit`, `zero_picks`,
`real_picks`, `held_picks`, counted on the device), beside `scope_reduce`'s
device seconds of the same programs (its `whole` rows: `attn_<l>` / `core` is
the kernel `mla_paged_core_dense`, `moe` / `experts` the expert-stream kernel,
`moe` / `zero` the identity term, `ffn_<l>` the dense feed-forwards).

A run that was not traced, a trace without `ff.engine_step`, a program whose
decode spans carry no `live_pages_distinct` (the parent of PR 53), or a
configuration without zero-computation experts gives None, and the readers
leave their metrics out.

By hand, after a traced run: python3 benchmark/longcat_trace.py .bench_trace/<cell>
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DISPATCH_COUNTS = ("k", "slots", "live_row_tokens", "live_pages_distinct")
RECORD_COUNTS = ("experts_hit", "zero_picks", "real_picks", "held_picks")


def is_longcat(ctx):
    return "zero_expert_num" in (ctx.get("config") or {})


def reduce_decode(planes):
    """{"programs", "row_steps", "row_tokens", "distinct_pages", and the
    RECORD_COUNTS} over the decode programs wholly inside the window; None
    without `ff.engine_step`, without device programs, or where a paired
    span lacks a count."""
    from benchmark import dsa_trace, moe_trace as mt, span_reduce as sr

    spans = sr._tick_line(planes)
    if spans is None:
        return None
    ops, _, programs = sr._device(planes)
    if programs is None:
        return None
    t0, t1 = sr._window(planes, ops)
    inside = sorted(s for n, s, d in programs
                    if sr.program_kind(n) == "decode" and s >= t0
                    and s + d <= t1)
    paired = dsa_trace._spans_before(spans, sr.DISPATCH, inside)
    rec = mt._spans_after(spans, mt.RECORD, inside)
    if not inside or any(c not in st for st in paired
                         for c in DISPATCH_COUNTS) \
            or any(c not in st for st in rec for c in RECORD_COUNTS):
        return None
    out = {"programs": len(inside), "row_steps": 0.0, "row_tokens": 0.0,
           "distinct_pages": 0.0}
    for st in paired:
        k, live = float(st["k"]), float(st["slots"])
        out["row_steps"] += k * live
        # a live row's context grows by one token a step
        out["row_tokens"] += k * float(st["live_row_tokens"]) \
            + live * k * (k - 1) / 2
        out["distinct_pages"] += float(st["live_pages_distinct"])
    for c in RECORD_COUNTS:
        out[c] = float(sum(float(st[c]) for st in rec))
    return out


def for_ctx(ctx):
    """{"decode": reduce_decode's dict, "scopes": scope_reduce's reduction}
    of THIS run's trace, made once per run (kept in `ctx`) and printed."""
    from benchmark import scope_reduce
    from benchmark import span_reduce as sr

    if not ctx.get("trace") or not is_longcat(ctx):
        return None
    if "longcat_trace" not in ctx:
        scopes = scope_reduce.for_ctx(ctx)
        path = sr.newest_xplane()
        dec = reduce_decode(sr.load(path)) if (scopes and path) else None
        ctx["longcat_trace"] = ({"decode": dec, "scopes": scopes}
                                if dec else None)
        print(f"[longcat_trace] decode programs wholly inside the window: "
              f"{dec or 'no page and pick counts on the spans of this run'}",
              flush=True)
    return ctx["longcat_trace"]


def whole_seconds(red, pick):
    """Own seconds of the (`decode`, op, phase) rows `pick(op, phase)`
    accepts, over the decode programs wholly inside the window."""
    return sum(sec for (kind, op, phase), sec in red["scopes"]["whole"].items()
               if kind == "decode" and pick(op, phase))


def is_core(op, phase):
    return op.startswith("attn_") and phase == "core"


if __name__ == "__main__":
    from benchmark import span_reduce as sr, trace_reduce as tr

    print(reduce_decode(sr.load(tr.find_xplane(sys.argv[1])))
          or "no ff.engine_step span with page and pick counts in this trace")
