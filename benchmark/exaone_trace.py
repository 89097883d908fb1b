"""A traced run's counts for the window / global attention readers: what the
decode and prefill programs that ran WHOLLY inside the traced window were
asked to do, from the spans that dispatched them, beside `scope_reduce`'s
device seconds of the same programs (its `whole` rows, by the op names
`attn_window` / `attn_global` the builder gives the two kinds of layer).

  * a decode program belongs to the last `ff.decode_dispatch` span that began
    before it (`span_reduce._pair_dispatches`' rule); where the model has
    window layers the span says `context_tokens_global` and
    `context_tokens_window`: the keys ONE layer of each kind read over the
    dispatch's steps, summed over the live slots;
  * a prefill program runs inside its own `ff.prefill` span, which says
    `prompt_tokens` (the prompt's rows; the bucket's padding is no useful
    work).

A trace without `ff.engine_step`, a program whose decode spans carry no such
count (every model without a window layer; the parent of PR 39) or a run that
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
KINDS = ("global", "window")


def reduce_window(planes):
    """{"decode": {"programs", "context_tokens_global",
    "context_tokens_window"}, "prefill": {"programs", "prompt_tokens":
    [rows of each]}} over the programs wholly inside the window; None
    without `ff.engine_step` or where no decode span carries the counts."""
    spans = sr._tick_line(planes)
    if spans is None:
        return None
    ops, _, programs = sr._device(planes)
    if programs is None:
        return None
    t0, t1 = sr._window(planes, ops)
    disp = sorted((s, st) for name, s, _, st in spans if name == sr.DISPATCH)
    if not any("context_tokens_window" in st for _, st in disp):
        return None
    dec = {"programs": 0, **{f"context_tokens_{k}": 0.0 for k in KINDS}}
    j, taken = -1, set()
    for ps, pe in sorted((s, s + d) for n, s, d in programs
                         if sr.program_kind(n) == "decode"):
        while j + 1 < len(disp) and disp[j + 1][0] <= ps:
            j += 1
        if j < 0 or j in taken:
            continue
        taken.add(j)
        st = disp[j][1]
        if ps < t0 or pe > t1 or "context_tokens_window" not in st:
            continue
        dec["programs"] += 1
        for k in KINDS:
            dec[f"context_tokens_{k}"] += float(st[f"context_tokens_{k}"])
    pre = {"programs": 0, "prompt_tokens": []}
    for ps, pe in sorted((s, s + d) for n, s, d in programs
                         if sr.program_kind(n) == "prefill"):
        if ps < t0 or pe > t1:
            continue
        st = next((st for n, s, e, st in spans
                   if n == PREFILL and s <= ps <= e), {})
        if "prompt_tokens" in st:
            pre["programs"] += 1
            pre["prompt_tokens"].append(int(float(st["prompt_tokens"])))
    return {"decode": dec, "prefill": pre}


def for_ctx(ctx):
    """{"counts": reduce_window's dict, "scopes": scope_reduce's reduction}
    of THIS run's trace, made once per run (kept in `ctx`) and printed; None
    where either is missing."""
    from benchmark import scope_reduce

    trace = ctx.get("trace")
    if not trace:
        return None
    if "exaone_trace" not in ctx:
        scopes = scope_reduce.for_ctx(ctx)
        path = sr.newest_xplane()
        counts = reduce_window(sr.load(path)) if (scopes and path) else None
        ctx["exaone_trace"] = ({"counts": counts, "scopes": scopes}
                               if counts else None)
        print(f"[exaone_trace] programs wholly inside the window: "
              f"{counts or 'no window counts on the spans of this run'}",
              flush=True)
    return ctx["exaone_trace"]


def whole_seconds(scopes, kind, op, phase):
    """Mean-of-chips own seconds of (kind, op, phase) over the programs that
    ran wholly inside the window."""
    return scopes["whole"].get((kind, op, phase), 0.0)


def paged_hbm_share(ctx, kind):
    """Percent of the HBM peak the decode attention of the layers of `kind`
    reaches: the keys and values they must read over the paged kernel's own
    seconds in the same programs."""
    from benchmark import exaone_flops, peaks

    red = for_ctx(ctx)
    if not red or "sliding_windows" not in (ctx.get("config") or {}):
        return None
    tokens = red["counts"]["decode"][f"context_tokens_{kind}"]
    sec = whole_seconds(red["scopes"], "decode", f"attn_{kind}", "core")
    if not tokens or not sec:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * exaone_flops.paged_bytes(ctx["config"], tokens, kind) \
        / (sec * peak)


def flash_roofline_share(ctx, kind):
    """Percent of the bf16 peak the prefill flash forward of the layers of
    `kind` reaches: the FLOPs the prompts NEED over the kernel's own seconds
    in the same programs."""
    from benchmark import exaone_flops, peaks

    red = for_ctx(ctx)
    if not red or "sliding_windows" not in (ctx.get("config") or {}):
        return None
    prompts = red["counts"]["prefill"]["prompt_tokens"]
    sec = whole_seconds(red["scopes"], "prefill", f"attn_{kind}", "core")
    if not prompts or not sec:
        return None
    need = sum(exaone_flops.flash_flops(ctx["config"], n, kind)
               for n in prompts)
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"]
    return 100.0 * need / (sec * peak)
