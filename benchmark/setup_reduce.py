"""What a run's set-up is made of, from the program's own lifecycle spans.

`setup_s` runs from the start of the process to the first measured request or
step. The program accounts for its part of that in
`flexflow_tpu.runtime.telemetry`: one span a phase (`model_compile` >
`strategy_search`, `init_params`, `init_optimizer`; `engine_build`; a `compile`
span for each program's first call; the engine's blocking `run` /
`prefill_into_cache`), kept apart from the ring so that a saturated window
does not push them out, each carrying what jax itself reported while it was
the innermost open (`trace_s`, `lower_s`, `backend_s`, `cache_load_s`,
`cache_hits`, `cache_requests`), and process totals `unspanned_*` for what
jax reported under none of them (the harness's own jits: a training cell's
reference, the checks after the window).

`for_ctx(ctx)` reads them IN THE RUN'S OWN PROCESS, once a run, keeps the
spans that began before the window and prints them as `[setup_reduce]` rows.
The window begins at the first `Request.t_submit` of the records (serving) or
at the ring's last `train_step` / `train_scan_chunk` event (training: a warm
window opens no lifecycle span, a check after it may). None for a program
without lifecycle spans (a parent of PR 48): the seven `setup_*` readers then
report nothing.
"""

TRAIN_EVENTS = ("train_step", "train_scan_chunk")
SEATING = ("run", "prefill_into_cache")
JAX_COUNTS = ("trace_s", "lower_s", "backend_s", "cache_load_s",
              "cache_hits", "cache_requests")


def end(span):
    return span["ts"] + span.get("dur", 0.0)


def inside(span, other):
    """Whether `span` lies within `other` (another span)."""
    return span is not other and other["ts"] <= span["ts"] \
        and end(span) <= end(other)


def outermost(spans):
    """The spans that lie within no other of the list."""
    return [s for s in spans if not any(inside(s, o) for o in spans)]


def seconds(spans):
    return sum(s.get("dur", 0.0) for s in spans) / 1e6


def union_s(spans):
    """Seconds covered by at least one of the spans."""
    total, upto = 0.0, float("-inf")
    for s in sorted(spans, key=lambda s: s["ts"]):
        total += max(0.0, end(s) - max(s["ts"], upto))
        upto = max(upto, end(s))
    return total / 1e6


def count(spans, key):
    return sum(s.get("args", {}).get(key, 0) for s in spans)


def named(spans, *names):
    return [s for s in spans if s["name"] in names]


def window_ts(ctx, events, to_us):
    """Where the window began, on the ring's clock (microseconds)."""
    submits = [r["request"].t_submit for r in ctx.get("records") or ()
               if r.get("request") is not None]
    if submits:
        return to_us(min(submits))
    steps = [e["ts"] for e in events if e["name"] in TRAIN_EVENTS]
    return max(steps) if steps else float("inf")


def reduce_setup(events, lifecycle, begins):
    """{"spans": the lifecycle spans that began before `begins`, "seated":
    the `run` / `prefill_into_cache` spans among them that also ENDED before
    it, "begins"}; None where there is none."""
    spans = [e for e in events if e["name"] in lifecycle
             and e.get("ph") == "X" and e["ts"] < begins]
    if not spans:
        return None
    return {"spans": spans, "begins": begins,
            "seated": [s for s in named(spans, *SEATING)
                       if end(s) <= begins]}


def seat_warm_s(red):
    """Seconds inside the seating spans less the `compile` spans nested in
    them: warm-up and document seating as pure execution."""
    compiles = outermost(named(red["spans"], "compile"))
    held = outermost(red["seated"])
    if not held:
        return None
    return seconds(held) - seconds(
        [c for c in compiles if any(inside(c, s) for s in held)])


def table(red, totals):
    rows = [f"window begins {red['begins'] / 1e6:.3f} s after the ring's "
            f"epoch; {len(red['spans'])} lifecycle spans began before it, "
            f"union {union_s(red['spans']):.3f} s",
            "start_s dur_s span track [program] trace/lower/backend/load s "
            "cache hits/requests"]
    for s in sorted(red["spans"], key=lambda s: s["ts"]):
        a = s.get("args", {})
        rows.append(
            f"{s['ts'] / 1e6:9.3f} {s.get('dur', 0.0) / 1e6:8.3f} "
            f"{s['name']} {s['pid']} [{a.get('program', '')}] "
            f"{a.get('trace_s', 0):.3f}/{a.get('lower_s', 0):.3f}/"
            f"{a.get('backend_s', 0):.3f}/{a.get('cache_load_s', 0):.3f} "
            f"{a.get('cache_hits', 0)}/{a.get('cache_requests', 0)}")
    rows.append("spans' sums: " + ", ".join(
        f"{k} {count(red['spans'], k):.6g}" for k in JAX_COUNTS))
    rows.append("under no lifecycle span (the whole process): " + ", ".join(
        f"{k} {v:.6g}" for k, v in sorted(totals.items())))
    return rows


def for_ctx(ctx):
    """The reduction of THIS run's set-up, made once per run (kept in `ctx`)
    and printed in full."""
    if "setup_reduce" not in ctx:
        from flexflow_tpu.runtime import telemetry

        red = None
        if hasattr(telemetry, "LIFECYCLE_SPANS"):
            events = telemetry.tracer().events()
            red = reduce_setup(events, telemetry.LIFECYCLE_SPANS,
                               window_ts(ctx, events, telemetry.to_us))
        for row in table(red, telemetry.setup_totals()) if red else [
                "no lifecycle span in this program's ring: the setup_* "
                "metrics are left out"]:
            print(f"[setup_reduce] {row}", flush=True)
        ctx["setup_reduce"] = red
    return ctx["setup_reduce"]
