"""Seconds jax spent tracing (`jaxpr_trace_duration`) and lowering
(`jaxpr_to_mlir_module_duration`) programs, summed over the lifecycle spans
that began before the window: `trace_s` + `lower_s`, each duration booked
by the program's telemetry to ONE span, the innermost open, and by own time
(a trace inside a trace counts once). It is the part of set-up that no warm
cache takes away: the per-layer kernel tracing and the per-weight init
programs of ROADMAP S14. What jax reported under no lifecycle span (the
harness's own jits) is in the `[setup_reduce]` rows, not here."""
NAME, UNIT = "setup_trace_lower_s", "s"
LAYER, MOVES, SOURCE = "model + compile", "setup_s", "program_counter"


def read(ctx):
    from benchmark import setup_reduce as sr

    red = sr.for_ctx(ctx)
    if not red:
        return None
    return sr.count(red["spans"], "trace_s") \
        + sr.count(red["spans"], "lower_s")
