"""Share of the device's busy time, over the traced slice, in device ops that
no scope table names: an instruction traced under no graph op and under none
of `sampler` / `loss` / `optimizer` / `grad_sync`, or a program the registry
does not hold (benchmark/scope_reduce.py prints the largest by instruction).
The tracing's own guard: code a later PR adds outside every scope shows
here. 0 is a reading."""
NAME, UNIT = "serve_unscoped_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "serve_tokens_per_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    if ctx.get("mode") != "serve":
        return None
    return scope_reduce.share(scope_reduce.for_ctx(ctx),
                              lambda kind, label: True, field="unscoped")
