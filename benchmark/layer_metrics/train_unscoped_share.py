"""Share of the device's busy time, over the traced slice, in device ops of
the train step that no scope table names: an instruction traced under no
graph op and under none of `loss` / `optimizer` / `grad_sync` (on four chips
also what GSPMD inserts without a jax scope), on the chip where it is largest
(benchmark/scope_reduce.py prints the largest by instruction). The tracing's
own guard: code a later PR adds outside every scope shows here. 0 is a
reading."""
NAME, UNIT = "train_unscoped_share", "%"
LAYER, MOVES, SOURCE = "train step", "train_tokens_per_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    if ctx.get("mode") != "train":
        return None
    return scope_reduce.share(scope_reduce.for_ctx(ctx),
                              lambda kind, label: True, field="unscoped")
