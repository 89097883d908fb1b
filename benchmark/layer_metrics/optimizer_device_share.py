"""Share of the device's busy time, over the traced slice, spent outside every
graph op in the train step's own code: the scopes `loss` (the loss and the
metrics, forward and transposed), `optimizer` (`optimizer.update`: Adam over
the f32 master weights) and `grad_sync` (the accumulating step's sums and
constraints), on the chip where it is largest (benchmark/scope_reduce.py). A
fusion XLA forms across a gradient matmul and the update that consumes it is
booked to its root instruction's scope. Lower is better at a fixed model."""
NAME, UNIT = "optimizer_device_share", "%"
LAYER, MOVES, SOURCE = "train step", "train_tokens_per_s", "device_trace"

SCOPES = ("loss", "optimizer", "grad_sync")


def read(ctx):
    from benchmark import scope_reduce

    if ctx.get("mode") != "train":
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op in SCOPES) or None
