"""Share of the device's busy time, over the traced slice, spent in device ops
traced under the attention ops (`attn_<i>`: projections, rotary, the layout
transposes, the flash kernels, the output projection; forward and transposes),
on the chip where it is largest. The `[scope_reduce]` rows split it into
`project` / `core` / `out` (benchmark/scope_reduce.py). It says how much of
the step attention is. Lower is better at a fixed model."""
NAME, UNIT = "attn_train_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "train_tokens_per_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    if ctx.get("mode") != "train":
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "attn") or None
