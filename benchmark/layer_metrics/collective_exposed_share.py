"""Share of the traced slice a chip spent in cross-chip collectives with no
compute running on it (worst chip): own time of all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute ops (and the `-done` waits
of asynchronous ones) on the chip's op line, over the window."""
NAME, UNIT = "collective_exposed_share", "%"
LAYER, MOVES, SOURCE = "train step", "train_tokens_per_s", "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    if ctx.get("mode") != "train" or not trace or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_share"]
