"""Share of the device's busy time, over the traced slice, under the SwiGLU
MLPs' scopes (`mlp_<i>`: one op a layer, ops/dense.py `GatedMLP`) and the
untied head's (`lm_head`): in decode the stream of 535 MB of weights a layer
and 1.56 GB of head, which is the rest of the step beside the retention
layers' states; booked by benchmark/scope_reduce.py from the programs' own
scope tables. Lower is better at a fixed model."""
NAME, UNIT = "retention_mlp_device_share", "%"
LAYER, MOVES, SOURCE = "dense op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import brumby_trace, scope_reduce

    if not brumby_trace.is_brumby(ctx):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op in ("mlp", "lm_head")) or None
