"""Share of the device's busy time, over the traced slice, under the dense
SwiGLU feed-forwards' scopes (`ffn_<l>_<j>`: two a layer, each ONE op,
ops/dense.py `GatedMLP`), the branch the shortcut-connected expert layer runs
beside: in decode the stream of 453 MB of weights a feed-forward, 3.6 GB a
step at depth 4; booked by benchmark/scope_reduce.py from the programs' own
scope tables. Lower is better at a fixed model."""
NAME, UNIT = "scmoe_dense_ffn_device_share", "%"
LAYER, MOVES, SOURCE = "dense op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import longcat_trace, scope_reduce

    if not longcat_trace.is_longcat(ctx):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op.startswith("ffn_")) or None
