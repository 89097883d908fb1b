"""Share of the device's busy time, over the traced slice, under the SwiGLU
MLPs' scopes (`mlp_<i>`: one op a layer, ops/dense.py `GatedMLP`: the
in-projection to gate and up, silu x up, the out-projection), booked by
benchmark/scope_reduce.py from the programs' own scope tables. In decode it is
the stream of 100.7 MB of weights a layer; lower is better at a fixed model."""
NAME, UNIT = "hybrid_mlp_device_share", "%"
LAYER, MOVES, SOURCE = "dense op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    if "layer_types" not in (ctx.get("config") or {}):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "mlp") or None
