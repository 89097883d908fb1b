"""Share of the device's busy time, over the traced slice, under the expert
ops' scopes (`moe_<i>`: the float32 router and its top-22, the two latent
projections, the expert stream kernel in decode or the grouped matmuls in a
prefill, the shared expert), booked by benchmark/scope_reduce.py from the
programs' own scope tables (`moe_device_share` times the expert matmuls alone,
by name). Lower is better at a fixed model."""
NAME, UNIT = "latent_moe_device_share", "%"
LAYER, MOVES, SOURCE = "moe op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    if "moe_latent_size" not in (ctx.get("config") or {}):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "moe") or None
