"""Share of the device's busy time, over the traced slice, spent in ops traced
under the expert op's name (`moe_<i>`: the router, the top-k, the sort into
expert order, the gather of the held rows, the grouped matmuls, the
scatter-add back, the shared experts; forward and transposes), read as
`mla_train_device_share` reads the attention's. Lower is better at a fixed
model: the same experts in less time."""
NAME, UNIT = "ep_train_moe_device_share", "%"
LAYER, MOVES, SOURCE = "moe op", "train_tokens_per_s", "device_trace"


def read(ctx):
    from benchmark import train_trace

    return train_trace.scope_share(ctx, "moe")
