"""Share of the device's busy time, over the traced slice, spent in ops traced
under the latent attention's name (`attn_<i>`: its projections, the rotary
passes, the expansion of K and V from the latents, the three flash kernels,
the output projection; forward and transposes). The scope of each device op
comes from the compiled step's own text (benchmark/train_trace.py
`scopes_of`). It says how much of the step the mechanism is. Lower is better
at a fixed model: the same attention in less time."""
NAME, UNIT = "mla_train_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "train_tokens_per_s", "device_trace"


def read(ctx):
    from benchmark import train_trace

    return train_trace.scope_share(ctx, "attn")
