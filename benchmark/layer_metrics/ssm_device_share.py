"""Share of the device's busy time, over the traced slice, under the Mamba-2
ops' scopes (`mamba_<i>`: in-projection, conv, the prefill's chunked scan or
the decode step's state update, the gated norm, the out-projection; the
seating of a prefilled state), booked by benchmark/scope_reduce.py from the
programs' own scope tables. It says how much of the tick the state-space
layers are. Lower is better at a fixed model: the same layers in less
time."""
NAME, UNIT = "ssm_device_share", "%"
LAYER, MOVES, SOURCE = "state-space op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "mamba") or None
