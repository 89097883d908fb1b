"""Share of the device's busy time, over the traced slice, under the WINDOW
attention layers' scopes (`attn_window_<i>`: projections, the QK norm,
rotary, the paged kernel over the slot's ring of pages in decode, the chunk's
flash forward against the last window + chunk keys in prefill, the seat of
the ring, the output projection), booked by benchmark/scope_reduce.py from
the programs' own scope tables. Lower is better at a fixed model."""
NAME, UNIT = "swa_window_attn_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "attn_window") or None
