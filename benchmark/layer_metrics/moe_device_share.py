"""Share of the device's busy time spent in the grouped expert matmuls of the
mixture-of-experts ops (`moe_<i>`), over the traced slice: own time of XLA's
`ragged-dot` Mosaic calls / busy time. The op's routing (router matmul, top-k,
the sort into expert order, the gathers, the gate-weighted sum) is small
anonymous fusions that the trace cannot attribute (benchmark/moe_trace.py:
under 2 % of busy time), so the share reads low by that much. It says how
much of the step the mechanism is: above 50 % the cell measures the expert
path, as it was built to. Lower is better at a fixed model: the same experts
in less time."""
NAME, UNIT = "moe_device_share", "%"
LAYER, MOVES, SOURCE = "moe op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import moe_trace

    red = moe_trace.for_ctx(ctx)
    if not red or not red["busy_s"] or not red["moe_s"]:
        return None
    return 100.0 * red["moe_s"] / red["busy_s"]
