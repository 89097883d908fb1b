"""Share of the device's busy time, over the traced slice, under the
power-retention ops' scopes (`retention_<i>`: the q / k / v / gate projections
with their norms and rotary, the prefill's and the tails' chunked scan, the
decode step's state update, the out projection; the seating of a prefilled
state and the copy of a snapshot), all phases, booked by
benchmark/scope_reduce.py from the programs' own scope tables. It says how
much of the tick the mixers are: the cell is well chosen if it reads over
half. Lower is better at a fixed model: the same layers in less time."""
NAME, UNIT = "retention_device_share", "%"
LAYER, MOVES, SOURCE = "retention op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import brumby_trace, scope_reduce

    if not brumby_trace.is_brumby(ctx):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "retention") or None
