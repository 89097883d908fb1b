"""Median, over the traced slice's whole scheduler ticks, of the seconds the
chip sat idle inside the tick: the length of an `ff.engine_step` span minus
the device's busy time under it (benchmark/span_reduce.py). It is what a
serial tick costs per tick (admit, build the slot arrays, dispatch, then after
the chunk walk `slots x k` tokens), the number ROADMAP S4 (dispatch ahead)
would take away; `device_idle_share` is the same idle time as a share of the
slice, from outside. Against a tick of 0.37 s it is small today; it stays
while the sampler repair shortens the tick."""
NAME, UNIT = "tick_idle_p50_s", "s"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import span_reduce, stats

    red = span_reduce.for_ctx(ctx)
    if not red or not red["tick_idle_s"]:
        return None
    return stats.median(red["tick_idle_s"])
