"""Share of decode slot-steps that emitted a kept token: the engine's own
counters, read as deltas across the window."""
NAME, UNIT = "decode_occupancy", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta")
    if not d or not d["decode_steps"]:
        return None
    return 100.0 * d["occupied_slot_steps"] / (d["decode_steps"]
                                               * ctx["slots"])
