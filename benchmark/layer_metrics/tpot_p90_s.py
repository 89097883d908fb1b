"""90th percentile over requests of the time per output token: decode
starved by admissions (a prefill runs to completion before the next decode
chunk) shows here before it shows in the median."""
NAME, UNIT = "tpot_p90_s", "s"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "host_clock"


def read(ctx):
    return (ctx.get("window") or {}).get("tpot_p90_s")
