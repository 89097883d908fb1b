"""Median over requests of the time per output token where every admission's
prefill stalls all decoding: about 46 ms / (1 - share of time in prefill).
The statistic is the window's `tpot_p50_s`; the name is its own because an
end-to-end metric of that name is judged in another cell. Recorded, not
judged: over a window's 82 requests the driver read spreads of 3.2 % and
4.5 % (BENCHMARK_REFUSED.md, PR 22; 0.9 % and 1.7 % in my chip runs), and a
bound of at most 10 % admits a spread under 5 % with too little room."""
NAME, UNIT = "tpot_stalled_p50_s", "s"
LAYER, MOVES, SOURCE = "serving engine", "serve_tokens_per_s", "host_clock"


def read(ctx):
    return (ctx.get("window") or {}).get("tpot_p50_s")
