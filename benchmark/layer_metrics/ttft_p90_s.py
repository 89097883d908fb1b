"""90th percentile over requests of (first token time - time the request was
DUE): below the knee it is the wait for the running decode chunk plus the
request's own prefill. Recorded, not judged: over a window's 107 requests the
driver read spreads of 2.7 % and 4.1 % (BENCHMARK_REFUSED.md, PR 22), and a
bound of at most 10 % admits a spread under 5 % with too little room. A later
`benchmark` PR judges it once a window holds several hundred requests
(PERF.md section 7)."""
NAME, UNIT = "ttft_p90_s", "s"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "host_clock"


def read(ctx):
    return (ctx.get("window") or {}).get("ttft_p90_s")
