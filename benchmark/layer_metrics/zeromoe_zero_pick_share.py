"""Share of the live rows' router picks that fell on a zero-computation
(identity) expert: `moe_zero_picks / (moe_zero_picks + moe_real_picks)`,
counted on the device inside the decode programs (ops/moe.py, summed as
`experts_hit` is) and read as the engine's `stats()` deltas across the window
(`generators/shared_doc_serving_arranged.py` hands them on); from the
`ff.record_tokens` spans of the decode dispatches wholly inside the traced
slice where a generator does not. 33.3 under even routing over 512 + 256
columns: how much expert work the routing removed."""
NAME, UNIT = "zeromoe_zero_pick_share", "%"
LAYER, MOVES, SOURCE = "router", "tpot_p50_s", "program_counter"


def read(ctx):
    from benchmark import longcat_trace

    d = ctx.get("stats_delta") or {}
    zero, real = d.get("moe_zero_picks"), d.get("moe_real_picks")
    if zero is None or real is None:
        red = longcat_trace.for_ctx(ctx)
        if not red:
            return None
        zero, real = red["decode"]["zero_picks"], red["decode"]["real_picks"]
    return 100.0 * zero / (zero + real) if zero + real else None
