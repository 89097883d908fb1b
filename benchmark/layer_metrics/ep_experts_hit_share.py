"""Share of the HELD experts a decode step has to stream: the engine's
`moe_experts_hit` (held experts with at least one live row, counted inside
the decode program and summed over expert layers and steps) over held experts
x expert layers x decode steps, as deltas across the window. The sizes are the
configuration file's: `experts_held` (first, count) of a router
`router_experts` wide, and the layers past `first_k_dense_replace`. With 32
live rows picking 8 of 256 an expert held here sees one row a step on average,
so about 1 - e^-1 of them are hit; a trained router, more skewed than a seeded
one, would move it."""
NAME, UNIT = "ep_experts_hit_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta") or {}
    cfg = ctx.get("config") or {}
    if not d.get("decode_steps") or "moe_experts_hit" not in d \
            or "experts_held" not in cfg or "router_experts" not in cfg:
        return None
    held = int(cfg["experts_held"][1])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * d["moe_experts_hit"] / (held * layers * d["decode_steps"])
