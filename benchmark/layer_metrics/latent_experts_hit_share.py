"""Share of the held expert weights a decode step has to stream: the engine's
`moe_experts_hit` (held experts with at least one live row, counted inside
the decode program and summed over expert layers and steps) over held experts
x the pattern's `E` layers x decode steps, as deltas across the window.
(`ep_experts_hit_share` counts the expert layers by `first_k_dense_replace`, a
key this family lacks: here they are the `E`s of `hybrid_override_pattern`.)
32 rows x 22 choices over 512 experts hit 1 - (1 - 22/512)^32 = 75 % of a
chip's 64."""
NAME, UNIT = "latent_experts_hit_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    from benchmark import nemotron_trace

    d = ctx.get("stats_delta") or {}
    cfg = ctx.get("config") or {}
    layers = nemotron_trace.pattern_count(ctx, "E")
    if not d.get("decode_steps") or "moe_experts_hit" not in d \
            or not layers or "n_routed_experts" not in cfg:
        return None
    return 100.0 * d["moe_experts_hit"] / (cfg["n_routed_experts"] * layers
                                           * d["decode_steps"])
