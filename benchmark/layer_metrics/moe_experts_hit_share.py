"""Share of the expert weights a decode step has to stream: the engine's
`moe_experts_hit` (experts with at least one live row, counted inside the
decode program and summed over MoE layers and steps) over experts x MoE
layers x decode steps, as deltas across the window. 100 % means every step
reads every expert of every layer. Rows of free slots route nowhere, so at low
occupancy a step streams fewer experts: this share is what that masking
buys, and it falls with the batch's live rows (32 rows x 8 choices over 64
experts hit about 98 % of them; 4 rows about 40 %)."""
NAME, UNIT = "moe_experts_hit_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta") or {}
    cfg, cut = ctx.get("config") or {}, ctx.get("cut") or {}
    if not d.get("decode_steps") or "moe_experts_hit" not in d \
            or "num_experts" not in cfg:
        return None
    layers = cut.get("model", {}).get("num_hidden_layers",
                                      cfg["num_hidden_layers"])
    return 100.0 * d["moe_experts_hit"] / (cfg["num_experts"] * layers
                                           * d["decode_steps"])
