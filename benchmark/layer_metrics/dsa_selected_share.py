"""Share of the cached tokens a decode row could see that its selection keeps,
over the window: the engine's `dsa_selected_tokens` / `dsa_context_tokens`
(both summed over live rows, decode steps and layers; a row keeps
min(context, index_topk)). It says how sparse this traffic makes the attention
core: 100 below index_topk, about 2048 / context above."""
NAME, UNIT = "dsa_selected_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta") or {}
    kept, seen = d.get("dsa_selected_tokens"), d.get("dsa_context_tokens")
    if not kept or not seen:
        return None
    return 100.0 * kept / seen
