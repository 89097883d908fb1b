"""How far the search's predicted step time (`model._search_summary`, the
simulator that ranked the strategy) is from the measured one:
100 x |predicted / measured - 1|. 0 is a perfect prediction; a cost model
that is far off ranks strategies the chip would rank otherwise."""
NAME, UNIT = "search_pred_error", "%"
LAYER, MOVES, SOURCE = "strategy search", "train_tokens_per_s", "program_counter"


def read(ctx):
    predicted = (ctx.get("search_summary") or {}).get("predicted_step_s")
    if not predicted or not ctx.get("step_s"):
        return None
    return 100.0 * abs(predicted / ctx["step_s"] - 1.0)
