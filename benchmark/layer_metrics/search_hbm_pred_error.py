"""How far the search's predicted peak HBM per chip is from the measured
peak on the fullest chip: 100 x |predicted / measured - 1| (PR 21 found it
off by more than 2x for placement programs). The search prunes strategies by
this prediction, so an error costs the faster strategies that would have fit,
or chooses one that does not."""
NAME, UNIT = "search_hbm_pred_error", "%"
LAYER, MOVES, SOURCE = "strategy search", "train_tokens_per_s", "program_counter"


def read(ctx):
    predicted = (ctx.get("search_summary") or {}).get("peak_hbm_bytes")
    measured = (ctx.get("device") or {}).get("memory_peak_bytes")
    if not predicted or not measured:
        return None
    return 100.0 * abs(predicted / measured - 1.0)
