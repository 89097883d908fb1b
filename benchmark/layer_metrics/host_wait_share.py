"""Share of fit()'s wall time its loop waited for input, from the last
round's `FFModel.last_step_breakdown` (host clock, host quantity)."""
NAME, UNIT = "host_wait_share", "%"
LAYER, MOVES, SOURCE = "input pipeline", "train_tokens_per_s", "program_span"


def read(ctx):
    bd = ctx.get("last_step_breakdown")
    if not bd or "host_wait_fraction" not in bd:
        return None
    return 100.0 * bd["host_wait_fraction"]
