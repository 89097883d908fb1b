"""Share of the window's prompt tokens that admissions found in the prefix
cache: the engine's `prefix_hit_tokens` / `prefix_prompt_tokens`. In a cell
over resident documents every prompt hits its whole document and prefills one
page of question (99.6 % at 16 k, 99.8 % at 32 k); under 99 a document was
evicted and its requests prefilled cold (the run's log says which)."""
NAME, UNIT = "prefix_hit_token_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta") or {}
    asked = d.get("prefix_prompt_tokens")
    if not asked or d.get("prefix_hit_tokens") is None:
        return None
    return 100.0 * d["prefix_hit_tokens"] / asked
