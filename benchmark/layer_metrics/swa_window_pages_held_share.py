"""What the window layers' rings hold, over what the same layers would hold
in tables like the global one's: the engine's `kv_page_steps_window` (pages
the window groups' rings hold, each backing every layer of its group, summed
over decode steps) over `kv_page_steps_global` (the global table's, the
same), as deltas across the window. A window layer's table would be a copy
of the global one, so the ratio of page ids IS the ratio of bytes a layer:
100 % would be no saving. The allocator's saving, read from the program; a
program without window groups reports neither counter."""
NAME, UNIT = "swa_window_pages_held_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta") or {}
    if not d.get("kv_page_steps_global") \
            or "kv_page_steps_window" not in d:
        return None
    return 100.0 * d["kv_page_steps_window"] / d["kv_page_steps_global"]
