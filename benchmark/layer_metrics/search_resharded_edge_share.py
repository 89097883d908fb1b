"""Share of the graph's producer-consumer edges on which the searched
strategy changes sharding: 100 x `resharded_edges` / `edges` of
`model._search_summary` (an edge counts when the simulator prices its
reshard above zero). Each such edge is a collective in the forward pass and
another in the backward; four identical layers under one strategy reshard on
a fifth of their edges, an unconverged search on nine tenths. A program
whose search does not count its edges reports nothing."""
NAME, UNIT = "search_resharded_edge_share", "%"
LAYER, MOVES, SOURCE = "strategy search", "train_tokens_per_s", "program_counter"


def read(ctx):
    summary = ctx.get("search_summary") or {}
    edges = summary.get("edges")
    if not edges or summary.get("resharded_edges") is None:
        return None
    return 100.0 * summary["resharded_edges"] / edges
