"""Share of the window's admissions that resumed from a snapshot of the
recurrent state on a trie node: the engine's `state_snapshot_hits` over its
`prefix_lookups` (one an admission). In a cell over resident documents every
admission hits its document's pages and the ONE snapshot on the last of them:
100; under 100 a document (or its snapshot) was evicted and its requests
prefilled cold. A checkout whose engine counts no snapshots reports nothing."""
NAME, UNIT = "snapshot_hit_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "program_counter"


def read(ctx):
    d = ctx.get("stats_delta") or {}
    if not d.get("prefix_lookups") or d.get("state_snapshot_hits") is None:
        return None
    return 100.0 * d["state_snapshot_hits"] / d["prefix_lookups"]
