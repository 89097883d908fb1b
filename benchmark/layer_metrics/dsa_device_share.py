"""Share of the device's busy time, over the traced slice, spent in the sparse
latent attention's own kernels: `dsa_index_scores` (the lightning indexer over
the pool's index keys) and `mla_paged_core` (the absorbed attention over its
latent rows). The selection between them and a prefill program's attention are
anonymous XLA fusions the trace cannot attribute (benchmark/dsa_trace.py says
how much of a decode program lies outside every named kernel), so the share
reads low by those. It says how much of the step the mechanism is. Lower is
better at a fixed model: the same attention in less time."""
NAME, UNIT = "dsa_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import dsa_trace

    red = dsa_trace.for_ctx(ctx)
    if not red or not red["busy_s"]:
        return None
    own = red["kernels_s"]["index"] + red["kernels_s"]["core"]
    return 100.0 * own / red["busy_s"] if own else None
