"""Share of the device's busy time, over the traced slice, spent in the sparse
latent attention's own work, in every program: the device ops traced under the
attention ops' phases `index` (index keys and scores), `select` (the
threshold's passes and the list of rows), `gather` (the take of the listed
pool rows) and `core` (the attention over them; a prefill program's blocked
attention and its pool write), booked by the scope table of each compiled
program (benchmark/scope_reduce.py). `dsa_device_share` reads the two named
kernels only and misses the selection, the gather and a prefill program's
whole attention. It says how much of the step the mechanism is. Lower is
better at a fixed model: the same attention in less time."""
NAME, UNIT = "dsa_sparse_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "device_trace"

PHASES = ("index", "select", "gather", "core")


def read(ctx):
    from benchmark import scope_reduce

    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "attn" and phase in PHASES) or None
