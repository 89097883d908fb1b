"""Share of the device's busy time, over the traced slice, under the GLOBAL
attention layers' scopes (`attn_global_<i>`: projections, the QK norm, the
paged kernel over the whole context in decode, the chunk's flash forward
against the whole prefix in prefill, the page writes, the output projection),
booked by benchmark/scope_reduce.py from the programs' own scope tables.
Lower is better at a fixed model: the same layers in less time. This cut
holds one global layer among five where the model holds one among four."""
NAME, UNIT = "swa_global_attn_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "attn_global") or None
