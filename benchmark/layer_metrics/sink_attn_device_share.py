"""Share of the device's busy time, over the traced slice, under the attention
layers' scopes of a MiMo-V2 model (`attn_global_<i>` and `attn_window_<i>`:
projections, partial rotary, the paged kernel over the whole context or the
ring in decode, the tail's flash forward in a hit prefill, the page writes and
snapshot copies, the output projection), booked by benchmark/scope_reduce.py
from the programs' own scope tables. Lower is better at a fixed model: the
same layers in less time. This cut holds two global layers among seven where
the model holds nine among 48, so the share reads above a deployment's."""
NAME, UNIT = "sink_attn_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    if "hybrid_layer_pattern" not in (ctx.get("config") or {}):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op in ("attn_global", "attn_window")) or None
