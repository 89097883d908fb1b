"""Share of the device's busy time, over the traced slice, spent in the dense
latent attention core: the kernel `mla_paged_core_dense` under the scopes
`attn_<l>_<j>` / `core` of the decode programs (every live page of every slot
read in place through the page table), and the blocked XLA core of the
prefix-hit prefills under the same scopes; booked by benchmark/scope_reduce.py
from the programs' own scope tables. It says how much of the step the
mechanism is. Lower is better at a fixed model: the same attention in less
time."""
NAME, UNIT = "mla_dense_core_device_share", "%"
LAYER, MOVES, SOURCE = "attention op", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import longcat_trace, scope_reduce

    if not longcat_trace.is_longcat(ctx):
        return None
    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: longcat_trace.is_core(op, phase)) or None
