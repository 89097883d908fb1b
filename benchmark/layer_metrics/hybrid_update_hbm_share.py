"""The decode state update's share of the chip's published HBM bandwidth at
ONE group of B / C and 64 heads (granite-4.0-h-micro): the bytes it has to
move over the time it took times the peak. Bytes: for each decode program that
ran wholly inside the traced slice, its dispatch span's `state_bytes` (the
engine's own count: steps x live slots x the bytes a slot holds over all 36
state-space layers, there and back: the float32 state H and the conv tail)
and, for its `k` steps x live `slots` and each Mamba layer, the step's decay /
dt x / B / C in and y out (`benchmark/granite_flops.py` `update_rows_bytes`).
The weights are not its (the in- and out-projection are other phases). Time:
own seconds of the device ops under `mamba_<i>` / `update` in those programs
(benchmark/scope_reduce.py `whole` rows). Bound by bytes: 2 FLOPs a state
element against 8 bytes. Over 100 is a wrong count, not a fast kernel."""
NAME, UNIT = "hybrid_update_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import granite_flops, granite_trace

    cfg = ctx.get("config") or {}
    if "layer_types" not in cfg:
        return None
    layers = granite_flops.layer_counts(cfg)[0]
    return granite_trace.hbm_share(
        ctx, lambda d: d["state_bytes"] + layers
        * granite_flops.update_rows_bytes(cfg, d["slot_steps"]),
        "mamba", "update")
