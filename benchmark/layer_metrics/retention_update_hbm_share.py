"""The power-retention decode step's share of the chip's published HBM
bandwidth (8 KV heads x 8320 rows of phi x 128 values, float32:
brumby-14b-base-serve): the bytes it has to move over the time it took times
the peak. Bytes: for each decode program that ran wholly inside the traced
slice, its dispatch span's `state_bytes` (the engine's own count: steps x live
slots x the bytes a slot holds over all the retention layers, S and z, there
and back) and, for its `k` steps x live `slots` and each layer, the step's
tile of q / k / v / decay rows in and the read-outs out
(`benchmark/brumby_flops.py` `update_rows_bytes`). The weights are not its
(the projections are other phases). Time: own seconds of the device ops under
`retention_<i>` / `update` in those programs (benchmark/scope_reduce.py
`whole` rows). Bound by bytes: 13 FLOPs a state element against 8 bytes. The
roof for a read-and-write stream on this chip is near 79 (PERF.md section 7,
PR 44 (3)), not 100; over 100 is a wrong count, not a fast kernel."""
NAME, UNIT = "retention_update_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import brumby_flops, brumby_trace

    if not brumby_trace.is_brumby(ctx):
        return None
    cfg = ctx["config"]
    layers = int(brumby_flops._z(cfg, ctx.get("cut"))["num_hidden_layers"])
    return brumby_trace.hbm_share(
        ctx, lambda d: d["state_bytes"] + layers
        * brumby_flops.update_rows_bytes(cfg, d["slot_steps"]),
        "retention", "update")
