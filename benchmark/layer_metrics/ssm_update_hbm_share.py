"""The decode state update's share of the chip's published HBM bandwidth: the
bytes it has to move over the time it took times the peak. Bytes: for each
decode program that ran wholly inside the traced slice, its dispatch span's
`state_bytes` (the engine's own count: steps x live slots x the bytes a slot
holds over all state-space layers, there and back: the float32 state H and
the conv tail) and, for its `k` steps x live `slots` and each `M` layer of
the pattern, the step's x / B / C / dt in and y out
(`benchmark/nemotron_flops.py` `update_rows_bytes`). The weights are not its
(the in- and out-projection are other phases). Time: own seconds of the device ops under
`mamba_<i>` / `update` in those programs (benchmark/scope_reduce.py `whole`
rows). Bound by bytes: 2 FLOPs a state element against 8 bytes."""
NAME, UNIT = "ssm_update_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import nemotron_flops, nemotron_trace, peaks

    red = nemotron_trace.for_ctx(ctx)
    layers = nemotron_trace.pattern_count(ctx, "M")
    if not red or not layers or not red["state"]["decode"]["slot_steps"]:
        return None
    sec = nemotron_trace.whole_seconds(red["scopes"], "decode", "mamba",
                                       "update")
    if not sec:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    dec = red["state"]["decode"]
    moved = dec["state_bytes"] + layers * nemotron_flops.update_rows_bytes(
        ctx["config"], dec["slot_steps"])
    return 100.0 * moved / (sec * peak)
