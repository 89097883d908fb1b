"""The prefill's chunked scan (SSD) against the roofline that binds it: the
least time its FLOPs and its bytes need at the chip's published peaks, over
the time it took. For each prefill program that ran wholly inside the traced
slice its `ff.prefill` span says `scan_rows` (the bucket's rows x the `M`
layers); `benchmark/nemotron_flops.py` `scan_flops` / `scan_bytes` count one
layer's work a row by the chunked form (C.B and the masked product inside a
chunk, the chunk's state and its read-out; x, B, C, dt in, y out, the float32
state once a chunk). Time: own seconds of the device ops under `mamba_<i>` /
`scan` in those programs (benchmark/scope_reduce.py `whole` rows). None where
the slice holds no whole prefill."""
NAME, UNIT = "ssm_scan_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import nemotron_flops, nemotron_trace, peaks

    red = nemotron_trace.for_ctx(ctx)
    if not red or not red["state"]["prefill"]["scan_rows"]:
        return None
    sec = nemotron_trace.whole_seconds(red["scopes"], "prefill", "mamba",
                                       "scan")
    if not sec:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])
    rows = red["state"]["prefill"]["scan_rows"]
    least = max(
        nemotron_flops.scan_flops(ctx["config"], rows) / peak["bf16_flops"],
        nemotron_flops.scan_bytes(ctx["config"], rows)
        / peak["hbm_bytes_per_s"])
    return 100.0 * least / sec
