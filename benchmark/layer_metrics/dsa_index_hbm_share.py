"""The lightning indexer's share of the chip's published HBM bandwidth in
decode, which is its roofline: a step scores ONE query row a slot against
every cached index key, 64 x 128 x 2 FLOPs a 256-byte key, far under the
chip's FLOPs a byte. Bytes: the context tokens the traced slice's decode
dispatches saw (`dsa_context_tokens` of their `ff.decode_dispatch` spans:
summed over live rows, steps and layers) priced by `benchmark/dsa_flops.py`
`index_bytes`, from the configuration file (the span's own `index_read_bytes`
is the engine's count of the same, which a test holds to this). Time:
own time of the `dsa_index_scores` kernel inside those programs."""
NAME, UNIT = "dsa_index_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import dsa_flops, dsa_trace, peaks

    red = dsa_trace.for_ctx(ctx)
    d = red and red["decode"]
    if not d or not d["index_s"] or not d["dsa_context_tokens"]:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return (100.0 * dsa_flops.index_bytes(ctx["config"],
                                          d["dsa_context_tokens"])
            / (d["index_s"] * peak))
