"""The paged-attention kernel's share of the chip's published HBM bandwidth in
decode: the KV bytes its calls have to stream, over the time they took times
the peak. Bytes: the sum of `kv_read_bytes` over the `ff.decode_dispatch`
spans whose program ran inside the traced slice (the engine counts, per active
slot and decode step, live pages x page_size x kv_bytes_per_token: every layer
reads its share of that once a step; the scratch page an idle slot's row
reads is not counted, so the share is of USEFUL bytes). Time: the Mosaic
custom-calls inside those decode programs (a decode program holds no other
kernel). Bound by bytes: one query token per slot makes the FLOPs negligible.
Far below 100 % the kernel is bound by its grid (slots x table width steps),
not by memory (benchmark/span_reduce.py pairs dispatches with programs)."""
NAME, UNIT = "paged_attn_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import peaks, span_reduce

    red = span_reduce.for_ctx(ctx)
    d = red and red["dispatch"]
    if not d or not d["pairs"] or not d["paged_attn_s"]:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * d["kv_read_bytes"] / (d["paged_attn_s"] * peak)
