"""The WINDOW layers' paged-attention kernel's share of the chip's published
HBM bandwidth in decode: the kernel with a sink in its running maximum and
denominator, over a slot's ring of two pages. Bytes: the dispatch spans'
`context_tokens_window` (min(context, 128) a live slot and step) x the keys
and values of a token at the PUBLISHED widths (benchmark/mimo_flops.py
`paged_bytes`: 8 KV heads x (192 + 128) x 2 B) x the window layers. Time: own
seconds of the device ops under `attn_window_<i>` / `core` in the decode
programs wholly inside the traced slice. Low by nature: a call reads two
pages a slot, so its fixed cost a slot weighs as much as its bytes."""
NAME, UNIT = "sink_window_paged_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import mimo_trace

    return mimo_trace.paged_hbm_share(ctx, "window")
