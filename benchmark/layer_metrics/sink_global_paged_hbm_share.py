"""The GLOBAL layers' paged-attention kernel's share of the chip's published
HBM bandwidth in decode, which is its roofline (one query row a slot, 16 query
heads a KV head). Bytes: for each decode program that ran wholly inside the
traced slice its dispatch span's `context_tokens_global` (the engine's count:
every live slot's context at each of the dispatch's steps) x the keys and
values of a token at the PUBLISHED widths (benchmark/mimo_flops.py
`paged_bytes`: 4 KV heads x (192 + 128) x 2 B, from the configuration file) x
the global layers. Time: own seconds of the device ops under
`attn_global_<i>` / `core` in those programs (benchmark/scope_reduce.py
`whole` rows). A pool that stores the 192-wide key row padded to 256 lanes
streams a fifth more bytes than are counted, and reads as a lower share."""
NAME, UNIT = "sink_global_paged_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import mimo_trace

    return mimo_trace.paged_hbm_share(ctx, "global")
