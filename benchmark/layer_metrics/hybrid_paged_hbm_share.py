"""The paged-attention kernel's share of the chip's published HBM bandwidth in
decode at heads of 64 (granite-4.0-h-micro's 4 attention layers: 8 key/value
heads of 64, a page of 128 x 8 x 64), which is its roofline (one query row a
slot). Bytes: for each decode program that ran wholly inside the traced slice
its dispatch span's `context_tokens` (the live slots' contexts at the
dispatch's first step, summed) x its `k` steps x the keys and values of a
token over the attention layers (`benchmark/granite_flops.py`
`kv_bytes_per_token`: 8192 B). Time: own seconds of the device ops under
`attn_<i>` / `core` in those programs (benchmark/scope_reduce.py `whole` rows;
`paged_attn_hbm_share`'s reader wants a decode program whose only kernel is
this one, and this model's holds the state update too). Over 100 is a wrong
count, not a fast kernel."""
NAME, UNIT = "hybrid_paged_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import granite_flops, granite_trace

    cfg = ctx.get("config") or {}
    if "layer_types" not in cfg:
        return None
    per_token = granite_flops.kv_bytes_per_token(cfg)
    return granite_trace.hbm_share(
        ctx, lambda d: d["context_token_steps"] * per_token, "attn", "core")
