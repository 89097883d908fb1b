"""The WINDOW layers' paged-attention kernel's share of the chip's published
HBM bandwidth in decode. Bytes: for each decode program that ran wholly
inside the traced slice its dispatch span's `context_tokens_window`
(min(context, window) keys a live slot and step) x the keys and values of a
token x the window layers (benchmark/exaone_flops.py `paged_bytes`). Time:
own seconds of the device ops under `attn_window_<i>` / `core` in those
programs. Low by nature: 32 slots x 128 keys x 4096 B is half a megabyte a
call, so the number says how far the call is bound by its launch and its
grid of slots, not by memory."""
NAME, UNIT = "swa_window_paged_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import exaone_trace

    return exaone_trace.paged_hbm_share(ctx, "window")
