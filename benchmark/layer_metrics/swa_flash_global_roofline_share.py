"""The prefill flash forward of the GLOBAL layers against the bf16 peak,
through `swa_flash_window_roofline_share`'s reader with the causal count:
4 x head_dim x heads x n (n + 1) / 2 FLOPs a layer for a prompt of n rows
(benchmark/exaone_flops.py `flash_flops`). Time: own seconds of the device
ops under `attn_global_<i>` / `core` in the prefill programs that ran wholly
inside the traced slice. A chunk of 2048 queries meets its whole prefix
bottom-right aligned; the bucket's padding rows are no useful work. None
where the slice holds no whole prefill."""
NAME, UNIT = "swa_flash_global_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import exaone_trace

    return exaone_trace.flash_roofline_share(ctx, "global")
