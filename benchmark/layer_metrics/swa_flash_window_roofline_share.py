"""The prefill flash forward of the WINDOW layers against the bf16 peak. For
each prefill program that ran wholly inside the traced slice its `ff.prefill`
span says `prompt_tokens`; benchmark/exaone_flops.py `flash_flops` counts
what the prompt NEEDS: 4 x head_dim x heads x sum_i min(i + 1, window) FLOPs
a layer (Q K^T and P V over the pairs the window lets through), x the window
layers. Time: own seconds of the device ops under `attn_window_<i>` / `core`
in those programs (benchmark/scope_reduce.py `whole` rows). Low by nature: a
512-row tile pair computes 1024 keys a query where 128 are needed, and the
bucket's padding rows are no useful work. None where the slice holds no
whole prefill."""
NAME, UNIT = "swa_flash_window_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import exaone_trace

    return exaone_trace.flash_roofline_share(ctx, "window")
