"""The flash kernels' NEEDED work as a share of the bf16 peak over their own
device time, forward and backward together. Needed (benchmark/kanana_flops.py
`flash_flops`, from the configuration file): a causal core of seq (seq + 1) /
2 (query, key) pairs a head; the forward kernel's two matmuls (Q K^T at 192, P
V at 128), the backward's four (dP and dQ in `flash_attention_bwd_dq`, dV and
dK in `flash_attention_bwd_dkv`); the Q K^T and dP both backward kernels
compute again do not count. Time: the named kernels' events that began inside
the traced slice (benchmark/train_trace.py), each call priced by its kind.
Compute-bound at these shapes (a 512 x 512 tile moves 0.6 MB for 0.3 GFLOP),
so the peak is the FLOP peak. A 192-wide contraction fills one and a half of
the MXU's 128-deep passes: the kernel pays for 256."""
NAME, UNIT = "mla_flash_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "train_tokens_per_s", "device_trace"


def shares(ctx):
    """{"fwd", "bwd", "all"}: needed FLOPs / (seconds x peak) in percent, or
    None."""
    from benchmark import kanana_flops, peaks, train_trace

    red = train_trace.for_ctx(ctx)
    sizes = ctx.get("sizes") or {}
    if not red or not red["flash"] or "qk_nope_head_dim" not in sizes:
        return None
    f = red["flash"]
    sequences = ctx["tokens_per_step"] // ctx["seq"]
    need = kanana_flops.flash_flops(sizes, sequences, ctx["seq"])
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"]
    fwd = (f["fwd"]["calls"] * need["fwd"], f["fwd"]["seconds"])
    bwd = ((f["bwd_dq"]["calls"] + f["bwd_dkv"]["calls"]) * need["bwd"] / 2,
           f["bwd_dq"]["seconds"] + f["bwd_dkv"]["seconds"])
    if not fwd[1] or not bwd[1]:
        return None
    return {"fwd": 100.0 * fwd[0] / (fwd[1] * peak),
            "bwd": 100.0 * bwd[0] / (bwd[1] * peak),
            "all": 100.0 * (fwd[0] + bwd[0]) / ((fwd[1] + bwd[1]) * peak)}


def read(ctx):
    s = shares(ctx)
    if s is None:
        return None
    print(f"[train_trace] flash roofline share: forward {s['fwd']:.2f} %, "
          f"backward {s['bwd']:.2f} %, together {s['all']:.2f} %",
          flush=True)
    return s["all"]
