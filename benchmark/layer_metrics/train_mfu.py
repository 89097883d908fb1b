"""Model FLOP/s utilization: tokens per second x the FLOPs a token's forward
and backward need (benchmark/flops.py, from the configuration file, no
recomputation) over chips x the chip's published bf16 peak. The end-to-end
rate on the chip's scale; not a kernel's roofline share, and it says nothing
about idle time."""
NAME, UNIT = "train_mfu", "%"
LAYER, MOVES, SOURCE = "train step", "train_tokens_per_s", "host_clock"


def read(ctx):
    if ctx.get("mode") != "train" or ctx["device"]["platform"] != "tpu":
        return None     # a share of a TPU's peak exists only on a TPU
    from benchmark import flops, peaks

    per_token = flops.train_flops_per_token(ctx["config"], ctx["seq"],
                                            layers=ctx["layers"])
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * ctx["train_tokens_per_s"] * per_token / peak
