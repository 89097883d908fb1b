"""Model FLOP/s utilization of ONE CHIP'S SHARE of an expert-parallel training
step: tokens per second x the FLOPs a token's forward and backward need on
this chip (benchmark/kanana_flops.py, from the configuration file: latent
attention's projections and its causal core at 192 + 128 a head and seq / 2
keys, the dense MLP, the shared experts, the router, the routed experts at
`num_experts_per_tok` x held / `router_experts` a token, the sliced head; x 3;
nothing recomputed counts) over chips x the chip's published bf16 peak. The
cell's share of the whole step's peak; not a kernel's roofline share, and it
says nothing about idle time."""
NAME, UNIT = "ep_train_mfu", "%"
LAYER, MOVES, SOURCE = "train step", "train_tokens_per_s", "host_clock"


def read(ctx):
    if ctx.get("mode") != "train" or ctx["device"]["platform"] != "tpu" \
            or "router_experts" not in (ctx.get("sizes") or {}):
        return None     # a share of a TPU's peak exists only on a TPU
    from benchmark import kanana_flops, peaks

    per_token = kanana_flops.train_flops_per_token(ctx["sizes"], ctx["seq"])
    peak = peaks.peaks_for(ctx["device_kind"])["bf16_flops"] * ctx["chips"]
    return 100.0 * ctx["train_tokens_per_s"] * per_token / peak
