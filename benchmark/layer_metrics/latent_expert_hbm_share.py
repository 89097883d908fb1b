"""The expert stream's share of the chip's published HBM bandwidth in decode,
for two-matrix experts in a latent: the bytes its calls have to stream over
the time they took times the peak. Bytes: `experts_hit` of the decode
dispatches whose program ran wholly inside the traced slice (the engine counts
on the device, per expert layer and step, the held experts with at least one
live row) x one expert's TWO matrices of latent x width in the weights' dtype
(`benchmark/nemotron_flops.py` `expert_bytes`, from the configuration file).
Time: own seconds of the Mosaic calls named after a `moe_<i>` scope inside
those programs (benchmark/moe_trace.py). A step holds about 1.4 rows an
expert, so the kernel is bound by streaming each hit expert once."""
NAME, UNIT = "latent_expert_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import moe_trace, nemotron_flops, peaks

    cfg = ctx.get("config") or {}
    if "moe_latent_size" not in cfg:
        return None
    red = moe_trace.for_ctx(ctx)
    dec = red and red["decode"]
    if not dec or not dec["grouped_s"] or not dec["experts_hit"]:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return (100.0 * nemotron_flops.expert_bytes(cfg, dec["experts_hit"])
            / (dec["grouped_s"] * peak))
