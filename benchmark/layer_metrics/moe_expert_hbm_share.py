"""The grouped expert matmuls' share of the chip's published HBM bandwidth in
decode, which is their roofline there: a step holds a few rows per expert, so
the kernel is bound by streaming each hit expert's three matrices once.
Bytes: `experts_hit` of the decode dispatches whose program ran inside the
traced slice (the engine counts on the device, per MoE layer and step, the
experts with at least one live row) x one expert's bytes in the weights'
dtype (`benchmark/moe_flops.py` `moe_bytes`, from the configuration file).
Time: own time of the `ragged-dot` Mosaic calls inside those programs.

The same reader logs the prefill side of the same kernel against both of its
bounds: `assignments` of the prefill programs in the slice x 3 matmuls x 2 x
hidden x width FLOPs over their grouped-matmul time x the bf16 peak, and
their `experts_hit` x an expert's bytes over that time x the HBM peak (a
512-token prompt gives an expert 64 rows: 64 FLOPs a byte against the chip's
240, so short prompts are bound by bandwidth there too). One metric per
entry: decode is the one the cell is about."""
NAME, UNIT = "moe_expert_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import moe_flops, moe_trace, peaks

    red = moe_trace.for_ctx(ctx)
    if not red:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])
    cfg = ctx["config"]
    pre = red["prefill"]
    if pre and pre["grouped_s"] and pre["assignments"] \
            and pre["experts_hit"]:
        flop = (moe_flops.moe_flops(cfg, pre["assignments"])
                / (pre["grouped_s"] * peak["bf16_flops"]))
        hbm = (moe_flops.moe_bytes(cfg, pre["experts_hit"])
               / (pre["grouped_s"] * peak["hbm_bytes_per_s"]))
        print(f"[moe_trace] prefill side: {pre['programs']} programs, "
              f"{pre['assignments']:.0f} assignments over "
              f"{pre['experts_hit']:.0f} experts in {pre['grouped_s']:.4f} "
              f"s of grouped matmuls = {100 * flop:.2f} % of "
              f"{peak['bf16_flops'] / 1e12:.0f} TFLOP/s, {100 * hbm:.2f} % "
              f"of {peak['hbm_bytes_per_s'] / 1e9:.0f} GB/s", flush=True)
    dec = red["decode"]
    if not dec or not dec["grouped_s"] or not dec["experts_hit"]:
        return None
    return (100.0 * moe_flops.moe_bytes(cfg, dec["experts_hit"])
            / (dec["grouped_s"] * peak["hbm_bytes_per_s"]))
