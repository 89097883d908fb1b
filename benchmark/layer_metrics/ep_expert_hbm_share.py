"""The held experts' kernel's share of the chip's published HBM bandwidth in
decode, which is its roofline there: a step gives an expert about one row, so
the expert-stream kernel is bound by streaming each HIT expert's three
matrices once. Bytes: `experts_hit` of the decode dispatches inside the traced
slice (the engine counts on the device, per expert layer and step, the held
experts with at least one live row) x one expert's bytes
(`benchmark/dsa_flops.py` `expert_bytes`: 3 x hidden x moe_intermediate_size
in bf16, from the configuration file). Time: own time of the `moe_<i>` Mosaic
calls inside those programs. PR 28's kernel at this model's shapes (7168 x
2048 experts, 16 held of 256 routed)."""
NAME, UNIT = "ep_expert_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import dsa_flops, dsa_trace, peaks

    red = dsa_trace.for_ctx(ctx)
    d = red and red["decode"]
    if not d or not d["expert_s"] or not d["experts_hit"]:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return (100.0 * dsa_flops.expert_bytes(ctx["config"], d["experts_hit"])
            / (d["expert_s"] * peak))
