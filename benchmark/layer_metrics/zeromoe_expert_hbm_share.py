"""The held experts' kernel's share of the chip's published HBM bandwidth in
decode, which is its roofline there (as `ep_expert_hbm_share` reckons it): a
step gives a held expert half a row, so the expert-stream kernel is bound by
streaming each HIT expert's three matrices once. Bytes: `experts_hit` of the
decode dispatches wholly inside the traced slice (the engine counts on the
device, per expert layer and step, the held experts with at least one live
row) x one expert's bytes (`benchmark/longcat_flops.py` `expert_bytes`: 3 x
6144 x 2048 in bf16, from the configuration file). Time: own time under the
scopes `moe_<l>` / `experts` inside those programs."""
NAME, UNIT = "zeromoe_expert_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import longcat_flops, longcat_trace, peaks

    red = longcat_trace.for_ctx(ctx)
    if not red or not red["decode"]["experts_hit"]:
        return None
    sec = longcat_trace.whole_seconds(
        red, lambda op, phase: op == "moe" and phase == "experts")
    if not sec:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return (100.0 * longcat_flops.expert_bytes(
        ctx["config"], red["decode"]["experts_hit"]) / (sec * peak))
