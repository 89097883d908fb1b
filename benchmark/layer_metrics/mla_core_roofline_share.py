"""The attention core's USEFUL work as a share of its roofline in decode.
Useful: per live row, step and layer only the min(context, index_topk) tokens
the selection keeps (`benchmark/dsa_flops.py` `selected_total`, from the
configuration file's index_topk and depth and the live rows, steps and
context tokens of the slice's `ff.decode_dispatch` spans), each one latent row of 576 bf16 values read once and 128 heads x
(576 + 512) multiply-adds (`benchmark/dsa_flops.py` `core_bound_s`: the larger
of bytes / HBM peak and FLOPs / bf16 peak; at these widths the two bounds are
equal to 1 %). Time: own time of the `mla_paged_core` kernel inside those
programs. A lowering that streams every live page under the mask reads near
index_topk / context of one that touched only the selected rows: that gap is
the next `perf_opt`'s."""
NAME, UNIT = "mla_core_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import dsa_flops, dsa_trace, peaks

    red = dsa_trace.for_ctx(ctx)
    d = red and red["decode"]
    if not d or not d["core_s"] or not d["dsa_context_tokens"] \
            or not d["row_steps"]:
        return None
    cfg = ctx["config"]
    kept = dsa_flops.selected_total(
        cfg, d["dsa_context_tokens"],
        d["row_steps"] * cfg["num_hidden_layers"])
    bound = dsa_flops.core_bound_s(cfg, kept,
                                   peaks.peaks_for(ctx["device_kind"]))
    return 100.0 * bound / d["core_s"]
