"""The attention core's USEFUL work as a share of its roofline in decode, the
row gather counted: `mla_core_roofline_share`'s numerator (per live row, step
and layer the min(context, index_topk) tokens the selection keeps, each one
latent row read once and 128 heads x (576 + 512) multiply-adds:
`benchmark/dsa_flops.py` `selected_total`, `core_bound_s`) over the own
seconds of EVERY device op traced under the attention ops' phases `gather`
and `core` (XLA's gather of the selected rows and the `mla_paged_core_gathered`
kernel; benchmark/scope_reduce.py) in the decode programs that ran wholly
inside the traced window, the programs whose dispatches the numerator counts.
`mla_core_roofline_share` times the named kernel alone and reads about 4 x
higher (PERF.md section 5); a gather moved into the kernel moves this one up
and leaves nothing outside it."""
NAME, UNIT = "mla_core_gather_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import dsa_flops, dsa_trace, peaks, scope_reduce

    red = scope_reduce.for_ctx(ctx)
    dsa = dsa_trace.for_ctx(ctx)
    d = dsa and dsa["decode"]
    if not red or not d or not d["dsa_context_tokens"] or not d["row_steps"]:
        return None
    own = sum(sec for (kind, op, phase), sec in red["whole"].items()
              if kind == "decode" and op == "attn"
              and phase in ("gather", "core"))
    if not own:
        return None
    cfg = ctx["config"]
    kept = dsa_flops.selected_total(
        cfg, d["dsa_context_tokens"],
        d["row_steps"] * cfg["num_hidden_layers"])
    bound = dsa_flops.core_bound_s(cfg, kept,
                                   peaks.peaks_for(ctx["device_kind"]))
    return 100.0 * bound / own
