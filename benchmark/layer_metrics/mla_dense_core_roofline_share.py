"""The dense latent core's NECESSARY work as a share of its roofline in decode.
Necessary: what ANY implementation must do for the decode programs wholly
inside the traced slice, the larger of every (slot, live row, head) pair's
2 x (576 + 512) FLOPs over the bf16 peak and each DISTINCT live page's bytes
ONCE over the HBM peak (`benchmark/longcat_flops.py` `core_bound_s`, from the
configuration file's widths; the rows and the distinct pages are the traffic's,
carried by the `ff.decode_dispatch` spans), times the model's attentions (two
a layer). Time: own time of the kernel `mla_paged_core_dense` (scope
`attn_<l>_<j>` / `core`) inside those programs. The first kernel streams a
page once a SLOT, so with 32 slots on 12 documents it reads under half: a
kernel that later streams a shared page once can approach 100 and cannot pass
it."""
NAME, UNIT = "mla_dense_core_roofline_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import longcat_flops, longcat_trace, peaks

    red = longcat_trace.for_ctx(ctx)
    if not red:
        return None
    d, cfg = red["decode"], ctx["config"]
    core_s = longcat_trace.whole_seconds(red, longcat_trace.is_core)
    if not core_s or not d["row_tokens"] or not d["distinct_pages"]:
        return None
    page = ctx["cut"]["engine"]["kv_page_size"]
    bound = longcat_flops.core_bound_s(
        cfg, d["row_tokens"], d["distinct_pages"], page,
        peaks.peaks_for(ctx["device_kind"]))
    attentions = longcat_flops.ATTENTIONS_A_LAYER * cfg["num_layers"]
    return 100.0 * attentions * bound / core_s
