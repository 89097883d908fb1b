"""Share of the device's busy time spent in custom-call ops, which on this
chip are the Pallas kernels (Mosaic's `tpu_custom_call`: flash forward in
prefill, the paged prefill write, paged attention in decode). Today's trace
names such an op after the jax scope that called it, not after the kernel, so
this is one share for all kernels; a share per kernel and its roofline wait
for stable kernel names (PERF.md section 7). Read where prefill stalls
decoding; in the other cells the kernels show in `breakdown.device_ops`."""
NAME, UNIT = "pallas_time_share", "%"
LAYER, MOVES, SOURCE = "kernels", "serve_tokens_per_s", "device_trace"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * trace["custom_call_s"] / trace["busy_s"]
