"""Share of the chip's busy time that prefill programs take: device seconds of
the programs whose jitted function says prefill (the device plane's
per-program line, benchmark/span_reduce.py) over the busy seconds of the
traced slice. Run-to-completion admission stalls every decoding slot for
exactly this time, so the time per output token is the idle engine's step /
(1 - this share); chunk-interleaved admission (ROADMAP D4) and a faster
prefill both show here first. 0 where the slice holds no prefill."""
NAME, UNIT = "prefill_device_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "serve_tokens_per_s", "device_trace"


def read(ctx):
    from benchmark import span_reduce

    red = span_reduce.for_ctx(ctx)
    if not red or red["device_by_kind"] is None or not red["busy_s"]:
        return None
    return 100.0 * red["device_by_kind"].get("prefill", 0.0) / red["busy_s"]
