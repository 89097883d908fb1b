"""Share of the device's busy time, over the traced slice, spent under the
`sampler` scope of the serve programs: the poison and finite check, the warp
of the logits (the sort of the vocabulary, the top-p scan, the top-k cut), the
draw and the argmax (`flexflow_tpu/ops/sampling.py`, booked by
benchmark/scope_reduce.py). The program computes the warp for every row, a
temperature-0 row included. Lower is better at a fixed model: ROADMAP S11's
repairs (a gate for all-greedy steps, a cheaper top-p) show here."""
NAME, UNIT = "sampler_device_share", "%"
LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import scope_reduce

    return scope_reduce.share(
        scope_reduce.for_ctx(ctx),
        lambda kind, op, phase: op == "sampler") or None
