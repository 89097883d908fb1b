"""A traced run's counts for the `sink_*` readers: what the decode programs
that ran WHOLLY inside the traced window were asked to read on each kind of
layer (`exaone_trace.reduce_window`, used as it is: the `ff.decode_dispatch`
span's `context_tokens_global` / `context_tokens_window`), beside
`scope_reduce`'s device seconds of the same programs under the op names
`attn_global` / `attn_window`, priced by `mimo_flops`' PUBLISHED bytes a
token (a kind of layer has its own KV head count, and keys are wider than
values).

A trace without `ff.engine_step`, a program whose decode spans carry no such
count, a configuration that is not MiMo-V2's or a run that was not traced
gives None, and the readers leave their metrics out.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import exaone_trace  # noqa: E402


def for_ctx(ctx):
    """`exaone_trace.for_ctx`'s reduction of THIS run, or None where the
    configuration has no `hybrid_layer_pattern`."""
    if "hybrid_layer_pattern" not in (ctx.get("config") or {}):
        return None
    return exaone_trace.for_ctx(ctx)


def paged_hbm_share(ctx, kind):
    """Percent of the HBM peak the decode attention of the layers of `kind`
    ("global" | "window") reaches: the keys and values they must read, at the
    published bytes a token, over the paged kernel's own seconds in the same
    programs."""
    from benchmark import mimo_flops, peaks

    red = for_ctx(ctx)
    if not red:
        return None
    tokens = red["counts"]["decode"][f"context_tokens_{kind}"]
    sec = exaone_trace.whole_seconds(red["scopes"], "decode", f"attn_{kind}",
                                     "core")
    if not tokens or not sec:
        return None
    peak = peaks.peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * mimo_flops.paged_bytes(ctx["config"], tokens, kind) \
        / (sec * peak)
