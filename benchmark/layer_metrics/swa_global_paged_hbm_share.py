"""The GLOBAL layers' paged-attention kernel's share of the chip's published
HBM bandwidth in decode, which is its roofline (one query row a slot). Bytes:
for each decode program that ran wholly inside the traced slice its dispatch
span's `context_tokens_global` (the engine's count: every live slot's context
at each of the dispatch's steps) x the keys and values of a token
(benchmark/exaone_flops.py `paged_bytes`: 2 x kv heads x head_dim in bf16,
from the configuration file) x the global layers. Time: own seconds of the
device ops under `attn_global_<i>` / `core` in those programs
(benchmark/scope_reduce.py `whole` rows). The first reading of this kernel at
contexts of 8-32 k."""
NAME, UNIT = "swa_global_paged_hbm_share", "%"
LAYER, MOVES, SOURCE = "kernels", "tpot_p50_s", "device_trace"


def read(ctx):
    from benchmark import exaone_trace

    return exaone_trace.paged_hbm_share(ctx, "global")
