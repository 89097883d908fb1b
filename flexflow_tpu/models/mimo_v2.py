"""MiMo-V2-Flash decoder (Xiaomi, HF `XiaomiMiMo/MiMo-V2-Flash` config.json):
the window / global walk of `models/exaone_moe.py` with attention arguments
that depend on the layer's kind.

    a = RMSNorm(h)                                        (eps 1e-5)
    q = a Wq (64 heads of 192), k = a Wk (KVH_t of 192),
    v = 0.707 a Wv (KVH_t of 128), no bias, no QK norm
        KVH_t = 4 on a global layer, 8 on a window layer
    q, k: rotary (rotate-half) over the first 64 entries of each head, the
        other 128 as they are; base 5e6 on a global layer, 1e4 on a window one
    s_ij = q_i k_j / sqrt(192); global: j <= i; window: i - 127 <= j <= i
    window layers only: a learned sink b_h a query head,
        p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))
    h += (sum_j p_ij v_j) Wo                              (64 x 128 -> 4096)
    m = RMSNorm(h)
    layer 0:   h += (silu(m Wgate) * m Wup) Wdown                 (16384)
    layer >=1: s = sigmoid(m Wr) in f32 (256);  T = top-8 of s + b
               g_e = s_e / sum_{e' in T} s_e'   (gates from s, never s + b)
               h += sum_{e in T, e held here} g_e SwiGLU_e(m)     (2048 each)
    logits = RMSNorm(h) Whead

`experts_held=(first, count)` builds one chip's share of the expert layers
(the router keeps its full width: ops/moe.py). Assumed, where config.json
does not say: the norms' place (pre-norm), no QK norm, the rotary entries
(the leading ones, rotate-half within them), the router's selection bias
`b`, the window as what masks (`attention_chunk_size` is not read). Not
built: the three multi-token-prediction layers, draft heads on which no logit
of the served model depends.
"""

from __future__ import annotations

from typing import Optional, Sequence

from flexflow_tpu.model import FFModel
from flexflow_tpu.models.exaone_moe import window_global_lm

# `hybrid_layer_pattern`: 1 = a window layer, 0 = a global one; after the
# leading global layer every six layers hold five window layers and a global
PERIOD = (1, 1, 1, 1, 0, 1)


def hybrid_layer_pattern(layers: int):
    """The published pattern's first `layers` entries: G at 0, 5, 11, ..."""
    return [0] + [PERIOD[(i - 1) % len(PERIOD)] for i in range(1, layers)]


def mimo_v2_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
               hidden: int = 4096, layers: int = 48, heads: int = 64,
               kv_heads: int = 4, swa_kv_heads: int = 8, head_dim: int = 192,
               v_head_dim: int = 128, rope_dim: int = 64,
               hybrid_pattern: Optional[Sequence[int]] = None,
               moe_layer_freq: Optional[Sequence[int]] = None,
               sliding_window: int = 128, ffn_hidden: int = 16384,
               num_experts: int = 256, experts_per_token: int = 8,
               expert_hidden: int = 2048, norm_topk_prob: bool = True,
               routed_scaling: float = 1.0, experts_held=None,
               score_bias_std: float = 0.0, vocab_size: int = 152576,
               rope_theta: float = 5e6, swa_rope_theta: float = 1e4,
               value_scale: float = 0.707, swa_sink: Optional[float] = 1.0,
               full_sink: Optional[float] = None,
               rms_norm_eps: float = 1e-5, flash_chunks: bool = True):
    """Decoder-only causal LM in the MiMo-V2-Flash shape; the defaults are
    the published sizes. Layer i is `attn_window_{i}` where
    `hybrid_pattern[i]` is 1, else `attn_global_{i}`; its feed-forward
    `ffn_*_{i}` where `moe_layer_freq[i]` is 0 (default: layer 0), else
    `moe_{i}`. `swa_sink` / `full_sink`: the standard deviation of the SEEDED
    draw of each kind's sink logits (a checkpoint's values replace it), None
    = that kind has no sink. `score_bias_std` shapes the seeded draw of the
    router's selection bias only."""
    pattern = list(hybrid_pattern if hybrid_pattern is not None
                   else hybrid_layer_pattern(layers))
    freq = list(moe_layer_freq if moe_layer_freq is not None
                else [0] + [1] * (layers - 1))

    def kind(kvh, theta, sink):
        return dict(kdim=heads * head_dim, vdim=heads * v_head_dim,
                    num_kv_heads=kvh, rope=True, rope_theta=theta,
                    rope_dim=rope_dim, sink=sink, value_scale=value_scale)

    return window_global_lm(
        ff, batch_size, seq_len, hidden, heads,
        ["sliding_attention" if p else "full_attention" for p in pattern],
        [sliding_window if p else 0 for p in pattern],
        ["sparse" if f else "dense" for f in freq],
        attention={
            "sliding_attention": kind(swa_kv_heads, swa_rope_theta, swa_sink),
            "full_attention": kind(kv_heads, rope_theta, full_sink)},
        ffn_hidden=ffn_hidden,
        moe=dict(num_experts=num_experts, hidden_dim=expert_hidden,
                 k=experts_per_token, renormalize=norm_topk_prob,
                 score_bias=score_bias_std, routed_scaling=routed_scaling,
                 experts_held=experts_held),
        vocab_size=vocab_size, rms_norm_eps=rms_norm_eps,
        flash_chunks=flash_chunks)
