"""K-EXAONE decoder (LG AI Research, HF `LGAI-EXAONE/K-EXAONE-236B-A23B`
config.json, `model_type` `exaone_moe`; the family's hybrid attention as
EXAONE 4.0 describes it): a pre-norm block whose attention is, by
`layer_types`, a WINDOW layer (rotary, the last `sliding_window` keys) or a
GLOBAL one (every earlier key, no position signal), a leading dense SwiGLU
layer, then layers of sigmoid-routed experts beside a shared expert.

    a = RMSNorm(h)                                        (eps 1e-5)
    q = a Wq (64 heads of 128), k = a Wk, v = a Wv (8 heads of 128), no bias
    q, k = RMSNorm over each head's 128 entries, a learned 128-vector each
    window layer:  q, k = rope(q), rope(k) (rotate-half, theta 1e6);
                   position i sees keys max(0, i - 127) .. i
    global layer:  no rotary; position i sees keys 0 .. i
    h += softmax(q k^T * 128^-0.5) v Wo
    m = RMSNorm(h)
    layer 0:   h += (silu(m Wgate) * m Wup) Wdown                 (18432)
    layer >=1: s = sigmoid(m Wr) in f32 (128);  T = top-8 of s + b
               g_e = 2.5 * s_e / sum_{e' in T} s_e'   (gates from s, never s + b)
               h += SwiGLU_shared(m) + sum_{e in T, e held here} g_e SwiGLU_e(m)
    logits = RMSNorm(h) Whead

`experts_held=(first, count)` builds one chip's share of the expert layers
(the router keeps its full width: ops/moe.py). Assumed, where config.json
does not say: the norms' place (pre-norm), the per-head QK norm, no rotary
on the global layers, the router's selection bias `b`. Not built: the
multi-token-prediction layer (`num_nextn_predict_layers`), a draft head on
which no logit of the served model depends.
"""

from __future__ import annotations

from typing import Optional, Sequence

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.models.llama import swiglu

PATTERN = ("sliding_attention", "sliding_attention", "sliding_attention",
           "full_attention")


def exaone_moe_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
                  hidden: int = 6144, layers: int = 48, heads: int = 64,
                  kv_heads: int = 8, head_dim: int = 128,
                  layer_types: Optional[Sequence[str]] = None,
                  sliding_windows: Optional[Sequence[int]] = None,
                  mlp_layer_types: Optional[Sequence[str]] = None,
                  sliding_window: int = 128, ffn_hidden: int = 18432,
                  num_experts: int = 128, experts_per_token: int = 8,
                  expert_hidden: int = 2048, shared_experts: int = 1,
                  routed_scaling: float = 2.5, norm_topk_prob: bool = True,
                  experts_held=None, score_bias_std: float = 0.0,
                  vocab_size: int = 153600, rope_theta: float = 1e6,
                  rms_norm_eps: float = 1e-5, flash_chunks: bool = True):
    """Decoder-only causal LM in the K-EXAONE shape; the defaults are the
    published sizes. Layer i's attention op is `attn_window_{i}` or
    `attn_global_{i}` by `layer_types[i]` (default: the `LLLG` pattern
    repeated), its window `sliding_windows[i]` (default `sliding_window`);
    its feed-forward `ffn_*_{i}` where `mlp_layer_types[i]` is "dense"
    (default: layer 0), else `moe_{i}`. `score_bias_std` shapes the SEEDED
    draw of the router's selection bias only (a checkpoint trains it from
    zero): loaded weights ignore it."""
    layer_types = list(layer_types or
                       [PATTERN[i % len(PATTERN)] for i in range(layers)])
    attention = dict(
        kdim=heads * head_dim, vdim=heads * head_dim, num_kv_heads=kv_heads,
        rope_theta=rope_theta, qk_norm="head")
    return window_global_lm(
        ff, batch_size, seq_len, hidden, heads, layer_types,
        sliding_windows or [sliding_window if t == "sliding_attention" else 0
                            for t in layer_types],
        mlp_layer_types or ["dense"] + ["sparse"] * (layers - 1),
        attention={"sliding_attention": dict(attention, rope=True),
                   "full_attention": dict(attention, rope=False)},
        ffn_hidden=ffn_hidden,
        moe=dict(num_experts=num_experts, hidden_dim=expert_hidden,
                 k=experts_per_token, renormalize=norm_topk_prob,
                 score_bias=score_bias_std, routed_scaling=routed_scaling,
                 shared_hidden_dim=shared_experts * expert_hidden,
                 experts_held=experts_held),
        vocab_size=vocab_size, rms_norm_eps=rms_norm_eps,
        flash_chunks=flash_chunks)


def window_global_lm(ff: FFModel, batch_size: int, seq_len: int, hidden: int,
                     heads: int, layer_types: Sequence[str],
                     sliding_windows: Sequence[int],
                     mlp_layer_types: Sequence[str], attention: dict,
                     ffn_hidden: int, moe: dict, vocab_size: int,
                     rms_norm_eps: float = 1e-5, flash_chunks: bool = True):
    """The walk this family of decoders shares (K-EXAONE above,
    models/mimo_v2.py): pre-norm blocks whose attention is by
    `layer_types[i]` a window layer (`attn_window_{i}`, the last
    `sliding_windows[i]` keys) or a global one (`attn_global_{i}`), each
    built with `attention[kind]`, the arguments of `ff.multihead_attention`
    that depend on the layer's kind (KV heads, key and value widths, rotary
    and its base, a QK norm, a sink, a value scale); a dense SwiGLU
    (`ffn_*_{i}`) where `mlp_layer_types[i]` is "dense", else sigmoid-routed
    dropless SwiGLU experts `moe_{i}` built with `moe`; a final norm and an
    untied head."""
    layer_types, mlp_layer_types = list(layer_types), list(mlp_layer_types)
    sliding_windows = list(sliding_windows)
    layers = len(layer_types)
    if not len(mlp_layer_types) == len(sliding_windows) == layers:
        raise ValueError(
            f"layer_types, mlp_layer_types and sliding_windows must each "
            f"name {layers} layers")
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    for i, (kind, window, mlp) in enumerate(
            zip(layer_types, sliding_windows, mlp_layer_types)):
        if kind not in PATTERN or (kind == "sliding_attention") != bool(
                window):
            raise ValueError(
                f"layer {i}: layer_types {kind!r} with window {window}")
        a = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln1_{i}")
        a = ff.multihead_attention(
            a, a, a, hidden, heads, causal=True, bias=False,
            eps=rms_norm_eps, window=int(window), flash_chunks=flash_chunks,
            name=f"attn_window_{i}" if window else f"attn_global_{i}",
            **attention[kind])
        t = ff.add(t, a, name=f"res1_{i}")
        m = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln2_{i}")
        if mlp == "dense":
            f = swiglu(ff, m, hidden, ffn_hidden, i)
        else:
            f = ff.moe(m, capacity_factor=None, expert="swiglu",
                       scoring="sigmoid", name=f"moe_{i}", **moe)
        t = ff.add(t, f, name=f"res2_{i}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="ln_f")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    return tokens, logits
