"""Granite 4.0-H hybrid decoder (IBM, HF `modeling_granitemoehybrid.py`,
`model_type` `granitemoehybrid`): every layer is a mixer AND a SwiGLU MLP,
each behind its own RMSNorm and a residual scaled by `residual_multiplier`;
the mixer is a Mamba-2 layer or grouped-query attention by `layer_types`.

    h0 = embedding_multiplier * E[tokens]
    for i in layers:
        a = RMSNorm(h)
        m = Mamba2(a)   if layer_types[i] == "mamba"  else  Attention(a)
        h = h + residual_multiplier * m
        h = h + residual_multiplier * W_out (silu(g) * u),  [g | u] = RMSNorm(h) W_in
    logits = RMSNorm(h) E^T / logits_scaling             # the head IS the embedding

  * `mamba`: ops/mamba.py `Mamba2Mixer` (one group of B / C, conv 4 with bias,
    no projection bias, dt = softplus(dt + dt_bias) unclamped, the gated
    RMSNorm over all of d_inner).
  * `attention`: causal grouped-query attention (ops/attention.py), no bias, NO
    rotary and no other position signal (`position_embedding_type` "nope": the
    state-space layers carry the order), `softmax(attention_multiplier * q k^T)
    v`: the multiplier is the op's `softmax_scale`, not 1 / sqrt(head size).
  * the MLP is one op (ops/dense.py `GatedMLP`), so a trace books it to
    `mlp_<i>`.

The multipliers ride `scalar_multiply` ops (`embed_scale`, `mix_scale_<i>`,
`mlp_scale_<i>`, `logits`), which XLA fuses into their neighbours. The family's
expert branch (`num_local_experts` > 0) is not built: the -H Micro model has
none.
"""

from __future__ import annotations

from typing import Sequence

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel

# granite-4.0-h-micro: attention at layers 5, 15, 25, 35 of 40
LAYER_TYPES_MICRO = tuple(
    "attention" if i % 10 == 5 else "mamba" for i in range(40))


def granite_hybrid_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
                      hidden: int = 2048,
                      layer_types: Sequence[str] = LAYER_TYPES_MICRO,
                      heads: int = 32, kv_heads: int = 8,
                      mamba_heads: int = 64, mamba_head_dim: int = 64,
                      n_groups: int = 1, state_size: int = 128,
                      conv_kernel: int = 4, chunk_size: int = 256,
                      ffn_hidden: int = 8192, vocab_size: int = 100352,
                      embedding_multiplier: float = 12.0,
                      residual_multiplier: float = 0.22,
                      attention_multiplier: float = 0.015625,
                      logits_scaling: float = 8.0,
                      rms_norm_eps: float = 1e-5):
    """Decoder-only causal LM in the Granite 4.0-H shape; the defaults are
    granite-4.0-h-micro's published config. Layer i's ops are `norm1_{i}`,
    `mamba_{i}` or `attn_{i}`, `mix_scale_{i}`, `res1_{i}`, `norm2_{i}`,
    `mlp_{i}`, `mlp_scale_{i}`, `res2_{i}`; the final tensor is `logits`."""
    layer_types = tuple(layer_types)
    if not layer_types or set(layer_types) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {layer_types!r}: a non-empty "
                         f"sequence of 'mamba' and 'attention'")
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    t = ff.scalar_multiply(t, embedding_multiplier, name="embed_scale")
    for i, kind in enumerate(layer_types):
        a = ff.rms_norm(t, eps=rms_norm_eps, name=f"norm1_{i}")
        if kind == "mamba":
            m = ff.mamba2(a, mamba_heads, mamba_head_dim, n_groups,
                          state_size, conv_kernel=conv_kernel,
                          chunk_size=chunk_size, eps=rms_norm_eps,
                          name=f"mamba_{i}")
        else:
            m = ff.multihead_attention(
                a, a, a, hidden, heads, causal=True, bias=False,
                num_kv_heads=kv_heads, rope=False,
                softmax_scale=attention_multiplier, name=f"attn_{i}")
        t = ff.add(t, ff.scalar_multiply(m, residual_multiplier,
                                         name=f"mix_scale_{i}"),
                   name=f"res1_{i}")
        f = ff.gated_mlp(ff.rms_norm(t, eps=rms_norm_eps, name=f"norm2_{i}"),
                         ffn_hidden, name=f"mlp_{i}")
        t = ff.add(t, ff.scalar_multiply(f, residual_multiplier,
                                         name=f"mlp_scale_{i}"),
                   name=f"res2_{i}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="norm_f")
    head = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    ff.tie_weights("lm_head", "kernel", "tok_embed", "kernel", "transpose")
    logits = ff.scalar_multiply(head, 1.0 / logits_scaling, name="logits")
    return tokens, logits
