"""Brumby decoder (Manifest AI, `model_type` `brumby`; "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239): the Qwen3-shaped block with
EVERY mixer a power-retention layer, so the model keeps one fixed-size state a
layer and sequence and no key or value row.

    h = E[tokens]                                         # untied, no multiplier
    for i in layers:
        h = h + PowerRetention(RMSNorm(h))                # ops/retention.py
        h = h + W_down (silu(g) * u),  [g | u] = RMSNorm(h) W_in
    logits = RMSNorm(h) W_head

The mixer's q and k keep the block's per-head RMSNorm and rotary; its gate is
a bias-free `hidden -> num_key_value_heads` projection through logsigmoid (a
seeded constant beside it sets the decay's range). The MLP is one op
(ops/dense.py `GatedMLP`), so a trace books it to `mlp_<i>`.
"""

from __future__ import annotations

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel


def brumby_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
              hidden: int = 5120, layers: int = 40, heads: int = 40,
              kv_heads: int = 8, head_dim: int = 128,
              ffn_hidden: int = 17408, vocab_size: int = 151936,
              rope_theta: float = 1e6, rms_norm_eps: float = 1e-6,
              chunk_size: int = 128, norm_eps: float = 1e-5,
              decay_floor=(1e-4, 1e-2)):
    """Decoder-only causal LM in the Brumby shape; the defaults are
    Brumby-14B-Base's published config. Layer i's ops are `norm1_{i}`,
    `retention_{i}`, `res1_{i}`, `norm2_{i}`, `mlp_{i}`, `res2_{i}`; the final
    tensor is `lm_head`'s."""
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    for i in range(layers):
        m = ff.power_retention(
            ff.rms_norm(t, eps=rms_norm_eps, name=f"norm1_{i}"), heads,
            kv_heads, head_dim, rope_theta=rope_theta, chunk_size=chunk_size,
            eps=rms_norm_eps, norm_eps=norm_eps, decay_floor=decay_floor,
            name=f"retention_{i}")
        t = ff.add(t, m, name=f"res1_{i}")
        f = ff.gated_mlp(ff.rms_norm(t, eps=rms_norm_eps, name=f"norm2_{i}"),
                         ffn_hidden, name=f"mlp_{i}")
        t = ff.add(t, f, name=f"res2_{i}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="norm_f")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    return tokens, logits
