"""OLMoE decoder (Muennighoff et al., arXiv:2409.02060; HF `modeling_olmoe.py`):
`llama_lm`'s pre-norm block with two changes.

  * QK-norm: RMSNorm with a learned scale over the whole q and k
    projections, before the head split and rotary (ops/attention.py
    `qk_norm`).
  * The MLP is a dropless mixture of SwiGLU experts: top-k of the f32
    softmax over all experts, gates NOT renormalised over the chosen k
    (`norm_topk_prob` false), no capacity and no dropped token
    (ops/moe.py, `capacity_factor=None`).

    a = RMSNorm(h);  q = RMSNorm_q(a Wq), k = RMSNorm_k(a Wk), v = a Wv
    h += causal_softmax(rope(q) rope(k)^T / sqrt(d)) v Wo
    m = RMSNorm(h);  p = softmax_f32(m Wr);  S = top-k of p
    h += sum_{e in S} p_e * ((silu(m Wgate_e) * (m Wup_e)) Wdown_e)
    logits = RMSNorm(h) Whead
"""

from __future__ import annotations

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel


def olmoe_lm(ff: FFModel, batch_size: int, seq_len: int = 256,
             hidden: int = 2048, layers: int = 16, heads: int = 16,
             kv_heads: int = 16, num_experts: int = 64,
             experts_per_token: int = 8, expert_hidden: int = 1024,
             vocab_size: int = 50304, rope_theta: float = 10000.0,
             rms_norm_eps: float = 1e-5, norm_topk_prob: bool = False,
             tie_embeddings: bool = False):
    """Decoder-only causal LM in the OLMoE shape; the defaults are
    OLMoE-1B-7B's published sizes. Op names follow `llama_lm` (`attn_{i}`,
    `ln1_{i}`, `ln2_{i}`), with `moe_{i}` in the MLP's place."""
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    for i in range(layers):
        a = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln1_{i}")
        a = ff.multihead_attention(
            a, a, a, hidden, heads, causal=True, bias=False,
            num_kv_heads=kv_heads, rope=True, rope_theta=rope_theta,
            qk_norm=True, eps=rms_norm_eps, name=f"attn_{i}")
        t = ff.add(t, a, name=f"res1_{i}")
        m = ff.moe(ff.rms_norm(t, eps=rms_norm_eps, name=f"ln2_{i}"),
                   num_experts=num_experts, hidden_dim=expert_hidden,
                   k=experts_per_token, capacity_factor=None,
                   expert="swiglu", renormalize=norm_topk_prob,
                   name=f"moe_{i}")
        t = ff.add(t, m, name=f"res2_{i}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="ln_f")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    if tie_embeddings:
        ff.tie_weights("lm_head", "kernel", "tok_embed", "kernel",
                       "transpose")
    return tokens, logits
