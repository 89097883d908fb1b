"""Nemotron-H / Nemotron 3 hybrid decoder (NVIDIA, HF `modeling_nemotron_h.py`,
`model_type` `nemotron_h`): a stack in which every layer is ONE mixer under
one pre-norm, chosen by a pattern string, not an attention + FFN pair.

    h <- h + mixer_c(RMSNorm(h))        c = pattern[i]

  * `M`: a Mamba-2 mixer (ops/mamba.py): in-projection to [z | xBC | dt], a
    causal depthwise conv of width 4 and silu over xBC, the selective
    recurrence `H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T`, `y_t = H_t C_t +
    D x_t` over `mamba_heads` heads of `mamba_head_dim` (B and C shared by
    `n_groups` groups of heads, `state_size` columns), a gated group RMSNorm
    and the out-projection.
  * `*`: causal grouped-query attention (ops/attention.py), no bias. The
    family's code applies NO rotary and no other position signal in these
    layers (the state-space layers carry the order); `rope=True` is the one
    argument that would change that.
  * `E`: a mixture of relu^2 experts (ops/moe.py): sigmoid scores in float32,
    the `experts_per_token` largest of score + bias chosen, gates the scores
    of the chosen renormalised and times `routed_scaling`; with `latent_dim`
    the experts work in that narrower space between two shared projections
    (LatentMoE); a relu^2 shared expert on the full width beside them.

(Nemotron-H's fourth pattern character, `-`, a dense relu^2 MLP, is not
built: Nemotron 3 has none.)

`logits = RMSNorm(h) W_head`, the head untied. `experts_held=(first, count)`
builds one chip's share of the expert layers. Not built: the multi-token-
prediction module (`num_nextn_predict_layers`), a draft head on which no
logit of the served model depends.
"""

from __future__ import annotations

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel

# the first 22 layers of Nemotron-3-Super-120B-A12B's published 88
PATTERN_22 = "MEMEMEM*EMEMEMEM*EMEME"


def nemotron_h_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
                  hidden: int = 4096, pattern: str = PATTERN_22,
                  heads: int = 32, kv_heads: int = 2,
                  mamba_heads: int = 128, mamba_head_dim: int = 64,
                  n_groups: int = 8, state_size: int = 128,
                  conv_kernel: int = 4, chunk_size: int = 128,
                  num_experts: int = 512, experts_per_token: int = 22,
                  expert_hidden: int = 2688, latent_dim: int = 1024,
                  shared_hidden: int = 5376, n_group: int = 1,
                  topk_group: int = 1, routed_scaling: float = 5.0,
                  norm_topk_prob: bool = True, experts_held=None,
                  score_bias_std: float = 0.0, aux_loss_weight: float = 0.0,
                  vocab_size: int = 131072,
                  rope: bool = False, rope_theta: float = 10000.0,
                  rms_norm_eps: float = 1e-5):
    """Decoder-only causal LM in the Nemotron-H shape; the defaults are
    Nemotron-3-Super-120B-A12B's published widths over the first 22 layers of
    its pattern. Layer i's ops are `norm_{i}`, then `mamba_{i}`, `attn_{i}` or
    `moe_{i}` by its pattern character, then `res_{i}`. `score_bias_std` shapes the SEEDED draw of the router's
    selection bias only (a checkpoint trains it from zero)."""
    if not pattern or set(pattern) - set("M*E"):
        raise ValueError(f"pattern {pattern!r}: a non-empty string of "
                         f"M, * and E")
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    for i, c in enumerate(pattern):
        a = ff.rms_norm(t, eps=rms_norm_eps, name=f"norm_{i}")
        if c == "M":
            m = ff.mamba2(a, mamba_heads, mamba_head_dim, n_groups,
                          state_size, conv_kernel=conv_kernel,
                          chunk_size=chunk_size, eps=rms_norm_eps,
                          name=f"mamba_{i}")
        elif c == "*":
            m = ff.multihead_attention(
                a, a, a, hidden, heads, causal=True, bias=False,
                num_kv_heads=kv_heads, rope=rope, rope_theta=rope_theta,
                name=f"attn_{i}")
        else:
            m = ff.moe(a, num_experts=num_experts, hidden_dim=expert_hidden,
                       k=experts_per_token, capacity_factor=None,
                       expert="relu2", renormalize=norm_topk_prob,
                       scoring="sigmoid", score_bias=score_bias_std,
                       n_group=n_group, topk_group=topk_group,
                       routed_scaling=routed_scaling,
                       shared_hidden_dim=shared_hidden,
                       experts_held=experts_held, latent_dim=latent_dim,
                       aux_weight=aux_loss_weight, name=f"moe_{i}")
        t = ff.add(t, m, name=f"res_{i}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="norm_f")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    return tokens, logits
