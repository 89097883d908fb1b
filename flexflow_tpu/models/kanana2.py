"""Kanana-2-30B-A3B decoder (HF `kakaocorp/kanana-2-30b-a3b-instruct-2601`
config.json, `model_type` deepseek_v3; DeepSeek-V2 / -V3 reports for the
latent attention and the router): `deepseek_v32_lm`'s pre-norm block without
the lightning indexer and without query compression, a leading dense SwiGLU
layer, then layers of sigmoid-routed experts beside two shared experts.

`a = RMSNorm(h)` (eps 1e-6). Attention (every layer; ops/mla.py with
`q_lora_rank=None`, `index_topk=None`):

    q_i = [ (a W_Q)_i^nope (128) ; RoPE((a W_Q)_i^rope) (64) ]           i = 1..32
    [cKV ; kR] = a W_DKV                      # 512 + 64;  cKV = RMSNorm(cKV);  kR = RoPE(kR), ONE key for all heads
    k_{s,i} = [ cKV_s W_UK,i (128) ; kR_s ]   v_{s,i} = cKV_s W_UV,i (128)
    o_{t,i} = sum_{s <= t} softmax_{s <= t}( q_{t,i} . k_{s,i} * 192^-0.5 ) v_{s,i}
    h += [o_1 .. o_32] W_O

RoPE is plain (`rope_theta` 1e6, `rope_scaling` null), pairs rotate-half
(the source's `rope_interleave` pairs neighbours: a fixed permutation of the
64 rotary columns of W_Q and W_DKV, invisible under seeded weights). Keys are
192 wide and values 128: under `fit()` and `predict()` the core is the Pallas
flash kernels at those two widths (ops/pallas_kernels.py).

Feed-forward: the first layer is SwiGLU of width `intermediate_size` (6144);
every later layer, with `m = RMSNorm(h)` (ops/moe.py):

    s = sigmoid(m W_r) in f32 (128);  s' = s + b            # b: e_score_correction_bias, selection only
    T = top-6 of s'                                           # n_group 1, topk_group 1: no group limit
    g_e = 2.448 * s_e / sum_{e' in T} s_e'                    # gates from s, never from s'
    h += SwiGLU_shared(m) + sum_{e in T, e held here} g_e SwiGLU_e(m)
                                                              # experts 768 wide; the 2 shared experts are ONE SwiGLU of 1536

`logits = RMSNorm(h) W_head` (untied). `experts_held=(first, count)` builds
one chip's share of the expert layers (the router keeps its full width).
Training: the loss is the cross-entropy alone (`aux_loss_weight` 0: the
config gives no coefficient for a balancing loss, and `noaux_tc`'s bias
update is a recipe outside the graph: `score_bias` gets no gradient and keeps
its value).

One builder serves both shapes: this function is `deepseek_v32_lm` called
with the arguments that differ, not a second copy of its loop.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.model import FFModel
from flexflow_tpu.models.deepseek_v32 import deepseek_v32_lm


def kanana2_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
               hidden: int = 2048, layers: int = 48, heads: int = 32,
               kv_lora_rank: int = 512, qk_nope_head_dim: int = 128,
               qk_rope_head_dim: int = 64, v_head_dim: int = 128,
               dense_layers: int = 1, ffn_hidden: int = 6144,
               num_experts: int = 128, experts_per_token: int = 6,
               expert_hidden: int = 768, shared_experts: int = 2,
               routed_scaling: float = 2.448, norm_topk_prob: bool = True,
               experts_held=None, score_bias_std: float = 0.0,
               aux_loss_weight: float = 0.0, vocab_size: int = 128256, rope_theta: float = 1e6,
               rope_scaling: Optional[dict] = None,
               rms_norm_eps: float = 1e-6):
    """Decoder-only causal LM in the Kanana-2-30B-A3B shape; the defaults
    are the published sizes. Op and weight names are `deepseek_v32_lm`'s
    (`attn_{i}` holds `w_q` in place of `w_dq`, `q_norm`, `w_uq`, and no
    index weight)."""
    return deepseek_v32_lm(
        ff, batch_size, seq_len=seq_len, hidden=hidden, layers=layers,
        heads=heads, q_lora_rank=None, kv_lora_rank=kv_lora_rank,
        qk_nope_head_dim=qk_nope_head_dim,
        qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
        index_n_heads=None, index_head_dim=None, index_topk=None,
        dense_layers=dense_layers, ffn_hidden=ffn_hidden,
        num_experts=num_experts, experts_per_token=experts_per_token,
        expert_hidden=expert_hidden, shared_experts=shared_experts,
        n_group=1, topk_group=1, routed_scaling=routed_scaling,
        norm_topk_prob=norm_topk_prob, experts_held=experts_held,
        score_bias_std=score_bias_std, aux_loss_weight=aux_loss_weight,
        vocab_size=vocab_size,
        rope_theta=rope_theta, rope_scaling=rope_scaling,
        rms_norm_eps=rms_norm_eps)
