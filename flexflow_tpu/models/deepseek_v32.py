"""DeepSeek-V3.2 decoder (DeepSeek-V2 and -V3 reports for the latent attention
and the router, the V3.2-Exp report for the lightning indexer; HF
`deepseek-ai/DeepSeek-V3.2` config.json): a pre-norm block whose attention
caches one latent row a token and attends a learned top-k of them, a leading
dense SwiGLU layer, then layers of sigmoid-routed experts beside a shared
expert.

`a = RMSNorm(h)` (eps 1e-6). Attention (every layer; ops/mla.py):

    cQ = RMSNorm(a W_DQ)                      # q_lora_rank 1536
    q_i = [ (cQ W_UQ)_i^nope (128) ; RoPE((cQ W_UQ)_i^rope) (64) ]      i = 1..128
    [cKV ; kR] = a W_DKV                      # 512 + 64;  cKV = RMSNorm(cKV);  kR = RoPE(kR), ONE key for all heads
    k_{s,i} = [ cKV_s W_UK,i (128) ; kR_s ]   v_{s,i} = cKV_s W_UV,i (128)
    indexer:  qI_j = (cQ W_IQ)_j  (j = 1..64, 128 wide, RoPE on the first 64),
              kI = LayerNorm(a W_IK) (128, RoPE on the first 64),
              w = a W_Iw * 64^-0.5 * 128^-0.5  (64)
              I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s)            for s <= t
    S_t = the 2048 positions s <= t of largest I_{t,s}  (all of them while t < 2048)
    o_{t,i} = sum_{s in S_t} softmax_{s in S_t}( q_{t,i} . k_{s,i} * 192^-0.5 * m^2 ) v_{s,i},   m = 0.1 ln 40 + 1
    h += [o_1 .. o_128] W_O

RoPE is YaRN's (`rope_theta` 1e4, factor 40, original 4096, `beta_fast` 32,
`beta_slow` 1; `mscale` = `mscale_all_dim`, so cos and sin carry no factor and
the `m^2` above is all of it). Cached per token and layer: `(cKV, kR)` = 576
values and `kI` = 128 values. Decode absorbs `W_UK` into the query and `W_UV`
into the output (the same numbers up to rounding); `predict` expands.

Feed-forward: the first `first_k_dense_replace` layers are SwiGLU of width
`intermediate_size` (18432); every later layer, with `m = RMSNorm(h)`
(ops/moe.py):

    s = sigmoid(m W_r) in f32 (256);  s' = s + b            # b: e_score_correction_bias, selection only
    group g's score = its two largest s' summed (8 groups of 32);  keep the 4 best groups;  T = top-8 of s' inside them
    g_e = 2.5 * s_e / sum_{e' in T} s_e'                      # gates from s, never from s'
    h += SwiGLU_shared(m) + sum_{e in T, e held here} g_e SwiGLU_e(m)     # widths 2048

`logits = RMSNorm(h) W_head`. `experts_held=(first, count)` builds one chip's
share of the expert layers (the router keeps its full width). Not built: the
multi-token-prediction module (`num_nextn_predict_layers`), a draft head on
which no logit of the served model depends.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel
from flexflow_tpu.models.llama import swiglu

YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 1.0}


def deepseek_v32_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
                    hidden: int = 7168, layers: int = 61, heads: int = 128,
                    q_lora_rank: Optional[int] = 1536,
                    kv_lora_rank: int = 512,
                    qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                    v_head_dim: int = 128,
                    index_n_heads: Optional[int] = 64,
                    index_head_dim: Optional[int] = 128,
                    index_topk: Optional[int] = 2048,
                    dense_layers: int = 3, ffn_hidden: int = 18432,
                    num_experts: int = 256, experts_per_token: int = 8,
                    expert_hidden: int = 2048, shared_experts: int = 1,
                    n_group: int = 8, topk_group: int = 4,
                    routed_scaling: float = 2.5, norm_topk_prob: bool = True,
                    experts_held=None, score_bias_std: float = 0.0,
                    uq_init_gain: float = 1.0,
                    aux_loss_weight: float = 1e-2,
                    vocab_size: int = 129280,
                    rope_theta: float = 10000.0,
                    rope_scaling: Optional[dict] = YARN,
                    rms_norm_eps: float = 1e-6):
    """Decoder-only causal LM in the DeepSeek-V3.2 shape; the defaults are
    the published sizes. Op names follow `llama_lm` (`attn_{i}`, `ln1_{i}`,
    `ln2_{i}`, `ffn_*_{i}` in the dense layers), with `moe_{i}` in the
    expert layers. `score_bias_std` and `uq_init_gain` shape the SEEDED
    draw only (the router's selection bias, which a checkpoint trains from
    zero, and the width of W_UQ): loaded weights ignore them.
    `aux_loss_weight` scales the balancing term `fit()` adds to the loss.
    `q_lora_rank=None` (queries projected from `a` directly) and
    `index_topk=None` (no indexer: plain causal latent attention) build the
    DeepSeek-V3 family's members without them (models/kanana2.py)."""
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    for i in range(layers):
        a = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln1_{i}")
        a = ff.latent_attention(
            a, hidden, heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
            qk_rope_head_dim, v_head_dim, index_n_heads, index_head_dim,
            index_topk, rope_theta=rope_theta, rope_scaling=rope_scaling,
            eps=rms_norm_eps, uq_init_gain=uq_init_gain, name=f"attn_{i}")
        t = ff.add(t, a, name=f"res1_{i}")
        m = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln2_{i}")
        if i < dense_layers:
            f = swiglu(ff, m, hidden, ffn_hidden, i)
        else:
            f = ff.moe(m, num_experts=num_experts, hidden_dim=expert_hidden,
                       k=experts_per_token, capacity_factor=None,
                       expert="swiglu", renormalize=norm_topk_prob,
                       scoring="sigmoid", score_bias=score_bias_std,
                       n_group=n_group, topk_group=topk_group,
                       routed_scaling=routed_scaling,
                       shared_hidden_dim=shared_experts * expert_hidden,
                       experts_held=experts_held,
                       aux_weight=aux_loss_weight, name=f"moe_{i}")
        t = ff.add(t, f, name=f"res2_{i}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="ln_f")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    return tokens, logits
