"""LongCat-Flash decoder (HF `meituan-longcat/LongCat-Flash-Chat` config.json;
the LongCat-Flash technical report and the released modelling code for what
the config does not say): every layer is a DOUBLE block, two latent attentions
and two dense SwiGLU feed-forwards, with ONE expert layer that reads the first
sub-block and is added at the end of the second (shortcut-connected MoE).

With `h` the residual stream and every norm an RMSNorm (eps 1e-5), layer `l`:

    a0 = N_in0(h);   h = h + MLA_0(a0)
    m0 = N_post0(h); s = MoE(m0);            h = h + FFN_0(m0)          # s is NOT added here
    a1 = N_in1(h);   h = h + MLA_1(a1)
    m1 = N_post1(h); h = h + FFN_1(m1) + s                              # the shortcut lands here

`FFN_j(x) = (silu(x W_g) * (x W_u)) W_d`, width 12288 (ops/dense.py
`GatedMLP`: W_in = [W_g | W_u]). `logits = N_f(h) W_head`; embedding and head
untied.

MLA (each of the two, own weights and own cache; ops/mla.py with
`index_topk=None`), `sq = (hidden / q_lora_rank)^0.5 = 2`, `skv = (hidden /
kv_lora_rank)^0.5 = 3.4641` (`mla_scale_q_lora` / `mla_scale_kv_lora`):

    cQ = RMSNorm(a W_DQ) (1536);   [q^nope_i (128) ; q^rope_i (64)] = sq * (cQ W_UQ)_i,   q^rope_i = RoPE(q^rope_i),  i = 1..64
    [cKV (512) ; kR (64)] = a W_DKV;   cKV = skv * RMSNorm(cKV);   kR = RoPE(kR)            # kR is not scaled; ONE key for all heads
    k_{s,i} = [cKV_s W_UK,i ; kR_s],  v_{s,i} = cKV_s W_UV,i
    o_{t,i} = sum_{s<=t} softmax_s(q_{t,i} . k_{s,i} * 192^-0.5) v_{s,i};   MLA = [o_1..o_64] W_O

RoPE theta 1e7 over the 64 rotary dims, no scaling; pairs rotate-half, as
everywhere in this package. Cached a token and attention: `[cKV ; kR]`, 576
values (stored 640 wide), the latent already scaled.

MoE (ops/moe.py with `zero_experts`), `E = 512` SwiGLU experts of 2048, `Z =
256` zero-computation (identity) experts, `k = 12`, `c = 6`:

    p = softmax_{E+Z}(m0 W_r) in float32 (the matmul in float32 too);   T = top-k of p + b        # b (E+Z): selection only
    g_e = c * p_e for e in T                                             # never from p + b, never renormalised
    MoE(m0) = sum_{e in T, e < E} g_e SwiGLU_e(m0)  +  (sum_{e in T, e >= E} g_e) * m0

`experts_held=(first, count)` builds one chip's share of the expert layers:
ITS experts' terms of the first sum plus the whole identity term (computed
where the token lives, once); the router keeps its full width.

Where the expert op stands in `model.ops`: where the equations put it,
straight behind `N_post0` and before `FFN_0`. In the LAST layer that is before
the last attention op although nothing cached reads it; the generation walk
(runtime/generation.py) trims a prefill's tail by DEPENDENCY (an op that no
cached op is downstream of), not by position in the list, so those experts run
on the last row only in a `last_only` prefill and on no row in a `skip_tail`
chunk. Not built: the multi-token-prediction head.
"""

from __future__ import annotations

from typing import Optional

from flexflow_tpu.ffconst import DataType
from flexflow_tpu.model import FFModel


def longcat_flash_lm(ff: FFModel, batch_size: int, seq_len: int = 4096,
                     hidden: int = 6144, layers: int = 28, heads: int = 64,
                     q_lora_rank: int = 1536, kv_lora_rank: int = 512,
                     qk_nope_head_dim: int = 128, qk_rope_head_dim: int = 64,
                     v_head_dim: int = 128, mla_scale_q_lora: bool = True,
                     mla_scale_kv_lora: bool = True, ffn_hidden: int = 12288,
                     num_experts: int = 512, zero_experts: int = 256,
                     experts_per_token: int = 12, expert_hidden: int = 2048,
                     routed_scaling: float = 6.0, experts_held=None,
                     score_bias_std: Optional[float] = 0.0,
                     uq_init_gain: float = 1.0, aux_loss_weight: float = 0.0,
                     vocab_size: int = 131072, rope_theta: float = 1e7,
                     rms_norm_eps: float = 1e-5):
    """Decoder-only causal LM in the LongCat-Flash shape; the defaults are
    LongCat-Flash-Chat's published sizes. Layer `l`'s ops are, for j in 0, 1,
    `ln_in_{l}_{j}`, `attn_{l}_{j}`, `res_attn_{l}_{j}`, `ln_post_{l}_{j}`,
    `ffn_{l}_{j}`, `res_ffn_{l}_{j}`, and `moe_{l}` behind `ln_post_{l}_0`
    with `res_moe_{l}` at the layer's end. `score_bias_std` and
    `uq_init_gain` shape the SEEDED draw only (the router's selection bias,
    which a checkpoint trains from zero, and the width of W_UQ)."""
    tokens = ff.create_tensor([batch_size, seq_len], dtype=DataType.DT_INT32,
                              name="input")
    t = ff.embedding(tokens, vocab_size, hidden, name="tok_embed")
    sq = (hidden / q_lora_rank) ** 0.5 if mla_scale_q_lora else 1.0
    skv = (hidden / kv_lora_rank) ** 0.5 if mla_scale_kv_lora else 1.0
    for l in range(layers):
        shortcut = None
        for j in range(2):
            a = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln_in_{l}_{j}")
            a = ff.latent_attention(
                a, hidden, heads, q_lora_rank, kv_lora_rank,
                qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                rope_theta=rope_theta, eps=rms_norm_eps,
                uq_init_gain=uq_init_gain, q_lora_scale=sq,
                kv_lora_scale=skv, name=f"attn_{l}_{j}")
            t = ff.add(t, a, name=f"res_attn_{l}_{j}")
            m = ff.rms_norm(t, eps=rms_norm_eps, name=f"ln_post_{l}_{j}")
            if j == 0:
                shortcut = ff.moe(
                    m, num_experts=num_experts, hidden_dim=expert_hidden,
                    k=experts_per_token, capacity_factor=None,
                    expert="swiglu", renormalize=False, scoring="softmax",
                    score_bias=score_bias_std,
                    routed_scaling=routed_scaling,
                    experts_held=experts_held, zero_experts=zero_experts,
                    router_f32=True, aux_weight=aux_loss_weight,
                    name=f"moe_{l}")
            f = ff.gated_mlp(m, ffn_hidden, name=f"ffn_{l}_{j}")
            t = ff.add(t, f, name=f"res_ffn_{l}_{j}")
        t = ff.add(t, shortcut, name=f"res_moe_{l}")
    t = ff.rms_norm(t, eps=rms_norm_eps, name="ln_f")
    logits = ff.dense(t, vocab_size, use_bias=False, name="lm_head")
    return tokens, logits
