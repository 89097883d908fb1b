"""Parameters and operations of Kanana-2's decoder (latent attention without
query compression, a leading dense SwiGLU layer, sigmoid-routed experts
beside shared experts) ON ONE CHIP'S SHARE, from a configuration file: the
yardsticks of `ep_train_mfu` and `mla_flash_roofline_share`. Computed from
the published sizes, never from the program's counters of its own work.
Recomputed operations do not count.

A configuration is the dict of a `benchmark/configs/*.json` file with the
source's keys (`hidden_size`, `num_attention_heads`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `intermediate_size`,
`moe_intermediate_size`, `n_shared_experts`, `num_experts_per_tok`,
`first_k_dense_replace`, `num_hidden_layers`, `vocab_size`) and the cut's
`router_experts` (the router's published width) and `experts_held` (first,
count). The source's `head_dim` (64) is the rotary width and is not read.
"""


def param_counts(cfg) -> dict:
    """Parameters held on this chip: per attention, dense MLP, shared
    experts, router (with its selection bias), one routed expert, each kind
    of layer, embedding and head (the vocabulary slice), and the total."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    c, dn, dr = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"])
    dv = cfg["v_head_dim"]
    attention = (d * h * (dn + dr)          # w_q
                 + d * (c + dr) + c         # w_dkv, kv_norm
                 + c * h * dn + c * h * dv  # w_uk, w_uv
                 + h * dv * d)              # wo
    dense_mlp = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    shared = cfg["n_shared_experts"] * expert
    router = d * cfg["router_experts"] + cfg["router_experts"]
    held = int(cfg["experts_held"][1])
    norms = 2 * d
    dense_layer = attention + dense_mlp + norms
    expert_layer = attention + shared + router + held * expert + norms
    n_dense = cfg["first_k_dense_replace"]
    n_expert = cfg["num_hidden_layers"] - n_dense
    embedding = head = cfg["vocab_size"] * d
    return {"attention": attention, "dense_mlp": dense_mlp, "shared": shared,
            "router": router, "expert": expert, "dense_layer": dense_layer,
            "expert_layer": expert_layer, "embedding": embedding,
            "head": head,
            "total": (n_dense * dense_layer + n_expert * expert_layer
                      + embedding + head + d)}


def forward_flops_per_token(cfg, seq: int) -> dict:
    """FLOPs one token's forward pass needs on this chip at sequence `seq`
    (a causal token sees seq / 2 keys on average), by part and in `total`:
    2 FLOPs a multiply-add; the embedding is a gather."""
    p = param_counts(cfg)
    h = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    layers = cfg["num_hidden_layers"]
    n_dense = cfg["first_k_dense_replace"]
    n_expert = layers - n_dense
    share = cfg["num_experts_per_tok"] * int(cfg["experts_held"][1]) \
        / cfg["router_experts"]         # routed experts a token meets HERE
    parts = {
        "projections": layers * 2 * (p["attention"] - cfg["kv_lora_rank"]),
        "core": layers * 2 * (dqk + cfg["v_head_dim"]) * h * seq / 2,
        "dense_mlp": n_dense * 2 * p["dense_mlp"],
        "shared": n_expert * 2 * p["shared"],
        "routed": n_expert * share * 2 * p["expert"],
        "router": n_expert * 2 * cfg["hidden_size"] * cfg["router_experts"],
        "head": 2 * p["head"],
    }
    parts["total"] = sum(parts.values())
    return parts


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward = 3 x forward (the backward of a matmul is two
    matmuls); nothing recomputed counts."""
    return 3.0 * forward_flops_per_token(cfg, seq)["total"]


def flash_flops(cfg, sequences: int, seq: int) -> dict:
    """FLOPs the causal attention core of ONE layer needs for `sequences`
    sequences of `seq` tokens: `fwd` is Q K^T and P V; `bwd` is dP = dO V^T,
    dV = P^T dO, dQ = dS K and dK = dS^T Q (the backward's recomputed Q K^T
    does not count). A causal core multiplies seq (seq + 1) / 2 (query, key)
    pairs a head."""
    pairs = sequences * cfg["num_attention_heads"] * seq * (seq + 1) / 2
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return {"fwd": 2.0 * pairs * (dqk + dv),
            "bwd": 2.0 * pairs * 2 * (dqk + dv)}
