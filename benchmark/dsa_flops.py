"""Operations and bytes of DeepSeek-V3.2's sparse latent attention and of its
held experts, from a configuration file: the yardsticks of
`dsa_index_hbm_share`, `mla_core_roofline_share` and `ep_expert_hbm_share`.
Computed from the published sizes, never from the program's counters of its
own work; the token and expert COUNTS they are given are facts of the traffic
(contexts, picks), which the engine's spans carry.

A configuration is the dict of a `benchmark/configs/*.json` file with the
source's keys (`kv_lora_rank`, `qk_rope_head_dim`, `index_head_dim`,
`index_topk`, `num_attention_heads`, `hidden_size`, `moe_intermediate_size`).
"""

BYTES = 2       # bf16, the precision the configuration states


def index_bytes(cfg, context_tokens: int) -> float:
    """Least HBM traffic of the lightning indexer for `context_tokens` cached
    tokens seen (summed over live rows, steps and layers): each token's
    index key once. The queries (64 x 128 a row) are negligible beside a
    context of thousands of keys."""
    return float(context_tokens) * cfg["index_head_dim"] * BYTES


def selected(cfg, context: int) -> int:
    """Tokens the attention core of one row may see of a `context`."""
    return min(int(context), int(cfg["index_topk"]))


def selected_total(cfg, context_tokens: int, rows: int) -> int:
    """Tokens the cores of `rows` (live row, step, layer) triples may see of
    contexts that sum to `context_tokens`: min(sum of contexts, rows x
    index_topk). That is the sum of `selected` over the rows when every
    context lies on one side of index_topk (in `dsa-docqa-saturated` each is
    over 16 k), and an upper bound of it otherwise; the engine's own
    `dsa_selected_tokens` is the exact sum, and a test holds the two
    together."""
    return min(int(context_tokens), int(rows) * int(cfg["index_topk"]))


def core_bytes(cfg, selected_tokens: int) -> float:
    """Least HBM traffic of the attention core for `selected_tokens` (summed
    over live rows, steps and layers): each selected token's latent row
    (cKV and the rotary key, unpadded) once; one row serves all heads."""
    return float(selected_tokens) * (cfg["kv_lora_rank"]
                                     + cfg["qk_rope_head_dim"]) * BYTES


def core_flops(cfg, selected_tokens: int) -> float:
    """FLOPs of the absorbed attention core for `selected_tokens`: per head
    and token a (c + d_R)-wide score and a c-wide weighted sum, 2 FLOPs a
    multiply-add."""
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * selected_tokens * cfg["num_attention_heads"] * (c + r + c)


def core_bound_s(cfg, selected_tokens: int, peak) -> float:
    """The roofline of the core's USEFUL work: the larger of its bytes over
    the HBM peak and its FLOPs over the bf16 peak."""
    return max(core_bytes(cfg, selected_tokens) / peak["hbm_bytes_per_s"],
               core_flops(cfg, selected_tokens) / peak["bf16_flops"])


def expert_params(cfg) -> int:
    """Parameters of one routed expert: gate, up and down projection."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg, experts_hit: int) -> float:
    """Least HBM traffic of the held experts in decode: each expert with at
    least one live row (summed over layers and steps) streams its three
    matrices once; a few rows per expert are negligible beside them."""
    return float(experts_hit) * expert_params(cfg) * BYTES


def cache_bytes_per_token(cfg, lanes: int = 128) -> dict:
    """Bytes one token takes in one layer's pools: the latent row as
    published (c + d_R), as stored (rounded up to whole 128-lane tiles) and
    the index key."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    stored = -(-row // lanes) * lanes
    return {"latent": row * BYTES, "latent_stored": stored * BYTES,
            "index_key": cfg["index_head_dim"] * BYTES,
            "stored": (stored + cfg["index_head_dim"]) * BYTES}


def layer_params(cfg) -> dict:
    """Parameters by part: one attention layer (MLA and indexer), the dense
    feed-forward, one expert layer as THIS configuration holds it
    (`n_routed_experts` held experts, the router over `router_experts`)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    mla = (h * rq + rq * heads * (dn + dr) + h * (c + dr)
           + c * heads * (dn + dv) + heads * dv * h)
    indexer = (rq * cfg["index_n_heads"] * cfg["index_head_dim"]
               + h * cfg["index_head_dim"] + h * cfg["index_n_heads"])
    shared = cfg["n_shared_experts"] * expert_params(cfg)
    router = h * cfg.get("router_experts", cfg["n_routed_experts"])
    return {"mla": mla, "indexer": indexer, "attention": mla + indexer,
            "dense_ffn": 3 * h * cfg["intermediate_size"],
            "expert_layer_ffn": shared + router
            + cfg["n_routed_experts"] * expert_params(cfg)}


def model_params(cfg) -> int:
    """All matrix parameters at the file's depth: attention in every layer,
    the dense feed-forward in the leading layers, experts in the others,
    the embedding and the untied head (norm scales left out)."""
    p = layer_params(cfg)
    n, d = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (n * p["attention"] + d * p["dense_ffn"]
            + (n - d) * p["expert_layer_ffn"]
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])
