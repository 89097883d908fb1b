"""Operations and bytes of LongCat-Flash's dense latent attention core, of its
held experts and of its parameters, from a configuration file: the yardsticks
of `mla_dense_core_roofline_share` and `zeromoe_expert_hbm_share`, and the
arithmetic of the configuration's cut. Computed from the published sizes,
never from the program's counters of its own work; the row, page and expert
COUNTS they are given are facts of the traffic (contexts, pages held, picks),
which the engine's spans carry.

A configuration is the dict of a `benchmark/configs/*.json` file with the
source's keys (`hidden_size`, `num_layers`, `num_attention_heads`,
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `ffn_hidden_size`, `expert_ffn_hidden_size`, `n_routed_experts`,
`zero_expert_num`, `vocab_size`) and the cut's `router_experts`.
"""

BYTES = 2       # bf16, the precision the configuration states
LANES = 128
ATTENTIONS_A_LAYER = 2      # every layer is a double block


def latent_row_bytes(cfg, stored=True) -> int:
    """Bytes of one cached token of ONE attention: [cKV ; kR], as stored
    (rounded up to whole 128-lane tiles: 640 of 576) or as published."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    if stored:
        row = -(-row // LANES) * LANES
    return row * BYTES


def cache_bytes_per_token(cfg) -> int:
    """Bytes one token takes in the pool: two latent rows a layer."""
    return ATTENTIONS_A_LAYER * cfg["num_layers"] * latent_row_bytes(cfg)


def core_flops(cfg, row_tokens: int) -> float:
    """FLOPs of the absorbed core for `row_tokens` (live row, cached row)
    pairs of ONE attention: per head a (c + d_R)-wide score and a c-wide
    weighted sum, 2 FLOPs a multiply-add."""
    c, r = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return 2.0 * row_tokens * cfg["num_attention_heads"] * (c + r + c)


def core_bytes(cfg, distinct_pages: int, page_size: int) -> float:
    """Least HBM traffic of ONE attention's core for `distinct_pages` live
    pages: each distinct page's rows once, as stored (the padding lanes lie
    between the rows and cannot be skipped by a page copy), however many
    slots hold it."""
    return float(distinct_pages) * page_size * latent_row_bytes(cfg)


def core_bound_s(cfg, row_tokens: int, distinct_pages: int, page_size: int,
                 peak) -> float:
    """The roofline of the work ANY implementation of the core must do: the
    larger of every (slot, live row, head) pair's FLOPs over the bf16 peak
    and each DISTINCT live page's bytes once over the HBM peak."""
    return max(core_flops(cfg, row_tokens) / peak["bf16_flops"],
               core_bytes(cfg, distinct_pages, page_size)
               / peak["hbm_bytes_per_s"])


def expert_params(cfg) -> int:
    """Parameters of one routed expert: gate, up and down projection."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def expert_bytes(cfg, experts_hit: int) -> float:
    """Least HBM traffic of the held experts in decode: each expert with at
    least one live row (summed over layers and steps) streams its three
    matrices once; a few rows per expert are negligible beside them."""
    return float(experts_hit) * expert_params(cfg) * BYTES


def layer_params(cfg) -> dict:
    """Parameters by part of ONE catalog layer (a double block): one latent
    attention, one dense feed-forward, the router over the published width,
    and the experts THIS configuration holds (`n_routed_experts`)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, c = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    mla = (h * rq + rq * heads * (dn + dr) + h * (c + dr)
           + c * heads * (dn + dv) + heads * dv * h)
    router = h * (cfg.get("router_experts", cfg["n_routed_experts"])
                  + cfg["zero_expert_num"])
    dense = 3 * h * cfg["ffn_hidden_size"]
    outside = ATTENTIONS_A_LAYER * (mla + dense) + router
    return {"mla": mla, "dense_ffn": dense, "router": router,
            "outside_experts": outside,
            "experts": cfg["n_routed_experts"] * expert_params(cfg),
            "layer": outside + cfg["n_routed_experts"] * expert_params(cfg)}


def model_params(cfg) -> int:
    """All matrix parameters at the file's depth, experts held and
    vocabulary: the layers, the embedding and the untied head (norm scales
    and the selection bias left out)."""
    return (cfg["num_layers"] * layer_params(cfg)["layer"]
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def published(cfg) -> dict:
    """The configuration with its `published` depth, expert count and
    vocabulary put back: the whole model's sizes."""
    pub = cfg["published"]
    return {**cfg, **pub, "router_experts": pub["n_routed_experts"]}
