"""Operations and bytes of a mixture-of-experts layer, from a configuration
file: the yardstick of `moe_expert_hbm_share` and of the prefill side's FLOP
share. Computed from the published sizes, never from the program's counters
of its own work (`flops.py` holds the dense block's; it knows no expert).

A configuration is the dict of a `benchmark/configs/*.json` file whose
`intermediate_size` is the width of ONE expert (OLMoE's reading, stated in
the file) and whose experts are SwiGLU: three matrices of hidden x width.
"""


def expert_params(cfg) -> int:
    """Parameters of one expert: gate, up and down projection."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def moe_bytes(cfg, experts_hit: int, bytes_per_param: float = 2.0) -> float:
    """Least HBM traffic of the grouped matmuls of MoE layers in which
    `experts_hit` experts (summed over layers and steps) have at least one
    row: each such expert's three matrices once. In decode a few rows per
    expert make the rows and results negligible beside them (a row is 4 KB,
    an expert 12.58 MB), so they are not counted: the share reads low, never
    above the truth."""
    return experts_hit * expert_params(cfg) * bytes_per_param


def moe_flops(cfg, assignments: int) -> float:
    """Forward FLOPs of the grouped matmuls for `assignments` (token,
    expert) pairs: three matmuls of hidden x width, 2 FLOPs a
    multiply-add."""
    return 2.0 * assignments * expert_params(cfg)


def layer_params(cfg) -> dict:
    """Parameters of one OLMoE layer by part, and their sum."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d = cfg.get("head_dim") or h // heads
    kv = cfg.get("num_key_value_heads") or heads
    parts = {
        "attention": 2 * h * heads * d + 2 * h * kv * d,
        "qk_norm": heads * d + kv * d,
        "router": h * cfg["num_experts"],
        "experts": cfg["num_experts"] * expert_params(cfg),
        "norms": 2 * h,
    }
    return {**parts, "total": sum(parts.values())}


def model_params(cfg, layers=None) -> int:
    """All parameters at `layers` deep (default: the file's depth): the
    layers, the embedding, the untied head, the last norm."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    table = cfg["vocab_size"] * cfg["hidden_size"]
    head = 0 if cfg.get("tie_word_embeddings") else table
    return (n * layer_params(cfg)["total"] + table + head
            + cfg["hidden_size"])
