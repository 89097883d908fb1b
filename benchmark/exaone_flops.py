"""Parameters, operations and bytes of a K-EXAONE model (window and global
attention layers, a dense SwiGLU layer, sigmoid-routed experts beside a
shared one), from a configuration file: the yardstick of the `swa_*`
per-layer metrics. Computed from the published sizes, never from the
program's counters of its own work.

A configuration is the dict of a `benchmark/configs/*.json` file with the
`exaone_moe` keys: `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `layer_types`, `sliding_windows`,
`mlp_layer_types`, `intermediate_size`, `moe_intermediate_size`,
`num_shared_experts`, `num_experts` (the experts HELD), `router_experts`
(the router's width; `num_experts` where the file has none), `vocab_size`.
"""

BYTES = 2       # bf16


def attention_params(cfg) -> int:
    """One layer's q, k, v and output projections, the two per-head QK norm
    vectors and the layer's pre-norm."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d + 2 * d + h)


def dense_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] \
        + cfg["hidden_size"]


def expert_params(cfg) -> int:
    """One routed expert: SwiGLU's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_shared_params(cfg) -> int:
    """What an expert layer holds beside its routed experts: the router and
    its selection bias, the shared expert, the pre-norm."""
    routed = cfg.get("router_experts", cfg["num_experts"])
    return (cfg["hidden_size"] * routed + routed
            + cfg["num_shared_experts"] * expert_params(cfg)
            + cfg["hidden_size"])


def model_params(cfg, experts=None) -> int:
    """All parameters of the file's layers with `experts` routed experts an
    expert layer (default: the held count). Embedding, untied head and the
    last norm included."""
    n_exp = cfg["num_experts"] if experts is None else experts
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for kind in cfg["mlp_layer_types"]:
        total += attention_params(cfg)
        total += dense_params(cfg) if kind == "dense" \
            else moe_shared_params(cfg) + n_exp * expert_params(cfg)
    return total


def layers_of(cfg) -> dict:
    """{"window": the layers with a window, "global": those without}."""
    n = sum(1 for w in cfg["sliding_windows"] if w)
    return {"window": n, "global": len(cfg["sliding_windows"]) - n}


def window_of(cfg) -> int:
    sizes = {w for w in cfg["sliding_windows"] if w}
    assert len(sizes) == 1, f"one window size expected, the file has {sizes}"
    return sizes.pop()


def cache_bytes_per_token(cfg) -> int:
    """Keys and values of one token in ONE layer, bf16."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def paged_bytes(cfg, context_tokens: float, kind: str) -> float:
    """Least HBM traffic of the decode attention of the layers of `kind`
    ("window" | "global") whose steps read `context_tokens` keys in all (ONE
    layer's count, summed over live slots and steps: min(context, window) on
    a window layer): each key and value once, in every layer of the kind.
    One query row a slot makes everything else negligible."""
    return context_tokens * cache_bytes_per_token(cfg) * layers_of(cfg)[kind]


def seen_pairs(rows: int, window=None) -> int:
    """(query, key) pairs a causal layer scores over a sequence of `rows`
    positions: all earlier keys and itself, or the last `window` of them."""
    if window is None or rows <= window:
        return rows * (rows + 1) // 2
    return window * (window + 1) // 2 + (rows - window) * window


def flash_flops(cfg, rows: int, kind: str) -> float:
    """FLOPs the prefill attention of the layers of `kind` NEEDS for a prompt
    of `rows` positions: Q K^T and P V, 2 x head_dim each a (query, key) pair
    and head, over the pairs the mask lets through. What a kernel computes
    beyond that (the masked half of a diagonal tile, a bucket's padding
    rows) is not counted."""
    window = window_of(cfg) if kind == "window" else None
    return (4.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * seen_pairs(rows, window) * layers_of(cfg)[kind])
