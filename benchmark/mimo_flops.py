"""Parameters, operations and bytes of a MiMo-V2 model (window layers with a
sink and their own KV head count beside global layers, keys wider than
values, a dense SwiGLU layer, sigmoid-routed experts with no shared one),
from a configuration file: the yardstick of the `sink_*` per-layer metrics.
Computed from the PUBLISHED sizes, never from the program's counters of its
own work and never from how the program lays a page out: a pool that pads a
192-wide key row to 256 lanes reads more bytes than are counted here, and so
reads as a LOWER share of the roof, never as more than the roof.

A configuration is the dict of a `benchmark/configs/*.json` file with the
`mimo_v2_flash` keys: `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `swa_num_key_value_heads`, `head_dim`, `v_head_dim`,
`hybrid_layer_pattern` (1 = window), `moe_layer_freq` (0 = dense),
`intermediate_size`, `moe_intermediate_size`, `n_routed_experts` (the experts
HELD), `router_experts` (the router's width; `n_routed_experts` where the
file has none), `sliding_window`, `vocab_size`.
"""

BYTES = 2       # bf16
KINDS = ("global", "window")


def kv_heads(cfg, kind: str) -> int:
    return cfg["swa_num_key_value_heads" if kind == "window"
               else "num_key_value_heads"]


def attention_params(cfg, kind: str) -> int:
    """One layer's q, k, v and output projections, a window layer's sink
    logits and the layer's pre-norm."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    d, dv, kvh = cfg["head_dim"], cfg["v_head_dim"], kv_heads(cfg, kind)
    sink = heads if cfg["add_swa_attention_sink_bias" if kind == "window"
                        else "add_full_attention_sink_bias"] else 0
    return h * heads * d + h * kvh * (d + dv) + heads * dv * h + sink + h


def dense_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] \
        + cfg["hidden_size"]


def expert_params(cfg) -> int:
    """One routed expert: SwiGLU's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg) -> int:
    """What an expert layer holds beside its routed experts: the router and
    its selection bias, the pre-norm. No shared expert."""
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    return cfg["hidden_size"] * routed + routed + cfg["hidden_size"]


def kind_of(cfg, i: int) -> str:
    return "window" if cfg["hybrid_layer_pattern"][i] else "global"


def model_params(cfg, experts=None) -> int:
    """All parameters of the file's layers with `experts` routed experts an
    expert layer (default: the held count). Embedding, untied head and the
    last norm included."""
    n_exp = cfg["n_routed_experts"] if experts is None else experts
    total = 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    for i, sparse in enumerate(cfg["moe_layer_freq"]):
        total += attention_params(cfg, kind_of(cfg, i))
        total += (router_params(cfg) + n_exp * expert_params(cfg)) if sparse \
            else dense_params(cfg)
    return total


def layers_of(cfg) -> dict:
    """{"window": the layers with a window, "global": those without}."""
    n = sum(1 for p in cfg["hybrid_layer_pattern"] if p)
    return {"window": n, "global": len(cfg["hybrid_layer_pattern"]) - n}


def cache_bytes_per_token(cfg, kind: str, key_lanes=None) -> int:
    """Keys and values of one token in ONE layer of `kind`, bf16, at the
    PUBLISHED widths: KV heads x (head_dim + v_head_dim) x 2. `key_lanes`
    prices a pool that stores a key row at another width (256 where the
    device pads 192 to whole lane tiles): the arithmetic of the
    configuration's memory, never of a roofline share."""
    d = cfg["head_dim"] if key_lanes is None else key_lanes
    return kv_heads(cfg, kind) * (d + cfg["v_head_dim"]) * BYTES


def paged_bytes(cfg, context_tokens: float, kind: str) -> float:
    """Least HBM traffic of the decode attention of the layers of `kind`
    whose steps read `context_tokens` keys in all (ONE layer's count, summed
    over live slots and steps: min(context, window) on a window layer): each
    key and value once, at the published widths, in every layer of the kind.
    One query row a slot makes everything else negligible."""
    return context_tokens * cache_bytes_per_token(cfg, kind) \
        * layers_of(cfg)[kind]


def resident_bytes(cfg, document_tokens, page_size: int,
                   key_lanes=None) -> int:
    """What the prefix cache holds of resident documents of
    `document_tokens` tokens each: every token on the global layers, and ONE
    page a window layer a document (ceil(window / page) pages)."""
    n = layers_of(cfg)
    snap = -(-cfg["sliding_window"] // page_size) * page_size
    return sum(
        t * cache_bytes_per_token(cfg, "global", key_lanes) * n["global"]
        + snap * cache_bytes_per_token(cfg, "window", key_lanes) * n["window"]
        for t in document_tokens)
