"""Operations and bytes the algorithm needs, from a configuration file.

The yardstick for `train_mfu` and for any roofline share: computed from the
published sizes, never from the program's own op counters (a PR may change
those). Recomputed operations do not count.

A configuration is the dict of a `benchmark/configs/*.json` file; `layers`
overrides `num_hidden_layers` for a cut that runs another depth.
"""


def _sizes(cfg, layers=None):
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return dict(hidden=cfg["hidden_size"], heads=heads, head_dim=head_dim,
                kv_heads=cfg.get("num_key_value_heads") or heads,
                inter=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"] if layers is None else layers,
                tied=bool(cfg.get("tie_word_embeddings", False)))


def param_counts(cfg, layers=None) -> dict:
    """Parameters of a pre-norm GQA + SwiGLU decoder without biases:
    `matmul` are the weights every token multiplies (projections, MLP, output
    head); the embedding table is a gather and the norms are vectors."""
    z = _sizes(cfg, layers)
    attn = (z["hidden"] * z["heads"] * z["head_dim"] * 2
            + z["hidden"] * z["kv_heads"] * z["head_dim"] * 2)
    mlp = 3 * z["hidden"] * z["inter"]
    head = z["hidden"] * z["vocab"]
    embed = 0 if z["tied"] else z["vocab"] * z["hidden"]
    norms = (2 * z["layers"] + 1) * z["hidden"]
    matmul = z["layers"] * (attn + mlp) + head
    return {"matmul": matmul, "embedding": embed, "norms": norms,
            "total": matmul + embed + norms}


def attention_flops_per_token(cfg, context: int, layers=None) -> float:
    """Forward FLOPs of QK^T and PV for one query token that attends to
    `context` keys, summed over layers: 2 matmuls x 2 FLOPs x context x
    (heads x head_dim)."""
    z = _sizes(cfg, layers)
    return 4.0 * context * z["heads"] * z["head_dim"] * z["layers"]


def forward_flops_per_token(cfg, context: int, layers=None) -> float:
    """One token's forward pass with `context` keys visible to it."""
    return (2.0 * param_counts(cfg, layers)["matmul"]
            + attention_flops_per_token(cfg, context, layers))


def train_flops_per_token(cfg, seq_len: int, layers=None) -> float:
    """Forward + backward of one token in a causal sequence of `seq_len`:
    6 x matmul parameters, plus causal attention (a token sees seq_len / 2
    keys on average) at 3 x its forward cost. No recomputation."""
    return (6.0 * param_counts(cfg, layers)["matmul"]
            + 3.0 * attention_flops_per_token(cfg, seq_len / 2.0, layers))


def weight_bytes(cfg, bytes_per_param: float, layers=None) -> float:
    return param_counts(cfg, layers)["total"] * bytes_per_param


def kv_bytes_per_token(cfg, bytes_per_value: float, layers=None) -> float:
    """Keys and values one token leaves in the cache, over all layers."""
    z = _sizes(cfg, layers)
    return 2.0 * z["layers"] * z["kv_heads"] * z["head_dim"] * bytes_per_value


def decode_step_bytes(cfg, bytes_per_param: float, kv_bytes: float,
                      context_tokens: int, layers=None) -> float:
    """Least HBM traffic of one decode step: every weight once (the embedding
    table is gathered, not streamed) plus the cached keys and values of all
    `context_tokens` the batch attends to."""
    counts = param_counts(cfg, layers)
    return ((counts["matmul"] + counts["norms"]) * bytes_per_param
            + kv_bytes_per_token(cfg, kv_bytes, layers) * context_tokens)
