"""Parameters, bytes and FLOPs of a Brumby configuration, from the
configuration file's own keys (benchmark/configs/brumby-14b-base-serve.json):
what the cell's per-layer readers divide measured seconds into, and what
PERF.md's memory table is made of. No jax, nothing imported from the program.

    retention layer: wq D x H x hd, wk and wv D x KV x hd, the gate D x KV, wo
                     H x hd x D (the MATRICES: 62.96 M); beside them the
                     gate's constant (KV) and the two per-head norms (2 hd)
    every layer:     the MLP D x 2 F + F x D (267.39 M); two RMSNorm scales
    once:            the embedding V x D, the head D x V (untied), the final norm
    the state:       KV x (hd / 2 + 1) x hd rows of phi (by diagonals: 8320 a
                     KV head at hd 128, where the upper triangle has 8256) x
                     (hd values + 1 for z) float32
"""


def _z(config, cut=None):
    return {**config, **((cut or {}).get("model", {}))}


def mixer_matrix_params(z):
    d, hd = int(z["hidden_size"]), int(z["head_dim"])
    h, kv = int(z["num_attention_heads"]), int(z["num_key_value_heads"])
    return 2 * d * h * hd + 2 * d * kv * hd + d * kv


def mlp_params(z):
    return 3 * int(z["hidden_size"]) * int(z["intermediate_size"])


def layer_matrix_params(config):
    """The matrices of one layer (ISSUE 50's 330.35 M at the published
    widths)."""
    z = _z(config)
    return mixer_matrix_params(z) + mlp_params(z)


def layer_vector_params(config):
    z = _z(config)
    return (int(z["num_key_value_heads"]) + 2 * int(z["head_dim"])
            + 2 * int(z["hidden_size"]))


def vocab_params(config):
    """The embedding OR the head: each V x D."""
    z = _z(config)
    return int(z["vocab_size"]) * int(z["hidden_size"])


def model_params(config, cut=None):
    """Every parameter the cut holds: its layers, the embedding, the untied
    head, the final norm."""
    z = _z(config, cut)
    return (int(z["num_hidden_layers"])
            * (layer_matrix_params(z) + layer_vector_params(z))
            + 2 * vocab_params(z) + int(z["hidden_size"]))


def phi_rows(config):
    """Rows of phi a KV head holds: (hd / 2 + 1) diagonals of hd."""
    hd = int(_z(config)["head_dim"])
    return (hd // 2 + 1) * hd


def state_bytes_per_layer(config):
    """One sequence's state in one retention layer: S (KV x rows x hd) and z
    (KV x rows), float32."""
    z = _z(config)
    hd = int(z["head_dim"])
    return 4 * int(z["num_key_value_heads"]) * phi_rows(z) * (hd + 1)


def snapshot_bytes(config, cut=None):
    """One snapshot (or one slot): every layer's state."""
    z = _z(config, cut)
    return int(z["num_hidden_layers"]) * state_bytes_per_layer(z)


def update_rows_bytes(config, slot_steps):
    """The decode state update's traffic beside the state, for ONE layer over
    `slot_steps` (steps x live slots): the step's tile (8 rows of hd float32 a
    KV head: the group's query heads, k, v, the decay) in, and two tiles out
    (the read-outs and the normaliser's lane sums); no weights (the
    projections are other phases)."""
    z = _z(config)
    return (slot_steps * int(z["num_key_value_heads"]) * 3 * 8
            * int(z["head_dim"]) * 4)


def update_flops(config, slot_steps):
    """FLOPs of the update for ONE layer: per state element a decay, a
    product with phi(k) v, an add, and a multiply-add for each of the group's
    R read-outs."""
    z = _z(config)
    hd = int(z["head_dim"])
    group = int(z["num_attention_heads"]) // int(z["num_key_value_heads"])
    return (slot_steps * int(z["num_key_value_heads"]) * phi_rows(z) * hd
            * (3 + 2 * group))


def decode_weights(config, cut=None):
    """The weights a decode step streams: every layer's matrices and the head
    (the embedding is a gather of a few rows)."""
    z = _z(config, cut)
    return (int(z["num_hidden_layers"]) * layer_matrix_params(z)
            + vocab_params(z))


def decode_weight_bytes(config, cut=None, bytes_per=2):
    return bytes_per * decode_weights(config, cut)


def decode_flops_per_token(config, cut=None):
    """Matmul FLOPs of one decode token (2 a weight, the head included; the
    state update is bandwidth-bound and not counted)."""
    return 2 * decode_weights(config, cut)
