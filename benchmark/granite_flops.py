"""Parameters, bytes and FLOPs of a Granite 4.0-H configuration, from the
configuration file's own keys (benchmark/configs/granite-4.0-h-micro-serve.json):
what the cell's per-layer readers divide measured seconds into, and what
PERF.md's memory table is made of. No jax, nothing imported from the program.

    Mamba layer:     in-projection D x (2 d_inner + 2 G N + H), conv
                     (d_inner + 2 G N) x (K + 1), dt_bias + A_log + D (3 H),
                     the gated norm d_inner, out-projection d_inner x D
    attention layer: wq D x heads x d, wk and wv D x kv_heads x d, wo
    every layer:     two RMSNorm scales (2 D), the MLP D x 2 F + F x D
    once:            the embedding V x D (the head is the same matrix), the
                     final norm D
"""


def _z(config, cut=None):
    return {**config, **((cut or {}).get("model", {}))}


def d_inner(z):
    return int(z["mamba_n_heads"]) * int(z["mamba_d_head"])


def conv_dim(z):
    return d_inner(z) + 2 * int(z["mamba_n_groups"]) * int(z["mamba_d_state"])


def head_dim(z):
    return int(z["hidden_size"]) // int(z["num_attention_heads"])


def mlp_params(z):
    return 3 * int(z["hidden_size"]) * int(z["shared_intermediate_size"])


def mamba_mixer_params(z):
    d, di, c, h = (int(z["hidden_size"]), d_inner(z), conv_dim(z),
                   int(z["mamba_n_heads"]))
    return (d * (di + c + h) + c * (int(z["mamba_d_conv"]) + 1) + 3 * h + di
            + di * d)


def attention_mixer_params(z):
    d, hd = int(z["hidden_size"]), head_dim(z)
    return d * hd * (2 * int(z["num_attention_heads"])
                     + 2 * int(z["num_key_value_heads"]))


def layer_counts(z):
    kinds = list(z["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def model_params(config, cut=None):
    """Every parameter the cut holds (the tied head counted once)."""
    z = _z(config, cut)
    m, a = layer_counts(z)
    d = int(z["hidden_size"])
    return (m * mamba_mixer_params(z) + a * attention_mixer_params(z)
            + (m + a) * (mlp_params(z) + 2 * d)
            + int(z["vocab_size"]) * d + d)


def state_bytes_per_layer(config, conv_bytes=2):
    """One sequence's recurrent state in one Mamba layer: H float32 (heads x
    head size x state size) and the conv tail (K - 1 rows of conv_dim) in the
    compute dtype."""
    z = _z(config)
    return (4 * d_inner(z) * int(z["mamba_d_state"])
            + conv_bytes * (int(z["mamba_d_conv"]) - 1) * conv_dim(z))


def snapshot_bytes(config, cut=None):
    """One snapshot (or one slot): every Mamba layer's state."""
    z = _z(config, cut)
    return layer_counts(z)[0] * state_bytes_per_layer(z)


def kv_bytes_per_token(config, cut=None, bytes_per=2):
    """Keys and values of one token over the attention layers."""
    z = _z(config, cut)
    return (layer_counts(z)[1] * 2 * int(z["num_key_value_heads"])
            * head_dim(z) * bytes_per)


def update_rows_bytes(config, slot_steps):
    """The decode state update's traffic beside the state, for ONE Mamba
    layer over `slot_steps` (steps x live slots): decay (H) and dt x (H x P)
    float32 in, B and C (G x N) float32 in, y (H x P) float32 out; no
    weights (the projections are other phases)."""
    z = _z(config)
    h, p = int(z["mamba_n_heads"]), int(z["mamba_d_head"])
    gn = int(z["mamba_n_groups"]) * int(z["mamba_d_state"])
    return slot_steps * 4 * (h + 2 * h * p + 2 * gn)


def decode_flops_per_token(config, cut=None):
    """Matmul FLOPs of one decode token (2 a weight, the head included; the
    state update and the attention over the context are bandwidth-bound and
    not counted)."""
    z = _z(config, cut)
    return 2 * model_params(config, cut)
