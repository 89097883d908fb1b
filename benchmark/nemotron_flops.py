"""Parameters, operations and bytes of a Nemotron-H model (Mamba-2 mixers,
attention, relu^2 experts in a latent), from a configuration file: the
yardstick of `ssm_update_hbm_share`, `ssm_scan_roofline_share` and
`latent_expert_hbm_share`. Computed from the published sizes, never from the
program's counters of its own work.

A configuration is the dict of a `benchmark/configs/*.json` file with the
`nemotron_h` keys: `mamba_num_heads` x `mamba_head_dim` = d_inner, `n_groups`,
`ssm_state_size`, `conv_kernel`, `chunk_size`; `moe_latent_size`,
`moe_intermediate_size`, `moe_shared_expert_intermediate_size`,
`n_routed_experts` (the experts HELD), `router_experts` (the router's width;
`n_routed_experts` where the file has none).
"""


def _mamba(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    d_inner = heads * p
    return heads, p, g, n, d_inner, d_inner + 2 * g * n


def mamba_params(cfg) -> int:
    """One `M` layer: in-projection to [z | xBC | dt], the depthwise conv and
    its bias, dt_bias, A_log, D, the gated norm, the out-projection and the
    layer's pre-norm."""
    heads, _, _, _, d_inner, conv = _mamba(cfg)
    h = cfg["hidden_size"]
    return (h * (d_inner + conv + heads) + conv * (cfg["conv_kernel"] + 1)
            + 3 * heads + d_inner + d_inner * h + h)


def attention_params(cfg) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return (2 * h * cfg["num_attention_heads"] * d
            + 2 * h * cfg["num_key_value_heads"] * d + h)


def expert_params(cfg) -> int:
    """One routed expert: two matrices in the latent."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def moe_shared_params(cfg) -> int:
    """What an `E` layer holds beside its routed experts: router and its
    selection bias, the two latent projections, the shared expert, the
    pre-norm."""
    h, lat = cfg["hidden_size"], cfg["moe_latent_size"]
    routed = cfg.get("router_experts", cfg["n_routed_experts"])
    return (h * routed + routed + 2 * h * lat
            + 2 * h * cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"] + h)


def model_params(cfg, pattern=None, experts=None, active=False) -> int:
    """All parameters of `pattern` (default: the file's) with `experts`
    routed experts a layer (default: the file's held count); `active`: the
    `num_experts_per_tok` a token uses instead. Embedding, untied head and
    the last norm included."""
    pattern = cfg["hybrid_override_pattern"] if pattern is None else pattern
    n_exp = cfg["n_routed_experts"] if experts is None else experts
    if active:
        n_exp = cfg["num_experts_per_tok"]
    per = {"M": mamba_params(cfg), "*": attention_params(cfg),
           "E": moe_shared_params(cfg) + n_exp * expert_params(cfg)}
    table = cfg["vocab_size"] * cfg["hidden_size"]
    return (sum(per[c] for c in pattern) + 2 * table + cfg["hidden_size"])


def expert_bytes(cfg, experts_hit: float, bytes_per_param: float = 2.0):
    """Least HBM traffic of the expert stream of `E` layers in which
    `experts_hit` experts (summed over layers and steps) have at least one
    row: each such expert's two matrices once. The rows (32 x 1024 values)
    are negligible beside an expert's 11 MB and are not counted."""
    return experts_hit * expert_params(cfg) * bytes_per_param


def state_bytes_per_slot(cfg, compute_bytes: float = 2.0) -> float:
    """One slot's recurrent state in one `M` layer: H (heads, P, N) in
    float32 and the conv tail (K - 1, conv_dim) in the compute dtype."""
    heads, p, _, n, _, conv = _mamba(cfg)
    return 4.0 * heads * p * n + compute_bytes * (cfg["conv_kernel"] - 1) * conv


def update_rows_bytes(cfg, slot_steps: float,
                      compute_bytes: float = 2.0) -> float:
    """What the decode update of one `M` layer moves beside the state over
    `slot_steps` (live slot, step) pairs: the step's x, B, C (compute dtype)
    and dt (float32) in, y (float32) out."""
    heads, _, _, _, d_inner, conv = _mamba(cfg)
    return slot_steps * (compute_bytes * conv + 4.0 * heads + 4.0 * d_inner)


def update_bytes(cfg, slot_steps: float, compute_bytes: float = 2.0) -> float:
    """Least HBM traffic of the decode update of one `M` layer over
    `slot_steps` (live slot, step) pairs: the state and the conv tail there
    and back (what the engine's `state_bytes` counts, over all layers), and
    `update_rows_bytes`. The weights are not its."""
    return (slot_steps * 2 * state_bytes_per_slot(cfg, compute_bytes)
            + update_rows_bytes(cfg, slot_steps, compute_bytes))


def scan_flops(cfg, rows: float) -> float:
    """FLOPs of the chunked scan (SSD) of one `M` layer over `rows`
    positions, by the chunked form's count at chunk Q: C.B inside a chunk
    (2 Q N a row and group), the masked product against x (2 Q P a row and
    head), the chunk's contribution to the state and its read-out (2 P N
    each a row and head). The causal half of the two Q x Q products is
    counted whole, as the matmuls compute it."""
    heads, p, g, n, _, _ = _mamba(cfg)
    q = cfg["chunk_size"]
    return rows * (2.0 * q * n * g + 2.0 * q * p * heads
                   + 4.0 * p * n * heads)


def scan_bytes(cfg, rows: float, compute_bytes: float = 2.0) -> float:
    """Least HBM traffic of that scan: x, B, C in and y out in the compute
    dtype, dt in float32, and the float32 state written and read once a
    chunk."""
    heads, p, g, n, d_inner, conv = _mamba(cfg)
    per_row = compute_bytes * (conv + d_inner) + 4.0 * heads
    per_chunk = 2 * 4.0 * heads * p * n
    return rows * per_row + rows / cfg["chunk_size"] * per_chunk
