"""Builder `nemotron_h_lm`: a configuration file -> the program's own
FFModel.

Calls `flexflow_tpu.models.nemotron_h.nemotron_h_lm` (a pattern string of
Mamba-2 mixers, grouped-query attention without rotary and relu^2 experts in a
latent beside a shared expert, one mixer a layer) with the published sizes of
the configuration, the chip's share of the experts (`experts_held`, the router
at its full width `router_experts`) and the FFConfig fields of the cut that
runs. Nothing of the program is changed or imitated here: this is the call a
user of the framework would write.

A checkout whose program has no `nemotron_h_lm` cannot run the configuration;
it says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders import llama_lm
from benchmark.builders.llama_lm import REHEARSAL_SCALE  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "nemotron_h.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/nemotron_h.py "
        "(nemotron_h_lm, the Mamba-2 op and its state protocol, relu^2 "
        "experts in a latent): a Nemotron-H configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_num_heads=8, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    chunk_size=8, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=40, router_experts=16,
    n_routed_experts=4, experts_held=[0, 4], num_experts_per_tok=4,
    vocab_size=512, hybrid_override_pattern="ME*M", num_hidden_layers=4)


def rehearsal_engine(engine_kw):
    """llama_lm's rule, and the pinned prompt buckets divided like every
    other length."""
    out = llama_lm.rehearsal_engine(engine_kw)
    if engine_kw.get("decode_buckets"):
        out["decode_buckets"] = [b // REHEARSAL_SCALE
                                 for b in engine_kw["decode_buckets"]]
    return out


def sizes_of(config, cut, rehearsal=False):
    """The sizes that run: the configuration's top-level keys, overridden by
    the cut's `model` group."""
    sizes = {**config, **cut.get("model", {})}
    if rehearsal:
        sizes.update(REHEARSAL_SIZES)
    return sizes


def build(config, cut, rehearsal=False):
    """(ff, tokens tensor, logits tensor), compiled. `cut["optimizer"]` is
    null for a serving cut: no optimizer state is allocated."""
    import flexflow_tpu as fft
    from flexflow_tpu.models.nemotron_h import nemotron_h_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    first, count = z["experts_held"]
    assert count == z["n_routed_experts"], (count, z["n_routed_experts"])
    assert len(z["hybrid_override_pattern"]) == z["num_hidden_layers"]
    tokens, logits = nemotron_h_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        pattern=z["hybrid_override_pattern"],
        heads=z["num_attention_heads"], kv_heads=z["num_key_value_heads"],
        mamba_heads=z["mamba_num_heads"], mamba_head_dim=z["mamba_head_dim"],
        n_groups=z["n_groups"], state_size=z["ssm_state_size"],
        conv_kernel=z["conv_kernel"], chunk_size=z["chunk_size"],
        num_experts=z["router_experts"],
        experts_per_token=z["num_experts_per_tok"],
        expert_hidden=z["moe_intermediate_size"],
        latent_dim=z["moe_latent_size"],
        shared_hidden=(z["n_shared_experts"]
                       * z["moe_shared_expert_intermediate_size"]),
        n_group=z["n_group"], topk_group=z["topk_group"],
        routed_scaling=float(z["routed_scaling_factor"]),
        norm_topk_prob=bool(z["norm_topk_prob"]),
        experts_held=(int(first), int(count)),
        score_bias_std=float(z["seeded_score_bias_std"]),
        vocab_size=z["vocab_size"], rope=bool(z["attention_rope"]),
        rope_theta=float(z["rope_theta"]), rms_norm_eps=float(z["norm_eps"]))
    opt = cut.get("optimizer")
    optimizer = None
    if opt:
        optimizer = getattr(fft, opt["type"])(
            **{k: v for k, v in opt.items() if k != "type"})
    ff.compile(optimizer,
               fft.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [fft.MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    return ff, tokens, logits
