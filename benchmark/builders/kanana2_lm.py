"""Builder `kanana2_lm`: a configuration file -> the program's own FFModel.

Calls `flexflow_tpu.models.kanana2.kanana2_lm` (latent attention without
query compression or indexer, a leading dense SwiGLU layer, then
sigmoid-routed experts beside two shared experts) with the published sizes
of the configuration, the chip's share of the experts (`experts_held`, the
router at its full width `router_experts`) and the FFConfig fields of the cut
that runs. Nothing of the program is changed or imitated here: this is the
call a user of the framework would write.

A checkout whose program has no `kanana2_lm` cannot run the configuration;
it says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale is one rule for every builder
from benchmark.builders.llama_lm import REHEARSAL_SCALE  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "kanana2.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/kanana2.py (kanana2_lm: "
        "latent attention without query compression or indexer under fit(), "
        "flash kernels at key and value widths that differ): a Kanana-2 "
        "configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    intermediate_size=128, moe_intermediate_size=32, router_experts=16,
    n_routed_experts=4, experts_held=[0, 4], num_experts_per_tok=4,
    vocab_size=512, num_hidden_layers=5, first_k_dense_replace=1)


def sizes_of(config, cut, rehearsal=False):
    """The sizes that run: the configuration's top-level keys, overridden by
    the cut's `model` group."""
    sizes = {**config, **cut.get("model", {})}
    if rehearsal:
        sizes.update(REHEARSAL_SIZES)
    return sizes


def build(config, cut, rehearsal=False):
    """(ff, tokens tensor, logits tensor), compiled with the cut's
    optimizer."""
    import flexflow_tpu as fft
    from flexflow_tpu.models.kanana2 import kanana2_lm

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
    assert z["q_lora_rank"] is None and z["rope_scaling"] is None
    assert (z["n_group"], z["topk_group"]) == (1, 1)
    tokens, logits = kanana2_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layers=z["num_hidden_layers"], heads=z["num_attention_heads"],
        kv_lora_rank=z["kv_lora_rank"],
        qk_nope_head_dim=z["qk_nope_head_dim"],
        qk_rope_head_dim=z["qk_rope_head_dim"], v_head_dim=z["v_head_dim"],
        dense_layers=z["first_k_dense_replace"],
        ffn_hidden=z["intermediate_size"], num_experts=z["router_experts"],
        experts_per_token=z["num_experts_per_tok"],
        expert_hidden=z["moe_intermediate_size"],
        shared_experts=z["n_shared_experts"],
        routed_scaling=float(z["routed_scaling_factor"]),
        norm_topk_prob=bool(z["norm_topk_prob"]),
        experts_held=(int(first), int(count)),
        score_bias_std=float(z["seeded_score_bias_std"]),
        vocab_size=z["vocab_size"], rope_theta=float(z["rope_theta"]),
        rms_norm_eps=float(z["rms_norm_eps"]))
    opt = cut["optimizer"]
    optimizer = getattr(fft, opt["type"])(
        **{k: v for k, v in opt.items() if k != "type"})
    ff.compile(optimizer,
               fft.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [fft.MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    return ff, tokens, logits
