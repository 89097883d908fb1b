"""Builder `longcat_flash_lm`: a configuration file -> the program's own
FFModel.

Calls `flexflow_tpu.models.longcat_flash.longcat_flash_lm` (double layers of
two latent attentions without an indexer and two dense SwiGLU feed-forwards,
one softmax-routed expert layer with zero-computation experts on a shortcut
across each) with the published sizes of the configuration, the chip's share
of the experts (`experts_held`, the router at its full width `router_experts`
+ `zero_expert_num`) and the FFConfig fields of the cut that runs. Nothing of
the program is changed or imitated here: this is the call a user of the
framework would write.

A checkout whose program has no `longcat_flash_lm` cannot run the
configuration; it says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders.llama_lm import (  # noqa: F401
    REHEARSAL_SCALE, rehearsal_engine)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "longcat_flash.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/longcat_flash.py "
        "(longcat_flash_lm, a paged pool for latent attention without an "
        "indexer, zero-computation experts): a LongCat-Flash configuration "
        "cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=16, kv_lora_rank=32,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
    ffn_hidden_size=128, expert_ffn_hidden_size=32, router_experts=16,
    zero_expert_num=8, n_routed_experts=4, experts_held=[0, 4], moe_topk=4,
    vocab_size=512, num_layers=2)


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
    from flexflow_tpu.models.longcat_flash import longcat_flash_lm

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
    tokens, logits = longcat_flash_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layers=z["num_layers"], heads=z["num_attention_heads"],
        q_lora_rank=z["q_lora_rank"], kv_lora_rank=z["kv_lora_rank"],
        qk_nope_head_dim=z["qk_nope_head_dim"],
        qk_rope_head_dim=z["qk_rope_head_dim"], v_head_dim=z["v_head_dim"],
        mla_scale_q_lora=bool(z["mla_scale_q_lora"]),
        mla_scale_kv_lora=bool(z["mla_scale_kv_lora"]),
        ffn_hidden=z["ffn_hidden_size"], num_experts=z["router_experts"],
        zero_experts=z["zero_expert_num"], experts_per_token=z["moe_topk"],
        expert_hidden=z["expert_ffn_hidden_size"],
        routed_scaling=float(z["routed_scaling_factor"]),
        experts_held=(int(first), int(count)),
        score_bias_std=float(z["seeded_score_bias_std"]),
        uq_init_gain=float(z["seeded_w_uq_gain"]),
        vocab_size=z["vocab_size"], rope_theta=float(z["rope_theta"]),
        rms_norm_eps=float(z["rms_norm_eps"]))
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
