"""Builder `olmoe_lm`: a configuration file -> the program's own FFModel.

Calls `flexflow_tpu.models.olmoe.olmoe_lm` (llama_lm's block with QK-norm and
a dropless top-k mixture of SwiGLU experts in the MLP's place) with the
published sizes of the configuration, `rms_norm_eps` included, and the
FFConfig fields of the cut that runs. Nothing of the program is changed or
imitated here: this is the call a user of the framework would write.

A checkout whose program has no `olmoe_lm` cannot run the configuration;
it says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders.llama_lm import (  # noqa: F401
    REHEARSAL_SCALE, rehearsal_engine)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "olmoe.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/olmoe.py (olmoe_lm, the "
        "dropless MoE op, QK-norm): an OLMoE configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(hidden_size=128, num_attention_heads=4,
                       num_key_value_heads=4, intermediate_size=64,
                       num_experts=8, num_experts_per_tok=2,
                       vocab_size=512, num_hidden_layers=2)


def sizes_of(config, cut, rehearsal=False):
    """The sizes that run: the configuration's top-level keys, overridden by
    the cut's `model` group (a depth another cut of the same file runs)."""
    sizes = {**config, **cut.get("model", {})}
    if rehearsal:
        sizes.update(REHEARSAL_SIZES)
    return sizes


def build(config, cut, rehearsal=False):
    """(ff, tokens tensor, logits tensor), compiled. `cut["optimizer"]` is
    null for a serving cut: no optimizer state is allocated."""
    import flexflow_tpu as fft
    from flexflow_tpu.models.olmoe import olmoe_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    tokens, logits = olmoe_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layers=z["num_hidden_layers"], heads=z["num_attention_heads"],
        kv_heads=z["num_key_value_heads"], num_experts=z["num_experts"],
        experts_per_token=z["num_experts_per_tok"],
        expert_hidden=z["intermediate_size"], vocab_size=z["vocab_size"],
        rope_theta=float(z["rope_theta"]),
        rms_norm_eps=float(z["rms_norm_eps"]),
        norm_topk_prob=bool(z["norm_topk_prob"]),
        tie_embeddings=bool(z.get("tie_word_embeddings", False)))
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
