"""Builder `exaone_moe_lm`: a configuration file -> the program's own
FFModel.

Calls `flexflow_tpu.models.exaone_moe.exaone_moe_lm` (window layers with
rotary beside global layers without, a QK norm per head, a leading dense
SwiGLU layer, then sigmoid-routed experts beside a shared expert) with the
published sizes of the configuration, the chip's share of the experts
(`experts_held`, the router at its full width `router_experts`) and the
FFConfig fields of the cut that runs. Nothing of the program is changed or
imitated here: this is the call a user of the framework would write.

A checkout whose program has no `exaone_moe_lm` cannot run the configuration;
it says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders import llama_lm
from benchmark.builders.llama_lm import REHEARSAL_SCALE  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "exaone_moe.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/exaone_moe.py "
        "(exaone_moe_lm, attention with a window, the page pool's window "
        "groups): a K-EXAONE configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement. The
# window is the page the rehearsal's engine gets (128 / REHEARSAL_SCALE).
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=48, router_experts=16,
    num_experts=4, experts_held=[0, 4], num_experts_per_tok=4,
    vocab_size=512, num_hidden_layers=3,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"],
    sliding_windows=[8, 0, 8], mlp_layer_types=["dense", "sparse", "sparse"])


def rehearsal_engine(engine_kw):
    """llama_lm's rule, and the prefill chunk divided like every other
    length."""
    out = llama_lm.rehearsal_engine(engine_kw)
    if engine_kw.get("prefill_chunk"):
        out["prefill_chunk"] = engine_kw["prefill_chunk"] // REHEARSAL_SCALE
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
    from flexflow_tpu.models.exaone_moe import exaone_moe_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    first, count = z["experts_held"]
    assert count == z["num_experts"], (count, z["num_experts"])
    layers = z["num_hidden_layers"]
    assert len(z["layer_types"]) == len(z["sliding_windows"]) \
        == len(z["mlp_layer_types"]) == layers
    tokens, logits = exaone_moe_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layers=layers, heads=z["num_attention_heads"],
        kv_heads=z["num_key_value_heads"], head_dim=z["head_dim"],
        layer_types=z["layer_types"], sliding_windows=z["sliding_windows"],
        mlp_layer_types=z["mlp_layer_types"],
        ffn_hidden=z["intermediate_size"], num_experts=z["router_experts"],
        experts_per_token=z["num_experts_per_tok"],
        expert_hidden=z["moe_intermediate_size"],
        shared_experts=z["num_shared_experts"],
        routed_scaling=float(z["routed_scaling_factor"]),
        norm_topk_prob=bool(z["norm_topk_prob"]),
        experts_held=(int(first), int(count)),
        score_bias_std=float(z["seeded_score_bias_std"]),
        vocab_size=z["vocab_size"],
        rope_theta=float(z["rope_parameters"]["rope_theta"]),
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
