"""Builder `mimo_v2_lm`: a configuration file -> the program's own FFModel.

Calls `flexflow_tpu.models.mimo_v2.mimo_v2_lm` (window layers with a sink and
`swa_num_key_value_heads` KV heads beside global layers with
`num_key_value_heads`, keys of `head_dim` beside values of `v_head_dim`,
rotary over a head's first `rope_dim` entries with a base a kind of layer, a
value scale, a leading dense SwiGLU layer, then sigmoid-routed experts with no
shared one) with the published sizes of the configuration, the chip's share of
the experts (`experts_held`, the router at its full width `router_experts`)
and the FFConfig fields of the cut that runs. Nothing of the program is
changed or imitated here: this is the call a user of the framework would
write.

A checkout whose program has no `mimo_v2_lm` cannot run the configuration; it
says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders import llama_lm
from benchmark.builders.llama_lm import REHEARSAL_SCALE  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "mimo_v2.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/mimo_v2.py (mimo_v2_lm, "
        "the attention op's sink, partial rotary and value scale, a prefix "
        "cache over window layers): a MiMo-V2 configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement. The
# window is the page the rehearsal's engine gets (128 / REHEARSAL_SCALE).
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
    swa_num_key_value_heads=2, swa_num_attention_heads=4, head_dim=24,
    swa_head_dim=24, v_head_dim=16, swa_v_head_dim=16, rope_dim=8,
    intermediate_size=96, moe_intermediate_size=48, router_experts=16,
    n_routed_experts=4, experts_held=[0, 4], num_experts_per_tok=4,
    vocab_size=512, num_hidden_layers=3, hybrid_layer_pattern=[0, 1, 1],
    moe_layer_freq=[0, 1, 1], sliding_window=8)


def rehearsal_engine(engine_kw):
    """llama_lm's rule, and the pinned prompt buckets and the prefill chunk
    divided like every other length."""
    out = llama_lm.rehearsal_engine(engine_kw)
    if engine_kw.get("decode_buckets"):
        out["decode_buckets"] = [b // REHEARSAL_SCALE
                                 for b in engine_kw["decode_buckets"]]
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


def model_kwargs(z):
    """`mimo_v2_lm`'s arguments from the sizes that run (a control builds the
    program otherwise by changing one of them: benchmark/mimo_controls.py)."""
    first, count = z["experts_held"]
    assert count == z["n_routed_experts"], (count, z["n_routed_experts"])
    layers = z["num_hidden_layers"]
    assert len(z["hybrid_layer_pattern"]) == len(z["moe_layer_freq"]) == layers
    assert z["n_shared_experts"] is None and not z["attention_bias"]
    assert z["swa_num_attention_heads"] == z["num_attention_heads"] \
        and z["swa_head_dim"] == z["head_dim"] \
        and z["swa_v_head_dim"] == z["v_head_dim"]
    sink = float(z["seeded_sink_std"])
    return dict(
        hidden=z["hidden_size"], layers=layers,
        heads=z["num_attention_heads"], kv_heads=z["num_key_value_heads"],
        swa_kv_heads=z["swa_num_key_value_heads"], head_dim=z["head_dim"],
        v_head_dim=z["v_head_dim"], rope_dim=z["rope_dim"],
        hybrid_pattern=z["hybrid_layer_pattern"],
        moe_layer_freq=z["moe_layer_freq"],
        sliding_window=z["sliding_window"],
        ffn_hidden=z["intermediate_size"], num_experts=z["router_experts"],
        experts_per_token=z["num_experts_per_tok"],
        expert_hidden=z["moe_intermediate_size"],
        norm_topk_prob=bool(z["norm_topk_prob"]),
        routed_scaling=float(z["routed_scaling_factor"] or 1.0),
        experts_held=(int(first), int(count)),
        score_bias_std=float(z["seeded_score_bias_std"]),
        vocab_size=z["vocab_size"], rope_theta=float(z["rope_theta"]),
        swa_rope_theta=float(z["swa_rope_theta"]),
        value_scale=float(z["attention_value_scale"]),
        swa_sink=sink if z["add_swa_attention_sink_bias"] else None,
        full_sink=sink if z["add_full_attention_sink_bias"] else None,
        rms_norm_eps=float(z["layernorm_epsilon"]))


def build(config, cut, rehearsal=False, **over):
    """(ff, tokens tensor, logits tensor), compiled. `cut["optimizer"]` is
    null for a serving cut: no optimizer state is allocated. `over` replaces
    arguments of `mimo_v2_lm` (a control's planted fault)."""
    import flexflow_tpu as fft
    from flexflow_tpu.models.mimo_v2 import mimo_v2_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    tokens, logits = mimo_v2_lm(ff, cfg.batch_size, seq_len=seq,
                                **{**model_kwargs(z), **over})
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
