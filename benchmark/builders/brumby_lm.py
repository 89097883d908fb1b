"""Builder `brumby_lm`: a configuration file -> the program's own FFModel.

Calls `flexflow_tpu.models.brumby.brumby_lm` (the Qwen3-shaped block with
every mixer a power-retention layer: per-head RMSNorm and rotary on q and k, a
gated decay, the keys' symmetric square for a state; a SwiGLU MLP as one op;
an untied head) with the published sizes of the configuration and the FFConfig
fields of the cut that runs. Nothing of the program is changed or imitated
here: this is the call a user of the framework would write.

A checkout whose program has no `brumby_lm` cannot run the configuration; it
says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders import llama_lm
from benchmark.builders.llama_lm import REHEARSAL_SCALE  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "brumby.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/brumby.py (brumby_lm, "
        "the power-retention op, a serving engine over a graph whose cached "
        "ops are all states): a Brumby configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, intermediate_size=96, vocab_size=512, num_hidden_layers=2,
    retention_chunk_size=8)


def rehearsal_engine(engine_kw):
    """llama_lm's rule, and the pinned prompt buckets divided like every
    other length."""
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


def build(config, cut, rehearsal=False):
    """(ff, tokens tensor, logits tensor), compiled. `cut["optimizer"]` is
    null for a serving cut: no optimizer state is allocated."""
    import flexflow_tpu as fft
    from flexflow_tpu.models.brumby import brumby_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    assert not z["tie_word_embeddings"] and not z["attention_bias"]
    assert z["hidden_act"] == "silu" and z["rope_scaling"] is None
    assert int(z["retention_power"]) == 2
    tokens, logits = brumby_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layers=z["num_hidden_layers"], heads=z["num_attention_heads"],
        kv_heads=z["num_key_value_heads"], head_dim=z["head_dim"],
        ffn_hidden=z["intermediate_size"], vocab_size=z["vocab_size"],
        rope_theta=float(z["rope_theta"]),
        rms_norm_eps=float(z["rms_norm_eps"]),
        chunk_size=int(z["retention_chunk_size"]),
        norm_eps=float(z["retention_norm_eps"]),
        decay_floor=tuple(z["retention_decay_floor"]))
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
