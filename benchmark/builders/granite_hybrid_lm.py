"""Builder `granite_hybrid_lm`: a configuration file -> the program's own
FFModel.

Calls `flexflow_tpu.models.granite_hybrid.granite_hybrid_lm` (every layer a
Mamba-2 mixer or grouped-query attention without rotary, then a SwiGLU MLP,
each behind its own RMSNorm and a scaled residual; a scaled embedding, a tied
head, scaled logits) with the published sizes of the configuration and the
FFConfig fields of the cut that runs. Nothing of the program is changed or
imitated here: this is the call a user of the framework would write.

A checkout whose program has no `granite_hybrid_lm` cannot run the
configuration; it says so when this file is loaded, before jax starts.
"""

import os

# the rehearsal's scale and its engine sizes are one rule for every builder
from benchmark.builders import llama_lm
from benchmark.builders.llama_lm import REHEARSAL_SCALE  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if not os.path.exists(os.path.join(ROOT, "flexflow_tpu", "models",
                                   "granite_hybrid.py")):
    raise ImportError(
        "this checkout's flexflow_tpu has no models/granite_hybrid.py "
        "(granite_hybrid_lm, the attention op's softmax_scale, snapshots of "
        "the recurrent state under the prefix cache): a Granite 4.0-H "
        "configuration cannot run here")

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_chunk_size=8, intermediate_size=96, shared_intermediate_size=96,
    vocab_size=512, num_hidden_layers=3,
    layer_types=["mamba", "attention", "mamba"])


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
    from flexflow_tpu.models.granite_hybrid import granite_hybrid_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    assert len(z["layer_types"]) == z["num_hidden_layers"]
    assert z["num_local_experts"] == 0 and z["tie_word_embeddings"]
    assert z["position_embedding_type"] == "nope"
    assert z["shared_intermediate_size"] == z["intermediate_size"]
    assert z["mamba_expand"] * z["hidden_size"] == (
        z["mamba_n_heads"] * z["mamba_d_head"]) or rehearsal
    tokens, logits = granite_hybrid_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layer_types=z["layer_types"], heads=z["num_attention_heads"],
        kv_heads=z["num_key_value_heads"], mamba_heads=z["mamba_n_heads"],
        mamba_head_dim=z["mamba_d_head"], n_groups=z["mamba_n_groups"],
        state_size=z["mamba_d_state"], conv_kernel=z["mamba_d_conv"],
        chunk_size=z["mamba_chunk_size"],
        ffn_hidden=z["shared_intermediate_size"],
        vocab_size=z["vocab_size"],
        embedding_multiplier=float(z["embedding_multiplier"]),
        residual_multiplier=float(z["residual_multiplier"]),
        attention_multiplier=float(z["attention_multiplier"]),
        logits_scaling=float(z["logits_scaling"]),
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
