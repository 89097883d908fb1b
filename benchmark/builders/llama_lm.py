"""Builder `llama_lm`: a configuration file -> the program's own FFModel.

Calls `flexflow_tpu.models.llama.llama_lm` (pre-norm RMSNorm, rotate-half
RoPE, grouped-query attention, SwiGLU, no biases) with the published sizes
of the configuration and the FFConfig fields of the cut that runs. Nothing
of the program is changed or imitated here: this is the call a user of the
framework would write.

A configuration names its builder (`"builder": "llama_lm"`); a later PR that
needs another model function adds a file beside this one.
"""

# the CPU rehearsal's size: control flow only, never a measurement
REHEARSAL_SIZES = dict(hidden_size=128, num_attention_heads=4,
                       num_key_value_heads=2, intermediate_size=256,
                       vocab_size=512, num_hidden_layers=2)
REHEARSAL_SCALE = 16        # every sequence length and page is divided by it


def sizes_of(config, cut, rehearsal=False):
    """The sizes that run: the configuration's top-level keys, overridden by
    the cut's `model` group (a depth another cut of the same file runs)."""
    sizes = {**config, **cut.get("model", {})}
    if rehearsal:
        sizes.update(REHEARSAL_SIZES)
    return sizes


def rehearsal_engine(engine_kw):
    s = REHEARSAL_SCALE
    out = {"kv_page_size": max(8, engine_kw.get("kv_page_size", 128) // s),
           "max_seq_len": engine_kw["max_seq_len"] // s,
           "serve_slots": min(4, engine_kw.get("serve_slots", 4))}
    if engine_kw.get("kv_pages"):
        out["kv_pages"] = 0     # derive: the rehearsal has no pool to size
    return out


def build(config, cut, rehearsal=False):
    """(ff, tokens tensor, logits tensor), compiled. `cut["optimizer"]` is
    null for a serving cut: no optimizer state is allocated."""
    import flexflow_tpu as fft
    from flexflow_tpu.models.llama import llama_lm

    z = sizes_of(config, cut, rehearsal)
    ffc = dict(cut["ffconfig"])
    if rehearsal:
        # the CPU backend has no bf16 matmul worth waiting for
        ffc.update(compute_dtype="float32", master_dtype="float32")
    seq = cut["graph_seq_len"] // (REHEARSAL_SCALE if rehearsal else 1)
    cfg = fft.FFConfig(seed=int(config["weights_seed"]), **ffc)
    ff = fft.FFModel(cfg)
    tokens, logits = llama_lm(
        ff, cfg.batch_size, seq_len=seq, hidden=z["hidden_size"],
        layers=z["num_hidden_layers"], heads=z["num_attention_heads"],
        kv_heads=z["num_key_value_heads"], ffn_hidden=z["intermediate_size"],
        vocab_size=z["vocab_size"], rope_theta=float(z["rope_theta"]),
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
