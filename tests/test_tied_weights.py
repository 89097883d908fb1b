"""Weight tying (FFModel.tie_weights).

Reference parity: the NMT subsystem's SharedVariable (nmt/rnn.h:37-51) —
one logical weight behind many ops, gradients two-level-reduced into it.
Here the destination op's weight resolves from the source's storage at
trace time, so autodiff accumulates both ops' gradients into one array.
Modern use pinned below: tied embedding / lm_head decoder.
"""

import numpy as np
import pytest

from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                          SGDOptimizer)
from flexflow_tpu.ffconst import DataType

VOCAB, HIDDEN = 61, 32


def _tied_lm(mesh=None, tie=True):
    cfg = FFConfig(batch_size=4, mesh_shape=mesh or {"data": 2})
    ff = FFModel(cfg)
    toks = ff.create_tensor([4, 6], dtype=DataType.DT_INT32, name="input")
    t = ff.embedding(toks, VOCAB, HIDDEN, name="embed")
    t = ff.multihead_attention(t, t, t, HIDDEN, 4, causal=True, bias=False,
                               rope=True, name="attn")
    t = ff.rms_norm(t, name="ln")
    logits = ff.dense(t, VOCAB, use_bias=False, name="lm_head")
    if tie:
        ff.tie_weights("lm_head", "kernel", "embed", "kernel", "transpose")
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=logits)
    return ff


def test_tied_storage_and_grad_accumulation():
    ff = _tied_lm()
    assert "kernel" not in ff.params.get("lm_head", {})
    # get_weights resolves through the tie
    np.testing.assert_array_equal(ff.get_weights("lm_head", "kernel"),
                                  ff.get_weights("embed", "kernel").T)

    rs = np.random.RandomState(0)
    batch = {"input": rs.randint(0, VOCAB, (4, 6)).astype(np.int32),
             "label": rs.randint(0, VOCAB, (4, 6, 1)).astype(np.int32)}
    w0 = ff.get_weights("embed", "kernel").copy()
    loss0, _ = ff._run_train_step(batch)
    w1 = ff.get_weights("embed", "kernel")
    # the lm_head gradient reaches rows the embedding gather never touched
    # (only 24 distinct tokens were gathered; CE over VOCAB classes
    # back-propagates into EVERY row through the tied projection)
    changed_rows = (np.abs(w1 - w0).sum(axis=1) > 0).sum()
    assert changed_rows == VOCAB, f"only {changed_rows}/{VOCAB} rows updated"
    # and training still optimizes
    losses = [float(loss0)]
    for _ in range(10):
        l, _ = ff._run_train_step(batch)
        losses.append(float(l))
    assert losses[-1] < losses[0]


def test_tied_model_generates():
    ff = _tied_lm()
    prompt = np.arange(8, dtype=np.int32).reshape(2, 4) % VOCAB
    out = ff.generate(prompt, max_new_tokens=4)
    assert out.shape == (2, 8)
    # decode matches the naive full-forward rescoring loop (tie resolved
    # identically on both paths)
    seq = prompt.copy()
    for _ in range(4):
        nxt = np.asarray(ff.predict({"input": seq}))[:, -1].argmax(-1)
        seq = np.concatenate([seq, nxt[:, None].astype(np.int32)], axis=1)
    np.testing.assert_array_equal(out, seq)


def test_untied_differs():
    """Sanity: tying actually changes the model (same seed, different
    first-step loss trajectory because lm_head == embed.T)."""
    a, b = _tied_lm(tie=True), _tied_lm(tie=False)
    rs = np.random.RandomState(1)
    batch = {"input": rs.randint(0, VOCAB, (4, 6)).astype(np.int32),
             "label": rs.randint(0, VOCAB, (4, 6, 1)).astype(np.int32)}
    la, _ = a._run_train_step(batch)
    lb, _ = b._run_train_step(batch)
    assert abs(float(la) - float(lb)) > 1e-6


def test_llama_tie_embeddings_flag():
    from flexflow_tpu.models.llama import llama_lm

    cfg = FFConfig(batch_size=2, mesh_shape={"data": 2})
    ff = FFModel(cfg)
    _, logits = llama_lm(ff, 2, seq_len=8, hidden=32, layers=1, heads=2,
                         vocab_size=VOCAB, tie_embeddings=True)
    ff.compile(SGDOptimizer(lr=0.05),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=logits)
    assert "kernel" not in ff.params.get("lm_head", {})
    rs = np.random.RandomState(2)
    batch = {"input": rs.randint(0, VOCAB, (2, 8)).astype(np.int32),
             "label": rs.randint(0, VOCAB, (2, 8, 1)).astype(np.int32)}
    l, _ = ff._run_train_step(batch)
    assert np.isfinite(float(l))


def test_tie_validation():
    cfg = FFConfig(batch_size=4, mesh_shape={"data": 2})
    ff = FFModel(cfg)
    toks = ff.create_tensor([4, 6], dtype=DataType.DT_INT32, name="input")
    t = ff.embedding(toks, VOCAB, HIDDEN, name="embed")
    logits = ff.dense(t, VOCAB, use_bias=False, name="head")
    with pytest.raises(ValueError, match="no op named"):
        ff.tie_weights("nope", "kernel", "embed", "kernel")
    with pytest.raises(ValueError, match="no weight"):
        ff.tie_weights("head", "bias", "embed", "kernel")
    with pytest.raises(ValueError, match="shape mismatch"):
        ff.tie_weights("head", "kernel", "embed", "kernel", "same")
    ff.tie_weights("head", "kernel", "embed", "kernel", "transpose")
    with pytest.raises(ValueError, match="already tied"):
        ff.tie_weights("head", "kernel", "embed", "kernel", "transpose")
    with pytest.raises(ValueError, match="SOURCE of an existing tie"):
        # embed.kernel is the source of head's tie; demoting it to a
        # destination would orphan both storages
        ff.dense(t, VOCAB, use_bias=False, name="head2")
        ff.tie_weights("embed", "kernel", "head2", "kernel", "transpose")
    ff.compile(final_tensor=logits)
    with pytest.raises(ValueError, match="tied"):
        ff.set_weights("head", "kernel", np.zeros((HIDDEN, VOCAB), np.float32))
    with pytest.raises(ValueError, match="before compile"):
        ff.tie_weights("head2", "kernel", "embed", "kernel", "transpose")
