"""The benchmark's plain reference of OLMoE and the repo's copy of it give the
same logits; `moe_flops.py` against hand arithmetic at the published sizes;
the serving check that picks its reference by the configuration's name, on
the CPU at a tiny size (no device number)."""
import os
import sys
import types

import numpy as np
import pytest

from benchmark import moe_flops, spec
from benchmark.reference import olmoe as bench_ref

sys.path.insert(0, os.path.join(spec.ROOT, "tests"))
import reference_olmoe as repo_ref  # noqa: E402

SIZES = dict(num_hidden_layers=2, rope_theta=10000.0, rms_norm_eps=1e-5,
             num_experts_per_tok=2, norm_topk_prob=False, vocab_size=61)


def tiny_params(seed=0, d=32, heads=2, hd=16, experts=4, width=24, vocab=61):
    rs = np.random.RandomState(seed)
    w = lambda *s: (rs.randn(*s) * 0.2).astype(np.float32)  # noqa: E731
    one = lambda n: (1 + 0.2 * rs.randn(n)).astype(np.float32)  # noqa: E731
    p = {"tok_embed": {"kernel": w(vocab, d)}, "ln_f": {"scale": one(d)},
         "lm_head": {"kernel": w(d, vocab)}}
    for i in range(2):
        p[f"ln1_{i}"], p[f"ln2_{i}"] = {"scale": one(d)}, {"scale": one(d)}
        p[f"attn_{i}"] = {"wq": w(d, heads, hd), "wk": w(d, heads, hd),
                          "wv": w(d, heads, hd), "wo": w(heads, hd, d),
                          "q_norm": one(heads * hd),
                          "k_norm": one(heads * hd)}
        p[f"moe_{i}"] = {"router": w(d, experts),
                         "w_gate": w(experts, d, width),
                         "w_up": w(experts, d, width),
                         "w_down": w(experts, width, d)}
    return p


def test_both_copies_of_the_reference_are_the_same_text():
    with open(bench_ref.__file__) as a, open(repo_ref.__file__) as b:
        assert a.read() == b.read()


def test_both_copies_give_the_same_logits_and_routing():
    import jax

    params = jax.tree.map(np.asarray, tiny_params())
    toks = np.random.RandomState(1).randint(1, 61, (24,)).astype(np.int32)
    ra, rb = [], []
    a = np.asarray(bench_ref.forward(params, toks, SIZES, routing=ra))
    b = np.asarray(repo_ref.forward(params, toks, SIZES, routing=rb))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (24, 61) and np.isfinite(a).all()
    assert len(ra) == 2 and ra[0].shape == (24, 2)
    np.testing.assert_array_equal(np.asarray(ra[1]), np.asarray(rb[1]))


def test_reference_is_causal_and_weights_each_token_by_its_own_gates():
    import jax

    params = jax.tree.map(np.asarray, tiny_params())
    toks = np.random.RandomState(2).randint(1, 61, (16,)).astype(np.int32)
    full = np.asarray(bench_ref.forward(params, toks, SIZES))
    changed = toks.copy()
    changed[10:] = 7
    part = np.asarray(bench_ref.forward(params, changed, SIZES))
    # same float32 arithmetic on the same prefix rows
    np.testing.assert_allclose(part[:10], full[:10], atol=1e-5, rtol=0)
    renorm = np.asarray(bench_ref.forward(
        params, toks, {**SIZES, "norm_topk_prob": True}))
    assert np.abs(renorm - full).max() > 1e-2    # an error of order 1


@pytest.fixture(scope="module")
def published():
    bench = spec.load_benchmark()
    _, entry = spec.find_workload(bench, "moe-chat-steady")
    return spec.load_config(spec.ROOT, entry)


def test_moe_bytes_and_flops_against_hand_arithmetic(published):
    cfg = published
    assert moe_flops.expert_params(cfg) == 3 * 2048 * 1024 == 6291456
    assert moe_flops.moe_bytes(cfg, 1) == 12582912          # 12.58 MB
    assert moe_flops.moe_bytes(cfg, 64 * 8) == pytest.approx(6.44e9, rel=1e-3)
    assert moe_flops.moe_flops(cfg, 1) == 2 * 6291456
    # a 2048-token prefill bucket: 16384 assignments a layer
    assert moe_flops.moe_flops(cfg, 2048 * 8) == pytest.approx(2.06e11,
                                                               rel=1e-3)
    layer = moe_flops.layer_params(cfg)
    assert layer["experts"] == 64 * 6291456 == 402653184
    assert layer["total"] == 419569664                      # 419.6 M
    assert moe_flops.model_params(cfg, 16) == pytest.approx(6.92e9, rel=1e-3)
    assert moe_flops.model_params(cfg, 8) == pytest.approx(3.56e9, rel=1e-3)
    assert moe_flops.model_params(cfg) == moe_flops.model_params(
        cfg, cfg["num_hidden_layers"])


def test_serve_check_ref_runs_the_configurations_reference():
    """The check loads benchmark/reference/<config["reference"]>.py and hands
    it the builder's sizes: a tiny OLMoE through `ff.predict` and a served
    request pass; a reference told to renormalise the gates does not."""
    import flexflow_tpu as fft
    from benchmark.reference import serve_check_ref
    from flexflow_tpu.models.olmoe import olmoe_lm

    sizes = dict(SIZES, num_hidden_layers=1, vocab_size=61)
    ff = fft.FFModel(fft.FFConfig(batch_size=1, mesh_shape={"data": 1},
                                  seed=4))
    _, logits = olmoe_lm(ff, 1, seq_len=16, hidden=32, layers=1, heads=2,
                         kv_heads=2, num_experts=4, experts_per_token=2,
                         expert_hidden=24, vocab_size=61)
    ff.compile(final_tensor=logits)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=64)
    rs = np.random.RandomState(3)
    reqs = eng.run([rs.randint(1, 61, (n,)).astype(np.int32)
                    for n in (5, 9, 7, 12)], max_new_tokens=6)
    records = [{"state": r.state, "prompt_tokens": int(r.prompt.size),
                "tokens": len(r.tokens), "request": r} for r in reqs]
    lines = []

    def harness(s):
        return types.SimpleNamespace(
            config={"reference": "olmoe",
                    "tolerances": {"predict_rel_rms": 1e-5,
                                   "emitted_margin": 1e-4}},
            cut={"graph_seq_len": 16}, scale=1, rehearsal=False,
            args=types.SimpleNamespace(seed=11), log=lines.append,
            builder=types.SimpleNamespace(sizes_of=lambda c, cut, r: s))

    got = serve_check_ref.run(harness(sizes), ff, records)
    assert got["ok"], lines
    assert got["predict_rel_rms"] < 1e-5 and got["worst_margin"] <= 1e-4
    # float32 on both sides: the program and the reference route alike
    assert got["expert_flip_share"] == 0.0
    assert any("expert choice" in ln for ln in lines)
    wrong = serve_check_ref.run(
        harness({**sizes, "norm_topk_prob": True}), ff, records)
    assert not wrong["ok"] and wrong["predict_rel_rms"] > 1e-2
