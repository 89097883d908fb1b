"""The cell `swa-mixed-lengths-saturated`: BENCHMARK.json's new entries and
the cell's files; `exaone_flops.py` against counts by hand (3712 M parameters
in the cut, 236 B published, 4096 B a token a layer, the window's FLOPs); the
kind `open_loop_serving_window` walked through its rehearsal; the seven
readers on a hand-made trace (times in ns) and `ctx`, and `None` where there
is nothing to read."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import exaone_flops as xf, exaone_trace as xt, spec

CELL = "swa-mixed-lengths-saturated"
CONFIG = "k-exaone-236b-a23b-serve"
NEW = ("swa_global_attn_device_share", "swa_window_attn_device_share",
       "swa_global_paged_hbm_share", "swa_window_paged_hbm_share",
       "swa_flash_window_roofline_share", "swa_flash_global_roofline_share",
       "swa_window_pages_held_share")
JOINED = ("tpot_p50_s", "serve_tokens_per_s", "decode_occupancy",
          "tpot_p90_s", "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
          "queue_wait_p90_s", "prefill_device_share", "sampler_device_share",
          "serve_unscoped_share", "ep_expert_hbm_share",
          "ep_experts_hit_share")
REDUCED = ["num_hidden_layers", "layer_types", "sliding_windows",
           "mlp_layer_types", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    w, entry = spec.find_workload(bench, CELL)
    return bench, w, entry, spec.load_config(spec.ROOT, entry), \
        spec.load_traffic(w["traffic"])


def test_benchmark_json_accepts_the_cell_and_finds_its_files(cell):
    bench, w, entry, cfg, traffic = cell
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, CELL, 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/"
        "config.json")
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "exaone_moe_lm", "exaone_moe", "serve")
    for key in ("assumed", "departures", "deployment", "tolerance_reasons",
                "left_out"):
        assert cfg[key]
    assert "EIGHT chips" in cfg["deployment"]
    for word in ("pre-norm", "per head", "no rotary", "selection bias"):
        assert any(word in a for a in cfg["assumed"]), word
    assert "num_nextn_predict_layers" in cfg["left_out"]
    cut = spec.cut_for(cfg, 1)
    assert cut["engine"] == {"serve_slots": 32, "kv_page_size": 128,
                             "kv_pages": 4096, "max_seq_len": 33792,
                             "prefix_cache": False, "prefill_chunk": 2048}
    assert cut["graph_seq_len"] == 32 * xf.window_of(cfg)
    assert sorted(cfg["tolerances"]) == sorted(cfg["tolerance_reasons"]) == [
        "emitted_margin_mean", "emitted_margin_pooled", "predict_rel_rms"]
    assert (cut["ffconfig"]["compute_dtype"],
            cut["ffconfig"]["master_dtype"]) == ("bfloat16", "bfloat16")
    assert traffic["kind"] == "open_loop_serving_window"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 1.1, "min": 256,
        "max": 32768}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 128,
        "max": 1024}
    assert traffic["drain_grace_s"] == 45 and "arrangement_seed" in traffic
    assert traffic["limits"] == {"ttft_s": 4.0, "tpot_s": 0.1, "share": 0.9}
    for kind, name in (("builders", cfg["builder"]),
                       ("reference", cfg["reference"]),
                       ("generators", traffic["kind"])):
        assert os.path.exists(os.path.join(spec.HERE, kind, name + ".py"))


def test_every_published_number_is_in_the_file_or_named_reduced(cell):
    cfg = cell[3]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    src = row["config"]
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in src.items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"]) == set(cfg["published"])
    for k in ("num_hidden_layers", "num_experts", "vocab_size",
              "num_nextn_predict_layers"):
        assert cfg["published"][k] == src[k]
    # the cut: the published layers 0-4, L L L G L with layer 0 dense
    for k in ("layer_types", "sliding_windows", "mlp_layer_types"):
        assert cfg[k] == src[k][:5]
    assert cfg["sliding_windows"] == [128, 128, 128, 0, 128]
    assert cfg["num_experts"] == cfg["experts_held"][1] == 16
    assert cfg["router_experts"] == src["num_experts"] == 128
    assert cfg["vocab_size"] * 8 == src["vocab_size"]
    # no width is cut
    for k in ("hidden_size", "head_dim", "num_attention_heads",
              "num_key_value_heads", "intermediate_size",
              "moe_intermediate_size", "num_experts_per_tok",
              "sliding_window"):
        assert cfg[k] == src[k]


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_s"
    mod = spec.load_module("layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        name, m["unit"], m["layer"], m["moves"], m["source"])
    # an untraced run, and a run of another model
    assert mod.read({"mode": "serve", "device": {"platform": "tpu"}}) is None
    assert mod.read({"mode": "serve", "stats_delta": {"decode_steps": 8},
                     "config": {"num_experts": 64}}) is None


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_metrics_that_read_it_as_they_are(cell, name):
    bench = cell[0]
    m = next(m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name)
    assert CELL in m["workloads"]
    paged = next(m for m in bench["per_layer"]
                 if m["name"] == "paged_attn_hbm_share")
    assert CELL not in paged["workloads"]   # one kind of paged kernel only


def test_published_size_and_the_cuts_bytes(cell):
    cfg = cell[3]
    attn = 6144 * 8192 * 2 + 2 * 6144 * 1024
    assert xf.attention_params(cfg) == attn + 2 * 128 + 6144
    assert round(attn / 1e6, 2) == 113.25
    assert xf.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert xf.dense_params(cfg) == 3 * 6144 * 18432 + 6144
    # the cut as it runs: 3712 M parameters = 7.42 GB in bf16
    total = xf.model_params(cfg)
    assert total == (5 * xf.attention_params(cfg) + xf.dense_params(cfg)
                     + 4 * (6144 * 128 + 128 + 37_748_736 + 6144
                            + 16 * 37_748_736) + 2 * 19200 * 6144 + 6144)
    assert round(total / 1e6) == 3712 and round(2 * total / 1e9, 2) == 7.42
    full = {**cfg, "vocab_size": 153600,
            "sliding_windows": [128, 128, 128, 0] * 12,
            "mlp_layer_types": ["dense"] + ["sparse"] * 47}
    # 236.6 B without the multi-token-prediction layer (left out)
    assert round(xf.model_params(full, experts=128) / 1e9, 1) == 236.6
    assert xf.cache_bytes_per_token(cfg) == 2 * 8 * 128 * 2 == 4096
    assert xf.layers_of(cfg) == {"window": 4, "global": 1}
    cut = spec.cut_for(cfg, 1)["engine"]
    pool = cut["kv_pages"] * cut["kv_page_size"] * 4096
    rings = (1 + cut["serve_slots"] * 2) * cut["kv_page_size"] * 4096 * 4
    assert round(pool / 1e9, 2) == 2.15 and round(rings / 1e9, 3) == 0.136
    # a uniform table: five layers a token
    assert round(5 * pool / 1e9, 1) == 10.7


def test_parameter_count_is_the_built_models_own(cell):
    cfg = cell[3]
    b = spec.load_module("builders", cfg["builder"])
    cut = spec.cut_for(cfg, 1)
    z = b.sizes_of(cfg, cut, rehearsal=True)
    ff, _, _ = b.build(cfg, cut, rehearsal=True)
    own = sum(int(v.size) for ws in ff.params.values() for v in ws.values())
    assert own == xf.model_params(z)


def test_yardsticks_against_counts_by_hand(cell):
    cfg = cell[3]
    assert xf.seen_pairs(5) == 15 and xf.seen_pairs(5, 128) == 15
    assert xf.seen_pairs(128, 128) == 128 * 129 // 2
    assert xf.seen_pairs(1000, 128) == sum(min(i + 1, 128)
                                           for i in range(1000))
    assert xf.flash_flops(cfg, 1000, "window") == 4 * 128 * 64 * 4 * sum(
        min(i + 1, 128) for i in range(1000))
    assert xf.flash_flops(cfg, 1000, "global") == 4 * 128 * 64 * 500500
    # 32 slots at 4.2 k tokens: 0.55 GB a step on the global layer, and the
    # four window layers' 128 keys a slot
    assert xf.paged_bytes(cfg, 32 * 4200, "global") == 32 * 4200 * 4096
    assert xf.paged_bytes(cfg, 32 * 128, "window") == 32 * 128 * 4096 * 4


def plane(name, **lines):
    return {"name": name,
            "lines": [{"name": k.replace("_", " "), "events": v}
                      for k, v in lines.items()]}


# one tick: a prefill program [2000, 4000) and a decode program
# [5000, 9000), the window [1000, 11000); a second decode program begins
# inside the window and ends after it
HOST = plane("/host:CPU", main=[
    ("bench.trace_window", 1000.0, 10000.0),
    ("ff.engine_step", 1000.0, 9500.0, {"tick": 1}),
    ("ff.prefill", 1500.0, 3000.0, {"bucket": 4096, "prompt_tokens": 3000,
                                    "program": "prefill_b4096"}),
    ("ff.decode_dispatch", 4800.0, 300.0, {
        "k": 8, "slots": 30, "context_tokens_global": 8 * 30 * 5000,
        "context_tokens_window": 8 * 30 * 128, "program": "decode_k8"}),
    ("ff.decode_dispatch", 9500.0, 300.0, {
        "k": 8, "slots": 32, "context_tokens_global": 1,
        "context_tokens_window": 1, "program": "decode_k8"}),
])
DEV = plane("/device:TPU:0", XLA_Ops=[
    ("%fusion.1 = bf16[2048,6144] fusion(...)", 2000.0, 2000.0),
    ("%fusion.2 = bf16[32,6144] fusion(...)", 5000.0, 4000.0),
    ("%fusion.3 = bf16[32,6144] fusion(...)", 10000.0, 3000.0),
], XLA_Modules=[
    ("jit_prefill(1)", 2000.0, 2000.0),
    ("jit_decode(2)", 5000.0, 4000.0),
    ("jit_decode(2)", 10000.0, 3000.0),
])


def test_reduce_window_pairs_whole_programs_with_their_spans():
    red = xt.reduce_window([HOST, DEV])
    assert red["decode"] == {"programs": 1,
                             "context_tokens_global": 8.0 * 30 * 5000,
                             "context_tokens_window": 8.0 * 30 * 128}
    assert red["prefill"] == {"programs": 1, "prompt_tokens": [3000]}
    assert xt.reduce_window([plane("/host:CPU", main=[]), DEV]) is None
    # spans without the counts (another model, the parent): nothing to read
    bare = plane("/host:CPU", main=[
        (e[0], e[1], e[2], {k: v for k, v in e[3].items()
                            if not k.startswith("context_tokens")})
        if len(e) > 3 else e for e in HOST["lines"][0]["events"]])
    assert xt.reduce_window([bare, DEV]) is None


def test_readers_turn_the_reduction_into_shares(cell):
    cfg = cell[3]
    counts = xt.reduce_window([HOST, DEV])
    scopes = {"whole": {("decode", "attn_global", "core"): 2000e-9,
                        ("decode", "attn_window", "core"): 800e-9,
                        ("prefill", "attn_global", "core"): 300e-9,
                        ("prefill", "attn_window", "core"): 200e-9},
              "chips": [{"busy_s": 9000e-9,
                         "rows": {("decode", "attn_global", "core"): 2000e-9,
                                  ("decode", "attn_global", "project"): 250e-9,
                                  ("prefill", "attn_global", "core"): 300e-9,
                                  ("decode", "attn_window", "core"): 800e-9,
                                  ("prefill", "attn_window", ""): 100e-9,
                                  ("decode", "moe", "experts"): 3000e-9}}]}
    ctx = {"trace": {"window_s": 1e-5}, "device_kind": "TPU v5 lite",
           "config": cfg, "cut": spec.cut_for(cfg, 1), "mode": "serve",
           "exaone_trace": {"counts": counts, "scopes": scopes},
           "scope_reduce": scopes,
           "stats_delta": {"decode_steps": 800,
                           "kv_page_steps_global": 800 * 1400,
                           "kv_page_steps_window": 800 * 64}}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("swa_global_attn_device_share") == pytest.approx(
        100 * 2550 / 9000)
    assert read("swa_window_attn_device_share") == pytest.approx(
        100 * 900 / 9000)
    assert read("swa_global_paged_hbm_share") == pytest.approx(
        100 * 8 * 30 * 5000 * 4096 / (2000e-9 * 819e9))
    assert read("swa_window_paged_hbm_share") == pytest.approx(
        100 * 8 * 30 * 128 * 4096 * 4 / (800e-9 * 819e9))
    assert read("swa_flash_global_roofline_share") == pytest.approx(
        100 * 4 * 128 * 64 * (3000 * 3001 // 2) / (300e-9 * 197e12))
    assert read("swa_flash_window_roofline_share") == pytest.approx(
        100 * 4 * 128 * 64 * 4 * (128 * 129 // 2 + 2872 * 128)
        / (200e-9 * 197e12))
    assert read("swa_window_pages_held_share") == pytest.approx(
        100 * 64 / 1400)
    # nothing under the scopes, or no whole program: left out, not raised
    scopes["whole"] = {}
    assert read("swa_global_paged_hbm_share") is None
    assert read("swa_flash_window_roofline_share") is None
    ctx["exaone_trace"] = None
    assert read("swa_window_paged_hbm_share") is None
    del ctx["stats_delta"]["kv_page_steps_window"]
    assert read("swa_window_pages_held_share") is None


def test_traffic_file_records_the_sweep_and_the_rule(cell):
    traffic = cell[4]
    knee = traffic["knee"]
    assert knee["sweep"] and all("rate_per_s" in r and "tpot_p50_s" in r
                                 for r in knee["sweep"])
    assert traffic["rate_per_s"] == pytest.approx(
        knee["factor"] * knee["knee_per_s"], rel=0.02)
    assert knee["factor"] == 1.15


def test_the_cell_walks_its_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "10",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 64, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL PASSED" in out.stdout
    assert "correct=True" in out.stdout and "check (b) long" in out.stdout
    assert "'swa_window_pages_held_share'" in out.stdout
