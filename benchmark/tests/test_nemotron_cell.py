"""The cell `ssm-latentmoe-chat-saturated`: BENCHMARK.json's new entries and
the cell's files; `nemotron_flops.py` against the published size (120.67 B,
12.77 B active), the cut's bytes (10.74 GB) and the built model's own
parameter count; the kind `open_loop_serving_state` walked through its
rehearsal; the six readers on a hand-made trace (times in ns) and `ctx`, and
`None` where there is nothing to read."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import nemotron_flops as nf, nemotron_trace as nt, spec
from benchmark.reference import serve_check_ref

CELL = "ssm-latentmoe-chat-saturated"
CONFIG = "nemotron-3-super-120b-a12b-serve"
NEW = ("ssm_device_share", "ssm_update_hbm_share", "ssm_scan_roofline_share",
       "latent_moe_device_share", "latent_expert_hbm_share",
       "latent_experts_hit_share")
JOINED = ("tpot_p50_s", "serve_tokens_per_s", "decode_occupancy",
          "tpot_p90_s", "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
          "queue_wait_p90_s", "prefill_device_share", "sampler_device_share",
          "serve_unscoped_share")
PATTERN = "MEMEMEM*EMEMEMEM*EMEME"


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
    # membership, never a count or a position: the next cell must not break
    # this file
    assert CELL in [x["name"] for x in bench["workloads"]]
    assert CONFIG in [x["name"] for x in bench["configs"]]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    assert cfg["published"]["num_hidden_layers"] == 88
    assert cfg["published"]["n_routed_experts"] == 512
    assert cfg["published"]["vocab_size"] == 131072
    assert cfg["published"]["hybrid_override_pattern"].startswith(PATTERN)
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "nemotron_h_lm", "nemotron_h", "serve")
    for key in ("assumed", "departures", "deployment", "tolerance_reasons"):
        assert cfg[key]
    assert "EIGHT chips" in cfg["deployment"]
    assert any("rotary" in a for a in cfg["assumed"])
    assert any("float32" in a for a in cfg["assumed"])
    assert any("multi-token" in d for d in cfg["departures"])
    cut = spec.cut_for(cfg, 1)
    assert cut["engine"] == {"serve_slots": 32, "kv_page_size": 128,
                             "kv_pages": 1056, "max_seq_len": 4096,
                             "prefix_cache": False,
                             "decode_buckets": [256, 512, 1024, 2048]}
    # check (a)'s sequence has the length checks (b) and (c) pad to: the
    # reference compiles for one shape
    assert cut["graph_seq_len"] == 2 * serve_check_ref.PAD_TO
    assert sorted(cfg["tolerances"]) == sorted(cfg["tolerance_reasons"]) == [
        "emitted_margin", "predict_rel_rms", "state_rel_rms"]
    assert (cut["ffconfig"]["compute_dtype"],
            cut["ffconfig"]["master_dtype"]) == ("bfloat16", "bfloat16")
    assert traffic["kind"] == "open_loop_serving_state"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                        "sigma": 0.8, "min": 128, "max": 2048}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 384,
                                        "sigma": 0.6, "min": 64, "max": 1024}
    assert traffic["drain_grace_s"] == 45 and "arrangement_seed" in traffic
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
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"])
    assert {k: row["config"][k] for k in differs} == cfg["published"]
    # the cut: the first 22 layers of the published pattern, 5 : 5 : 1
    assert cfg["hybrid_override_pattern"] == PATTERN \
        == row["config"]["hybrid_override_pattern"][:22]
    assert [PATTERN.count(c) for c in "ME*"] == [10, 10, 2]
    assert cfg["num_hidden_layers"] == len(PATTERN)
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] == 64
    assert cfg["router_experts"] == 512
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["attention_rope"] is False


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert CELL in m["workloads"] and m["moves"] == "tpot_p50_s"
    mod = spec.load_module("layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        name, m["unit"], m["layer"], m["moves"], m["source"])
    # an untraced run, and a run of another model
    assert mod.read({"mode": "serve", "device": {"platform": "tpu"}}) is None
    assert mod.read({"mode": "serve", "stats_delta": {"decode_steps": 8},
                     "config": {"num_experts": 64}}) is None


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_metrics_that_read_nothing_model_specific(
        cell, name):
    bench = cell[0]
    m = next(m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name)
    assert CELL in m["workloads"]


def test_published_size_and_the_cuts_bytes(cell):
    cfg = cell[3]
    full = {**cfg, **cfg["published"]}
    assert nf.mamba_params(full) == 109_640_064
    assert nf.attention_params(full) == 35_655_680
    assert nf.moe_shared_params(full) == 54_530_560
    assert nf.expert_params(full) == 2 * 1024 * 2688 == 5_505_024
    total = nf.model_params(full, experts=512)
    assert round(total / 1e9, 2) == 120.67
    assert total == (40 * 109_640_064 + 8 * 35_655_680
                     + 40 * (54_530_560 + 512 * 5_505_024)
                     + 2 * 131072 * 4096 + 4096)
    assert round(nf.model_params(full, active=True) / 1e9, 2) == 12.77
    # the cut as it runs: bf16
    assert round(2 * nf.model_params(cfg) / 1e9, 2) == 10.74
    assert nf.state_bytes_per_slot(cfg) == 4 * 128 * 64 * 128 + 2 * 3 * 10240
    cut = spec.cut_for(cfg, 1)["engine"]
    state = cut["serve_slots"] * 10 * nf.state_bytes_per_slot(cfg)
    pages = cut["kv_pages"] * cut["kv_page_size"] * 2 * 2 * 2 * 128 * 2
    assert round(state / 1e9, 2) == 1.36 and round(pages / 1e9, 2) == 0.28


def test_parameter_count_is_the_built_models_own(cell):
    cfg = cell[3]
    b = spec.load_module("builders", cfg["builder"])
    z = b.sizes_of(cfg, spec.cut_for(cfg, 1), rehearsal=True)
    z = {**z, "head_dim": z["hidden_size"] // z["num_attention_heads"]}
    ff, _, _ = b.build(cfg, spec.cut_for(cfg, 1), rehearsal=True)
    own = sum(int(v.size) for ws in ff.params.values() for v in ws.values())
    assert own == nf.model_params(z)


def test_yardsticks_against_counts_by_hand(cell):
    cfg = cell[3]
    assert nf.expert_bytes(cfg, 480) == 480 * 5_505_024 * 2
    per = 2 * (4 * 128 * 64 * 128 + 2 * 3 * 10240) + (
        2 * 10240 + 4 * 128 + 4 * 8192)
    assert nf.update_bytes(cfg, 256) == 256 * per
    # one row: C.B over a chunk for 8 groups, the masked product for 128
    # heads, the state in and out for 128 heads
    assert nf.scan_flops(cfg, 1) == (2 * 128 * 128 * 8 + 2 * 128 * 64 * 128
                                     + 4 * 64 * 128 * 128)
    assert nf.scan_bytes(cfg, 128) == (128 * (2 * (10240 + 8192) + 4 * 128)
                                       + 2 * 4 * 128 * 64 * 128)


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
    ("ff.prefill", 1500.0, 3000.0, {"bucket": 512, "scan_rows": 5120,
                                    "program": "prefill_b512"}),
    ("ff.decode_dispatch", 4800.0, 300.0, {
        "k": 8, "slots": 30, "state_bytes": 2 * 8 * 30 * 42557440,
        "program": "decode_k8"}),
    ("ff.record_tokens", 9100.0, 300.0, {"experts_hit": 3840,
                                        "assignments": 660}),
    ("ff.decode_dispatch", 9500.0, 300.0, {
        "k": 8, "slots": 32, "state_bytes": 1, "program": "decode_k8"}),
])
DEV = plane("/device:TPU:0", XLA_Ops=[
    ("%fusion.1 = bf16[512,18560] fusion(...)", 2000.0, 2000.0),
    ("%fusion.2 = f32[32,128,64] fusion(...)", 5000.0, 4000.0),
    ("%fusion.3 = f32[32,128,64] fusion(...)", 10000.0, 3000.0),
], XLA_Modules=[
    ("jit_prefill(1)", 2000.0, 2000.0),
    ("jit_decode(2)", 5000.0, 4000.0),
    ("jit_decode(2)", 10000.0, 3000.0),
])


def test_reduce_state_pairs_whole_programs_with_their_spans():
    red = nt.reduce_state([HOST, DEV])
    assert red["decode"] == {"programs": 1, "slot_steps": 240.0,
                             "state_bytes": 2.0 * 8 * 30 * 42557440}
    assert red["prefill"] == {"programs": 1, "scan_rows": 5120.0}
    assert nt.reduce_state([plane("/host:CPU", main=[]), DEV]) is None
    # spans without the counts (another model, the parent): nothing to read
    bare = plane("/host:CPU", main=[
        (e[0], e[1], e[2], {k: v for k, v in e[3].items()
                            if k not in ("state_bytes", "scan_rows")})
        if len(e) > 3 else e for e in HOST["lines"][0]["events"]])
    assert nt.reduce_state([bare, DEV]) is None


def test_readers_turn_the_reduction_into_shares(cell):
    cfg = cell[3]
    state = nt.reduce_state([HOST, DEV])
    scopes = {"whole": {("decode", "mamba", "update"): 2000e-9,
                        ("prefill", "mamba", "scan"): 400e-9},
              "chips": [{"busy_s": 9000e-9,
                         "rows": {("decode", "mamba", "update"): 2500e-9,
                                  ("prefill", "mamba", "scan"): 400e-9,
                                  ("decode", "moe", "experts"): 3000e-9,
                                  ("decode", "moe", "latent"): 600e-9,
                                  ("decode", "attn", "core"): 100e-9}}]}
    ctx = {"trace": {"window_s": 1e-5}, "device_kind": "TPU v5 lite",
           "config": cfg, "cut": spec.cut_for(cfg, 1), "mode": "serve",
           "nemotron_trace": {"state": state, "scopes": scopes},
           "scope_reduce": scopes,
           "moe_trace": {"decode": {"grouped_s": 3000e-9,
                                    "experts_hit": 3840.0}},
           "stats_delta": {"decode_steps": 800,
                           "moe_experts_hit": 64 * 10 * 800 * 0.75}}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("ssm_device_share") == pytest.approx(100 * 2900 / 9000)
    assert read("latent_moe_device_share") == pytest.approx(100 * 3600 / 9000)
    assert read("latent_experts_hit_share") == pytest.approx(75.0)
    assert read("latent_expert_hbm_share") == pytest.approx(
        100 * 3840 * 11010048 / (3000e-9 * 819e9))
    assert read("ssm_update_hbm_share") == pytest.approx(
        100 * 10 * nf.update_bytes(cfg, 240) / (2000e-9 * 819e9))
    flops, moved = nf.scan_flops(cfg, 5120), nf.scan_bytes(cfg, 5120)
    assert read("ssm_scan_roofline_share") == pytest.approx(
        100 * max(flops / 197e12, moved / 819e9) / 400e-9)
    # nothing under the scopes, or no whole program: left out, not raised
    scopes["whole"] = {}
    assert read("ssm_update_hbm_share") is None
    assert read("ssm_scan_roofline_share") is None
    ctx["nemotron_trace"] = None
    assert read("ssm_update_hbm_share") is None


def test_traffic_file_records_the_sweep_and_the_rule(cell):
    traffic = cell[4]
    knee = traffic["knee"]
    assert knee["sweep"] and all("rate_per_s" in r and "tpot_p50_s" in r
                                 for r in knee["sweep"])
    assert traffic["rate_per_s"] == pytest.approx(
        knee["factor"] * knee["knee_per_s"], rel=0.02)
    assert knee["factor"] in (1.15, 1.3)


def test_the_cell_walks_its_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 64, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL PASSED" in out.stdout
    assert "correct=True" in out.stdout and "check (c) state" in out.stdout
    assert "'latent_experts_hit_share'" in out.stdout
