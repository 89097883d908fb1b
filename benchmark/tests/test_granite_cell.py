"""The cell `hybrid-ssm-docqa-saturated`: BENCHMARK.json's new entries and the
cell's files; `granite_flops.py` against counts by hand (3.19 B parameters,
76.4 MB a snapshot, 8192 B of keys and values a token); the four new readers
on a hand-made trace (times in ns) and `ctx`, and `None` where there is
nothing to read; the kind `shared_doc_serving_state` walked through its
rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import granite_flops as gf, granite_trace as gt, spec
from benchmark.tests.test_nemotron_cell import plane

CELL = "hybrid-ssm-docqa-saturated"
CONFIG = "granite-4.0-h-micro-serve"
NEW = ("hybrid_update_hbm_share", "hybrid_paged_hbm_share",
       "hybrid_mlp_device_share", "snapshot_hit_share")
JOINED = ("tpot_p50_s", "serve_tokens_per_s", "decode_occupancy",
          "tpot_p90_s", "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
          "prefill_device_share", "queue_wait_p90_s", "sampler_device_share",
          "serve_unscoped_share", "prefix_hit_token_share",
          "ssm_device_share")


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
    assert CELL in [x["name"] for x in bench["workloads"]]
    assert CONFIG in [x["name"] for x in bench["configs"]]
    # nothing is cut, and the file says so
    assert entry["reduced"] == cfg["reduced"] == []
    assert "nothing cut" in entry["why"] and "nothing is cut" in \
        cfg["deployment"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/"
        "config.json")
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "granite_hybrid_lm", "granite_hybrid", "serve")
    for key in ("assumed", "departures", "deployment", "tolerance_reasons"):
        assert cfg[key]
    assert any("float32" in a for a in cfg["assumed"])
    assert any("nope" in a for a in cfg["assumed"])
    assert any("time_step_limit" in a for a in cfg["assumed"])
    cut = spec.cut_for(cfg, 1)
    eng = cut["engine"]
    assert eng["prefix_cache"] is True and eng["kv_page_size"] == 128
    assert eng["serve_slots"] % 8 == 0 and 16 <= eng["state_snapshots"]
    assert eng["decode_buckets"] == [8192, 16384]
    assert sorted(cfg["tolerances"]) == sorted(cfg["tolerance_reasons"]) == [
        "emitted_margin", "predict_rel_rms", "state_rel_rms"]
    assert traffic["kind"] == "shared_doc_serving_state"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["documents"] == [{"count": 12, "tokens": 8064},
                                    {"count": 4, "tokens": 16256}]
    assert traffic["question_tokens"] == {"dist": "uniform", "min": 16,
                                          "max": 112}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 192,
                                        "sigma": 0.6, "min": 32, "max": 768}
    assert traffic["drain_grace_s"] == 45 and "arrangement_seed" in traffic
    for kind, name in (("builders", cfg["builder"]),
                       ("reference", cfg["reference"]),
                       ("generators", traffic["kind"])):
        assert os.path.exists(os.path.join(spec.HERE, kind, name + ".py"))


def test_every_published_number_is_in_the_file(cell):
    cfg = cell[3]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if cfg.get(k, "-") != v} \
        == set()
    assert cfg["published"] == row["config"]
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == 40
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_s"
    mod = spec.load_module("layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        name, m["unit"], m["layer"], m["moves"], m["source"])
    # an untraced run, and a run of another model or of the parent's engine
    assert mod.read({"mode": "serve", "device": {"platform": "tpu"}}) is None
    assert mod.read({"mode": "serve", "stats_delta": {"prefix_lookups": 8},
                     "trace": {"window_s": 1.0},
                     "config": {"num_experts": 64}}) is None


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_metrics_whose_readers_read_it_as_it_is(
        cell, name):
    bench = cell[0]
    m = next(m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name)
    assert m["workloads"][-1] == CELL       # appended, nothing else moved
    for other in ("ssm_update_hbm_share", "ssm_scan_roofline_share",
                  "paged_attn_hbm_share"):
        assert CELL not in next(x for x in bench["per_layer"]
                                if x["name"] == other)["workloads"]


def test_counts_by_hand(cell):
    cfg = cell[3]
    # a Mamba layer: in-projection 2048 x (4096 + 4352 + 64), conv 4352 x 4
    # + 4352, dt_bias + A_log + D, the gated norm, out-projection
    mamba = (2048 * 8512 + 4352 * 5 + 3 * 64 + 4096 + 4096 * 2048)
    attn = 2048 * 64 * (32 + 8 + 8 + 32)
    mlp = 2048 * 16384 + 8192 * 2048
    assert gf.mamba_mixer_params(cfg) == mamba
    assert gf.attention_mixer_params(cfg) == attn
    assert gf.mlp_params(cfg) == mlp
    total = (36 * mamba + 4 * attn + 40 * (mlp + 2 * 2048)
             + 100352 * 2048 + 2048)
    assert gf.model_params(cfg) == total
    assert round(total / 1e9, 2) == 3.19
    assert round(2 * total / 1e9, 2) == 6.38
    # a snapshot: 36 x (H 64 x 64 x 128 float32 + conv tail 3 x 4352 bf16)
    assert gf.state_bytes_per_layer(cfg) == 64 * 64 * 128 * 4 + 3 * 4352 * 2
    assert gf.snapshot_bytes(cfg) == 36 * 2123264 == 76437504
    # keys and values of a token: 4 layers x 2 x 8 heads x 64 x 2 B
    assert gf.kv_bytes_per_token(cfg) == 8192
    # the update's rows: decay 64, dt x and y 2 x 4096, B and C 2 x 128, f32
    assert gf.update_rows_bytes(cfg, 10) == 10 * 4 * (64 + 8192 + 256)
    assert gf.decode_flops_per_token(cfg) == 2 * total


def test_parameter_count_is_the_built_models_own(cell):
    """At the rehearsal's size, through the cell's own builder."""
    cfg = cell[3]
    builder = spec.load_module("builders", cfg["builder"])
    cut = spec.cut_for(cfg, 1)
    ff, _, _ = builder.build(cfg, cut, rehearsal=True)
    z = builder.sizes_of(cfg, cut, rehearsal=True)
    built = sum(int(v.size) for ws in ff.params.values() for v in ws.values())
    assert built == gf.model_params(z)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=64, state_snapshots=2)
    st = eng.stats()
    assert st["state_bytes_per_slot"] == gf.snapshot_bytes(z) \
        + 2 * 2 * (z["mamba_d_conv"] - 1) * gf.conv_dim(z)  # f32 tail here
    assert st["state_snapshot_pool_bytes"] == 3 * st["state_bytes_per_slot"]
    assert st["kv_bytes_per_token"] == gf.kv_bytes_per_token(z, bytes_per=4)


# one tick: a decode program [5000, 9000), the window [1000, 11000); a
# second decode program begins inside the window and ends after it
HOST = plane("/host:CPU", main=[
    ("bench.trace_window", 1000.0, 10000.0),
    ("ff.engine_step", 1000.0, 9500.0, {"tick": 1}),
    ("ff.decode_dispatch", 4800.0, 300.0, {
        "k": 8, "slots": 30, "context_tokens": 300000,
        "state_bytes": 2 * 8 * 30 * 76437504, "program": "decode_k8"}),
    ("ff.decode_dispatch", 9500.0, 300.0, {
        "k": 8, "slots": 32, "context_tokens": 320000, "state_bytes": 1,
        "program": "decode_k8"}),
])
DEV = plane("/device:TPU:0", XLA_Ops=[
    ("%fusion.2 = f32[48,64,64] fusion(...)", 5000.0, 4000.0),
    ("%fusion.3 = f32[48,64,64] fusion(...)", 10000.0, 3000.0),
], XLA_Modules=[
    ("jit_decode(2)", 5000.0, 4000.0),
    ("jit_decode(2)", 10000.0, 3000.0),
])


def test_reduce_decode_pairs_whole_programs_with_their_spans():
    red = gt.reduce_decode([HOST, DEV])
    # the second program ends after the window: not counted
    assert red == {"programs": 1, "slot_steps": 240.0,
                   "state_bytes": 2.0 * 8 * 30 * 76437504,
                   "context_token_steps": 8 * 300000.0}
    assert gt.reduce_decode([plane("/host:CPU", main=[]), DEV]) is None
    bare = plane("/host:CPU", main=[
        (e[0], e[1], e[2], {k: v for k, v in e[3].items()
                            if k != "state_bytes"})
        if len(e) > 3 else e for e in HOST["lines"][0]["events"]])
    assert gt.reduce_decode([bare, DEV]) is None


def test_readers_turn_the_reduction_into_shares(cell):
    cfg = cell[3]
    dec = gt.reduce_decode([HOST, DEV])
    scopes = {"whole": {("decode", "mamba", "update"): 2000e-9,
                        ("decode", "attn", "core"): 500e-9},
              "chips": [{"busy_s": 9000e-9,
                         "rows": {("decode", "mamba", "update"): 2500e-9,
                                  ("decode", "mlp", ""): 1800e-9,
                                  ("prefill", "mlp", ""): 900e-9,
                                  ("decode", "attn", "core"): 500e-9}}]}
    ctx = {"trace": {"window_s": 1e-5}, "device_kind": "TPU v5 lite",
           "config": cfg, "cut": spec.cut_for(cfg, 1), "mode": "serve",
           "granite_trace": {"decode": dec, "scopes": scopes},
           "scope_reduce": scopes,
           "stats_delta": {"prefix_lookups": 40, "state_snapshot_hits": 39}}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("hybrid_mlp_device_share") == pytest.approx(100 * 2700 / 9000)
    assert read("ssm_device_share") == pytest.approx(100 * 2500 / 9000)
    assert read("snapshot_hit_share") == pytest.approx(97.5)
    assert read("hybrid_update_hbm_share") == pytest.approx(
        100 * (dec["state_bytes"] + 36 * gf.update_rows_bytes(cfg, 240))
        / (2000e-9 * 819e9))
    assert read("hybrid_paged_hbm_share") == pytest.approx(
        100 * 8 * 300000 * 8192 / (500e-9 * 819e9))
    scopes["whole"] = {}
    assert read("hybrid_update_hbm_share") is None
    assert read("hybrid_paged_hbm_share") is None
    ctx["granite_trace"] = None
    assert read("hybrid_update_hbm_share") is None
    # the parent's engine counts no snapshots: nothing, not a raise
    ctx["stats_delta"] = {"prefix_lookups": 40}
    assert read("snapshot_hit_share") is None


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
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 64, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL PASSED" in out.stdout
    assert "correct=True" in out.stdout and "check (c) state" in out.stdout
    assert "'snapshot_hit_share'" in out.stdout
    assert "6 of 6 admissions resumed from one" in out.stdout
