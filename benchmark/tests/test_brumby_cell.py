"""The cell `retention-docqa-saturated`: BENCHMARK.json's new entries and the
cell's files; `brumby_flops.py` against counts by hand (330.35 M a layer,
34.3 MB of state a layer and sequence, 6.42 GB of weights); the three new
readers on a hand-made reduction and `ctx`, and `None` where there is nothing
to read; the kind `shared_doc_serving_retention` walked through its
rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import brumby_flops as bf, spec

CELL = "retention-docqa-saturated"
CONFIG = "brumby-14b-base-serve"
NEW = ("retention_update_hbm_share", "retention_device_share",
       "retention_mlp_device_share")
JOINED = ("tpot_p50_s", "serve_tokens_per_s", "decode_occupancy",
          "tpot_p90_s", "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
          "prefill_device_share", "queue_wait_p90_s", "sampler_device_share",
          "serve_unscoped_share", "prefix_hit_token_share",
          "snapshot_hit_share", "setup_seat_warm_s")


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
    assert len(entry["source"]) <= 200
    # appended behind what was there (later PRs append behind it)
    names = [x["name"] for x in bench["workloads"]]
    assert names.index(CELL) > names.index("swa-sink-docqa-saturated")
    assert CONFIG in [x["name"] for x in bench["configs"]]
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
        "config.json")
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "brumby_lm", "brumby", "serve")
    for key in ("assumed", "departures", "deployment", "tolerance_reasons"):
        assert cfg[key]
    assert "EIGHT" in cfg["deployment"] and "both" in cfg["deployment"]
    assert any("retention_power 2" in a for a in cfg["assumed"])
    assert any("logsigmoid" in a for a in cfg["assumed"])
    assert any("eps_n" in a for a in cfg["assumed"])
    assert any("switch-over" in d for d in cfg["departures"])
    cut = spec.cut_for(cfg, 1)
    eng = cut["engine"]
    assert eng["prefix_cache"] is True and eng["kv_page_size"] == 128
    assert 16 <= eng["serve_slots"] <= 24 and eng["state_snapshots"] == 12
    assert eng["max_seq_len"] == cfg["max_position_embeddings"] == 32768
    assert eng["decode_buckets"] == [16384, 30720]
    assert sorted(cfg["tolerances"]) == sorted(cfg["tolerance_reasons"]) == [
        "emitted_margin", "predict_rel_rms", "state_rel_rms", "state_rel_rms_last"]
    assert traffic["kind"] == "shared_doc_serving_retention"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["documents"] == [{"count": 6, "tokens": 16256},
                                    {"count": 6, "tokens": 30592}]
    assert all(d["tokens"] % 128 == 0 for d in traffic["documents"])
    assert traffic["question_tokens"] == {"dist": "uniform", "min": 16,
                                          "max": 112}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 256,
                                        "sigma": 0.6, "min": 64, "max": 1024}
    assert 30592 + 112 + 1024 <= eng["max_seq_len"]
    assert traffic["drain_grace_s"] == 45 and "arrangement_seed" in traffic
    assert traffic["limits"] == {"ttft_s": 2.0, "tpot_s": 0.1, "share": 0.9}
    for kind, name in (("builders", cfg["builder"]),
                       ("reference", cfg["reference"]),
                       ("generators", traffic["kind"]),
                       ("reference", "serve_check_retention")):
        assert os.path.exists(os.path.join(spec.HERE, kind, name + ".py"))
    # the reference is in the repo twice, the same text
    with open(os.path.join(spec.HERE, "reference", "brumby.py")) as a, \
            open(os.path.join(spec.ROOT, "tests",
                              "reference_brumby.py")) as b:
        assert a.read() == b.read()


def test_every_published_number_is_in_the_file(cell):
    cfg = cell[3]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Brumby-14B-Base")
    assert cfg["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if cfg.get(k, "-") != v} \
        == {"num_hidden_layers"}
    assert cfg["published"] == row["config"]
    assert cfg["num_hidden_layers"] == 5 >= 4
    assert row["config"]["num_hidden_layers"] % cfg["num_hidden_layers"] == 0


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
    assert mod.read({"mode": "serve", "trace": {"window_s": 1.0},
                     "config": {"layer_types": ["mamba"]}}) is None
    assert mod.read({"mode": "serve", "trace": None,
                     "config": cell[3]}) is None


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_metrics_whose_readers_read_it_as_it_is(
        cell, name):
    bench = cell[0]
    m = next(m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name)
    # appended behind the cells that were there, nothing else moved
    assert m["workloads"].index(CELL) \
        > m["workloads"].index("hybrid-ssm-docqa-saturated")
    for other in ("ssm_device_share", "hybrid_update_hbm_share",
                  "hybrid_paged_hbm_share", "paged_attn_hbm_share"):
        assert CELL not in next(x for x in bench["per_layer"]
                                if x["name"] == other)["workloads"]


def test_counts_by_hand(cell):
    cfg = cell[3]
    mixer = 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8
    mlp = 3 * 5120 * 17408
    assert bf.mixer_matrix_params(cfg) == mixer
    assert bf.layer_matrix_params(cfg) == mixer + mlp == 330_342_400
    # ISSUE 50's 330.35 M (its four terms rounded to 10 k each)
    assert abs(bf.layer_matrix_params(cfg) - 330.35e6) < 1e4
    assert bf.vocab_params(cfg) == 151936 * 5120 == 777_912_320
    total = 5 * (mixer + mlp + 8 + 256 + 2 * 5120) + 2 * 777_912_320 + 5120
    assert bf.model_params(cfg) == total
    assert round(2 * total / 1e9, 2) == 6.42
    # the state: 8 KV heads x 65 diagonals x 128 rows x (128 values + z)
    assert bf.phi_rows(cfg) == 8320 == cfg["state_rows_per_kv_head"]
    assert 8256 <= bf.phi_rows(cfg) <= 9216
    assert bf.state_bytes_per_layer(cfg) == 8 * 8320 * 129 * 4 \
        == 34_344_960 == cfg["state_bytes_per_layer"]
    assert bf.snapshot_bytes(cfg) == 5 * 34_344_960
    eng = spec.cut_for(cfg, 1)["engine"]
    held = (2 * total + (eng["serve_slots"] + eng["state_snapshots"] + 1)
            * bf.snapshot_bytes(cfg))
    assert 12.5e9 < held < 13.5e9       # about 80 % of the chip
    # a decode step at full slots: the states there and back against the
    # weights (five layers and the head): about five eighths
    state = 2 * eng["serve_slots"] * bf.snapshot_bytes(cfg)
    weights = bf.decode_weight_bytes(cfg)
    assert round(weights / 1e9, 2) == 4.86
    assert 0.6 < state / (state + weights) < 0.66
    assert bf.update_rows_bytes(cfg, 10) == 10 * 8 * 3 * 8 * 128 * 4
    assert bf.update_flops(cfg, 1) == 8 * 8320 * 128 * 13


def test_parameter_count_is_the_built_models_own(cell):
    """At the rehearsal's size, through the cell's own builder; the engine
    over it holds no row."""
    cfg = cell[3]
    builder = spec.load_module("builders", cfg["builder"])
    cut = spec.cut_for(cfg, 1)
    ff, _, _ = builder.build(cfg, cut, rehearsal=True)
    z = builder.sizes_of(cfg, cut, rehearsal=True)
    built = sum(int(v.size) for ws in ff.params.values() for v in ws.values())
    assert built == bf.model_params(z)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=64, state_snapshots=2)
    st = eng.stats()
    assert st["state_bytes_per_slot"] == bf.snapshot_bytes(z)
    assert st["state_snapshot_pool_bytes"] == 3 * st["state_bytes_per_slot"]
    assert st["kv_pool_bytes"] == 0 and st["kv_bytes_per_token"] == 0


def test_readers_turn_the_reduction_into_shares(cell):
    cfg = cell[3]
    dec = {"programs": 1, "slot_steps": 192.0,
           "state_bytes": 2.0 * 8 * 24 * bf.snapshot_bytes(cfg),
           "context_token_steps": 0.0}
    scopes = {"whole": {("decode", "retention", "update"): 0.1,
                        ("decode", "mlp", ""): 0.05},
              "chips": [{"busy_s": 0.2,
                         "rows": {("decode", "retention", "update"): 0.1,
                                  ("decode", "retention", "project"): 0.01,
                                  ("prefill_hit", "retention", "scan"): 0.01,
                                  ("decode", "mlp", ""): 0.05,
                                  ("decode", "lm_head", ""): 0.01,
                                  ("decode", "sampler", ""): 0.02}}]}
    ctx = {"trace": {"window_s": 1.0}, "device_kind": "TPU v5 lite",
           "config": cfg, "cut": spec.cut_for(cfg, 1), "mode": "serve",
           "brumby_trace": {"decode": dec, "scopes": scopes},
           "scope_reduce": scopes}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("retention_device_share") == pytest.approx(100 * 0.12 / 0.2)
    assert read("retention_mlp_device_share") == pytest.approx(
        100 * 0.06 / 0.2)
    assert read("retention_update_hbm_share") == pytest.approx(
        100 * (dec["state_bytes"] + 5 * bf.update_rows_bytes(cfg, 192))
        / (0.1 * 819e9))
    scopes["whole"] = {}
    assert read("retention_update_hbm_share") is None
    ctx["brumby_trace"] = None
    assert read("retention_update_hbm_share") is None


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
