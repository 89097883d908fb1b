"""BENCHMARK.json keeps the driver's rules, the harness refuses what the
driver refuses, and a configuration, traffic mix, builder or per-layer metric
ADDED to the directories is found without editing a file."""
import json
import os
import shutil

import pytest

from benchmark import spec


@pytest.mark.parametrize("bad", ["", "has space", "a,b", "a/b", ".lead",
                                 "-lead", "x" * 65, "grün", None])
def test_names_the_driver_refuses(bad):
    with pytest.raises(spec.SpecError):
        spec.check_name(bad)


@pytest.mark.parametrize("bad", ["", "tokens per s", "µs", "x" * 17,
                                 "a,b"])
def test_units_the_driver_refuses(bad):
    with pytest.raises(spec.SpecError):
        spec.check_unit(bad)


def test_good_names_and_units():
    for n in ("chat-steady", "internlm2-1.8b-serve", "_x", "9lives"):
        assert spec.check_name(n) == n
    for u in ("tokens/s", "%", "s", "GB", "count"):
        assert spec.check_unit(u) == u


def test_benchmark_json_meets_the_contract():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    assert all(0.01 <= m["bound"] <= 0.1 for m in e2e.values())
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        _, entry = spec.find_workload(bench, w["name"])
        config = spec.load_config(spec.ROOT, entry)
        assert entry["file"].startswith(tuple(bench["paths"]))
        assert entry["reduced"] == config["reduced"]
        for key in ("source", "reduced", "assumed", "departures", "builder",
                    "mode", "cuts"):
            assert key in config
        spec.cut_for(config, w["chips"])
        traffic = spec.load_traffic(w["traffic"])
        spec.load_module("generators", traffic["kind"])
        spec.load_module("builders", config["builder"])
        # every cell: setup_s, another end-to-end metric, a per-layer one
        assert len(spec.metrics_for(bench, "end_to_end", w["name"])) >= 2
        layer = spec.metrics_for(bench, "per_layer", w["name"])
        assert layer
        here = {m["name"] for m in spec.metrics_for(bench, "end_to_end",
                                                    w["name"])}
        assert all(m["moves"] in here for m in layer)
    for m in bench["per_layer"]:
        mod = spec.load_module("layer_metrics", m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["name"], m["unit"], m["layer"], m["moves"], m["source"])
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}


def test_no_width_is_reduced():
    widths = ("hidden_size", "intermediate", "latent", "state", "proj",
              "head", "expan", "experts_per")
    for c in spec.load_benchmark()["configs"]:
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in widths), key


def test_added_files_are_found_without_editing_any(tmp_path):
    """A later PR adds a traffic mix, a configuration, a per-layer metric
    and their BENCHMARK.json entries; nothing that exists is edited."""
    root = tmp_path / "repo"
    shutil.copytree(spec.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    here = str(root / "benchmark")
    bench = spec.load_benchmark()
    # the additions: files ...
    chat = spec.load_traffic("chat-steady")
    (root / "benchmark/traffic/chat-overload.json").write_text(
        json.dumps({**chat, "rate_per_s": 9.9}))
    cfg = spec.load_config(spec.ROOT, bench["configs"][0])
    cfg["name"] = "another-model"
    (root / "benchmark/configs/another-model.json").write_text(
        json.dumps(cfg))
    (root / "benchmark/layer_metrics/queue_wait_p50_s.py").write_text(
        'NAME, UNIT = "queue_wait_p50_s", "s"\n'
        'LAYER, MOVES, SOURCE = "serving engine", "tpot_p50_s", '
        '"host_clock"\n\n\ndef read(ctx):\n    return ctx.get("queue")\n')
    # ... and entries
    bench["configs"].append({"name": "another-model", "source": "paper",
                             "file": "benchmark/configs/another-model.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "another.overload",
                               "config": "another-model",
                               "traffic": "chat-overload", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "queue_wait_p50_s", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "serving engine",
                               "moves": "tpot_p50_s",
                               "workloads": ["another.overload"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    bench2 = spec.load_benchmark(str(root))
    w, entry = spec.find_workload(bench2, "another.overload")
    assert spec.load_config(str(root), entry)["name"] == "another-model"
    assert spec.load_traffic(w["traffic"], here=here)["rate_per_s"] == 9.9
    names = [m["name"] for m in spec.metrics_for(bench2, "per_layer",
                                                 "another.overload")]
    assert "queue_wait_p50_s" in names and "host_wait_share" not in names
    mod = spec.load_module("layer_metrics", "queue_wait_p50_s", here=here)
    assert mod.read({"queue": 0.25}) == 0.25
    # a reader that finds nothing returns nothing
    assert mod.read({}) is None
    with pytest.raises(spec.SpecError):
        spec.load_module("layer_metrics", "not_there", here=here)


def test_readers_return_nothing_outside_their_cells():
    serve_ctx = {"mode": "serve", "stats_delta": {"decode_steps": 10,
                                                  "occupied_slot_steps": 80},
                 "slots": 16, "window": {"tpot_p90_s": 0.02},
                 "compiles_in_window": 0}
    occ = spec.load_module("layer_metrics", "decode_occupancy")
    assert occ.read(serve_ctx) == pytest.approx(50.0)
    assert occ.read({"mode": "train"}) is None
    assert spec.load_module("layer_metrics", "train_mfu").read(
        serve_ctx) is None
    assert spec.load_module("layer_metrics", "host_wait_share").read(
        serve_ctx) is None
    assert spec.load_module("layer_metrics", "device_idle_share").read(
        serve_ctx) is None      # no trace: nothing to read
    err = spec.load_module("layer_metrics", "search_pred_error")
    assert err.read({"search_summary": {"predicted_step_s": 1.2},
                     "step_s": 0.6}) == pytest.approx(100.0)
    assert err.read({"search_summary": {"predicted_step_s": 0.3},
                     "step_s": 0.6}) == pytest.approx(50.0)
    assert err.read(serve_ctx) is None
    mfu = spec.load_module("layer_metrics", "train_mfu")
    cfg = spec.load_config(spec.ROOT, spec.load_benchmark()["configs"][1])
    ctx = {"mode": "train", "config": cfg, "seq": 4096, "layers": 2,
           "chips": 1, "device_kind": "TPU v5 lite",
           "device": {"platform": "tpu"}, "train_tokens_per_s": 20000.0}
    assert mfu.read(ctx) == pytest.approx(100 * 20000 * 3.624e9 / 197e12,
                                          rel=1e-3)
