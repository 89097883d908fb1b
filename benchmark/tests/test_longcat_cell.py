"""The cell `mla-zeromoe-docqa-saturated`: BENCHMARK.json's new entries and the
cell's files; `longcat_flops.py` against counts by hand (638.9 M a layer
outside the experts, 5.17 B held here, the published 560 B from the same
function, 10240 B of cache a token); the five new readers on a hand-made
reduction and `ctx`, and `None` where there is nothing to read; the cell
walked through its rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import longcat_flops as lf, spec

CELL = "mla-zeromoe-docqa-saturated"
CONFIG = "longcat-flash-chat-serve"
NEW = ("mla_dense_core_roofline_share", "mla_dense_core_device_share",
       "zeromoe_expert_hbm_share", "zeromoe_zero_pick_share",
       "scmoe_dense_ffn_device_share")
JOINED = ("tpot_p50_s", "serve_tokens_per_s", "decode_occupancy",
          "tpot_p90_s", "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
          "prefill_device_share", "queue_wait_p90_s", "sampler_device_share",
          "serve_unscoped_share", "pallas_time_share",
          "prefix_hit_token_share", "setup_seat_warm_s")


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
    assert names.index(CELL) > names.index("retention-docqa-saturated")
    assert CONFIG in [x["name"] for x in bench["configs"]]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/"
        "main/config.json")
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "longcat_flash_lm", "longcat_flash", "serve")
    for key in ("assumed", "departures", "deployment", "tolerance_reasons"):
        assert cfg[key]
    assert "32" in cfg["deployment"] and "identity" in cfg["deployment"]
    assert any("(hidden_size / q_lora_rank)^0.5" in a for a in cfg["assumed"])
    assert any("seeded_score_bias_std" in a for a in cfg["assumed"])
    # every published width uncut
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"],
            cfg["router_experts"] + cfg["zero_expert_num"], cfg["moe_topk"],
            cfg["routed_scaling_factor"]) == (
        6144, 64, 1536, 512, 128, 64, 128, 12288, 2048, 768, 12, 6)
    assert cfg["experts_held"] == [0, 16] and cfg["n_routed_experts"] == 16
    cut = spec.cut_for(cfg, 1)
    eng = cut["engine"]
    assert eng["prefix_cache"] is True and eng["kv_page_size"] == 128
    assert eng["serve_slots"] == 32 and eng["max_seq_len"] == 33792
    assert sorted(cfg["tolerances"]) == sorted(cfg["tolerance_reasons"]) == [
        "emitted_margin_mean", "predict_rel_rms"]
    assert traffic["kind"] == "shared_doc_serving_arranged"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["documents"] == [{"count": 8, "tokens": 16256},
                                    {"count": 4, "tokens": 32640}]
    assert all(d["tokens"] % 128 == 0 for d in traffic["documents"])
    assert traffic["question_tokens"] == {"dist": "uniform", "min": 16,
                                          "max": 112}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 256,
                                        "sigma": 0.6, "min": 64, "max": 1024}
    assert 32640 + 112 + 1024 <= eng["max_seq_len"]
    assert "arrangement_seed" in traffic
    assert traffic["limits"] == {"ttft_s": 2.0, "tpot_s": 0.1, "share": 0.9}
    # the pool: the resident documents and the most the live requests add
    resident = 8 * 127 + 4 * 255
    assert resident == 2036 and resident + 32 * 9 <= eng["kv_pages"] == 2432
    for kind, name in (("builders", cfg["builder"]),
                       ("reference", cfg["reference"]),
                       ("generators", traffic["kind"])):
        assert os.path.exists(os.path.join(spec.HERE, kind, name + ".py"))
    # the reference is in the repo twice, the same text
    with open(os.path.join(spec.HERE, "reference", "longcat_flash.py")) as a, \
            open(os.path.join(spec.ROOT, "tests",
                              "reference_longcat_flash.py")) as b:
        assert a.read() == b.read()


def test_every_published_number_is_in_the_file(cell):
    cfg = cell[3]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LongCat-Flash-Chat")
    assert cfg["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert changed == set(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in changed}
    # the floors: a whole period and four layers, 8 experts, 1/8 vocabulary
    assert cfg["num_layers"] == 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "tpot_p50_s"
    mod = spec.load_module("layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        name, m["unit"], m["layer"], m["moves"], m["source"])
    # an empty context, an untraced run, a run of another model, and a run
    # of a program without the spans (the parent's engine)
    assert mod.read({}) is None
    assert mod.read({"mode": "serve", "device": {"platform": "tpu"}}) is None
    assert mod.read({"mode": "serve", "trace": {"window_s": 1.0},
                     "config": {"layer_types": ["mamba"]}}) is None
    assert mod.read({"mode": "serve", "trace": None,
                     "config": cell[3]}) is None
    assert mod.read({"mode": "serve", "trace": {"window_s": 1.0},
                     "config": cell[3], "longcat_trace": None,
                     "scope_reduce": None, "stats_delta": {}}) is None


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_metrics_whose_readers_read_it_as_it_is(
        cell, name):
    bench = cell[0]
    m = next(m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name)
    assert CELL in m["workloads"]
    # appended behind the cells that were there
    assert all(m["workloads"].index(CELL) > m["workloads"].index(c)
               for c in m["workloads"] if c != CELL)
    for other in ("ep_expert_hbm_share", "ep_experts_hit_share",
                  "mla_core_roofline_share", "dsa_device_share",
                  "paged_attn_hbm_share"):
        assert CELL not in next(x for x in bench["per_layer"]
                                if x["name"] == other)["workloads"]


def test_counts_by_hand(cell):
    cfg = cell[3]
    p = lf.layer_params(cfg)
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 64 * 128 * 6144)
    assert p["mla"] == mla == 90_570_752
    assert p["dense_ffn"] == 3 * 6144 * 12288 == 226_492_416
    assert p["router"] == 6144 * 768
    assert p["outside_experts"] == 2 * (mla + 226_492_416) + 6144 * 768
    assert round(p["outside_experts"] / 1e6, 1) == 638.8
    assert lf.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert p["layer"] == p["outside_experts"] + 16 * 37_748_736
    total = 4 * p["layer"] + 2 * 16384 * 6144
    assert lf.model_params(cfg) == total
    assert round(total / 1e9, 2) == 5.17 and round(2 * total / 1e9, 2) == 10.35
    # the published model from the same function
    assert round(lf.model_params(lf.published(cfg)) / 1e9) == 561
    # cache: two latent rows a layer, 640 stored lanes of 576
    assert lf.latent_row_bytes(cfg) == 1280
    assert lf.latent_row_bytes(cfg, stored=False) == 1152
    assert lf.cache_bytes_per_token(cfg) == 8 * 1280 == 10240
    eng = spec.cut_for(cfg, 1)["engine"]
    pool = eng["kv_pages"] * eng["kv_page_size"] * 10240
    assert round(pool / 1e9, 2) == 3.19
    assert 13.0e9 < 2 * total + pool < 14.0e9       # ISSUE 53: >= 13 GB held
    # the core: 109 FLOP/B of a stored row, under the v5e's ridge of 240
    assert lf.core_flops(cfg, 1) == 2 * 64 * (576 + 512)
    assert round(lf.core_flops(cfg, 1) / 1280) == 109
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # one slot a page: bandwidth-bound; 32 slots a page: compute-bound
    one = lf.core_bound_s(cfg, 128, 1, 128, peak)
    assert one == pytest.approx(128 * 1280 / 819e9)
    many = lf.core_bound_s(cfg, 32 * 128, 1, 128, peak)
    assert many == pytest.approx(32 * 128 * lf.core_flops(cfg, 1) / 197e12)
    assert lf.expert_bytes(cfg, 6) == 6 * 37_748_736 * 2


def test_parameter_count_is_the_built_models_own(cell):
    """At the rehearsal's size, through the cell's own builder; the engine
    over it holds two latent rows a layer and token."""
    cfg = cell[3]
    builder = spec.load_module("builders", cfg["builder"])
    cut = spec.cut_for(cfg, 1)
    ff, _, _ = builder.build(cfg, cut, rehearsal=True)
    z = builder.sizes_of(cfg, cut, rehearsal=True)
    matrices = sum(int(v.size) for ws in ff.params.values()
                   for v in ws.values() if v.ndim >= 2)
    assert matrices == lf.model_params(z)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=64)
    # float32 at the rehearsal's size: twice the bf16 bytes
    assert eng.stats()["kv_bytes_per_token"] == 2 * lf.cache_bytes_per_token(z)


def test_readers_turn_the_reduction_into_shares(cell):
    cfg = cell[3]
    dec = {"programs": 2, "row_steps": 512.0, "row_tokens": 512 * 21000.0,
           "distinct_pages": 16 * 2000.0, "experts_hit": 300.0,
           "zero_picks": 2000.0, "real_picks": 4144.0, "held_picks": 128.0}
    scopes = {"whole": {("decode", "attn_0", "core"): 0.05,
                        ("decode", "attn_3", "core"): 0.05,
                        ("decode", "attn_3", "project"): 0.01,
                        ("decode", "moe", "experts"): 0.04,
                        ("decode", "ffn_1", ""): 0.06},
              "chips": [{"busy_s": 0.4,
                         "rows": {("decode", "attn_0", "core"): 0.06,
                                  ("decode", "attn_3", "core"): 0.06,
                                  ("prefill_hit", "attn_2", "core"): 0.03,
                                  ("decode", "attn_3", "project"): 0.01,
                                  ("decode", "moe", "experts"): 0.04,
                                  ("decode", "moe", "zero"): 0.001,
                                  ("decode", "ffn_1", ""): 0.06,
                                  ("decode", "ffn_2", ""): 0.02,
                                  ("decode", "lm_head", ""): 0.01}}]}
    ctx = {"trace": {"window_s": 1.0}, "device_kind": "TPU v5 lite",
           "config": cfg, "cut": spec.cut_for(cfg, 1), "mode": "serve",
           "longcat_trace": {"decode": dec, "scopes": scopes},
           "scope_reduce": scopes, "stats_delta": {}}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("mla_dense_core_device_share") == pytest.approx(
        100 * 0.15 / 0.4)
    assert read("scmoe_dense_ffn_device_share") == pytest.approx(
        100 * 0.08 / 0.4)
    assert read("zeromoe_zero_pick_share") == pytest.approx(
        100 * 2000 / 6144)
    assert read("zeromoe_expert_hbm_share") == pytest.approx(
        100 * 300 * 37_748_736 * 2 / (0.04 * 819e9))
    flops_s = dec["row_tokens"] * 2 * 64 * 1088 / 197e12
    bytes_s = dec["distinct_pages"] * 128 * 1280 / 819e9
    assert read("mla_dense_core_roofline_share") == pytest.approx(
        100 * 8 * max(flops_s, bytes_s) / 0.1)
    # the engine's own counters win where the generator hands them on
    ctx["stats_delta"] = {"moe_zero_picks": 1, "moe_real_picks": 3}
    assert read("zeromoe_zero_pick_share") == pytest.approx(25.0)
    scopes["whole"] = {}
    assert read("mla_dense_core_roofline_share") is None
    assert read("zeromoe_expert_hbm_share") is None
    ctx["longcat_trace"] = None
    assert read("mla_dense_core_roofline_share") is None


def test_traffic_file_records_the_sweep_and_the_rule(cell):
    traffic = cell[4]
    knee = traffic["knee"]
    assert knee["sweep"] and all("rate_per_s" in r and "tpot_p50_s" in r
                                 for r in knee["sweep"])
    assert traffic["rate_per_s"] == pytest.approx(
        knee["factor"] * knee["knee_per_s"], rel=0.02)
    assert knee["factor"] == 1.15 and "find_again_when" in knee


def test_the_cell_walks_its_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 64, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL PASSED" in out.stdout
    assert "correct=True" in out.stdout
    assert "'expert_set_differs'" in out.stdout
