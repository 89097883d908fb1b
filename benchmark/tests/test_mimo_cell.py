"""The cell `swa-sink-docqa-saturated`: BENCHMARK.json's new entries and the
cell's files; the configuration's arithmetic and `mimo_flops.py` against
counts by hand (3429.96 M parameters, 2560 B a token on a global layer and
5120 on a window one, 170 MB for a resident 32 k document); the three new
readers on a hand-made reduction, and `None` where there is nothing to read;
the kind `shared_doc_serving_window` walked through its rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import mimo_flops as mf, spec

CELL = "swa-sink-docqa-saturated"
CONFIG = "mimo-v2-flash-serve"
NEW = ("sink_global_paged_hbm_share", "sink_window_paged_hbm_share",
       "sink_attn_device_share")
JOINED = ("tpot_p50_s", "serve_tokens_per_s", "decode_occupancy",
          "tpot_p90_s", "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
          "prefill_device_share", "queue_wait_p90_s", "sampler_device_share",
          "serve_unscoped_share", "ep_expert_hbm_share",
          "ep_experts_hit_share", "prefix_hit_token_share",
          "snapshot_hit_share")
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]


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
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/"
        "config.json")
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "mimo_v2_lm", "mimo_v2", "serve")
    for key in ("assumed", "departures", "left_out", "deployment",
                "published", "tolerance_reasons"):
        assert cfg[key]
    assert "SIXTEEN chips" in cfg["deployment"]
    assert "multi_token_prediction_layers" in cfg["left_out"]
    cut = spec.cut_for(cfg, 1)
    eng = cut["engine"]
    assert eng == {"serve_slots": 48, "kv_page_size": 128, "kv_pages": 3072,
                   "max_seq_len": 33792, "prefix_cache": True,
                   "state_snapshots": 18, "decode_buckets": [16384, 32768],
                   "prefill_chunk": 2048}
    assert cut["graph_seq_len"] // cfg["sliding_window"] >= 32
    assert sorted(cfg["tolerances"]) == sorted(cfg["tolerance_reasons"]) == [
        "emitted_margin_mean", "predict_rel_rms", "ring_rel_rms"]
    assert traffic["kind"] == "shared_doc_serving_window"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["documents"] == [{"count": 12, "tokens": 16256},
                                    {"count": 4, "tokens": 32640}]
    assert traffic["question_tokens"] == {"dist": "uniform", "min": 16,
                                          "max": 112}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 256,
                                        "sigma": 0.6, "min": 64, "max": 1024}
    assert traffic["drain_grace_s"] == 45
    assert traffic["arrangement_seed"] == 4600000046
    assert traffic["limits"] == {"ttft_s": 2.0, "tpot_s": 0.1, "share": 0.9}
    for kind, name in (("builders", cfg["builder"]),
                       ("reference", cfg["reference"]),
                       ("generators", traffic["kind"])):
        assert os.path.exists(os.path.join(spec.HERE, kind, name + ".py"))


def test_a_seed_draws_the_questions_and_never_the_documents(cell):
    """The resident documents decide which held experts a request's tokens
    hit all its life, so they belong to the arrangement: two seeds share the
    documents, the due times and the lengths, and differ in every question."""
    import numpy as np

    from benchmark.generators import shared_doc_serving
    traffic = cell[4]
    gen = spec.load_module("generators", traffic["kind"])
    a, b = (gen.generate(traffic, seed, 4.0, 19072, 16)
            for seed in (1, 3000004702))
    assert len(a.docs) == 16
    assert all(np.array_equal(x, y) for x, y in zip(a.docs, b.docs))
    assert np.array_equal(a.due, b.due) and np.array_equal(a.max_new,
                                                           b.max_new)
    for k, (p, q) in enumerate(zip(a.prompts, b.prompts)):
        doc = a.docs[a.doc_of[k]]
        assert p.size == q.size and np.array_equal(p[:doc.size], doc)
        assert np.array_equal(q[:doc.size], doc)
        assert not np.array_equal(p[doc.size:], q[doc.size:])
    # the arrangement is `shared_doc_serving`'s; only the documents differ
    c = shared_doc_serving.generate(traffic, 1, 4.0, 19072, 16)
    assert np.array_equal(a.due, c.due)
    assert [p.size for p in a.prompts] == [p.size for p in c.prompts]
    assert not np.array_equal(a.docs[0], c.docs[0])
    assert all(np.array_equal(p[a.docs[d].size:], r[a.docs[d].size:])
               for d, p, r in zip(a.doc_of, a.prompts, c.prompts))


def test_every_published_number_is_in_the_file(cell):
    cfg = cell[3]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    assert cfg["source"] == row["source_url"]
    assert {k for k, v in row["config"].items() if cfg.get(k, "-") != v} \
        == set(REDUCED)
    # the cut keeps the published pattern's first seven entries
    assert cfg["hybrid_layer_pattern"] \
        == row["config"]["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert cfg["moe_layer_freq"] == row["config"]["moe_layer_freq"][:7]
    assert cfg["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert (cfg["router_experts"], cfg["experts_held"]) == (256, [0, 16])
    assert cfg["published"]["n_routed_experts"] == 256
    # no width is touched
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "v_head_dim", "num_attention_heads",
                "num_key_value_heads", "swa_num_key_value_heads",
                "num_experts_per_tok", "sliding_window"):
        assert cfg[key] == row["config"][key]
    assert cfg["rope_dim"] == int(0.334 * 192) // 2 * 2 == 64


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    # (a later cell may join the list: `in`, not `==`)
    assert CELL in m["workloads"] and m["moves"] == "tpot_p50_s"
    mod = spec.load_module("layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        name, m["unit"], m["layer"], m["moves"], m["source"])
    # an untraced run, and a run of another model (K-EXAONE's keys)
    assert mod.read({"mode": "serve", "device": {"platform": "tpu"}}) is None
    assert mod.read({"mode": "serve", "trace": {"window_s": 1.0},
                     "config": {"sliding_windows": [128, 0]}}) is None


@pytest.mark.parametrize("name", JOINED)
def test_the_cell_joins_the_metrics_whose_readers_read_it_as_it_is(
        cell, name):
    bench = cell[0]
    m = next(m for m in bench["end_to_end"] + bench["per_layer"]
             if m["name"] == name)
    # appended behind the cells the list had (a later cell may follow it)
    assert CELL in m["workloads"][1:]
    for other in ("swa_global_paged_hbm_share", "paged_attn_hbm_share",
                  "hybrid_paged_hbm_share"):
        assert CELL not in next(x for x in bench["per_layer"]
                                if x["name"] == other)["workloads"]


def test_counts_by_hand(cell):
    cfg = cell[3]
    q, o = 4096 * 64 * 192, 64 * 128 * 4096
    glob = q + 4096 * 4 * (192 + 128) + o + 4096
    win = q + 4096 * 8 * (192 + 128) + o + 64 + 4096
    assert mf.attention_params(cfg, "global") == glob
    assert mf.attention_params(cfg, "window") == win
    assert round(glob / 1e6, 2) == 89.13 and round(win / 1e6, 2) == 94.38
    expert = 3 * 4096 * 2048
    assert mf.expert_params(cfg) == expert
    layer = 4096 * 256 + 256 + 4096 + 16 * expert
    total = (2 * glob + 5 * win + 3 * 4096 * 16384 + 4096 + 6 * layer
             + 2 * 19072 * 4096 + 4096)
    assert mf.model_params(cfg) == total
    assert round(total / 1e6, 1) == 3430.0
    assert round(2 * total / 1e9, 2) == 6.86
    assert mf.layers_of(cfg) == {"window": 5, "global": 2}
    assert mf.cache_bytes_per_token(cfg, "global") == 2560
    assert mf.cache_bytes_per_token(cfg, "window") == 5120
    # a pool that padded the 192-wide key row to 256 lanes would hold
    assert mf.cache_bytes_per_token(cfg, "global", key_lanes=256) == 3072
    assert mf.paged_bytes(cfg, 1000, "global") == 1000 * 2560 * 2
    assert mf.paged_bytes(cfg, 128, "window") == 128 * 5120 * 5
    # a resident 32 k document: every token on two layers, one page on five
    assert mf.resident_bytes(cfg, [32640], 128) \
        == 32640 * 2560 * 2 + 128 * 5120 * 5 == 170393600
    # a uniform table would hold it on all seven at the window layers' width
    assert 7 * 5120 * 32640 == 1169817600


def test_parameter_count_is_the_built_models_own(cell):
    """At the rehearsal's size, through the cell's own builder; and the
    engine's pools are what the arithmetic says."""
    cfg = cell[3]
    builder = spec.load_module("builders", cfg["builder"])
    cut = spec.cut_for(cfg, 1)
    ff, _, _ = builder.build(cfg, cut, rehearsal=True)
    z = builder.sizes_of(cfg, cut, rehearsal=True)
    built = sum(int(v.size) for ws in ff.params.values() for v in ws.values())
    assert built == mf.model_params(z)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=8,
                                 max_seq_len=64, state_snapshots=2)
    st = eng.stats()
    f32 = 2     # the rehearsal computes in float32: twice bf16's bytes
    assert st["kv_bytes_per_token"] \
        == f32 * mf.cache_bytes_per_token(z, "global") * 1
    assert st["kv_window_pool_bytes"] == 2 * (1 + 2 * 2) * 8 * f32 \
        * mf.cache_bytes_per_token(z, "window")
    assert st["state_snapshot_pool_bytes"] == 2 * 3 * 8 * f32 \
        * mf.cache_bytes_per_token(z, "window")


def test_readers_turn_the_reduction_into_shares(cell):
    cfg = cell[3]
    counts = {"decode": {"programs": 2, "context_tokens_global": 8e6,
                         "context_tokens_window": 8 * 48 * 128.0},
              "prefill": {"programs": 0, "prompt_tokens": []}}
    scopes = {"whole": {("decode", "attn_global", "core"): 0.06,
                        ("decode", "attn_window", "core"): 0.004},
              "chips": [{"busy_s": 0.2,
                         "rows": {("decode", "attn_global", "core"): 0.07,
                                  ("decode", "attn_window", "project"): 0.01,
                                  ("prefill", "attn_window", "core"): 0.02,
                                  ("decode", "moe", ""): 0.05}}]}
    ctx = {"trace": {"window_s": 1.0}, "device_kind": "TPU v5 lite",
           "config": cfg, "cut": spec.cut_for(cfg, 1), "mode": "serve",
           "exaone_trace": {"counts": counts, "scopes": scopes},
           "scope_reduce": scopes}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("sink_global_paged_hbm_share") == pytest.approx(
        100 * 8e6 * 2560 * 2 / (0.06 * 819e9))
    assert read("sink_window_paged_hbm_share") == pytest.approx(
        100 * 8 * 48 * 128 * 5120 * 5 / (0.004 * 819e9))
    assert read("sink_global_paged_hbm_share") < 100
    assert read("sink_attn_device_share") == pytest.approx(100 * 0.10 / 0.2)
    scopes["whole"] = {}
    assert read("sink_global_paged_hbm_share") is None
    assert read("sink_window_paged_hbm_share") is None
    ctx["exaone_trace"] = None
    assert read("sink_global_paged_hbm_share") is None


def test_traffic_file_records_the_sweep_and_the_rule(cell):
    traffic = cell[4]
    knee = traffic["knee"]
    assert knee["sweep"] and all("rate_per_s" in r and "tpot_p50_s" in r
                                 for r in knee["sweep"])
    assert traffic["rate_per_s"] == pytest.approx(
        knee["factor"] * knee["knee_per_s"], rel=0.02)
    assert knee["factor"] == 1.15
    assert knee["what_sets_it"] and knee["find_again_when"]


def test_the_cell_walks_its_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 64, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL PASSED" in out.stdout
    assert "correct=True" in out.stdout and "check (c) the rings" in out.stdout
    assert "'snapshot_hit_share'" in out.stdout
    assert "6 of 6 admissions resumed from one" in out.stdout
