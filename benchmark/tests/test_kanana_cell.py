"""The cell `moe-mla-train-4k`: BENCHMARK.json's new entries and the cell's
files; `kanana_flops.py` against the built model's own parameter count and a
count made by hand; the kind `train_job_ref` walked through its rehearsal; the
five readers on a hand-made trace (times in ns) and `ctx`, and `None` where
there is nothing to read."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import kanana_flops as kf, spec, train_trace as tt

CELL = "moe-mla-train-4k"
NEW = ("ep_train_mfu", "mla_flash_roofline_share", "mla_train_device_share",
       "ep_train_moe_device_share", "ep_train_rows_held_share")
CALL = ', custom_call_target="tpu_custom_call"'
FWD = ("%flash_attention_fwd.7 = (bf16[64,4096,128]{2,1,0}, f32[64,4096,8]"
       "{2,1,0}) custom-call(bf16[64,4096,192]{2,1,0} %q)" + CALL)
DQ = ("%flash_attention_bwd_dq.7 = bf16[64,4096,192]{2,1,0} custom-call("
      "bf16[64,4096,192]{2,1,0} %q)" + CALL)
DKV = ("%flash_attention_bwd_dkv.7 = (bf16[64,4096,192]{2,1,0}, "
       "bf16[64,4096,128]{2,1,0}) custom-call(bf16[64,4096,192]{2,1,0} %q)"
       + CALL)
PROJ = "%fusion.12 = bf16[2,4096,32,192]{3,2,1,0} fusion(bf16[2,4096,2048] %a)"
GROUPED = ("%ragged-dot-none.3 = bf16[12288,768]{1,0} custom-call(bf16[12288,"
           "2048]{1,0} %rows)" + CALL)
HEAD = "%fusion.90 = f32[8192,16032]{1,0} fusion(bf16[8192,2048]{1,0} %h)"
ADAM = "%fusion.200 = f32[2048,6144]{1,0} fusion(f32[2048,6144]{1,0} %w)"
HLO = """
ENTRY %main {
  %fusion.12 = bf16[2,4096,32,192]{3,2,1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(step)/jit(main)/jvp(attn_3)/dot_general" source_file="x.py"}
  %flash_attention_fwd.7 = (bf16[64,4096,128]{2,1,0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/jvp(attn_3)/jvp(flash_attention_fwd)/pallas_call"}
  %flash_attention_bwd_dq.7 = bf16[64,4096,192]{2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/transpose(jvp(attn_3))/flash_attention_bwd_dq/pallas_call"}
  %flash_attention_bwd_dkv.7 = (bf16[64,4096,192]{2,1,0}) custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/transpose(jvp(attn_3))/flash_attention_bwd_dkv/pallas_call"}
  %ragged-dot-none.3 = bf16[12288,768]{1,0} custom-call(%rows), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jit(main)/jvp(moe_2)/while/body/ragged_dot"}
  %fusion.90 = f32[8192,16032]{1,0} fusion(%h), kind=kOutput, metadata={op_name="jit(step)/jit(main)/jvp(lm_head)/dot_general"}
  ROOT %fusion.200 = f32[2048,6144]{1,0} fusion(%w), kind=kLoop, metadata={op_name="jit(step)/jit(main)/adam/mul"}
}
"""
OPS = ("input", "tok_embed", "attn_3", "moe_2", "lm_head", "ln1_3")


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    w, entry = spec.find_workload(bench, CELL)
    return bench, w, entry, spec.load_config(spec.ROOT, entry), \
        spec.load_traffic(w["traffic"])


def test_benchmark_json_accepts_the_cell_and_finds_its_files(cell):
    bench, w, entry, cfg, traffic = cell
    assert (w["config"], w["traffic"], w["chips"]) == (
        "kanana-2-30b-a3b-train", CELL, 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert (cfg["builder"], cfg["reference"], cfg["mode"]) == (
        "kanana2_lm", "kanana2", "train")
    for key in ("assumed", "departures", "deployment", "tolerance_reasons"):
        assert cfg[key]
    assert "8 chips" in cfg["deployment"] or "EIGHT chips" in cfg["deployment"]
    cut = spec.cut_for(cfg, 1)
    assert (cut["graph_seq_len"], cut["ffconfig"]["batch_size"],
            cut["ffconfig"]["grad_accum_steps"]) == (4096, 2, 1)
    assert cut["ffconfig"]["mesh_shape"] == {"data": 1}
    assert (cut["ffconfig"]["compute_dtype"],
            cut["ffconfig"]["master_dtype"]) == ("bfloat16", "float32")
    assert cut["optimizer"]["type"] == "AdamOptimizer" \
        and cut["optimizer"]["alpha"] == 1e-4
    checked = {tuple(x) for x in cut["update_check_weights"]}
    assert {("attn_0", n) for n in ("w_q", "w_dkv", "kv_norm", "w_uk", "w_uv",
                                    "wo")} <= checked
    assert {("moe_1", n) for n in ("router", "w_gate", "w_down",
                                   "shared_up")} <= checked
    assert set(cfg["tolerances"]["adam_step1_rel"]) == {"attn", "moe"}
    for kind, name in (("builders", cfg["builder"]),
                       ("reference", cfg["reference"]),
                       ("generators", traffic["kind"])):
        spec.load_module(kind, name)
    assert traffic["kind"] == "train_job_ref" \
        and traffic["distinct_batches"] == 8
    # 7 cells of 24, one on four chips
    assert len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_every_published_number_is_in_the_file_or_named_reduced(cell):
    cfg = cell[3]
    catalog = os.path.join("/opt/skills/guides/model-configs",
                           "architectures.jsonl")
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert cfg["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if cfg.get(k, "-") != v}
    assert differs == set(cfg["reduced"])
    # the floors: four expert layers after the dense one, >= 8 experts, 1/8
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] == cfg["experts_held"][1] >= 8
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["router_experts"] == cfg["published"]["n_routed_experts"]


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    m = next(m for m in bench["per_layer"] if m["name"] == name)
    assert m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s"
    mod = spec.load_module("layer_metrics", name)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
        name, m["unit"], m["layer"], m["moves"], m["source"])
    assert m["layer"] in {x["layer"] for x in bench["per_layer"]
                          if x["name"] not in NEW}


def test_the_cell_joins_the_training_metrics_but_not_the_dense_mfu(cell):
    bench = cell[0]
    by = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in by["train_tokens_per_s"]["workloads"]
    assert CELL in by["host_wait_share"]["workloads"]
    assert CELL not in by["train_mfu"]["workloads"]
    got = {m["name"] for m in spec.metrics_for(bench, "per_layer", CELL)}
    assert got == set(NEW) | {"compiles_in_window", "host_wait_share"}


def test_parameter_count_is_the_built_models_own(cell):
    """576.0 M at the cut and 26.35 M an attention, from the configuration
    file, against the shapes `kanana2_lm` itself declares (no weight is
    drawn: the graph is built, not compiled)."""
    import numpy as np

    import flexflow_tpu as fft
    from flexflow_tpu.models.kanana2 import kanana2_lm

    cfg = cell[3]
    p = kf.param_counts(cfg)
    assert round(p["attention"] / 1e6, 2) == 26.35
    assert round(p["total"] / 1e6, 1) == 576.0
    ff = fft.FFModel(fft.FFConfig(batch_size=2, mesh_shape={"data": 1}))
    kanana2_lm(ff, 2, seq_len=128, layers=cfg["num_hidden_layers"],
               experts_held=tuple(cfg["experts_held"]),
               score_bias_std=cfg["seeded_score_bias_std"],
               vocab_size=cfg["vocab_size"])
    built = {op.name: sum(int(np.prod(w.shape)) for w in op.weight_specs())
             for op in ff.ops}
    assert sum(built.values()) == p["total"]
    assert built["attn_0"] == built["attn_4"] == p["attention"]
    assert built["moe_1"] == (p["shared"] + p["router"]
                              + cfg["experts_held"][1] * p["expert"])
    assert built["lm_head"] == p["head"] == built["tok_embed"]


def test_flops_against_a_count_by_hand(cell):
    cfg = cell[3]
    f = kf.forward_flops_per_token(cfg, 4096)
    # ISSUE 32's arithmetic, a token's forward at sequence 4096, in MFLOP
    by_hand = {"projections": 5 * 52.69, "core": 5 * 41.94,
               "dense_mlp": 75.50, "shared": 4 * 18.87, "routed": 4 * 7.08,
               "router": 4 * 0.524, "head": 65.67}
    for part, mflop in by_hand.items():
        assert f[part] / 1e6 == pytest.approx(mflop, rel=2e-3), part
    assert f["total"] / 1e6 == pytest.approx(720.2, rel=1e-3)
    assert kf.train_flops_per_token(cfg, 4096) == 3 * f["total"]
    core = kf.flash_flops(cfg, 2, 4096)
    pairs = 2 * 32 * 4096 * 4097 / 2
    assert core["fwd"] == 2 * pairs * 320 and core["bwd"] == 2 * core["fwd"]
    # the step's core, by the two routes: seq / 2 keys a token on average
    assert 5 * core["fwd"] / 8192 == pytest.approx(f["core"], rel=1e-3)


def test_kernels_and_scopes_are_told_apart_by_their_names():
    assert [tt.kernel_of(n) for n in (FWD, DQ, DKV, PROJ, GROUPED)] \
        == ["fwd", "bwd_dq", "bwd_dkv", None, None]
    assert tt.kernel_of("%jvp_attn_1__flash_attention_fwd.3 = x") == "fwd"
    scopes = tt.scopes_of(HLO, OPS)
    assert scopes == {"fusion.12": "attn", "flash_attention_fwd.7": "attn",
                      "flash_attention_bwd_dq.7": "attn",
                      "flash_attention_bwd_dkv.7": "attn",
                      "ragged-dot-none.3": "moe", "fusion.90": "lm_head"}


def planes():
    """One chip: two steps inside the window, each a projection, the three
    flash kernels, a grouped matmul, the head and the optimizer."""
    ops = []
    for t in (1000, 6000):
        ops += [(PROJ, t, 400), (FWD, t + 400, 1000),
                (GROUPED, t + 1400, 600), (HEAD, t + 2000, 500),
                (DQ, t + 2500, 900), (DKV, t + 3400, 1100),
                (ADAM, t + 4500, 300)]
    return ops


@pytest.fixture
def reduced(monkeypatch):
    ops = planes()
    monkeypatch.setattr(tt.sr, "_device", lambda p: (
        ops, [(1000, 5800), (6000, 10800)], None))
    monkeypatch.setattr(tt.sr, "_window", lambda p, o: (0, 11000))
    return tt.reduce_train(None, tt.scopes_of(HLO, OPS))


def test_reduce_train_books_kernels_and_scopes(reduced):
    red = reduced
    assert red["busy_s"] == pytest.approx(9600e-9)
    assert red["flash"]["fwd"] == {"calls": 2,
                                   "seconds": pytest.approx(2000e-9)}
    assert red["flash"]["bwd_dq"]["seconds"] == pytest.approx(1800e-9)
    assert red["flash"]["bwd_dkv"]["seconds"] == pytest.approx(2200e-9)
    assert red["scope_s"]["attn"] == pytest.approx(6800e-9)
    assert red["scope_s"]["moe"] == pytest.approx(1200e-9)
    assert red["scope_s"]["lm_head"] == pytest.approx(1000e-9)
    assert "adam" not in red["scope_s"]
    # no scopes handed over, no flash kernel in the trace: nothing to feed
    bare = tt.reduce_train(None, None)
    assert bare["scope_s"] is None and bare["flash"]


def test_readers_turn_the_reduction_into_shares(reduced, cell):
    cfg = cell[3]
    need = kf.flash_flops(cfg, 2, 4096)
    ctx = {"mode": "train", "trace": {"window_s": reduced["window_s"]},
           "train_trace": reduced, "sizes": cfg, "config": cfg, "seq": 4096,
           "tokens_per_step": 8192, "chips": 1, "device_kind": "TPU v5 lite",
           "device": {"platform": "tpu"}, "train_tokens_per_s": 30000.0,
           "last_step_breakdown": {"moe_steps": 8,
                                   "moe_assignments_total": 8 * 4 * 6144,
                                   "moe_experts_hit_total": 8 * 4 * 16,
                                   "moe_rows_max": 420}}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("ep_train_mfu") == pytest.approx(
        100 * 30000 * 2.16072e9 / 197e12, rel=1e-4)
    both = 2 * need["fwd"] + 2 * need["bwd"]
    assert read("mla_flash_roofline_share") == pytest.approx(
        100 * both / (6000e-9 * 197e12))
    assert read("mla_train_device_share") == pytest.approx(100 * 6800 / 9600)
    assert read("ep_train_moe_device_share") == pytest.approx(
        100 * 1200 / 9600)
    assert read("ep_train_rows_held_share") == pytest.approx(12.5)
    # a program that lacks what this PR adds: no named kernel, no scopes, no
    # routing counts, another configuration's sizes
    bare = {**ctx, "sizes": {"hidden_size": 4096}, "last_step_breakdown": {
                "host_wait_fraction": 0.002},
            "train_trace": {**reduced, "flash": None, "scope_s": None}}
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(bare) is None
    for name in NEW:        # an untraced or a serving run
        assert spec.load_module("layer_metrics", name).read(
            {"mode": "serve", "device": {"platform": "tpu"}}) is None


def test_train_job_ref_walks_its_rehearsal():
    out = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 64, out.stdout[-2000:] + out.stderr[-2000:]
    assert "REHEARSAL PASSED" in out.stdout
    assert "correct=True" in out.stdout
    assert "'ep_train_rows_held_share'" in out.stdout
