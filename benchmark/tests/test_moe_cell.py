"""The cell `moe-chat-steady`: BENCHMARK.json's new entries are accepted and
the cell's files are found by name; the generator that picks the reference
re-exports what `knee_sweep.py` calls; the three MoE readers on hand-made
counters and a hand-made trace (times in ns)."""
import pytest

from benchmark import moe_trace as mt, spec

CELL = "moe-chat-steady"
GROUPED = ('%ragged-dot-none = bf16[256,1024]{1,0} custom-call(s32[1]{0} %a, '
           'bf16[256,2048]{1,0} %x, bf16[64,2048,1024]{2,1,0} %w), '
           'custom_call_target="tpu_custom_call"')
LAYOUT = ('%ragged-dot-metadata = (s32[65]{0}, s32[64]{0}) custom-call('
          's32[64]{0} %gs), custom_call_target="tpu_custom_call"')
# what a Pallas grouped matmul called under the op's scope would be named (the
# paged kernel is `attn_<i>.<n>` the same way)
PALLAS = ('%moe_3.7 = bf16[256,1024]{1,0} custom-call(s32[64]{0} %gs, '
          'bf16[256,2048]{1,0} %x, bf16[64,2048,1024]{2,1,0} %w), '
          'custom_call_target="tpu_custom_call"')
ATTN = ('%attn_0_.1 = bf16[32,1,16,128]{3,2,1,0} custom-call(s32[32,32]{1,0} '
        '%a), custom_call_target="tpu_custom_call"')


def test_benchmark_json_accepts_the_cell_and_finds_its_files():
    bench = spec.load_benchmark()
    w, entry = spec.find_workload(bench, CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (
        "olmoe-1b-7b-serve", CELL, 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == ("https://huggingface.co/allenai/OLMoE-1B-7B-"
                               "0125-Instruct/blob/main/config.json")
    cfg = spec.load_config(spec.ROOT, entry)
    cut = spec.cut_for(cfg, 1)
    assert cfg["builder"] == "olmoe_lm" and cfg["reference"] == "olmoe"
    assert cut["engine"]["serve_slots"] == 32
    assert cut["engine"]["max_seq_len"] == cfg["max_position_embeddings"]
    builder = spec.load_module("builders", cfg["builder"])
    z = builder.sizes_of(cfg, cut)
    assert z["num_hidden_layers"] == cut["model"]["num_hidden_layers"] \
        == cfg["num_hidden_layers"] < cfg["published"]["num_hidden_layers"]
    assert builder.sizes_of(cfg, cut, True)["vocab_size"] == 512
    spec.load_module("reference", cfg["reference"]).forward
    traffic = spec.load_traffic(w["traffic"])
    assert traffic["kind"] == "open_loop_serving_ref"
    assert traffic["arrivals"] == "jittered"
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                        "sigma": 0.8, "min": 32, "max": 2048}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 192,
                                        "sigma": 0.7, "min": 16, "max": 768}
    assert traffic["limits"] == {"ttft_s": 1.0, "tpot_s": 0.05, "share": 0.9}
    # judged on what ISSUE 25 names
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    # 0.7 x the knee that was found; ISSUE 25's 200 requests would need
    # 0.83 x (the traffic file says so)
    assert traffic["rate_per_s"] == pytest.approx(
        0.7 * traffic["knee"]["knee_per_s"])
    assert round(traffic["rate_per_s"] * bench["run_seconds"]) == 170
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", CELL)}
    assert e2e == {"tpot_p50_s", "serve_tokens_per_s", "setup_s"}
    layer = {m["name"] for m in spec.metrics_for(bench, "per_layer", CELL)}
    assert {"moe_device_share", "moe_expert_hbm_share",
            "moe_experts_hit_share", "decode_occupancy", "tpot_p90_s",
            "ttft_p90_s", "device_idle_share", "tick_idle_p50_s",
            "queue_wait_p90_s", "compiles_in_window"} == layer


@pytest.mark.parametrize("name", ["moe_device_share", "moe_expert_hbm_share",
                                  "moe_experts_hit_share"])
def test_new_metrics_are_read_in_this_cell_only(name):
    bench = spec.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tpot_p50_s"
    # a run that was not traced, or a program without the counters (the
    # parent commit), reports nothing and raises nothing
    mod = spec.load_module("layer_metrics", name)
    assert mod.read({"mode": "serve", "trace": None, "stats_delta": {
        "decode_steps": 80}, "config": {}, "cut": {}}) is None


def test_generator_reexports_what_knee_sweep_calls():
    from benchmark.generators import open_loop_serving as base

    gen = spec.load_module("generators", "open_loop_serving_ref")
    for name in ("build_engine", "warm", "drive", "latency_metrics",
                 "attainment"):
        assert getattr(gen, name) is getattr(base, name)
    assert gen.run is not base.run
    sched = gen.generate(spec.load_traffic(CELL), 3000002501, 51.0, 50304)
    assert len(sched.prompts) == 170 and sched.max_new.min() >= 16
    assert max(p.size for p in sched.prompts) <= 2048


def test_the_arrangement_is_the_files_and_the_tokens_are_the_seeds():
    """The driver refused the cell while `--seed` drew the arrangement
    (`tpot_p50_s` spread wider than its bound): the traffic file now names
    it, and a seed draws the prompts' tokens only."""
    import numpy as np

    from benchmark.generators import open_loop_serving as base

    gen = spec.load_module("generators", "open_loop_serving_ref")
    traffic = spec.load_traffic(CELL)
    fixed = traffic["arrangement_seed"]
    want = base.generate(traffic, fixed, 51.0, 50304)
    # seeds as large as the driver's (over 32 signed bits)
    a, a2, b = (gen.generate(traffic, s, 51.0, 50304)
                for s in (2**31 + 7, 2**31 + 7, 3000002501))
    for s in (a, b):
        assert np.array_equal(s.due, want.due)
        assert np.array_equal(s.max_new, want.max_new)
        assert [p.size for p in s.prompts] == [p.size for p in want.prompts]
        assert all(p.dtype == np.int32 and p.min() >= 1 and p.max() < 50304
                   for p in s.prompts)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, a2.prompts))
    assert not any(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts)
                   if x.size >= 64)
    # the rehearsal's scale reaches the lengths of the fixed arrangement too
    small = gen.generate(traffic, 3, 51.0, 512, scale=8)
    assert max(p.size for p in small.prompts) <= 2048 // 8


def test_experts_hit_share_from_the_engines_counters():
    mod = spec.load_module("layer_metrics", "moe_experts_hit_share")
    ctx = {"stats_delta": {"decode_steps": 100, "moe_experts_hit": 38400},
           "config": {"num_experts": 64, "num_hidden_layers": 16},
           "cut": {"model": {"num_hidden_layers": 8}}}
    assert mod.read(ctx) == pytest.approx(75.0)     # 38400 / (64 x 8 x 100)
    ctx["cut"] = {}
    assert mod.read(ctx) == pytest.approx(37.5)


def plane(name, **lines):
    return {"name": name,
            "lines": [{"name": k.replace("_", " "), "events": v}
                      for k, v in lines.items()]}


def op(name, start, dur):
    return (name, start, dur)


# one tick: a prefill program [2000, 4000) and a decode program
# [5000, 9000), the window [1000, 11000)
HOST = plane("/host:CPU", main=[
    ("bench.trace_window", 1000.0, 10000.0),
    ("ff.engine_step", 1000.0, 9500.0, {"tick": 1}),
    ("ff.prefill", 1500.0, 3000.0, {"assignments": 4096,
                                    "experts_hit": 64}),
    ("ff.decode_dispatch", 4800.0, 300.0, {"k": 8, "slots": 2}),
    ("ff.token_fetch", 5100.0, 4000.0, {}),
    ("ff.record_tokens", 9100.0, 300.0, {"experts_hit": 500,
                                        "assignments": 1024}),
])
DEV = plane("/device:TPU:0", XLA_Ops=[
    op("%fusion.1 = bf16[1,2048] fusion(...)", 2000.0, 500.0),
    op(GROUPED, 2500.0, 1000.0),
    op("%fusion.7 = f32[512,64] fusion(...)", 3500.0, 500.0),
    op("%while.3 = (s32[]) while(...)", 5000.0, 4000.0),     # the scan
    op(ATTN, 5000.0, 1000.0),
    op("%sort.5 = (f32[32,64], s32[32,64]) sort(...)", 6000.0, 250.0),
    op(LAYOUT, 6250.0, 250.0),
    op(GROUPED, 6500.0, 1500.0),
    op("%fusion.9 = f32[32,50304] fusion(...)", 8000.0, 1000.0),
], XLA_Modules=[
    ("jit_prefill(1)", 2000.0, 2000.0),
    ("jit_decode(2)", 5000.0, 4000.0),
])


@pytest.mark.parametrize("name, is_moe", [
    (GROUPED, True), (LAYOUT, True), (PALLAS, True),
    (PALLAS.replace("%moe_3.7", "%jvp_moe_3_.7"), True),
    (ATTN, False),
    # an op that only READS a grouped matmul's result is not one
    ("%select.4 = bf16[256,2048] fusion(bf16[256,2048] %ragged-dot-none.2)",
     False),
    # a fusion under the scope is no kernel; `remoe_1` is another scope
    ("%moe_3.9 = bf16[256,64] fusion(bf16[256,2048] %x)", False),
    (PALLAS.replace("%moe_3.7", "%remoe1.7"), False),
])
def test_moe_ops_are_mosaic_calls_named_ragged_dot_or_after_the_scope(
        name, is_moe):
    assert mt.is_grouped_matmul(name) is is_moe


def test_reduce_moe_books_own_time_and_pairs_programs_with_their_counts():
    red = mt.reduce_moe([HOST, DEV])
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["busy_s"] == pytest.approx(6000e-9)
    # prefill: grouped 1000; decode: layout 250 + grouped 1500 (the while's
    # own time is what its children leave)
    assert red["moe_s"] == pytest.approx(2750e-9)
    assert list(red["by_op"])[0].startswith("ragged-dot-none")
    assert red["decode"] == {"programs": 1, "grouped_s": pytest.approx(
        1750e-9), "experts_hit": 500.0, "assignments": 1024.0}
    assert red["prefill"] == {"programs": 1, "grouped_s": pytest.approx(
        1000e-9), "experts_hit": 64.0, "assignments": 4096.0}
    assert mt.reduce_moe([plane("/host:CPU", main=[]), DEV]) is None


def test_a_pallas_kernel_under_the_moe_scope_is_read_by_the_same_metrics():
    """The kernel swap PERF.md queues (a Pallas grouped matmul in place of
    `ragged_dot`) must not need a new yardstick: same events, the kernel
    named after its `moe_<i>` scope, same reduction."""
    swapped = plane("/device:TPU:0", **{
        ln["name"].replace(" ", "_"): [
            (PALLAS if e[0] == GROUPED else e[0], *e[1:])
            for e in ln["events"] if e[0] != LAYOUT]
        for ln in DEV["lines"]})
    red = mt.reduce_moe([HOST, swapped])
    assert red["moe_s"] == pytest.approx(2500e-9)
    assert list(red["by_op"])[0].startswith("moe_N.7")
    assert red["decode"]["grouped_s"] == pytest.approx(1500e-9)
    assert red["prefill"]["grouped_s"] == pytest.approx(1000e-9)


def test_readers_turn_the_reduction_into_shares():
    red = mt.reduce_moe([HOST, DEV])
    ctx = {"trace": {"window_s": red["window_s"]}, "moe_trace": red,
           "device_kind": "TPU v5 lite",
           "config": {"hidden_size": 2048, "intermediate_size": 1024}}
    share = spec.load_module("layer_metrics", "moe_device_share").read(ctx)
    assert share == pytest.approx(100 * 2750 / 6000)
    hbm = spec.load_module("layer_metrics", "moe_expert_hbm_share").read(ctx)
    assert hbm == pytest.approx(100 * 500 * 12582912 / (1750e-9 * 819e9))
    # a program whose spans carry no counts (the parent): nothing
    bare = dict(red, decode=dict(red["decode"], experts_hit=None))
    assert spec.load_module("layer_metrics", "moe_expert_hbm_share").read(
        {**ctx, "moe_trace": bare}) is None
