"""The cell `dsa-docqa-saturated`: BENCHMARK.json's new entries and the cell's
files; the generator's schedule (one arrangement whatever the seed, two prompt
lengths, two prefix-hit variants, the pool's arithmetic); `dsa_flops.py`
against counts made by hand; the seven readers on hand-made counters and a
hand-made trace (times in ns)."""

import numpy as np
import pytest

from benchmark import dsa_flops, dsa_trace as dt, spec
from benchmark.generators import shared_doc_serving as gen

CELL = "dsa-docqa-saturated"
NEW = ("dsa_device_share", "dsa_index_hbm_share", "mla_core_roofline_share",
       "dsa_selected_share", "ep_expert_hbm_share", "prefix_hit_token_share",
       "ep_experts_hit_share")
INDEX = ('%dsa_index_scores.3 = f32[32,260,128]{2,1,0} custom-call(s32[32,260]'
         '{1,0} %pt), custom_call_target="tpu_custom_call"')
CORE = ('%mla_paged_core.2 = bf16[32,128,512]{2,1,0} custom-call(s32[32,260]'
        '{1,0} %pt), custom_call_target="tpu_custom_call"')
EXPERT = ('%moe_3.11 = bf16[32,7168]{1,0} custom-call(s32[16]{0} %sizes), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.12 = bf16[32,7168]{1,0} fusion(bf16[32,7168]{1,0} %x)"


@pytest.fixture(scope="module")
def cell():
    bench = spec.load_benchmark()
    w, entry = spec.find_workload(bench, CELL)
    return bench, w, entry, spec.load_config(spec.ROOT, entry), \
        spec.load_traffic(w["traffic"])


def test_benchmark_json_accepts_the_cell_and_finds_its_files(cell):
    bench, w, entry, cfg, traffic = cell
    assert (w["config"], w["traffic"], w["chips"]) == (
        "deepseek-v3.2-serve", CELL, 1)
    assert len(w["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == [
        "n_routed_experts", "vocab_size", "first_k_dense_replace",
        "num_hidden_layers"]
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V3.2/blob/main/"
        "config.json")
    assert cfg["published"] == {
        "n_routed_experts": 256, "vocab_size": 129280,
        "first_k_dense_replace": 3, "num_hidden_layers": 61}
    assert cfg["builder"] == "deepseek_v32_lm" \
        and cfg["reference"] == "deepseek_v32"
    for key in ("assumed", "departures", "left_out", "deployment"):
        assert cfg[key]
    assert len(cfg["departures"]) == 5
    cut = spec.cut_for(cfg, 1)
    assert cut["graph_seq_len"] == 4096 > cfg["index_topk"]
    eng = cut["engine"]
    assert (eng["serve_slots"], eng["kv_page_size"], eng["max_seq_len"],
            eng["prefix_cache"]) == (32, 128, 33280, True)
    builder = spec.load_module("builders", cfg["builder"])
    z = builder.sizes_of(cfg, cut)
    assert z["experts_held"] == [0, 16] and z["router_experts"] == 256
    assert builder.sizes_of(cfg, cut, True)["vocab_size"] == 512
    spec.load_module("reference", cfg["reference"]).forward
    assert traffic["kind"] == "shared_doc_serving"
    assert traffic["end_to_end"] == ["tpot_p50_s", "serve_tokens_per_s"]
    assert traffic["drain_grace_s"] == 45
    assert traffic["arrangement_seed"] == 3000003000
    e2e = {m["name"] for m in spec.metrics_for(bench, "end_to_end", CELL)}
    assert e2e == {"tpot_p50_s", "serve_tokens_per_s", "setup_s"}


def test_every_published_number_is_in_the_file_or_named_reduced(cell):
    """The catalog row's `config` (copied here by hand from
    architectures.jsonl): every number under its key, a changed one in
    `reduced` with the published value beside it, no width among them."""
    _, _, _, cfg, _ = cell
    row = {"attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
           "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64,
           "index_topk": 2048, "intermediate_size": 18432,
           "kv_lora_rank": 512, "max_position_embeddings": 163840,
           "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
           "n_routed_experts": 256, "n_shared_experts": 1,
           "norm_topk_prob": True, "num_attention_heads": 128,
           "num_experts_per_tok": 8, "num_hidden_layers": 61,
           "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
           "q_lora_rank": 1536, "qk_nope_head_dim": 128,
           "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
           "rope_theta": 10000, "routed_scaling_factor": 2.5,
           "topk_group": 4, "v_head_dim": 128, "vocab_size": 129280}
    for key, value in row.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


@pytest.mark.parametrize("name", NEW)
def test_new_metrics_are_read_in_this_cell_only(cell, name):
    bench = cell[0]
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    mod = spec.load_module("layer_metrics", name)
    assert entry["workloads"] == [CELL]
    assert (entry["unit"], entry["layer"], entry["moves"],
            entry["source"]) == (mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE)
    assert mod.read({"mode": "train"}) is None
    assert mod.read({"mode": "serve", "stats_delta": {}, "trace": None}) \
        is None


@pytest.mark.parametrize("name", ["prefill_device_share",
                                  "pallas_time_share"])
def test_older_shares_of_layers_that_run_here_list_the_cell(cell, name):
    """Prefix-hit prefills are a fifth of this cell's busy time and the named
    Mosaic kernels most of it: the two accepted shares that move
    `serve_tokens_per_s` are read here too, after the cell they had."""
    entry = next(m for m in cell[0]["per_layer"] if m["name"] == name)
    assert entry["workloads"] == ["longprompt-steady", CELL]
    assert entry["moves"] in cell[4]["end_to_end"]


def test_generator_exports_what_knee_sweep_calls():
    for name in ("generate", "build_engine", "warm", "drive",
                 "latency_metrics", "attainment"):
        assert callable(getattr(gen, name))


@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_the_arrangement_is_the_files_and_the_tokens_are_the_seeds(cell,
                                                                   seed):
    traffic = {**cell[4], "rate_per_s": 4.0}
    a = gen.generate(traffic, seed, 51.0, 16160)
    b = gen.generate(traffic, 1, 51.0, 16160)
    assert len(a.prompts) == 204 == round(4.0 * 51)
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.max_new, b.max_new)
    np.testing.assert_array_equal(a.doc_of, b.doc_of)
    assert [p.size for p in a.prompts] == [p.size for p in b.prompts]
    assert not np.array_equal(a.docs[0][:64], b.docs[0][:64])
    again = gen.generate(traffic, seed, 51.0, 16160)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts,
                                                    again.prompts))
    # 11 documents of 127 pages, 5 of 255; a prompt is its document + a
    # question of 16-112 tokens: two buckets, each one page past its hit
    assert sorted(d.size for d in a.docs) == [16256] * 11 + [32640] * 5
    for p, d in zip(a.prompts, a.doc_of):
        doc = a.docs[d]
        assert np.array_equal(p[:doc.size], doc)
        assert 16 <= p.size - doc.size <= 112
    hits = {(gen._pow2(p.size), (p.size - 1) // 128) for p in a.prompts}
    assert hits == {(16384, 127), (32768, 255)}
    assert a.max_new.min() >= 32 and a.max_new.max() <= 512
    assert 140 <= np.median(a.max_new) <= 180
    assert set(a.doc_of) == set(range(16))
    assert a.describe()["requests_by_document_tokens"].keys() == {16256,
                                                                  32640}


def test_pool_arithmetic_leaves_the_documents_resident(cell):
    _, _, _, cfg, traffic = cell
    eng = spec.cut_for(cfg, 1)["engine"]
    pool = gen.pool_arithmetic(traffic, eng["kv_page_size"],
                               eng["serve_slots"])
    assert pool["resident_pages"] == 11 * 127 + 5 * 255 == 2672
    # a live request holds the question's page, the bucket's padding and
    # up to 512 answer tokens: (32768 + 512) / 128 - 255 = 5 pages
    assert pool["live_pages_most"] == 32 * 5
    assert pool["largest_bucket"] == 32768 \
        and 32768 + 512 == eng["max_seq_len"]
    # nothing but documents is ever published, so nothing evicts them
    assert pool["resident_pages"] + pool["live_pages_most"] \
        < eng["kv_pages"] - 1
    with pytest.raises(ValueError, match="whole pages"):
        gen.pool_arithmetic({**traffic, "documents": [
            {"count": 1, "tokens": 1000}]}, 128, 32)


def test_flops_and_bytes_against_hand_counts(cell):
    cfg = cell[3]
    p = dsa_flops.layer_params(cfg)
    # ISSUE 30's count: MLA 11.01 + 37.75 + 4.13 + 16.78 + 117.44 M
    assert p["mla"] == (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576
                        + 512 * 128 * 256 + 128 * 128 * 7168) == 187_105_280
    assert p["indexer"] == 1536 * 64 * 128 + 7168 * 128 + 7168 * 64 \
        == 13_959_168
    assert p["dense_ffn"] == 3 * 7168 * 18432 == 396_361_728
    assert dsa_flops.expert_params(cfg) == 3 * 7168 * 2048 == 44_040_192
    assert p["expert_layer_ffn"] == 17 * 44_040_192 + 7168 * 256
    total = dsa_flops.model_params(cfg)
    assert total == (5 * 201_064_448 + 396_361_728
                     + 4 * (17 * 44_040_192 + 1_835_008)
                     + 2 * 16160 * 7168)
    assert round(total / 1e9, 3) == 4.635      # 9.27 GB in bf16
    # per cached token and layer: 576 values as published, 640 as stored
    assert dsa_flops.cache_bytes_per_token(cfg) == {
        "latent": 1152, "latent_stored": 1280, "index_key": 256,
        "stored": 1536}
    assert dsa_flops.index_bytes(cfg, 1000) == 256_000
    assert dsa_flops.selected(cfg, 1500) == 1500 \
        and dsa_flops.selected(cfg, 30000) == 2048
    assert dsa_flops.core_bytes(cfg, 2048) == 2048 * 1152
    assert dsa_flops.core_flops(cfg, 2048) == 2 * 2048 * 128 * 1088
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    # the two bounds nearly meet: 1.4066 ns of bytes, 1.4138 ns of FLOPs
    assert dsa_flops.core_bound_s(cfg, 1, peak) == pytest.approx(
        278528 / 197e12)
    assert dsa_flops.core_bytes(cfg, 1) / 819e9 == pytest.approx(
        1.4066e-9, rel=1e-3)
    assert dsa_flops.expert_bytes(cfg, 10) == 10 * 88_080_384


def test_program_counts_agree_with_the_yardstick(cell):
    """What the op puts on a decode dispatch's span is the yardstick's count
    of the same contexts."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.ops.mla import LatentAttention

    cfg = cell[3]
    ff = FFModel(FFConfig(batch_size=1, mesh_shape={"data": 1}))
    x = ff.create_tensor([1, 8, 64], name="x")
    op = LatentAttention(ff, "a", [x], 64, 4, 48, cfg["kv_lora_rank"], 32,
                         cfg["qk_rope_head_dim"], 32, 4,
                         cfg["index_head_dim"], cfg["index_topk"])
    ctx = np.asarray([[100, 101], [30000, 30001]])
    got = op.decode_span_counts(ctx)
    assert got["index_read_bytes"] == dsa_flops.index_bytes(cfg, ctx.sum())
    assert got["dsa_selected_tokens"] == sum(dsa_flops.selected(cfg, c)
                                             for c in ctx.ravel())
    # the reader's count, from the file's index_topk and the spans' rows
    # and context tokens: the same where every context is on one side of
    # index_topk (as in the cell), an upper bound across it
    for rows in (ctx[:1], ctx[1:]):
        assert dsa_flops.selected_total(cfg, rows.sum(), rows.size) \
            == op.decode_span_counts(rows)["dsa_selected_tokens"]
    assert dsa_flops.selected_total(cfg, ctx.sum(), ctx.size) \
        >= got["dsa_selected_tokens"]
    assert op.cache_bytes_per_token() \
        == dsa_flops.cache_bytes_per_token(cfg)["stored"]


def test_kernels_are_told_apart_by_their_names():
    assert [dt.kernel_of(n) for n in (INDEX, CORE, EXPERT, FUSION)] \
        == ["index", "core", "expert", None]


def planes():
    """One chip, one tick line: two decode programs inside the window (each
    an index call, a core call, an expert call and a fusion), one cut by the
    window's end; spans with the counts their dispatches carry."""
    ops, programs, tick = [], [], []
    for i, t in enumerate((1000, 3000, 9500)):
        programs.append(("jit_decode(7)", t, 1000))
        ops += [(INDEX, t, 100), (FUSION, t + 100, 200),
                (CORE, t + 300, 400), (EXPERT, t + 700, 250)]
        tick += [("ff.decode_dispatch", t - 50, 40,
                  {"k": 8, "slots": 32, "context_tokens": 600000,
                   "dsa_context_tokens": 5 * 8 * 600000,
                   "index_read_bytes": 5 * 8 * 600000 * 256,
                   "dsa_selected_tokens": 5 * 8 * 60000}),
                 ("ff.record_tokens", t + 1010, 20,
                  {"experts_hit": 300, "assignments": 500})]
    return ops, programs, tick


def test_reduce_dsa_books_own_time_and_pairs_programs_with_their_counts(
        monkeypatch):
    ops, programs, tick = planes()
    monkeypatch.setattr(dt.sr, "_tick_line", lambda p: [
        (n, s, s + d, st) for n, s, d, st in tick])
    monkeypatch.setattr(dt.sr, "_device", lambda p: (
        ops, [(s, s + d) for _, s, d in programs], programs))
    monkeypatch.setattr(dt.sr, "_window", lambda p, o: (0, 10000))
    red = dt.reduce_dsa(None)
    d = red["decode"]
    assert d["programs"] == 2                      # the third is cut
    assert d["index_s"] == pytest.approx(200e-9)
    assert d["core_s"] == pytest.approx(800e-9)
    assert d["expert_s"] == pytest.approx(500e-9)
    assert d["other_s"] == pytest.approx(500e-9)   # fusions and gaps
    assert d["experts_hit"] == 600
    assert d["row_steps"] == 2 * 8 * 32
    assert d["dsa_context_tokens"] == 2 * 5 * 8 * 600000
    # the window's own seconds also hold the cut program's first calls
    assert red["kernels_s"]["index"] == pytest.approx(300e-9)
    assert red["kernels_s"]["core"] == pytest.approx(1000e-9)



def test_readers_turn_the_reduction_into_shares(monkeypatch, cell):
    cfg = cell[3]
    red = {"window_s": 5.0, "busy_s": 4.0,
           "kernels_s": {"index": 0.2, "core": 1.0, "expert": 0.8},
           "decode": {"programs": 20, "program_s": 3.5, "index_s": 0.2,
                      "core_s": 1.0, "expert_s": 0.8, "other_s": 1.5,
                      "dsa_context_tokens": 0.15 * 819e9 / 256,
                      "row_steps": 0.5 / (278528 / 197e12) / (5 * 2048),
                      "k": 160.0, "slots": 640.0,
                      "experts_hit": 0.6 * 819e9 / 88_080_384}}
    ctx = {"mode": "serve", "trace": {"window_s": 5.0}, "dsa_trace": red,
           "config": cfg, "device_kind": "TPU v5e",
           "stats_delta": {"dsa_selected_tokens": 2048 * 40,
                           "dsa_context_tokens": 20480 * 40,
                           "decode_steps": 100, "moe_experts_hit": 3600,
                           "prefix_hit_tokens": 16256 * 3 + 32640,
                           "prefix_prompt_tokens": 16320 * 3 + 32700}}

    def read(name):
        return spec.load_module("layer_metrics", name).read(ctx)

    assert read("dsa_device_share") == pytest.approx(30.0)
    assert read("dsa_index_hbm_share") == pytest.approx(75.0)
    assert read("mla_core_roofline_share") == pytest.approx(50.0)
    assert read("ep_expert_hbm_share") == pytest.approx(75.0)
    assert read("dsa_selected_share") == pytest.approx(10.0)
    assert read("prefix_hit_token_share") == pytest.approx(
        100 * 81408 / 81660)
    # 16 held experts x 4 expert layers x 100 steps
    assert read("ep_experts_hit_share") == pytest.approx(56.25)
    # a program without the op: spans carry no counts, kernels no time
    bare = {**ctx, "stats_delta": {}, "dsa_trace": {
        **red, "kernels_s": dict.fromkeys(red["kernels_s"], 0.0),
        "decode": {**red["decode"], "index_s": 0.0, "core_s": 0.0,
                   "expert_s": 0.0, "dsa_context_tokens": None,
                   "row_steps": None, "experts_hit": None}}}
    for name in NEW:
        assert spec.load_module("layer_metrics", name).read(bare) is None


def test_traffic_file_records_the_sweep_and_the_rule(cell):
    traffic = cell[4]
    knee = traffic["knee"]
    assert knee["sweep"] and knee["knee_per_s"] > 0
    factor = traffic["rate_per_s"] / knee["knee_per_s"]
    assert 1.1 <= factor <= 1.35, factor
    assert traffic["rate_per_s"] == pytest.approx(
        round(traffic["rate_per_s"] / 0.05) * 0.05)
    assert "find_again_when" in knee
    assert traffic["limits"] == {"ttft_s": 2.0, "tpot_s": 0.1, "share": 0.9}


def test_check_b_fails_for_a_fault_planted_in_the_paged_path(monkeypatch):
    """The timed path is what check (b) judges: through the engine at the
    rehearsal's size a sound window passes it, and the same window with two
    resident documents holding each other's pages (benchmark/dsa_controls.py
    `wrong_pages`: the pool's rows, read in place by the prefix-hit prefill
    and by the paged decode kernels) does not, under the file's own limit."""
    from benchmark import dsa_controls as dc, run as bench_run

    # what `run.py --rehearsal` sets: the CPU interprets the kernels
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("FF_FORCE_FLASH_ATTENTION", "1")
    h = bench_run.load_cell(spec.load_benchmark(), CELL, seed=5,
                            seconds=3.0, rehearsal=True)
    h.traffic = traffic = {**h.traffic,
                           "documents": h.traffic["rehearsal"]["documents"]}
    sched = gen.generate(traffic, 5, h.seconds, h.vocab, h.scale)
    ff, eng = gen.build_engine(h)
    gen.warm(h, eng, traffic)
    z = h.builder.sizes_of(h.config, h.cut, True)
    reference = spec.load_module("reference", h.config["reference"])
    short = min(d.size for d in sched.docs)
    got = {}
    for name in ("sound", "wrong_pages"):
        with dc.planted(name, eng, ff, sched.docs, {}):
            records, _, _ = gen.drive(eng, sched, h.seconds, 600.0,
                                      h.annotate)
        for k, r in enumerate(records):
            r["index"] = k
        got[name] = gen.check_emitted(h, reference, z, ff.params, records,
                                      sched, {short})
    limit = h.config["tolerances"]["emitted_margin_mean"]
    assert got["sound"]["ok"] and got["sound"]["worst_mean_margin"] < 1e-3
    assert not got["wrong_pages"]["ok"]
    assert got["wrong_pages"]["worst_mean_margin"] > limit
    # undone: the documents read as they were seated
    with dc.planted("sound", eng, ff, sched.docs, {}):
        records, _, _ = gen.drive(eng, sched, h.seconds, 600.0, h.annotate)
    for k, r in enumerate(records):
        r["index"] = k
    assert gen.check_emitted(h, reference, z, ff.params, records, sched,
                             {short})["ok"]
