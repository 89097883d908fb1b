"""scope_reduce on hand-made traces: two programs that both hold a `fusion.7`,
known tables, and the seven readers on what it returns. Times in ns; the
window is [1000, 21000) = 20 us."""
import pytest

from benchmark import scope_reduce as sc, span_reduce as sr, spec

CORE = ('%mla_paged_core_gathered.5 = bf16[32,128,512]{2,1,0} custom-call('
        'bf16[32,128,640]{2,1,0} %q), custom_call_target="tpu_custom_call"')


def plane(name, **lines):
    return {"name": name,
            "lines": [{"name": k.replace("_", " "), "events": v}
                      for k, v in lines.items()]}


def dispatch(start, program, **counts):
    return ("ff.decode_dispatch", start, 400.0,
            {"k": 8, "slots": 2, "program": program, **counts})


# one tick: a hit prefill [2000, 5000), a decode program [6000, 14000) whose
# while loop [6500, 13500) holds the gather, the kernel and the sort; a
# second decode program [15000, 23000) runs over the slice's end
HOST = plane("/host:CPU", main=[
    ("bench.trace_window", 1000.0, 20000.0),
    ("ff.engine_step", 1000.0, 13500.0, {"tick": 1}),
    ("ff.prefill", 1500.0, 4000.0, {"bucket": 128,
                                    "program": "prefill_hit_b128_m255"}),
    dispatch(5600.0, "decode_k8", dsa_context_tokens=40000),
    ("ff.record_tokens", 14100.0, 300.0, {"experts_hit": 10}),
    ("ff.engine_step", 14600.0, 9000.0, {"tick": 2}),
    dispatch(14700.0, "decode_k8", dsa_context_tokens=41000),
])
DEV = plane("/device:TPU:0", XLA_Ops=[
    ("%fusion.7 = bf16[1,128,7168] fusion(%p)", 2000.0, 2000.0),
    ("%copy.3 = bf16[8] copy(%p)", 4000.0, 1000.0),
    ("%fusion.9 = f32[32] fusion(%p)", 6000.0, 500.0),
    ("%while.1 = (s32[]) while(%t)", 6500.0, 7000.0),
    ("%fusion.7 = bf16[65536,640] fusion(%pool)", 6500.0, 3000.0),
    (CORE, 9500.0, 1000.0),
    ("%sort.12 = f32[32,16160] sort(%l)", 10500.0, 2500.0),
    ("%fusion.7 = bf16[65536,640] fusion(%pool)", 15000.0, 3000.0),
    (CORE, 18000.0, 2000.0),
    ("%sort.12 = f32[32,16160] sort(%l)", 20000.0, 3000.0),
], XLA_Modules=[
    ("jit_prefill(111)", 2000.0, 3000.0),
    ("jit_decode(222)", 6000.0, 8000.0),
    ("jit_decode(222)", 15000.0, 8000.0),
])
TABLES = {
    "prefill_hit_b128_m255": {"fusion.7": ("moe_3", "experts")},
    "decode_k8": {"fusion.7": ("attn_2", "gather"),
                  "mla_paged_core_gathered.5": ("attn_2", "core"),
                  "sort.12": ("sampler", ""),
                  "fusion.9": ("attn_0", "project")},
}
MODULES = {"prefill_hit_b128_m255": "jit_prefill",
           "prefill_b2048": "jit_prefill", "decode_k8": "jit_decode"}


def test_two_programs_with_one_instruction_name_read_their_own_tables():
    red = sc.reduce_scopes([HOST, DEV], TABLES, MODULES)
    assert red["window_s"] == pytest.approx(20e-6)
    # the prefill program's fusion.7 is an expert matmul, the decode
    # programs' the row gather: 3000 whole + 3000 of the second program
    assert red["rows"][("prefill", "moe", "experts")] == pytest.approx(2e-6)
    assert red["rows"][("decode", "attn", "gather")] == pytest.approx(6e-6)
    # the second program's sort is cut at the window's end: 2500 + 1000
    assert red["rows"][("decode", "sampler", "")] == pytest.approx(3.5e-6)
    assert red["rows"][("decode", "attn", "core")] == pytest.approx(3e-6)
    assert red["rows"][("decode", "attn", "project")] == pytest.approx(.5e-6)


def test_an_op_without_an_entry_is_unscoped_by_instruction():
    red = sc.reduce_scopes([HOST, DEV], TABLES, MODULES)
    # copy.3 is in no table; while.1's own time is 7000 - 6500 of children
    assert red["unscoped"] == pytest.approx({
        ("prefill", "copy.3 = bf16[8] copy(%p)"): 1e-6,
        ("decode", "while.1 = (s32[]) while(%t)"): .5e-6})
    assert red["unscoped_s"] == pytest.approx(1.5e-6)
    # rows and unscoped seconds are all of the busy time
    assert sum(red["rows"].values()) + red["unscoped_s"] \
        == pytest.approx(red["busy_s"])
    assert red["busy_s"] == pytest.approx((3000 + 7500 + 6000) * 1e-9)
    assert red["by_program"] == pytest.approx(
        {"decode_k8": 13.5e-6, "prefill_hit_b128_m255": 3e-6})


def test_whole_programs_only_where_a_numerator_counts_whole_programs():
    red = sc.reduce_scopes([HOST, DEV], TABLES, MODULES)
    assert red["whole"][("decode", "attn", "gather")] == pytest.approx(3e-6)
    assert red["whole"][("decode", "attn", "core")] == pytest.approx(1e-6)


@pytest.mark.parametrize("case", ["a span names another function's program",
                                  "no span: the one program of that name",
                                  "no span: two programs of that name"])
def test_a_module_without_a_matching_dispatch(case):
    host = plane("/host:CPU", main=[("bench.trace_window", 1000.0, 20000.0)])
    if case == "a span names another function's program":
        # the decode dispatch began last, but the module is a prefill
        host["lines"][0]["events"] += [("ff.engine_step", 1000.0, 9000.0),
                                       dispatch(1500.0, "decode_k8")]
    dev = plane("/device:TPU:0",
                XLA_Ops=[("%fusion.7 = f32[2] fusion(%p)", 2000.0, 1000.0)],
                XLA_Modules=[("jit_prefill(1)", 2000.0, 1000.0)])
    modules = dict(MODULES)
    if case != "no span: two programs of that name":
        del modules["prefill_b2048"]
    red = sc.reduce_scopes([host, dev], TABLES, modules)
    if case == "no span: two programs of that name":
        assert red["rows"] == {} and red["unscoped_s"] == pytest.approx(1e-6)
    else:
        assert red["rows"] == pytest.approx(
            {("prefill", "moe", "experts"): 1e-6})


def test_a_train_step_is_one_module_a_step_and_chips_are_averaged():
    """No `ff.` span at all; two chips, the second with a collective no
    table names: rows are the mean, a share the worst chip's."""
    host = plane("/host:CPU", main=[("bench.trace_window", 0.0, 10000.0)])
    ops = [("%fusion.1 = f32[8] fusion(%p)", 0.0, 4000.0),
           ("%fusion.2 = f32[8] fusion(%g)", 4000.0, 4000.0)]
    chip0 = plane("/device:TPU:0", XLA_Ops=ops,
                  XLA_Modules=[("jit_step(7)", 0.0, 8000.0)])
    chip1 = plane("/device:TPU:1", XLA_Ops=ops + [
        ("%all-reduce.3 = f32[8] all-reduce(%g)", 8000.0, 2000.0)],
        XLA_Modules=[("jit_step(7)", 0.0, 10000.0)])
    tables = {"train_step": {"fusion.1": ("attn_1", "core"),
                             "fusion.2": ("optimizer", "")}}
    modules = {"train_step": "jit_step"}
    assert sc.programs_in([host, chip0, chip1], modules) == {"train_step"}
    red = sc.reduce_scopes([host, chip0, chip1], tables, modules)
    assert red["rows"] == pytest.approx({("train", "attn", "core"): 4e-6,
                                         ("train", "optimizer", ""): 4e-6})
    assert red["busy_s"] == pytest.approx(9e-6)
    assert red["unscoped_s"] == pytest.approx(1e-6)
    assert sc.share(red, lambda kind, op, phase: op == "attn") \
        == pytest.approx(50.0)          # chip 0: 4000 of 8000
    assert sc.share(red, lambda kind, label: True, field="unscoped") \
        == pytest.approx(20.0)          # chip 1: 2000 of 10000


def test_programs_in_asks_for_what_the_trace_shows_and_no_more():
    # two prefill programs share `jit_prefill`: only the one a span names
    assert sc.programs_in([HOST, DEV], MODULES) == {
        "prefill_hit_b128_m255", "decode_k8"}


def test_the_table_prints_rows_that_sum_to_the_busy_time():
    def slow(pl):       # the same trace in units of 0.1 ms
        return {"name": pl["name"], "lines": [
            {"name": ln["name"],
             "events": [(e[0], e[1] * 1e5, e[2] * 1e5) + tuple(e[3:])
                        for e in ln["events"]]} for ln in pl["lines"]]}

    red = sc.reduce_scopes([slow(HOST), slow(DEV)], TABLES, MODULES)
    lines = sc.table(red)
    text = "\n".join(lines)
    for part in ("decode   attn           gather", "unscoped",
                 "while.1", "(rows under 0.5 %)", "seconds by program"):
        assert part in text, part
    # every row is over 0.5 % here: the printed seconds are all of them
    printed = sum(float(ln.split()[0]) for ln in lines[1:-1]
                  if "%    " not in ln)    # but the unscoped sum's parts
    assert printed == pytest.approx(red["busy_s"]) == pytest.approx(1.65)
    assert lines[-1].startswith(
        "rows + unscoped = 1.6500 s of busy 1.6500 s")


READERS = ("mla_core_gather_roofline_share", "dsa_sparse_device_share",
           "sampler_device_share", "attn_train_device_share",
           "optimizer_device_share", "serve_unscoped_share",
           "train_unscoped_share")


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture
def fresh(monkeypatch):
    """for_ctx() reading `planes` as the newest trace on disk, and `tables`
    as what the program's registry gives."""
    class Prog:
        def __init__(self, name, module):
            self.name, self.module = name, module

    def use(planes, tables, modules):
        from flexflow_tpu.runtime import profiler

        monkeypatch.setattr(sr, "newest_xplane", lambda: "hand-made")
        monkeypatch.setattr(sr, "load", lambda path: planes)
        monkeypatch.setattr(profiler, "live_programs", lambda: [
            Prog(n, m) for n, m in modules.items()])
        asked = []
        monkeypatch.setattr(
            profiler, "program_scopes",
            lambda names=None: asked.append(set(names)) or {
                n: tables[n] for n in names})
        return asked
    return use


CFG = {"index_topk": 2048, "num_hidden_layers": 5, "kv_lora_rank": 512,
       "qk_rope_head_dim": 64, "num_attention_heads": 128}


def test_the_serving_readers_on_a_trace_with_tables(fresh, capsys):
    asked = fresh([HOST, DEV], TABLES, MODULES)
    ctx = {"trace": {"window_s": 20e-6}, "device_kind": "TPU v5 lite",
           "mode": "serve", "config": CFG}
    busy = 16500.0
    assert reader("sampler_device_share").read(ctx) == pytest.approx(
        100 * 3500 / busy)
    assert reader("dsa_sparse_device_share").read(ctx) == pytest.approx(
        100 * (6000 + 3000) / busy)
    assert reader("serve_unscoped_share").read(ctx) == pytest.approx(
        100 * 1500 / busy)
    # one decode program wholly inside: 2 rows x 8 steps x 5 layers keep
    # min(40000, 80 x 2048) tokens; gather 3000 + core 1000 ns
    from benchmark import dsa_flops, peaks

    bound = dsa_flops.core_bound_s(CFG, 40000,
                                   peaks.peaks_for("TPU v5 lite"))
    assert reader("mla_core_gather_roofline_share").read(ctx) \
        == pytest.approx(100 * bound / 4000e-9)
    for name in ("attn_train_device_share", "optimizer_device_share",
                 "train_unscoped_share"):
        assert reader(name).read(ctx) is None, name
    # the tables were asked for once, and only the traced programs'
    assert asked == [{"prefill_hit_b128_m255", "decode_k8"}]
    out = capsys.readouterr().out
    assert out.count("[scope_reduce] window") == 1
    assert "program_scopes() read 2 of 3 registered programs" in out


@pytest.mark.parametrize("case", ["untraced", "another run's trace",
                                  "a program without the registry"])
def test_the_readers_return_none_where_there_is_nothing_to_read(
        fresh, monkeypatch, case):
    fresh([HOST, DEV], TABLES, MODULES)
    if case == "a program without the registry":
        from flexflow_tpu.runtime import profiler

        monkeypatch.delattr(profiler, "program_scopes")
    window = 3.0 if case == "another run's trace" else 20e-6
    for mode in ("serve", "train"):
        ctx = {"trace": None if case == "untraced" else {"window_s": window},
               "device_kind": "TPU v5 lite", "mode": mode, "config": CFG}
        for name in READERS:
            assert reader(name).read(ctx) is None, (mode, name)
