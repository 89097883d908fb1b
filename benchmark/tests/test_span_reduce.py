"""span_reduce on hand-made traces with known spans, programs and gaps, and
the four readers on what it returns. Times in ns; the window is
[1000, 21000) = 20 us and holds two ticks."""
import pytest

from benchmark import span_reduce as sr, spec, trace_reduce as tr

ATTN = ('%attn_0_.1 = bf16[16,1,16,128]{3,2,1,0} custom-call(s32[16,66]{1,0} '
        '%a), custom_call_target="tpu_custom_call"')


def plane(name, **lines):
    return {"name": name,
            "lines": [{"name": k.replace("_", " "), "events": v}
                      for k, v in lines.items()]}


def dispatch(start, dur, kv):
    return ("ff.decode_dispatch", start, dur,
            {"k": 8, "slots": 2, "context_tokens": 300, "kv_read_bytes": kv})


# the driving thread: tick 1 [1000, 10000) admits and decodes, tick 2
# [11000, 20000) only decodes; the harness's bench.engine_step wraps each
HOST = plane("/host:CPU", main=[
    ("bench.trace_window", 1000.0, 20000.0),
    ("bench.engine_step", 1000.0, 9500.0),          # [1000, 10500)
    ("ff.engine_step", 1000.0, 9000.0, {"tick": 1}),
    ("ff.admit", 1000.0, 3000.0, {"admitted": 1}),  # [1000, 4000)
    ("ff.prefill", 1500.0, 2500.0, {"bucket": 64}),
    ("ff.prefill_fetch", 3000.0, 1000.0),           # [3000, 4000)
    ("ff.decode_chunk", 4000.0, 6000.0),            # [4000, 10000)
    ("ff.decode_prepare", 4000.0, 500.0),
    dispatch(4500.0, 500.0, 1000),                  # [4500, 5000)
    ("ff.token_fetch", 5000.0, 4000.0),             # [5000, 9000)
    ("ff.record_tokens", 9000.0, 1000.0),
    ("ff.slo_tick", 10000.0, 200.0),                # outside ff.engine_step
    ("bench.engine_step", 11000.0, 9500.0),
    ("ff.engine_step", 11000.0, 9000.0, {"tick": 2}),
    ("ff.decode_chunk", 11000.0, 9000.0),
    ("ff.decode_prepare", 11000.0, 1000.0),
    dispatch(12000.0, 500.0, 3000),
    ("ff.token_fetch", 12500.0, 6500.0),            # [12500, 19000)
    ("ff.record_tokens", 19000.0, 1000.0),
], other=[("some_runtime_call", 0.0, 30000.0)])
# the chip: a prefill program [2000, 3800), decode programs [4800, 8800)
# and [12400, 18800) with one Mosaic call each; idle in between
DEV = plane("/device:TPU:0", XLA_Ops=[
    ("fusion.1", 2000.0, 1800.0),
    ("fusion.2", 4800.0, 3000.0), (ATTN, 7800.0, 1000.0),
    ("fusion.2", 12400.0, 4400.0), (ATTN, 16800.0, 2000.0),
], XLA_Modules=[
    ("jit_prefill(111)", 2000.0, 1800.0),
    ("jit_decode(222)", 4800.0, 4000.0),
    ("jit_decode(222)", 12400.0, 6400.0),
])


def test_idle_goes_to_the_innermost_span_cut_at_span_boundaries():
    red = sr.reduce_spans([HOST, DEV])
    assert red["window_s"] == pytest.approx(20e-6)
    assert red["busy_s"] == pytest.approx((1800 + 4000 + 6400) * 1e-9)
    # gaps: [1000,2000) admit 500 + prefill 500; [3800,4800) prefill_fetch
    # 200, decode_prepare 500, decode_dispatch 300; [8800,12400)
    # token_fetch 200, record_tokens 1000, slo_tick 200, bench.engine_step
    # 300 (no ff. span open), nothing open 500, decode_prepare 1000,
    # decode_dispatch 400; [18800,21000) token_fetch 200, record_tokens
    # 1000, bench.engine_step 500, nothing open 500
    assert red["idle_by_span"] == pytest.approx({
        "ff.admit": 500e-9, "ff.prefill": 500e-9,
        "ff.prefill_fetch": 200e-9, "ff.decode_prepare": 1500e-9,
        "ff.decode_dispatch": 700e-9, "ff.token_fetch": 400e-9,
        "ff.record_tokens": 2000e-9, "ff.slo_tick": 200e-9,
        "bench.engine_step": 800e-9, tr.UNATTRIBUTED: 1000e-9})
    assert red["idle_s"] == pytest.approx(7800e-9)
    # the chip idle under a wait, here always after the program was done:
    # [3800,4000) of prefill_fetch, [8800,9000) and [18800,19000) of
    # token_fetch (each program began before the host started to wait)
    assert red["fetch_idle"] == pytest.approx(
        {"ff.prefill_fetch": [0.0, 0.0, 200e-9],
         "ff.token_fetch": [0.0, 0.0, 400e-9]})
    # under ff.engine_step itself nothing idles here: every ff. second is a
    # leaf's; bench.engine_step and no-span seconds are not
    assert red["leaf_idle_share"] == pytest.approx(6000 / 7800)
    assert red["long_gaps"] == []


def test_self_time_is_duration_minus_children():
    red = sr.reduce_spans([HOST, DEV])
    own = red["self_by_span"]
    assert own["ff.engine_step"] == pytest.approx(0.0)
    assert own["ff.admit"] == pytest.approx(500e-9)         # 3000 - 2500
    assert own["ff.prefill"] == pytest.approx(1500e-9)      # 2500 - 1000
    assert own["ff.decode_chunk"] == pytest.approx(0.0)
    assert own["ff.token_fetch"] == pytest.approx(10500e-9)
    assert own["bench.engine_step"] == pytest.approx(800e-9)
    assert tr.UNATTRIBUTED not in own
    assert red["spans"]["ff.engine_step"] == 2
    assert red["spans"]["ff.decode_dispatch"] == 2


def test_idle_inside_each_whole_tick_and_device_seconds_by_kind():
    red = sr.reduce_spans([HOST, DEV])
    # tick 1 [1000,10000): busy 1800 + 4000; tick 2 [11000,20000): 6400
    assert red["tick_idle_s"] == pytest.approx([3200e-9, 2600e-9])
    assert red["device_by_kind"] == pytest.approx(
        {"prefill": 1800e-9, "decode": 10400e-9})
    d = red["dispatch"]
    assert (d["pairs"], d["programs"], d["dispatches"]) == (2, 2, 2)
    assert d["kv_read_bytes"] == 4000 and d["k"] == 16
    assert d["paged_attn_s"] == pytest.approx(3000e-9)


@pytest.mark.parametrize("case", ["program before its dispatch was traced",
                                  "program cut by the slice's end",
                                  "dispatch whose program was not traced"])
def test_dispatches_pair_with_programs_at_the_slices_edges(case):
    host = plane("/host:CPU", main=[
        ("bench.trace_window", 1000.0, 9000.0),     # [1000, 10000)
        ("ff.engine_step", 2000.0, 3000.0),
        dispatch(2000.0, 100.0, 500),
        ("ff.engine_step", 6000.0, 3500.0),
        dispatch(6000.0, 100.0, 700)])
    progs = [("jit_decode(1)", 2200.0, 2000.0),
             ("jit_decode(1)", 6200.0, 3000.0)]
    want = {"pairs": 2, "kv_read_bytes": 1200}
    if case == "program before its dispatch was traced":
        # in flight when the trace began: no dispatch span precedes it
        progs.insert(0, ("jit_decode(1)", 900.0, 800.0))
    elif case == "program cut by the slice's end":
        progs[1] = ("jit_decode(1)", 6200.0, 5000.0)    # ends at 11200
        want = {"pairs": 1, "kv_read_bytes": 500}
    else:
        progs.pop()         # the trace stopped before the program began
        want = {"pairs": 1, "kv_read_bytes": 500}
    dev = plane("/device:TPU:0",
                XLA_Ops=[(ATTN, s + 100.0, 200.0) for _, s, _ in progs],
                XLA_Modules=progs)
    d = sr.reduce_spans([host, dev])["dispatch"]
    assert {k: d[k] for k in want} == want
    assert d["paged_attn_s"] == pytest.approx(want["pairs"] * 200e-9)


def test_idle_under_a_fetch_is_split_by_when_the_chip_idled():
    """Before the chip began what the host waits for (launch latency), in
    between two programs, after it was done (the copy back)."""
    host = plane("/host:CPU", main=[
        ("ff.engine_step", 0.0, 1000.0),
        ("ff.decode_dispatch", 0.0, 100.0),
        ("ff.token_fetch", 100.0, 900.0)])
    dev = plane("/device:TPU:0", XLA_Ops=[
        ("warm", 0.0, 50.0),                        # so the window opens at 0
        ("a", 300.0, 200.0), ("b", 600.0, 300.0),   # bubble [500, 600)
        ("later", 1100.0, 100.0)])
    red = sr.reduce_spans([host, dev])
    # gap [50,300): dispatch 50, fetch head 200; [500,600) in between;
    # [900,1100): fetch tail 100, nothing open 100
    assert red["fetch_idle"] == pytest.approx(
        {"ff.token_fetch": [200e-9, 100e-9, 100e-9]})
    assert red["idle_by_span"]["ff.token_fetch"] == pytest.approx(400e-9)
    assert "before the chip began" in "\n".join(sr.table(red))


def test_a_long_gap_is_named_by_the_spans_under_it():
    ms = 1e6
    host = plane("/host:CPU", main=[
        ("ff.engine_step", 0.0, 100 * ms),
        ("ff.admit", 0.0, 30 * ms), ("ff.decode_chunk", 30 * ms, 70 * ms)])
    dev = plane("/device:TPU:0", XLA_Ops=[("a", 0.0, 10 * ms),
                                          ("b", 50 * ms, 50 * ms)])
    red = sr.reduce_spans([host, dev])
    (sec, parts), = red["long_gaps"]
    assert sec == pytest.approx(0.040)
    assert parts == pytest.approx({"ff.admit": 0.020,
                                   "ff.decode_chunk": 0.020})
    assert red["device_by_kind"] is None and red["dispatch"] is None
    assert "gap of 40.0 ms: " in "\n".join(sr.table(red))


def test_the_table_prints_every_part():
    text = "\n".join(sr.table(sr.reduce_spans([HOST, DEV])))
    for part in ("idle seconds by innermost span", "self seconds by span",
                 "device seconds by kind of program", "ff.token_fetch",
                 "under a leaf ff. span", "decode dispatches"):
        assert part in text


def test_program_kinds():
    assert sr.program_kind("jit_prefill(123)") == "prefill"
    assert sr.program_kind("jit_prefill_final(1)") == "prefill"
    assert sr.program_kind("jit_decode(9)") == "decode"
    assert sr.program_kind("jit_decode_verify(9)") == "decode"
    assert sr.program_kind("jit_kv_page_write(2)") == "other"


READERS = ("tick_idle_p50_s", "paged_attn_hbm_share", "prefill_device_share",
           "queue_wait_p90_s")


class Req:
    def __init__(self, t_submit, t_admit=None):
        self.t_submit = t_submit
        if t_admit is not None:
            self.t_admit = t_admit


def reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture
def fresh(monkeypatch):
    """for_ctx() reading `planes` as the newest trace on disk."""
    def use(planes):
        monkeypatch.setattr(sr, "newest_xplane", lambda: "hand-made")
        monkeypatch.setattr(sr, "load", lambda path: planes)
    return use


def test_the_readers_on_a_trace_with_spans(fresh, capsys):
    fresh([HOST, DEV])
    ctx = {"trace": {"window_s": 20e-6}, "device_kind": "TPU v5 lite",
           "records": [{"state": "done", "request": Req(1.0, 1.25)},
                       {"state": "done", "request": Req(2.0, 2.05)},
                       {"state": "failed", "request": Req(3.0, 9.0)}]}
    assert reader("tick_idle_p50_s").read(ctx) == pytest.approx(2900e-9)
    # 4000 B over 3000 ns of kernel time at 819 GB/s
    assert reader("paged_attn_hbm_share").read(ctx) == pytest.approx(
        100 * 4000 / (3000e-9 * 819e9))
    assert reader("prefill_device_share").read(ctx) == pytest.approx(
        100 * 1800 / 12200)
    assert reader("queue_wait_p90_s").read(ctx) == pytest.approx(0.23)
    assert capsys.readouterr().out.count("idle seconds by innermost") == 1


@pytest.mark.parametrize("case", ["no ff. span (the parent)", "rehearsal",
                                  "another run's trace"])
def test_the_readers_return_none_where_there_is_nothing_to_read(fresh, case):
    host = plane("/host:CPU", main=[
        e for e in HOST["lines"][0]["events"] if e[0].startswith("bench.")])
    fresh([host, DEV] if case == "no ff. span (the parent)" else [HOST, DEV])
    window = 3.0 if case == "another run's trace" else 20e-6
    ctx = {"trace": None if case == "rehearsal" else {"window_s": window},
           "device_kind": "TPU v5 lite",
           "records": [{"state": "done", "request": Req(1.0)}]}
    for name in READERS:
        assert reader(name).read(ctx) is None, name
    if case == "rehearsal":     # the stamps are the program's, not the trace's
        ctx["records"] = [{"state": "done", "request": Req(1.0, 1.5)}]
        assert reader("queue_wait_p90_s").read(ctx) == pytest.approx(0.5)


def test_load_keeps_the_stats_of_ff_and_bench_events(tmp_path):
    """A real jax.profiler trace on the CPU: `load` finds the annotations
    with their keyword stats and drops the runtime's own events."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "cell"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.engine_step"):
        with jax.profiler.TraceAnnotation("ff.decode_dispatch", k=8,
                                          kv_read_bytes=12345):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path = sr.newest_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    events = [e for p in sr.load(path) for ln in p["lines"]
              for e in ln["events"]]
    assert {e[0] for e in events} == {"bench.engine_step",
                                      "ff.decode_dispatch"}
    (stats,) = [e[3] for e in events if e[0] == "ff.decode_dispatch"]
    assert stats == {"k": 8, "kv_read_bytes": 12345}
