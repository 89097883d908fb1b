"""Set-up as spans (runtime/telemetry.py LIFECYCLE_SPANS, ISSUE 48).

`setup_s` is judged in every cell of the benchmark; these hold what it is made
of: one live span a phase or a program (never one a weight or a tick), jax's
own compile durations booked once to the innermost of them or to the process
totals, events that outlive a saturated window's ticks, silence under
`telemetry="off"`, and the seven `setup_*` readers of the benchmark against
rings made by hand.
"""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from flexflow_tpu import (ActiMode, FFConfig, FFModel, SGDOptimizer,
                          SingleDataLoader)
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import telemetry

VOCAB = 61
SETUP_METRICS = ("setup_init_params_s", "setup_compile_s",
                 "setup_trace_lower_s", "setup_backend_compile_s",
                 "setup_cache_miss_programs", "setup_seat_warm_s",
                 "setup_program_s")
TRAIN_CELLS = {"train-4k", "train-4k-search-4chip", "moe-mla-train-4k"}


@pytest.fixture(autouse=True)
def fresh_ring():
    telemetry.reset()
    yield
    telemetry.reset()


def lifecycle(name=None):
    return [e for e in telemetry.tracer().events()
            if e["name"] in telemetry.LIFECYCLE_SPANS
            and name in (None, e["name"])]


def within(child, parent):
    return parent["ts"] <= child["ts"] \
        and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1.0


def dense_job(**cfg):
    model = FFModel(FFConfig(batch_size=16, epochs=1, seed=3, **cfg))
    x = model.create_tensor([16, 8], name="x")
    t = model.dense(x, 16, ActiMode.AC_MODE_RELU, name="fc1")
    model.dense(t, 4, name="out")
    model.compile(SGDOptimizer(lr=0.1))
    rs = np.random.RandomState(7)
    SingleDataLoader(model, x, rs.randn(64, 8).astype(np.float32))
    SingleDataLoader(model, model.label_tensor,
                     rs.randint(0, 4, (64, 1)).astype(np.int32))
    return model


def tiny_lm(**cfg):
    model = FFModel(FFConfig(batch_size=2, mesh_shape={"data": 1}, **cfg))
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1, heads=2,
                         kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (n,)).astype(np.int32) for n in lengths]


# ---- the spans ---------------------------------------------------------------

def test_compile_opens_each_phase_once_with_its_counts():
    dense_job(search_budget=8)
    whole, = lifecycle("model_compile")
    search, = lifecycle("strategy_search")
    init, = lifecycle("init_params")
    moments, = lifecycle("init_optimizer")
    assert {e["pid"] for e in lifecycle()} == {"setup"}
    assert all(within(e, whole) for e in (search, init, moments))
    assert whole["args"]["ops"] == 3 and whole["args"]["weights"] == 4
    assert whole["args"]["weight_bytes"] == 4 * (8 * 16 + 16 + 16 * 4 + 4)
    assert whole["args"]["mesh"].startswith("data=")
    assert search["args"]["budget"] == 8
    assert search["args"]["simulator"] in ("native", "python")
    assert search["args"]["candidates"] == search["args"]["seeds"] + 8
    # one jitted program a weight, and jax's own durations on the span
    assert init["args"]["programs"] == init["args"]["weights"] == 4
    assert init["args"]["bytes"] == whole["args"]["weight_bytes"]
    assert init["args"]["trace_s"] > 0 and init["args"]["backend_s"] > 0
    assert init["args"]["cache_requests"] >= 1
    assert "bytes" in moments["args"]
    # a span a phase: nothing here grows with the weights
    assert len(lifecycle()) == 4


def test_fit_opens_one_compile_span_a_program_and_none_once_warm():
    model = dense_job()
    model.fit(epochs=1, verbose=False)
    first = lifecycle("compile")
    programs = [e["args"]["program"] for e in first]
    assert programs and set(programs) <= {"train_step", "train_scan"}
    assert len(set(programs)) == len(programs)
    for e in first:
        assert e["pid"] == "setup" and e["args"]["trace_s"] > 0
        assert e["args"]["lower_s"] > 0 and e["args"]["backend_s"] > 0
    model.fit(epochs=1, verbose=False)      # a second round
    model.next_batch_all()
    model.update()                          # a warm step
    later = [e["args"]["program"] for e in lifecycle("compile")]
    assert sorted(set(later)) == sorted(later)
    assert set(later) - set(programs) <= {"train_step"}     # update()'s own
    n = len(later)
    model.update()
    model.fit(epochs=1, verbose=False)
    assert len(lifecycle("compile")) == n


def test_eval_and_predict_open_a_compile_span_for_each_batch_shape():
    model = dense_job()
    batch = {"x": np.zeros((16, 8), np.float32)}
    labelled = {**batch, "label": np.zeros((16, 1), np.int32)}
    model.predict(batch)
    model.predict(batch)
    model.evaluate(labelled)
    model.evaluate(labelled)
    assert sorted(e["args"]["program"] for e in lifecycle("compile")) \
        == ["eval_step", "predict"]


def test_engine_build_run_and_prefill_into_cache():
    model = tiny_lm()
    telemetry.reset()
    eng = model.make_serving_engine(serve_slots=2, kv_page_size=8,
                                    max_seq_len=32)
    built, = lifecycle("engine_build")
    assert built["pid"] == "setup"
    assert built["args"]["slots"] == 2
    assert built["args"]["pages"] == eng.num_pages
    assert built["args"]["pool_bytes"] == eng.stats()["kv_pool_bytes"] > 0

    reqs = eng.run(prompts(1, (5, 9)), max_new_tokens=3)
    ran, = lifecycle("run")
    assert ran["pid"] == eng._tm_track
    assert ran["args"] == {"prompts": 2, "prompt_tokens": 14, "tokens": 6}
    compiles = lifecycle("compile")
    assert len(compiles) == eng.recompile_count >= 2
    for e in compiles:
        assert within(e, ran) and e["pid"] == eng._tm_track
        assert e["args"]["program"] and e["args"]["trace_s"] > 0
        # no persistent cache in the suite: every program compiled
        assert e["args"]["cache"] == "miss"
        assert e["args"]["cache_requests"] > e["args"].get("cache_hits", 0)
    # the durations went to the programs' spans, not to the span around
    assert "trace_s" not in ran["args"]

    doc = prompts(2, (16,))[0]
    assert eng.prefill_into_cache(doc) == 2
    seated, = lifecycle("prefill_into_cache")
    assert seated["args"]["prompt_tokens"] == 16
    assert seated["args"]["prompts"] == 1 and seated["args"]["tokens"] == 0

    # a warm engine compiles nothing: no new `compile` span, tick or run
    eng.run([r.prompt for r in reqs], max_new_tokens=3)    # the hit programs
    n = len(lifecycle("compile"))
    eng.run([r.prompt for r in reqs], max_new_tokens=3)
    eng.submit(reqs[0].prompt, 3)
    while eng.step():
        pass
    assert len(lifecycle("compile")) == n == eng.recompile_count
    assert len(lifecycle("run")) == 3


# ---- jax's own durations -----------------------------------------------------

def test_each_duration_is_booked_once_to_the_innermost_span_or_the_totals():
    handed = []

    def listen(event, duration, **kw):
        handed.append((event.rsplit("/", 1)[-1], kw.get("fun_name"),
                       duration))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        @jax.jit
        def inner_fn(x):
            return jnp.tanh(x) * 2.0

        @jax.jit
        def outer_fn(x):
            return inner_fn(x) + inner_fn(x * 3.0) + 1.0

        x = jax.block_until_ready(jnp.arange(4.0))
        tr = telemetry.tracer()
        del handed[:]
        zero = telemetry.setup_totals()
        with tr.span("model_compile", track="setup") as whole:
            with tr.span("compile", track="setup", program="p") as prog:
                jax.block_until_ready(outer_fn(x))
            booked = dict(prog.args)
        after = dict(whole.args)
        assert telemetry.setup_totals() == zero     # all under the spans
        jax.block_until_ready(jax.jit(lambda v: v * 5.0 - 1.0)(x))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    totals = {k: v - zero[k] for k, v in telemetry.setup_totals().items()}

    def raw(kind, name=None):
        return sum(d for k, f, d in handed
                   if k == kind and name in (None, f))

    # jax handed the traces of the inner function and of jnp's own jitted
    # functions AND the outer one that holds them all: a second is counted
    # once, by own time, so the sum is the two OUTERMOST traces'
    nested = raw("jaxpr_trace_duration", "inner_fn") \
        + raw("jaxpr_trace_duration", "tanh")
    assert nested > 0
    outermost = raw("jaxpr_trace_duration", "outer_fn") \
        + raw("jaxpr_trace_duration", "<lambda>")
    assert raw("jaxpr_trace_duration") >= outermost + nested
    got = booked["trace_s"] + after.get("trace_s", 0.0) \
        + totals["unspanned_trace_s"]
    assert got == pytest.approx(outermost, abs=5e-4)
    assert booked["trace_s"] == pytest.approx(
        raw("jaxpr_trace_duration", "outer_fn"), abs=5e-4)
    # nothing nests in a lowering or a backend compile here
    for kind, count in (("jaxpr_to_mlir_module_duration", "lower_s"),
                        ("backend_compile_duration", "backend_s")):
        assert booked[count] + after.get(count, 0.0) \
            + totals["unspanned_" + count] \
            == pytest.approx(raw(kind), abs=5e-4)
    # the innermost span took the program's; the one around it kept none
    assert booked["cache_requests"] >= 1 and "lower_s" not in after
    # under no lifecycle span: the process totals, which nothing evicts
    assert totals["unspanned_lower_s"] > 0
    assert totals["unspanned_cache_requests"] >= 1
    held = telemetry.setup_totals()
    for _ in range(telemetry.TRACE_RING_CAP + 10):
        telemetry.tracer().instant("filler")
    assert telemetry.setup_totals() == held


# ---- lifecycle events outlive the window ---------------------------------------

def test_lifecycle_events_outlive_fifty_thousand_tick_spans(tmp_path):
    tr = telemetry.tracer()
    with tr.span("model_compile", track="setup", ops=3):
        with tr.span("init_params", track="setup", weights=4):
            pass
    with tr.span("engine_build", track="setup"):
        pass
    with tr.span("run", track="replica0"):
        with tr.span("compile", track="replica0", program="decode_k8"):
            with tr.span("compile_fetch", track="replica0"):
                pass
    for tick in range(50_000):
        with tr.span("engine_step", track="replica0", tick=tick):
            pass
    with tr.span("prefill_into_cache", track="replica0"):
        pass
    events = tr.events()
    assert [e["name"] for e in events[:5]] == [
        "model_compile", "init_params", "engine_build", "run", "compile"]
    assert events[-1]["name"] == "prefill_into_cache"
    ticks = [e for e in events if e["name"] == "engine_step"]
    assert len(ticks) == telemetry.TRACE_RING_CAP       # fixed memory
    assert ticks[0]["args"]["tick"] == 50_000 - telemetry.TRACE_RING_CAP
    assert not tr.events(name="compile_fetch")          # the ring's, gone
    assert len(tr) == len(events) == telemetry.TRACE_RING_CAP + 6
    stamps = [e["ts"] for e in events]
    assert stamps == sorted(stamps)
    # the exports see the same one list
    assert telemetry.export_chrome_trace(str(tmp_path / "t.json")) \
        == len(events)
    # and the lifecycle deque is capped too
    for _ in range(telemetry.LIFECYCLE_CAP + 5):
        with tr.span("run", track="replica0"):
            pass
    assert len(lifecycle()) == telemetry.LIFECYCLE_CAP


# ---- off ---------------------------------------------------------------------

@pytest.mark.parametrize("route", ["set_enabled(False)",
                                   'FFConfig(telemetry="off")'])
def test_telemetry_off_gives_no_span_no_listener_work_and_totals_of_zero(
        route, monkeypatch):
    registered = []
    if route == "set_enabled(False)":
        prev = telemetry.set_enabled(False)
        cfg = {}
    else:
        # a process that is off from its start never registers a listener
        prev = telemetry.enabled()
        cfg = {"telemetry": "off"}
        monkeypatch.setattr(telemetry, "_listening", False)
        monkeypatch.setattr(
            jax.monitoring, "register_event_duration_secs_listener",
            registered.append)
        monkeypatch.setattr(jax.monitoring, "register_event_listener",
                            registered.append)
    try:
        model = dense_job(**cfg)
        model.fit(epochs=1, verbose=False)
        model.predict({"x": np.zeros((16, 8), np.float32)})
        lm = tiny_lm(**cfg)
        eng = lm.make_serving_engine(serve_slots=2, kv_page_size=8,
                                     max_seq_len=32)
        eng.run(prompts(3, (5,)), max_new_tokens=2)
        eng.prefill_into_cache(prompts(4, (16,))[0])
        jax.block_until_ready(jax.jit(lambda v: v * 7.0)(jnp.arange(3.0)))
        assert lifecycle() == [] and len(telemetry.tracer()) == 0
    finally:
        telemetry.set_enabled(prev)
    if route == "set_enabled(False)":
        assert set(telemetry.setup_totals().values()) == {0}
    else:
        assert registered == [] and not telemetry._listening


# ---- the benchmark's readers ----------------------------------------------------

def reader(name):
    return spec.load_module("layer_metrics", name).read


def serving_ring():
    """A serving run's set-up, made by hand; the window's first request is
    submitted 25 s after `base`."""
    base = time.perf_counter()
    put = telemetry.tracer().complete

    def at(name, start, dur, track="setup", **counts):
        put(name, base + start, dur, track=track, **counts)

    at("model_compile", 0, 10, trace_s=0.1, lower_s=0.2, backend_s=0.3,
       cache_requests=2, cache_hits=2)
    at("init_params", 1, 6, trace_s=1.0, lower_s=2.0, backend_s=2.5,
       cache_load_s=2.0, cache_requests=20, cache_hits=20)
    at("init_optimizer", 7, 1)
    at("engine_build", 10, 1)
    at("run", 11, 10, "replica0", prompts=4)
    at("compile", 11, 3, "replica0", program="prefill_b32", trace_s=0.5,
       lower_s=0.5, backend_s=1.5, cache_requests=1)
    at("compile", 15, 1, "replica0", program="decode_k8", backend_s=0.25,
       cache_requests=1, cache_hits=1)
    at("compile", 15.25, 0.25, "replica0", program="nested")
    at("engine_step", 12, 0.5, "replica0")          # a tick: the ring's
    at("prefill_into_cache", 21, 2, "replica0")
    at("run", 24, 6, "replica0")        # began before the window, ended in it
    at("compile", 26, 1, "replica0", program="late", trace_s=9.0,
       cache_requests=5)
    return {"mode": "serve", "records": [
        {"state": "unsent"},
        {"request": types.SimpleNamespace(t_submit=base + 25.0)},
        {"request": types.SimpleNamespace(t_submit=base + 31.0)}]}


def training_ring():
    base = time.perf_counter()
    put = telemetry.tracer().complete
    put("model_compile", base, 5.0, track="setup", trace_s=0.5,
        cache_requests=3, cache_hits=1)
    put("init_params", base + 1, 2.0, track="setup", lower_s=0.25)
    put("compile", base + 8, 4.0, track="setup", program="train_scan",
        backend_s=3.0, cache_requests=1)
    put("train_scan_chunk", base + 12, 1.0, track="train")
    put("train_scan_chunk", base + 20, 1.0, track="train")  # the window's last
    put("compile", base + 22, 2.0, track="setup", program="predict",
        trace_s=7.0, cache_requests=1)      # a check after the window
    return {"mode": "train"}


@pytest.mark.parametrize("name,serving,training", [
    ("setup_init_params_s", 7.0, 2.0),
    ("setup_compile_s", 4.0, 4.0),
    ("setup_trace_lower_s", 4.3, 0.75),
    ("setup_backend_compile_s", 4.55, 3.0),
    ("setup_cache_miss_programs", 1, 3),
    ("setup_seat_warm_s", 8.0, None),
    ("setup_program_s", 29.0, 9.0),
])
def test_reader_against_a_ring_made_by_hand(name, serving, training, capsys):
    ctx = serving_ring()
    assert reader(name)(ctx) == pytest.approx(serving, abs=1e-4)
    rows = capsys.readouterr().out
    assert rows.count("[setup_reduce]") >= 12 and "decode_k8" in rows
    assert reader(name)(ctx) == pytest.approx(serving, abs=1e-4)
    assert "[setup_reduce]" not in capsys.readouterr().out     # once a run
    telemetry.reset()
    got = reader(name)(training_ring())
    assert got is None if training is None \
        else got == pytest.approx(training, abs=1e-4)


@pytest.mark.parametrize("name", SETUP_METRICS)
def test_reader_returns_none_where_no_span_opened(name, monkeypatch):
    # ticks only: the program opened no lifecycle span
    telemetry.tracer().complete("engine_step", time.perf_counter(), 0.5)
    assert reader(name)({"mode": "serve", "records": []}) is None
    # a span of another phase is no reading of this one
    telemetry.tracer().complete("engine_build", time.perf_counter(), 1.0)
    got = reader(name)({"mode": "train"})
    if name in ("setup_init_params_s", "setup_compile_s",
                "setup_seat_warm_s"):
        assert got is None
    else:
        assert got is not None and got >= 0
    # a parent's program has no lifecycle spans at all: nothing, no raise
    monkeypatch.delattr(telemetry, "LIFECYCLE_SPANS")
    assert reader(name)({"mode": "serve"}) is None


def test_the_benchmark_accepts_the_appended_entries():
    bench = spec.load_benchmark()
    # appended together, in this order (later PRs append behind them)
    added = [m for m in bench["per_layer"] if m["name"] in SETUP_METRICS]
    assert tuple(m["name"] for m in added) == SETUP_METRICS
    cells = {w["name"] for w in bench["workloads"]}
    for m in added:
        mod = spec.load_module("layer_metrics", m["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == (
            m["name"], m["unit"], m["layer"], m["moves"], m["source"])
        assert (m["layer"], m["moves"], m["better"]) == (
            "model + compile", "setup_s", "lower")
        if m["name"] == "setup_seat_warm_s":
            assert set(m["workloads"]) == cells - TRAIN_CELLS
        else:
            assert "workloads" not in m     # every cell reports setup_s
    for cell in cells:
        names = {m["name"] for m in
                 spec.metrics_for(bench, "per_layer", cell)}
        assert len(names & set(SETUP_METRICS)) == (
            6 if cell in TRAIN_CELLS else 7)
