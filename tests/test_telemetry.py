"""Unified telemetry plane (runtime/telemetry.py, ISSUE 13).

Correctness anchors:
  * the registry is exact — labeled series are independent, counters
    survive a concurrent-increment stress bit-for-bit, histogram bucket
    math is pinned against hand-computed buckets and the Prometheus
    exposition format against a golden string;
  * the trace ring is bounded (fixed memory whatever the traffic) and a
    request's span tree stays CONNECTED across threads, replicas,
    failover resubmission and the prefill->decode handoff (one trace id
    rides the request everywhere);
  * zero behavior change: ``stats()``/``health()`` on engine and router
    still carry every pre-telemetry key (pinned superset lists) — the
    registry is an export plane over those dicts, not a replacement of
    their contract;
  * FF_FAULT injections annotate the trace at their fire site
    (``telemetry.fault_events()``) — a drill's trace shows where the
    fault landed;
  * ``FFConfig.telemetry="off"`` / ``set_enabled(False)`` short-circuit
    every emit.
"""

import json
import threading
import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject, telemetry
from flexflow_tpu.runtime.telemetry import Registry, Tracer, log_bounds

VOCAB = 61


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (n,)).astype(np.int32) for n in lengths]


# ---------------------------------------------------------------- registry


def test_counter_labeled_series_independent():
    reg = Registry()
    c = reg.counter("req_total", "requests", labels=("replica", "role"))
    c.labels("0", "mixed").inc()
    c.labels("0", "mixed").inc(2)
    c.labels("1", "decode").inc(5)
    assert c.labels("0", "mixed").get() == 3
    assert c.labels("1", "decode").get() == 5
    assert c.labels(replica="1", role="decode").get() == 5  # kw spelling
    assert len(c.children()) == 2


def test_gauge_set_and_label_free():
    reg = Registry()
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.set(3)
    assert g.labels().get() == 3
    # idempotent re-registration returns the same family
    assert reg.gauge("depth", "queue depth") is g
    with pytest.raises(ValueError):
        reg.counter("depth")        # kind mismatch must raise


def test_label_arity_checked():
    reg = Registry()
    c = reg.counter("x_total", labels=("a",))
    with pytest.raises(ValueError):
        c.labels("1", "2")


def test_log_bounds():
    b = log_bounds(0.001, 0.01)
    assert b == (0.001, 0.002, 0.004, 0.008, 0.016)
    with pytest.raises(ValueError):
        log_bounds(0, 1)
    with pytest.raises(ValueError):
        log_bounds(1, 2, growth=1.0)


def test_histogram_bucket_math():
    reg = Registry()
    h = reg.histogram("lat", "latency", labels=("r",),
                      bounds=(0.001, 0.01, 0.1, 1.0))
    ch = h.labels("0")
    for v in (0.0005, 0.001, 0.005, 0.05, 0.5, 5.0, 50.0):
        ch.observe(v)
    # le-semantics: a value equal to a bound lands IN that bucket
    assert ch.counts == [2, 1, 1, 1, 2]    # last = +Inf bucket
    assert ch.count == 7
    assert ch.sum == pytest.approx(55.5565)
    # cumulative counts in the exposition
    text = reg.to_prometheus()
    assert 'lat_bucket{r="0",le="0.001"} 2' in text
    assert 'lat_bucket{r="0",le="1"} 5' in text
    assert 'lat_bucket{r="0",le="+Inf"} 7' in text
    assert 'lat_count{r="0"} 7' in text


def test_histogram_quantiles():
    reg = Registry()
    h = reg.histogram("q", bounds=(1.0, 2.0, 4.0, 8.0))
    ch = h.labels()
    assert ch.quantile(0.5) == 0.0          # empty
    for _ in range(100):
        ch.observe(1.5)                      # all in the (1, 2] bucket
    q50 = ch.quantile(0.50)
    assert 1.0 <= q50 <= 2.0                 # exact to the bucket
    ch.observe(100.0)                        # +Inf bucket clamps
    assert ch.quantile(1.0) == 8.0


def test_concurrent_increment_stress():
    reg = Registry()
    c = reg.counter("stress_total", labels=("t",))
    h = reg.histogram("stress_lat", bounds=(0.5, 1.0))
    n_threads, per = 8, 5000

    def work(i):
        ch = c.labels(str(i % 2))
        for _ in range(per):
            ch.inc()
            h.observe(0.75)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(ch.get() for ch in c.children())
    assert total == n_threads * per          # no lost increments
    assert h.labels().count == n_threads * per
    assert h.labels().counts[1] == n_threads * per


def test_prometheus_golden():
    """Exposition format pinned: HELP/TYPE lines, label quoting,
    histogram cumulative buckets + sum + count, integer rendering."""
    reg = Registry()
    c = reg.counter("ff_req_total", "requests served", labels=("replica",))
    c.labels("0").inc(4)
    g = reg.gauge("ff_up", "liveness")
    g.set(1)
    h = reg.histogram("ff_lat_seconds", "latency", bounds=(0.5, 1.0))
    h.observe(0.25)
    h.observe(0.75)
    expected = (
        "# HELP ff_req_total requests served\n"
        "# TYPE ff_req_total counter\n"
        'ff_req_total{replica="0"} 4\n'
        "# HELP ff_up liveness\n"
        "# TYPE ff_up gauge\n"
        "ff_up 1\n"
        "# HELP ff_lat_seconds latency\n"
        "# TYPE ff_lat_seconds histogram\n"
        'ff_lat_seconds_bucket{le="0.5"} 1\n'
        'ff_lat_seconds_bucket{le="1"} 2\n'
        'ff_lat_seconds_bucket{le="+Inf"} 2\n'
        "ff_lat_seconds_sum 1\n"
        "ff_lat_seconds_count 2\n")
    assert reg.to_prometheus() == expected


def test_json_snapshot_shape():
    reg = Registry()
    reg.counter("a_total", "x", labels=("k",)).labels("v").inc(2)
    reg.histogram("b", bounds=(1.0, 2.0)).observe(1.5)
    snap = reg.snapshot()
    assert snap["a_total"]["type"] == "counter"
    assert snap["a_total"]["series"] == [
        {"labels": {"k": "v"}, "value": 2}]
    row = snap["b"]["series"][0]
    assert row["count"] == 1 and row["buckets"] == {"1": 0, "2": 1}
    json.dumps(snap)    # must be JSON-serializable as-is


def test_collector_weakref_does_not_leak():
    reg = Registry()

    class Obj:
        def collect(self, r):
            r.gauge("from_obj").set(1)

    o = Obj()
    reg.add_collector(o.collect)
    reg.to_prometheus()
    assert reg._families["from_obj"].labels().get() == 1
    del o
    import gc

    gc.collect()
    reg.to_prometheus()                     # dead collector pruned, no crash
    assert reg._collectors == []


# ---------------------------------------------------------------- tracing


def test_trace_ring_bounded():
    tr = Tracer(cap=64)
    for i in range(500):
        tr.instant("e", trace_id=f"t{i}")
    assert len(tr) == 64
    evs = tr.events()
    assert evs[0]["args"]["trace_id"] == "t436"    # oldest fell off


def test_lifecycle_events_are_kept_apart_and_merged_by_ts():
    """A span of LIFECYCLE_SPANS does not ride the ring: the ring's cap
    evicts ticks only, `events()` hands back one list with the lifecycle
    events placed by `ts`, and `reset()` clears both."""
    tr = Tracer(cap=8)
    with tr.span("model_compile", track="setup"):       # parent: first by ts
        with tr.span("init_params", track="setup"):
            pass
        tr.instant("mark")
    for i in range(50):
        tr.instant("tick", trace_id=f"t{i}")
    with tr.span("run", track="replica0"):
        pass
    names = [e["name"] for e in tr.events()]
    assert names == ["model_compile", "init_params"] + ["tick"] * 8 + ["run"]
    assert len(tr) == 11
    assert [e["name"] for e in tr.events(name="init_params")] \
        == ["init_params"]
    stamps = [e["ts"] for e in tr.events()]
    assert stamps == sorted(stamps)
    tr.reset()
    assert len(tr) == 0 and tr.events() == []


@pytest.mark.parametrize("name,lifecycle", [
    ("model_compile", True), ("strategy_search", True),
    ("init_params", True), ("init_optimizer", True), ("engine_build", True),
    ("compile", True), ("run", True), ("prefill_into_cache", True),
    ("compile_fetch", False), ("engine_step", False), ("prefill", False)])
def test_which_spans_collect_jaxs_compile_counts(name, lifecycle):
    """Only a lifecycle span is the target of jax's durations while it is
    the innermost open; a tick's span neither collects nor hides one."""
    tr = Tracer()
    with tr.span("engine_build", track="setup") as outer:
        with tr.span(name, track="setup") as sp:
            telemetry._on_jax_duration(
                "/jax/core/compile/backend_compile_duration", 0.25)
            telemetry._on_jax_event("/jax/compilation_cache/cache_hits")
            telemetry._on_jax_event("/jax/not/ours")
        inner, outer_args = dict(sp.args), dict(outer.args)
    took = inner if lifecycle else outer_args
    assert took["backend_s"] == 0.25 and took["cache_hits"] == 1
    assert not (outer_args if lifecycle else inner)
    ev = tr.events(name=name)[-1]       # the inner one began later
    assert ("backend_s" in ev.get("args", {})) == lifecycle


def test_span_nesting_thread_local_and_tree():
    tr = Tracer()
    with tr.span("root", trace_id="tX", track="a"):
        with tr.span("child", trace_id="tX", track="b"):
            time.sleep(0.001)
        tr.instant("mark", trace_id="tX", track="a")
    tree = tr.trace_tree("tX")
    assert tree["root"]["name"] == "root"
    assert tree["complete"], tree
    assert set(tree["names"]) == {"root", "child"}
    assert [e["name"] for e in tree["annotations"]] == ["mark"]
    assert tree["tracks"] == ["a", "b"]


def test_current_trace_id_follows_span_stack():
    with telemetry.tracer().span("outer", trace_id="ctx1"):
        assert telemetry.current_trace_id() == "ctx1"
        with telemetry.tracer().span("inner", trace_id="ctx2"):
            assert telemetry.current_trace_id() == "ctx2"
        assert telemetry.current_trace_id() == "ctx1"
    assert telemetry.current_trace_id() is None


def test_cross_thread_begin_end():
    tr = Tracer()
    h = tr.begin("work", trace_id="tc", track="r0")

    def closer():
        tr.end(h, state="done")

    t = threading.Thread(target=closer)
    t.start()
    t.join()
    evs = tr.events(trace_id="tc")
    assert len(evs) == 1 and evs[0]["args"]["state"] == "done"
    tr.end(h)           # double-end is a no-op
    tr.end(0)           # zero handle (telemetry off) is a no-op
    assert len(tr.events(trace_id="tc")) == 1


def test_set_enabled_short_circuits():
    reg = Registry()
    c = reg.counter("off_total")
    h = reg.histogram("off_lat", bounds=(1.0,))
    tr = Tracer()
    prev = telemetry.set_enabled(False)
    try:
        sp = tr.span("x", trace_id="off")
        assert sp is telemetry.NULL_SPAN
        with sp:
            pass
        assert tr.begin("y") == 0
        tr.instant("z", trace_id="off")
        c.inc()
        h.observe(0.5)
        assert len(tr) == 0
        assert c.labels().get() == 0 and h.labels().count == 0
    finally:
        telemetry.set_enabled(prev)
    c.inc()
    assert c.labels().get() == 1


def test_chrome_trace_export(tmp_path):
    tr = telemetry.tracer()
    with tr.span("exported", trace_id="exp1", track="t"):
        pass
    path = str(tmp_path / "trace.json")
    n = telemetry.export_chrome_trace(path)
    assert n >= 1
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list)
    for ev in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(ev)
        if ev["ph"] == "X":
            assert "dur" in ev


# ------------------------------------------------------- fault annotations


def test_fault_injection_annotates_trace(monkeypatch):
    monkeypatch.setenv("FF_FAULT", "io_fail@save:1")
    faultinject.reset()
    before = len(telemetry.fault_events())
    with pytest.raises(faultinject.InjectedFault):
        faultinject.maybe_fail("io_fail", "save")
    evs = telemetry.fault_events()
    assert len(evs) == before + 1
    assert evs[-1]["args"]["kind"] == "io_fail"
    assert evs[-1]["args"]["site"] == "save"
    # the counter series fired too
    text = telemetry.registry().to_prometheus()
    assert 'ff_fault_fired_total{kind="io_fail",site="save"}' in text
    monkeypatch.delenv("FF_FAULT")
    faultinject.reset()


# ----------------------------------------------------------------- logger


def test_logger_env_alias_precedence(monkeypatch):
    from flexflow_tpu import logger as fflog

    monkeypatch.delenv("FLEXFLOW_LOG_LEVEL", raising=False)
    monkeypatch.setenv("FF_LOGGING_LEVEL", "debug")
    assert fflog._env("FLEXFLOW_LOG_LEVEL", "FF_LOGGING_LEVEL") == "debug"
    monkeypatch.setenv("FLEXFLOW_LOG_LEVEL", "error")
    assert fflog._env("FLEXFLOW_LOG_LEVEL", "FF_LOGGING_LEVEL") == "error"


def test_logger_json_format_carries_trace_id():
    import logging

    from flexflow_tpu.logger import _JsonFormatter

    fmt = _JsonFormatter()
    rec = logging.LogRecord("flexflow_tpu", logging.INFO, "f.py", 1,
                            "hello %s", ("world",), None)
    with telemetry.tracer().span("logline", trace_id="log-7"):
        row = json.loads(fmt.format(rec))
    assert row["msg"] == "hello world"
    assert row["level"] == "info"
    assert row["trace_id"] == "log-7"
    row2 = json.loads(fmt.format(rec))
    assert "trace_id" not in row2           # no active span -> no id


# ----------------------------------------------- zero-behavior-change pins

# stats()/health() keys as of the PR BEFORE telemetry (ISSUE 12 state):
# the telemetry plane may ADD keys, never remove or rename these.
ENGINE_STATS_KEYS = {
    "requests", "completed", "failed", "timeouts", "tokens_generated",
    "decode_steps", "recompiles", "occupancy", "occupied_slot_steps",
    "ttft_p50_ms", "ttft_p99_ms", "free_pages", "kv_pages",
    "kv_page_size", "serve_slots", "kv_cache_dtype", "weight_dtype",
    "kv_pool_bytes", "kv_bytes_per_token", "tokens_per_pool_gb",
    "kv_capacity_vs_bf16", "kv_effective_page_capacity", "pages_in_use",
    "kv_pages_cached", "kv_pages_shared", "host_kv_pages",
    "kv_pages_hbm", "kv_pages_host", "tier_demotions", "tier_promotions",
    "tier_demote_failures", "tier_promote_failures",
    "tier_host_evictions", "tier_pending_migrations",
    "prefill_only_requests", "prefix_slab_exports", "prefix_slab_imports",
    "prefix_pages_imported", "prefix_cache", "prefix_lookups",
    "prefix_hits", "prefix_hit_rate", "prefill_tokens_saved",
    "prefix_evictions", "prefix_refs_live", "speculate_k",
    "spec_proposed", "spec_accepted", "spec_accept_rate",
    "paged_attention_impl", "pages_touched", "last_pages_touched",
}
ENGINE_HEALTH_KEYS = {
    "status", "admitting", "active_slots", "queued", "serve_slots",
    "free_pages", "completed", "failed", "timeouts", "occupancy",
    "recompiles", "pages_in_use", "kv_pages_shared", "prefix_hit_rate",
    "spec_accept_rate", "kv_cache_dtype", "weight_dtype",
    "kv_bytes_per_token", "tokens_per_pool_gb",
}
ROUTER_STATS_KEYS = {
    "replicas", "alive", "roles", "submitted", "dispatched", "completed",
    "failed", "timeouts", "rejected", "fenced", "resubmitted",
    "handoffs", "handoff_fallbacks", "queued", "max_queue",
    "ttft_p50_ms", "ttft_p99_ms", "affinity_keys", "affinity_host_keys",
    "per_replica", "fleet",
}
ROUTER_HEALTH_KEYS = {
    "status", "admitting", "alive", "replicas", "queued", "outstanding",
    "fenced", "max_queue",
}


def test_engine_stats_health_keys_superset(ff):
    eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8)
    st = eng.stats()
    missing = ENGINE_STATS_KEYS - set(st)
    assert not missing, f"stats() lost pre-telemetry keys: {missing}"
    hl = eng.health()
    missing = ENGINE_HEALTH_KEYS - set(hl)
    assert not missing, f"health() lost pre-telemetry keys: {missing}"


def test_router_stats_health_keys_superset(ff):
    router = ff.make_serving_router(replicas=2, max_seq_len=32,
                                    kv_page_size=8, start=False)
    try:
        st = router.stats()
        missing = ROUTER_STATS_KEYS - set(st)
        assert not missing, f"stats() lost pre-telemetry keys: {missing}"
        hl = router.health()
        missing = ROUTER_HEALTH_KEYS - set(hl)
        assert not missing, f"health() lost pre-telemetry keys: {missing}"
    finally:
        router.close()


# ------------------------------------------------- engine/router telemetry


def test_engine_emits_histograms_and_spans(ff):
    eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8)
    eng.set_telemetry_identity("t0", "solo-test")
    reqs = eng.run(_prompts(3, [5, 9, 12]), max_new_tokens=4)
    assert all(r.state == "done" for r in reqs)
    reg = telemetry.registry()
    hist = reg.histogram("ff_serving_ttft_seconds", labels=("replica",
                                                            "role"))
    assert hist.labels("t0", "solo-test").count == 3
    itl = reg.histogram("ff_serving_intertoken_seconds",
                        labels=("replica", "role"))
    assert itl.labels("t0", "solo-test").count == 3 * 3  # 4 tokens -> 3 gaps
    qw = reg.histogram("ff_serving_queue_wait_seconds",
                       labels=("replica", "role"))
    assert qw.labels("t0", "solo-test").count == 3
    # every request has a connected span tree: queue_wait + prefill
    # (cold) + decode, and the decode span closed at retirement
    for r in reqs:
        tree = telemetry.trace_tree(r.trace_id)
        assert {"queue_wait", "prefill", "decode"} <= set(tree["names"])
        decode = [e for e in tree["spans"] if e["name"] == "decode"][0]
        assert decode["args"]["state"] == "done"
        assert decode["args"]["tokens"] == 4
        prefill = [e for e in tree["spans"] if e["name"] == "prefill"][0]
        assert prefill["args"]["kind"] == "cold"
    # the scrape exports the stats() dict as labeled gauges
    text = reg.to_prometheus()
    assert ('ff_serving_completed{replica="t0",role="solo-test"}'
            in text)
    assert 'ff_serving_ttft_seconds_bucket{replica="t0"' in text


def test_engine_compile_says_hit_or_miss_from_jaxs_own_events(ff, caplog):
    """`_compiled_call` lists no cache directory: what its span and its log
    line say of the persistent cache is what jax reported while the span
    was the innermost open."""
    import logging

    from flexflow_tpu.runtime import serving

    assert not hasattr(serving, "compilation_cache_entries")
    assert not hasattr(serving, "compilation_cache_dir")
    telemetry.reset()
    eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8)
    from flexflow_tpu.logger import fflogger

    fflogger.addHandler(caplog.handler)     # it does not propagate to root
    try:
        with caplog.at_level(logging.INFO, logger="flexflow_tpu"):
            eng.run(_prompts(31, [5]), max_new_tokens=2)
    finally:
        fflogger.removeHandler(caplog.handler)
    spans = telemetry.tracer().events(name="compile")
    assert len(spans) == eng.recompile_count >= 2
    lines = [r.getMessage() for r in caplog.records
             if "serving: compiled" in r.getMessage()]
    assert len(lines) == len(spans)
    for ev, line in zip(spans, lines):
        a = ev["args"]
        # the suite runs without a persistent cache: jax asked, nothing hit
        assert a["cache"] == "miss" and a.get("cache_hits", 0) == 0
        assert "persistent cache MISS" in line
        assert f"{a['cache_requests']} of {a['cache_requests']}" in line


def test_engine_prefix_hit_span_kind(ff):
    eng = ff.make_serving_engine(max_seq_len=48, kv_page_size=8)
    rs = np.random.RandomState(5)
    system = rs.randint(1, VOCAB, (16,)).astype(np.int32)
    p1 = np.concatenate([system, rs.randint(1, VOCAB, (3,)).astype(np.int32)])
    p2 = np.concatenate([system, rs.randint(1, VOCAB, (4,)).astype(np.int32)])
    r1 = eng.run([p1], max_new_tokens=2)[0]
    r2 = eng.run([p2], max_new_tokens=2)[0]
    k1 = [e for e in telemetry.trace_tree(r1.trace_id)["spans"]
          if e["name"] == "prefill"][0]["args"]
    k2 = [e for e in telemetry.trace_tree(r2.trace_id)["spans"]
          if e["name"] == "prefill"][0]["args"]
    assert k1["kind"] == "cold" and k1["matched_pages"] == 0
    assert k2["kind"] == "hit" and k2["matched_pages"] == 2


def test_engine_telemetry_off_is_silent(ff, tmp_path):
    from flexflow_tpu.runtime import flightrec

    cfg_prev = ff.config.telemetry
    fr_prev = ff.config.flight_recorder_dir
    ff.config.telemetry = "off"
    # the flight recorder + SLO evaluator (ISSUE 15) must short-circuit
    # at the SAME single predicate: even with a bundle directory and an
    # SLO spec configured, "off" silences them alongside every emit
    ff.config.flight_recorder_dir = str(tmp_path)
    ff.config.slo_ttft_p99_s = 0.001
    try:
        eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8)
        eng.set_telemetry_identity("off0", "off-test")
        ring_before = len(telemetry.tracer())
        log_before = len(flightrec.log_ring())
        reqs = eng.run(_prompts(7, [5, 9]), max_new_tokens=3)
        assert all(r.state == "done" for r in reqs)
        hist = telemetry.registry().histogram(
            "ff_serving_ttft_seconds", labels=("replica", "role"))
        assert hist.labels("off0", "off-test").count == 0
        assert not telemetry.tracer().events(trace_id=reqs[0].trace_id)
        assert len(telemetry.tracer()) == ring_before
        # engine construction itself configured the recorder with
        # telemetry="off" (the call is unconditional for exactly this):
        # even with a directory and SLO specs set, every piece is silent
        flightrec.trip("engine_exception", replica="off0")
        assert flightrec.recorder().wait_pending(2.0)
        assert flightrec.list_bundles(str(tmp_path)) == []
        assert flightrec.slo_monitor().maybe_evaluate() == []
        assert flightrec.slo_monitor().evaluate() == []
        assert len(flightrec.log_ring()) == log_before
    finally:
        ff.config.telemetry = cfg_prev
        ff.config.flight_recorder_dir = fr_prev
        ff.config.slo_ttft_p99_s = 0.0
        flightrec.reset()


def test_router_trace_tree_complete(ff):
    router = ff.make_serving_router(replicas=1, max_seq_len=32,
                                    kv_page_size=8, start=False)
    try:
        reqs = router.run(_prompts(11, [5, 9, 14]), max_new_tokens=4,
                          timeout=600)
        assert all(r.state == "done" for r in reqs)
        for r in reqs:
            tree = telemetry.trace_tree(r.trace_id)
            assert tree["complete"], tree
            assert tree["root"]["name"] == "request"
            assert tree["root"]["args"]["state"] == "done"
            assert {"queue_wait", "prefill", "decode"} <= set(tree["names"])
            assert any(e["name"] == "dispatch"
                       for e in tree["annotations"])
        recent = router.recent_traces()
        assert {t["trace_id"] for t in recent} >= \
            {r.trace_id for r in reqs}
    finally:
        router.close()


@pytest.mark.parametrize("read", ["the live ring", "scrape and bundle"])
def test_failover_span_continuity(ff, monkeypatch, tmp_path, read):
    """A crash-failover request keeps ONE trace: spans on both replicas
    under the same root, a resubmit annotation in between, and the
    fault annotation marks where the drill landed. Read from the live
    ring; and read as an operator would after the fact: the scrape
    carries the latency histograms and the failover counters as labeled
    series over both replicas, and the drill's trigger storm (crash fault,
    replica fence) leaves exactly ONE manifest-intact flight bundle whose
    own trace holds the failed-over requests' whole span trees."""
    from flexflow_tpu.runtime import flightrec

    # crash at the 2nd busy tick: tick 1 genuinely ADMITTED work on
    # replica 0 (prefills ran), so failed-over traces carry spans from
    # both replicas; enough requests that work is still queued/in-flight
    # when the crash lands
    monkeypatch.setenv("FF_FAULT", "crash(2)@replica:0")
    faultinject.reset()
    bundled = read == "scrape and bundle"
    if bundled:
        # a debounce this long keeps the storm ONE pending record, which
        # flush() publishes after the fleet has settled
        for knob, value in (("flight_recorder_dir", str(tmp_path)),
                            ("flight_debounce_s", 600.0),
                            ("flight_cooldown_s", 600.0),
                            ("flight_window_s", 600.0)):
            monkeypatch.setattr(ff.config, knob, value)
    try:
        # decode_chunk=2: a request takes 4+ ticks, so tick-2 work is
        # genuinely mid-decode when the replica dies
        router = ff.make_serving_router(replicas=2, max_seq_len=32,
                                        kv_page_size=8,
                                        health_timeout_s=60,
                                        decode_chunk=2, start=False)
        reqs = router.run(_prompts(13, [6, 10, 15, 7, 11, 9,
                                        8, 12, 5, 14, 10, 7]),
                          max_new_tokens=8, timeout=600)
        st = router.stats()
        assert st["fenced"] == 1 and st["resubmitted"] >= 1
        resub = [r for r in reqs if r.attempts == 2]
        assert resub, "the crash was supposed to catch work in flight"
        if bundled:
            _scrape_and_bundle(router, resub, str(tmp_path))
            router.close()
            return
        for r in resub:
            assert r.state == "done"
            tree = telemetry.trace_tree(r.trace_id)
            assert tree["complete"], tree
            assert tree["root"]["args"]["state"] == "done"
            marks = [e["name"] for e in tree["annotations"]]
            assert "resubmit" in marks
        # at least one failed-over request was ADMITTED on the dead
        # replica first: its one trace carries prefill spans from both
        # replicas (the span-continuity acceptance)
        assert any(
            len({e["pid"] for e in
                 telemetry.trace_tree(r.trace_id)["spans"]
                 if e["name"] == "prefill"}) == 2
            for r in resub), "no trace crossed both replicas"
        # the drill's fault annotation is present
        faults = telemetry.fault_events()
        assert any(e["args"]["kind"] == "crash"
                   and e["args"]["site"] == "replica" for e in faults)
        router.close()
    finally:
        monkeypatch.delenv("FF_FAULT", raising=False)
        faultinject.reset()
        if bundled:
            flightrec.reset()


def _scrape_and_bundle(router, failed_over, flight_dir):
    import os

    from flexflow_tpu.runtime import flightrec

    text = telemetry.registry().to_prometheus()
    for needle in ("ff_serving_ttft_seconds_bucket",
                   "ff_serving_intertoken_seconds_bucket",
                   "ff_serving_queue_wait_seconds_bucket",
                   "ff_router_ttft_seconds_bucket", "ff_router_fenced",
                   "ff_router_resubmitted", "ff_router_timeouts",
                   "ff_router_rejected", "ff_router_replica_up",
                   "ff_hbm_bytes"):
        assert needle in text, f"scrape missing {needle}"
    for r in range(2):
        assert f'replica="{r}",role="mixed"' in text, \
            f"scrape has no series for replica {r}"
    path = flightrec.recorder().flush()
    assert path and flightrec.list_bundles(flight_dir) == [path]
    flightrec.verify_bundle(path)

    def read(name):
        with open(os.path.join(path, name)) as f:
            return json.load(f)

    trig = read("trigger.json")
    assert (trig["cause"], trig["args"]["kind"]) == ("fault", "crash")
    assert "replica_fence" in [m["cause"] for m in trig["merged_triggers"]]
    assert trig["stack"]
    evs = read("trace.json")["traceEvents"]
    for r in failed_over:
        mine = [e for e in evs if e["ph"] == "X"
                and e.get("args", {}).get("trace_id") == r.trace_id]
        root = max((e for e in mine if e["name"] == "request"),
                   key=lambda e: e.get("dur", 0.0))
        t0, t1 = root["ts"], root["ts"] + root.get("dur", 0.0)
        assert all(t0 - 1.0 <= e["ts"] <= t1 + 1.0 for e in mine), \
            f"bundle trace incomplete for failed-over {r.trace_id}"
    assert read("engines.json")["router"]["stats"]["fenced"] == 1
    assert any(src.get("kv_pool", 0) > 0
               for src in read("hbm.json")["sources"].values())


def test_handoff_span_continuity(ff):
    """A prefill->decode handoff request keeps ONE trace: handoff_export
    on the prefill replica, handoff_import + hit prefill + decode on the
    decode replica, all inside the router's root span."""
    router = ff.make_serving_router(
        replicas=2, roles=["prefill", "decode"], max_seq_len=48,
        kv_page_size=8, start=False)
    try:
        rs = np.random.RandomState(17)
        system = rs.randint(1, VOCAB, (16,)).astype(np.int32)
        prompts = [np.concatenate(
            [system, rs.randint(1, VOCAB, (3,)).astype(np.int32)])
            for _ in range(4)]
        reqs = router.run(prompts, max_new_tokens=4, timeout=600)
        assert all(r.state == "done" for r in reqs)
        handed = [r for r in reqs if r.handoff]
        assert handed, "no request ever handed off"
        for r in handed:
            tree = telemetry.trace_tree(r.trace_id)
            assert tree["complete"], tree
            names = set(tree["names"])
            assert {"handoff_export", "handoff_import", "prefill",
                    "decode"} <= names, names
            # export on replica0 (prefill), decode on replica1
            by = {e["name"]: e["pid"] for e in tree["spans"]}
            assert by["handoff_export"] == "replica0"
            assert by["decode"] == "replica1"
    finally:
        router.close()


# --------------------------------------------------------- training spans


def test_fit_emits_step_spans_and_histogram():
    from flexflow_tpu import (ActiMode, LossType, MetricsType,
                              SGDOptimizer, SingleDataLoader)

    # host-resident data + no prefetch: the per-step (t_b..t_d) loop the
    # span emitter instruments
    cfg = FFConfig(batch_size=16, epochs=1, seed=3,
                   device_resident_data=False, native_dataloader=False,
                   prefetch_depth=0)
    model = FFModel(cfg)
    x = model.create_tensor([16, 8], name="x")
    t = model.dense(x, 16, ActiMode.AC_MODE_RELU, name="fc1")
    model.dense(t, 4, name="out")
    model.compile(SGDOptimizer(lr=0.1),
                  LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                  [MetricsType.METRICS_ACCURACY])
    rs = np.random.RandomState(7)
    SingleDataLoader(model, x, rs.randn(64, 8).astype(np.float32))
    SingleDataLoader(model, model.label_tensor,
                     rs.randint(0, 4, (64, 1)).astype(np.int32))
    before = telemetry.registry().histogram(
        "ff_train_step_seconds").labels().count
    model.fit(verbose=False)
    after = telemetry.registry().histogram(
        "ff_train_step_seconds").labels().count
    assert after > before
    steps = telemetry.tracer().events(name="train_step")
    assert steps, "fit() emitted no train_step spans"
    sid = steps[-1]["args"]["trace_id"]
    names = set(telemetry.trace_tree(sid)["names"])
    assert "host_wait" in names and "dispatch" in names


# ------------------------------- the tick's spans, ring and profiler (ISSUE 23)

# a decode tick's phases, in the order they run inside `decode_chunk`
DECODE_PHASES = ["decode_prepare", "decode_dispatch", "token_fetch",
                 "record_tokens"]


def _inside(child, parent, slack=1.0):
    """Ring events: child's [ts, ts + dur] within parent's (us)."""
    return (parent["ts"] - slack <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack)


def _ticks(eng, since_us):
    """[(engine_step event, [the events under it, by start])] of `eng`'s
    track since `since_us`, from the ring."""
    evs = [e for e in telemetry.tracer().events()
           if e["pid"] == eng._tm_track and e["ph"] == "X"
           and e["ts"] >= since_us]
    out = []
    for step in (e for e in evs if e["name"] == "engine_step"):
        kids = sorted((e for e in evs if e is not step
                       and e["name"] not in ("queue_wait", "decode")
                       and _inside(e, step)),
                      key=lambda e: (e["ts"], -e["dur"]))
        out.append((step, kids))
    return out


def _run_warm(eng, seed, lengths, max_new):
    """Run the same prompts twice (the first run compiles); returns the
    second run's requests, the ring clock before it and the stats delta."""
    eng.run(_prompts(seed, lengths), max_new_tokens=max_new)
    eng.flush_prefix_cache()
    st0, t0 = eng.stats(), telemetry.now_us()
    reqs = eng.run(_prompts(seed, lengths), max_new_tokens=max_new)
    st1 = eng.stats()
    return reqs, t0, {k: st1[k] - st0[k] for k in
                      ("decode_steps", "pages_touched", "kv_read_bytes",
                       "kv_streamed_bytes", "tokens_generated",
                       "completed")}


@pytest.fixture(scope="module")
def profiled(ff, tmp_path_factory):
    """ONE jax.profiler trace on the CPU holding hand-made spans, a tiny
    engine run with telemetry on and one with telemetry off, each under a
    marker annotation; the host plane's `ff.*` and marker events with their
    stats, and what the ring and the engine's counters said meanwhile."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8,
                                 decode_chunk=2)
    eng.run(_prompts(21, [5, 9, 12]), max_new_tokens=5)      # compiles
    eng.flush_prefix_cache()
    prev = ff.config.telemetry
    ff.config.telemetry = "off"
    try:
        off = ff.make_serving_engine(max_seq_len=32, kv_page_size=8,
                                     decode_chunk=2)
    finally:
        ff.config.telemetry = prev
    off.run(_prompts(21, [5, 9, 12]), max_new_tokens=5)
    off.flush_prefix_cache()

    logdir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tr = telemetry.tracer()
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with TraceAnnotation("mark.manual"):
            with tr.span("outer", trace_id="prof-1", track="t", depth=0):
                with tr.span("x", n=3) as sp:
                    sp.annotate(tokens=5)
            was = telemetry.set_enabled(False)
            with tr.span("disabled", n=1):
                pass
            telemetry.set_enabled(was)
        st0, t0 = eng.stats(), telemetry.now_us()
        with TraceAnnotation("mark.on"):
            reqs = eng.run(_prompts(21, [5, 9, 12]), max_new_tokens=5)
        st1 = eng.stats()
        ring_before = len(tr)
        with TraceAnnotation("mark.off"):
            off_reqs = off.run(_prompts(21, [5, 9, 12]), max_new_tokens=5)
        ring_after = len(tr)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(logdir + "/plugins/profile/*/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events += [{"name": e.name, "t0": e.start_ns,
                        "t1": e.start_ns + e.duration_ns,
                        "stats": dict(e.stats)}
                       for e in line.events
                       if e.name.startswith(("ff.", "mark."))]

    def under(mark):
        (m,) = [e for e in events if e["name"] == "mark." + mark]
        return [e for e in events if e["name"].startswith("ff.")
                and m["t0"] <= e["t0"] and e["t1"] <= m["t1"]]

    return {"under": under, "eng": eng, "reqs": reqs, "off_reqs": off_reqs,
            "ring_since": t0, "ring_grew_off": ring_after - ring_before,
            "delta": {k: st1[k] - st0[k] for k in
                      ("decode_steps", "kv_read_bytes", "kv_streamed_bytes",
                       "pages_touched")}}


@pytest.mark.parametrize("case", ["count at entry", "count from annotate()",
                                  "nests inside the enclosing span",
                                  "trace id rides as a stat",
                                  "set_enabled(False) writes no annotation"])
def test_span_lies_in_the_profilers_host_plane(profiled, case):
    by = {e["name"]: e for e in profiled["under"]("manual")}
    if case == "count at entry":
        assert by["ff.x"]["stats"]["n"] == 3
    elif case == "count from annotate()":
        assert by["ff.x"]["stats"]["tokens"] == 5
    elif case == "nests inside the enclosing span":
        assert by["ff.outer"]["t0"] <= by["ff.x"]["t0"]
        assert by["ff.x"]["t1"] <= by["ff.outer"]["t1"]
    elif case == "trace id rides as a stat":
        assert by["ff.outer"]["stats"] == {"depth": 0, "trace_id": "prof-1"}
        assert by["ff.x"]["stats"]["trace_id"] == "prof-1"   # inherited
    else:
        assert set(by) == {"ff.outer", "ff.x"}


def test_ring_and_profiler_hold_the_same_tick_spans(profiled):
    """One call site, two sinks: every live span of the engine's run is in
    the ring under its name and in the host plane under `ff.` + name, as
    often; the waits and lifecycles that cross ticks are ring-only."""
    eng = profiled["eng"]
    ring = [e["name"] for e in telemetry.tracer().events()
            if e["pid"] == eng._tm_track and e["ph"] == "X"
            and e["ts"] >= profiled["ring_since"]]
    plane = [e["name"] for e in profiled["under"]("on")]
    ring_only = {"queue_wait", "decode"}
    assert ring_only <= set(ring)
    assert sorted("ff." + n for n in ring if n not in ring_only) \
        == sorted(plane)
    # (`run` is the blocking call's own lifecycle span, around its ticks)
    assert {"ff.run", "ff.engine_step", "ff.admit", "ff.prefill",
            "ff.prefill_fetch", "ff.decode_chunk", "ff.slo_tick"} \
        | {"ff." + n for n in DECODE_PHASES} == set(plane)
    # the host blocked on the device only under a *_fetch name, and the
    # dispatch's counts reached the profiler as stats
    disp = [e["stats"] for e in profiled["under"]("on")
            if e["name"] == "ff.decode_dispatch"]
    assert sum(d["k"] for d in disp) == profiled["delta"]["decode_steps"]
    assert sum(d["kv_read_bytes"] for d in disp) \
        == profiled["delta"]["kv_read_bytes"] > 0
    # what the kernel's turns fetch for them: never less, and the same at
    # one page a turn
    assert {d["paged_turn_pages"] for d in disp} == {1}
    assert sum(d["kv_streamed_bytes"] for d in disp) \
        == profiled["delta"]["kv_streamed_bytes"] \
        == profiled["delta"]["kv_read_bytes"]


def test_telemetry_off_writes_neither_ring_event_nor_annotation(profiled):
    assert all(r.state == "done" for r in profiled["off_reqs"])
    assert profiled["under"]("off") == []
    assert profiled["ring_grew_off"] == 0


def test_tick_span_tree_and_dispatch_counts(ff):
    eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8,
                                 decode_chunk=2)
    reqs, since, delta = _run_warm(eng, 23, [5, 9, 12, 7], 6)
    assert all(r.state == "done" for r in reqs)
    ticks = _ticks(eng, since)
    assert ticks and [s["args"]["tick"] for s, _ in ticks] \
        == list(range(ticks[0][0]["args"]["tick"],
                      ticks[0][0]["args"]["tick"] + len(ticks)))
    admitted = 0
    for step, kids in ticks:
        by = {}
        for e in kids:
            by.setdefault(e["name"], []).append(e)
        assert set(by) <= {"admit", "prefill", "prefill_fetch",
                           "decode_chunk", *DECODE_PHASES}
        for adm in by.get("admit", []):
            assert len(by["prefill"]) == adm["args"]["admitted"]
            admitted += adm["args"]["admitted"]
            for p, f in zip(by["prefill"], by["prefill_fetch"]):
                assert _inside(p, adm) and _inside(f, p)
                assert p["args"]["kind"] == "cold" and p["args"]["ok"]
        if "decode_chunk" in by:
            (chunk,) = by["decode_chunk"]
            phases = [by[n][0] for n in DECODE_PHASES]
            assert all(len(by[n]) == 1 for n in DECODE_PHASES)
            assert all(_inside(p, chunk) for p in phases)
            # one after another, in the order they run
            assert all(a["ts"] + a["dur"] <= b["ts"] + 1.0
                       for a, b in zip(phases, phases[1:]))
            assert chunk["args"]["tokens"] \
                == by["record_tokens"][0]["args"]["tokens"]
    assert admitted == len(reqs)
    disp = [e["args"] for _, kids in ticks for e in kids
            if e["name"] == "decode_dispatch"]
    assert sum(d["k"] for d in disp) == delta["decode_steps"]
    assert sum(d["kv_read_bytes"] for d in disp) == delta["kv_read_bytes"]
    assert sum(d["kv_streamed_bytes"] for d in disp) \
        == delta["kv_streamed_bytes"] >= delta["kv_read_bytes"]
    assert eng.stats()["paged_turn_pages"] == 1     # the gather has no turns
    # pages_touched counts the final step's frontier once per dispatch: k
    # steps of it bound what the k steps stream
    st = eng.stats()
    per_page = eng.page_size * st["kv_bytes_per_token"]
    assert 0 < delta["kv_read_bytes"] \
        <= delta["pages_touched"] * eng.decode_chunk * per_page
    retired = sum(e["args"]["retired"] for _, kids in ticks for e in kids
                  if e["name"] == "record_tokens")
    first_token_retired = sum(1 for r in reqs if len(r.tokens) == 1)
    assert retired + first_token_retired == delta["completed"]


@pytest.mark.parametrize("slots,chunk", [(2, 2), (8, 8)])
def test_spans_per_tick_do_not_grow_with_slots_or_chunk(ff, slots, chunk):
    """One span per phase per tick: 7 under `engine_step` and itself, plus
    two per admitted request; never one per token or per slot."""
    eng = ff.make_serving_engine(max_seq_len=48, kv_page_size=8,
                                 serve_slots=slots, decode_chunk=chunk)
    reqs, since, delta = _run_warm(eng, 29, [5, 9, 12, 7, 6, 11, 8, 10], 17)
    assert all(r.state == "done" for r in reqs)
    assert delta["tokens_generated"] == 8 * 17
    ticks = _ticks(eng, since)
    for step, kids in ticks:
        admitted = sum(e["args"]["admitted"] for e in kids
                       if e["name"] == "admit")
        assert len(kids) <= 6 + 2 * admitted
    assert max(len(kids) for _, kids in ticks
               if not any(e["name"] == "admit" for e in kids)) == 5
    slo = [e for e in telemetry.tracer().events(name="slo_tick")
           if e["pid"] == eng._tm_track and e["ts"] >= since]
    assert len(slo) == len(ticks)


@pytest.mark.parametrize("mode", ["on", "off"])
def test_t_admit_splits_ttft(ff, mode):
    """queue wait + prefill: t_submit <= t_admit <= first token, whether or
    not telemetry is on (the stamp is the request's, not the ring's)."""
    prev = ff.config.telemetry
    ff.config.telemetry = mode
    try:
        eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8,
                                     serve_slots=2)
    finally:
        ff.config.telemetry = prev
    reqs = eng.run(_prompts(31, [5, 9, 12, 7, 6]), max_new_tokens=3)
    assert all(r.state == "done" for r in reqs)
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_submit + r.ttft
    # 5 requests through 2 slots: the later ones really waited
    assert max(r.t_admit - r.t_submit for r in reqs) \
        > min(r.t_admit - r.t_submit for r in reqs)
