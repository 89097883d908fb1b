"""Flight recorder + SLO health plane + HBM ledger (runtime/flightrec.py,
ISSUE 15).

Correctness anchors:
  * the recorder state machine — log-ring bounds under concurrent
    writers, trigger debounce (a storm merges into ONE pending bundle),
    cooldown suppression, bundle ATOMICITY (manifest-hashed publish; a
    torn write is detected by the same verifier the checkpoint layer
    trusts, and an unpublished tmp dir is invisible), keep-K retention;
  * SLO window math — a breach fires only after a full window of a
    series' own traffic (first sight = baseline, never judgement), an
    empty window neither confirms nor clears, and a breach clears only
    after ``slo_clear_windows`` consecutive healthy windows (hysteresis);
  * the HBM ledger exports per-subsystem ``ff_hbm_bytes`` series and the
    fflint cross-check gauge;
  * the ``/healthz`` rollup is ok|degraded|breach with per-SLO reasons;
  * ``FFConfig.telemetry="off"`` short-circuits recorder, SLO evaluator
    and log ring at the same single predicate as every other emit.
"""

import json
import logging
import os
import threading

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import flightrec, telemetry
from flexflow_tpu.runtime.checkpoint import CheckpointCorruptError

VOCAB = 53


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    flightrec.reset()
    yield
    flightrec.reset()
    telemetry.reset()


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def _cfg(tmp_path=None, **kw):
    base = dict(batch_size=2, mesh_shape={"data": 1})
    if tmp_path is not None:
        base["flight_recorder_dir"] = str(tmp_path)
    base.update(kw)
    return FFConfig(**base)


def _rec(name="flexflow_tpu", msg="m", level=logging.INFO):
    return logging.LogRecord(name, level, __file__, 1, msg, (), None)


# ------------------------------------------------------------- log ring


def test_log_ring_bounded_under_concurrent_writers():
    ring = flightrec.LogRing(cap=256)
    threads = [threading.Thread(
        target=lambda i=i: [ring.record(_rec(msg=f"w{i}-{j}"))
                            for j in range(500)])
        for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ring) == 256                 # bounded, whatever the load
    rows = ring.recent()
    assert len(rows) == 256
    assert all({"ts", "level", "logger", "msg"} <= set(r) for r in rows)
    assert ring.recent(5) == rows[-5:]


def test_fflogger_feeds_process_ring(tmp_path):
    flightrec.configure(_cfg(tmp_path))
    from flexflow_tpu.logger import fflogger

    fflogger.warning("flightrec-needle-%d", 41)
    assert any("flightrec-needle-41" in r["msg"]
               for r in flightrec.log_ring().recent())


# ------------------------------------------------- trigger state machine


def test_trip_is_noop_without_directory():
    flightrec.configure(_cfg())            # no flight_recorder_dir
    flightrec.trip("fence", replica=0)
    assert flightrec.recorder().wait_pending(1.0)
    st = flightrec.recorder().stats()
    assert st["bundles_written"] == 0 and not st["pending"]


def test_trip_debounce_merges_and_cooldown_suppresses(tmp_path):
    flightrec.configure(_cfg(tmp_path, flight_debounce_s=0.05,
                             flight_cooldown_s=60.0))
    flightrec.trip("replica_fence", replica=1, reason="crash")
    flightrec.trip("fault", kind="crash", site="replica")  # the storm
    assert flightrec.recorder().wait_pending(10.0)
    bundles = flightrec.list_bundles(str(tmp_path))
    assert len(bundles) == 1, bundles      # one bundle, not N
    trig = json.load(open(os.path.join(bundles[0], "trigger.json")))
    assert trig["cause"] == "replica_fence"
    assert trig["args"]["replica"] == 1
    assert len(trig["merged_triggers"]) == 1
    assert trig["merged_triggers"][0]["cause"] == "fault"
    assert trig["stack"]                   # where the trigger fired
    # inside the cooldown a new trigger is SUPPRESSED, not written
    flightrec.trip("replica_fence", replica=2)
    assert flightrec.recorder().wait_pending(1.0)
    assert len(flightrec.list_bundles(str(tmp_path))) == 1
    assert flightrec.recorder().triggers_suppressed == 1
    # the NEXT bundle attributes exactly that suppressed trigger to
    # itself (a delta since the previous bundle, not a lifetime total)
    p2 = flightrec.dump()
    t2 = json.load(open(os.path.join(p2, "trigger.json")))
    assert t2["suppressed_in_cooldown"] == 1
    p3 = flightrec.dump()
    t3 = json.load(open(os.path.join(p3, "trigger.json")))
    assert t3["suppressed_in_cooldown"] == 0


def test_flush_forces_pending_write(tmp_path):
    flightrec.configure(_cfg(tmp_path, flight_debounce_s=600.0))
    flightrec.trip("watchdog_fire", label="step 7")
    assert flightrec.recorder().stats()["pending"]
    path = flightrec.recorder().flush()
    assert path and os.path.isdir(path)
    assert flightrec.list_bundles(str(tmp_path)) == [path]
    # a flush that caused no write returns None — never a stale
    # previous bundle's path masquerading as this incident's
    assert flightrec.recorder().flush() is None


def test_retention_keeps_newest_k(tmp_path):
    flightrec.configure(_cfg(tmp_path, flight_keep=2))
    paths = [flightrec.dump(note=i) for i in range(4)]  # manual: no
    #                                     cooldown, always writes
    assert all(paths)
    left = flightrec.list_bundles(str(tmp_path))
    assert len(left) == 2
    assert left == paths[-2:]              # the newest K survive


# ------------------------------------------------------ bundle contents

BUNDLE_FILES = {"trigger.json", "trace.json", "metrics.json",
                "logs.jsonl", "fingerprint.json", "engines.json",
                "hbm.json", "slo.json", "sanitizer.json",
                "ff_manifest.json"}


def test_bundle_contents_manifest_and_torn_write(tmp_path):
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=5.0))
    telemetry.tracer().instant("drill_marker", track="t", k=1)
    telemetry.registry().counter("bundle_probe_total").inc(3)
    path = flightrec.dump(cause="manual", operator="test")
    assert set(os.listdir(path)) == BUNDLE_FILES
    flightrec.verify_bundle(path)          # intact
    trace = json.load(open(os.path.join(path, "trace.json")))
    assert any(e["name"] == "drill_marker" for e in trace["traceEvents"])
    metrics = json.load(open(os.path.join(path, "metrics.json")))
    assert metrics["bundle_probe_total"]["series"][0]["value"] == 3
    fp = json.load(open(os.path.join(path, "fingerprint.json")))
    assert fp["config"]["slo_ttft_p99_s"] == 5.0
    assert "env" in fp
    slo = json.load(open(os.path.join(path, "slo.json")))
    assert slo["specs"] == {"ttft_p99": 5.0}
    san = json.load(open(os.path.join(path, "sanitizer.json")))
    assert san["mode"] in ("off", "on", "strict")
    assert san["ranks"]["router"] < san["ranks"]["engine"]
    for key in ("tracked_locks", "violation_pairs", "violations",
                "retraces"):
        assert key in san
    # torn-write drill: flip bytes mid-payload — the manifest catches it
    victim = os.path.join(path, "metrics.json")
    blob = bytearray(open(victim, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(blob))
    with pytest.raises(CheckpointCorruptError):
        flightrec.verify_bundle(path)
    # a manifest-less dir is a torn/foreign write, never "intact"
    bare = tmp_path / (flightrec.BUNDLE_PREFIX + "99999_bare")
    bare.mkdir()
    with pytest.raises(CheckpointCorruptError):
        flightrec.verify_bundle(str(bare))


def test_unpublished_tmp_dir_is_invisible(tmp_path):
    flightrec.configure(_cfg(tmp_path))
    torn = tmp_path / "tmp-bundle-bundle_00007_crash"
    torn.mkdir()
    (torn / "trigger.json").write_text("{}")
    assert flightrec.list_bundles(str(tmp_path)) == []
    p = flightrec.dump()
    assert flightrec.list_bundles(str(tmp_path)) == [p]


def test_dump_without_directory_raises_and_off_returns_none(tmp_path):
    flightrec.configure(_cfg())
    with pytest.raises(ValueError):
        flightrec.dump()
    flightrec.configure(_cfg(tmp_path, telemetry="off"))
    assert flightrec.dump() is None        # the off contract covers
    #                                        manual dumps too
    flightrec.trip("fence")
    assert flightrec.recorder().stats()["bundles_written"] == 0


# --------------------------------------------------------- SLO windows


def _ttft_child(replica="0", role="mixed"):
    return telemetry.registry().histogram(
        "ff_serving_ttft_seconds", labels=("replica", "role")).labels(
        replica, role)


def test_slo_breach_only_after_full_window_then_hysteresis(tmp_path):
    m = flightrec.slo_monitor()
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1,
                             slo_window_s=30.0, slo_clear_windows=2))
    ch = _ttft_child()
    ch.observe(0.5)                        # way over the ceiling
    # no full window has elapsed: the tick returns at one time compare
    assert m.maybe_evaluate() == []
    # first judged window only BASELINES a series it has never seen —
    # a breach can only fire on a full window of the series' own traffic
    assert m.evaluate() == []
    ch.observe(0.5)
    ev = m.evaluate()
    assert [e["slo"] for e in ev] == ["ttft_p99"]
    assert ev[0]["replica"] == "0" and ev[0]["value"] > 0.1
    reg = telemetry.registry()
    breach = reg.counter("ff_slo_breach_total",
                         labels=("slo", "replica"))
    assert breach.labels("ttft_p99", "0").get() == 1
    assert reg.gauge("ff_slo_margin", labels=("slo", "replica")).labels(
        "ttft_p99", "0").get() < 0
    status = reg.gauge("ff_slo_status", labels=("slo", "replica"))
    assert status.labels("ttft_p99", "0").get() == 0
    assert telemetry.tracer().events(name="slo_breach")
    # an EMPTY window neither confirms nor clears
    assert m.evaluate() == []
    assert m.breaches() and m.breaches()[0]["slo"] == "ttft_p99"
    # hysteresis: one healthy window is not a clear...
    ch.observe(0.001)
    assert m.evaluate() == []
    assert m.breaches()
    # ...two consecutive healthy windows are
    ch.observe(0.001)
    m.evaluate()
    assert m.breaches() == []
    assert status.labels("ttft_p99", "0").get() == 1
    assert telemetry.tracer().events(name="slo_clear")


def test_slo_fleet_series_replica_label(tmp_path):
    """Label-free histograms (router TTFT, train step) are judged and
    REPORTED as replica="fleet" — /healthz and /slo.json join against
    the metric labels exactly."""
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1))
    m = flightrec.slo_monitor()
    ch = telemetry.registry().histogram("ff_router_ttft_seconds").labels()
    m.evaluate()
    ch.observe(2.0)
    ev = m.evaluate()
    assert ev and ev[0]["replica"] == "fleet"
    assert m.breaches()[0]["replica"] == "fleet"
    row = [s for s in m.describe()["series"]
           if s["slo"] == "ttft_p99"][0]
    assert row["labels"]["replica"] == "fleet"


def test_slo_warmup_traffic_never_judged(tmp_path):
    """rebaseline() (called by engine/router warmup) restarts every
    snapshot: compile-inflated TTFTs before it are invisible."""
    m = flightrec.slo_monitor()
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1))
    ch = _ttft_child()
    m.evaluate()                           # series is known
    ch.observe(9.0)                        # "warmup compile" TTFT
    m.rebaseline()
    assert m.evaluate() == []              # the 9s never judged
    ch.observe(0.01)
    assert m.evaluate() == []              # healthy window stays clean


def test_slo_ratio_floor_breach_and_clear(tmp_path):
    m = flightrec.slo_monitor()
    flightrec.configure(_cfg(tmp_path, slo_prefix_hit_rate_min=0.8,
                             slo_clear_windows=1))
    counters = {"prefix_hits": 0, "prefix_lookups": 0,
                "spec_accepted": 0, "spec_proposed": 0}

    def source():
        return ("r7", dict(counters))

    m.add_source(source)
    assert m.evaluate() == []              # baseline
    counters["prefix_hits"] += 1
    counters["prefix_lookups"] += 10      # windowed rate 0.1 < 0.8
    ev = m.evaluate()
    assert ev and ev[0]["slo"] == "prefix_hit_rate" \
        and ev[0]["replica"] == "r7"
    assert telemetry.registry().counter(
        "ff_slo_breach_total", labels=("slo", "replica")).labels(
        "prefix_hit_rate", "r7").get() == 1
    # empty denominator window: no judgement either way
    assert m.evaluate() == []
    assert m.breaches()
    counters["prefix_hits"] += 10
    counters["prefix_lookups"] += 10      # windowed rate 1.0
    m.evaluate()
    assert m.breaches() == []             # clear_windows=1


def test_slo_breach_trips_recorder(tmp_path):
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1,
                             slo_trip_recorder=True,
                             flight_debounce_s=600.0))
    m = flightrec.slo_monitor()
    ch = _ttft_child("2", "decode")
    m.evaluate()
    ch.observe(3.0)
    assert m.evaluate()
    path = flightrec.recorder().flush()
    assert path is not None
    trig = json.load(open(os.path.join(path, "trigger.json")))
    assert trig["cause"] == "slo_breach"
    assert trig["args"]["slo"] == "ttft_p99"


def test_slo_disabled_spec_clears_breach_state(tmp_path):
    """Reconfiguring with a spec turned OFF prunes its breached state —
    /healthz cannot wedge at 'breach' for an SLO nobody watches."""
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1))
    m = flightrec.slo_monitor()
    ch = _ttft_child()
    m.evaluate()
    ch.observe(5.0)
    assert m.evaluate()
    assert m.breaches()
    flightrec.configure(_cfg(tmp_path))    # spec off
    assert m.breaches() == []
    assert flightrec.health_rollup()["status"] != "breach"


def test_telemetry_off_short_circuits_everything(tmp_path):
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1,
                             flight_debounce_s=0.0))
    m = flightrec.slo_monitor()
    ch = _ttft_child()
    m.evaluate()
    prev = telemetry.set_enabled(False)    # the process-wide switch
    try:
        ring0 = len(flightrec.log_ring())
        flightrec.log_ring().record(_rec(msg="dropped"))
        assert len(flightrec.log_ring()) == ring0
        flightrec.trip("fence")
        assert flightrec.recorder().stats()["bundles_written"] == 0
        assert m.evaluate() == [] and m.maybe_evaluate() == []
        assert flightrec.dump() is None
    finally:
        telemetry.set_enabled(prev)
    # the module's own gate (the recorder alone off) behaves identically
    flightrec.set_enabled(False)
    try:
        flightrec.trip("fence")
        assert flightrec.recorder().stats()["bundles_written"] == 0
    finally:
        flightrec.set_enabled(True)


# ---------------------------------------------------------- HBM ledger


def test_hbm_ledger_sources_and_lint_crosscheck():
    led = flightrec.hbm_ledger()

    def src():
        return ("fakepool", {"kv_pool": 1000, "adapter_pool": 24})

    led.add_source(src)
    led.set_lint_estimate(2048.0)
    snap = led.snapshot()
    assert snap["sources"]["fakepool"]["kv_pool"] == 1000
    assert snap["total_tracked_bytes"] == 1024
    assert snap["lint_estimated_bytes"] == 2048.0
    assert snap["lint_vs_tracked_ratio"] == 2.0
    text = telemetry.registry().to_prometheus()
    assert ('ff_hbm_bytes{source="fakepool",subsystem="kv_pool"} 1000'
            in text)
    assert "ff_hbm_total_tracked_bytes 1024" in text
    assert "ff_hbm_lint_estimated_bytes 2048" in text


# ------------------------------------------------------- health rollup


def test_health_rollup_ok_degraded_breach(tmp_path):
    ok_probe = {"kind": "router", "status": "busy", "alive": 2,
                "replicas": 2, "fenced": 0}

    def probe():
        return dict(ok_probe)

    flightrec.register_health_source(probe)
    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1))
    roll = flightrec.health_rollup()
    assert roll["status"] == "ok" and roll["slos"] == {"ttft_p99": "ok"}
    ok_probe.update(fenced=1, alive=1)
    roll = flightrec.health_rollup()
    assert roll["status"] == "degraded"
    assert any("fenced" in r for r in roll["degraded_reasons"])
    # an active SLO breach outranks degraded
    m = flightrec.slo_monitor()
    ch = _ttft_child()
    m.evaluate()
    ch.observe(5.0)
    m.evaluate()
    roll = flightrec.health_rollup()
    assert roll["status"] == "breach"
    assert roll["slos"]["ttft_p99"][0]["replica"] == "0"


def test_healthz_and_slo_json_endpoints(tmp_path):
    import urllib.error
    import urllib.request

    flightrec.configure(_cfg(tmp_path, slo_ttft_p99_s=0.1,
                             slo_clear_windows=1))
    port = telemetry.start_http_server(0)
    try:
        m = flightrec.slo_monitor()
        ch = _ttft_child()
        m.evaluate()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            body = json.loads(r.read())
            assert r.status == 200 and body["status"] == "ok"
        ch.observe(5.0)
        m.evaluate()                       # breach -> 503
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "breach"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/slo.json", timeout=10) as r:
            slo = json.loads(r.read())
        assert slo["specs"] == {"ttft_p99": 0.1}
        assert slo["breaches"]
        ch.observe(0.001)
        m.evaluate()                       # clears (clear_windows=1)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        telemetry.stop_http_server()


# -------------------------------------------------- engine integration


def test_engine_sources_ride_the_bundle(ff, tmp_path):
    prev = ff.config.flight_recorder_dir
    ff.config.flight_recorder_dir = str(tmp_path)
    try:
        eng = ff.make_serving_engine(max_seq_len=32, kv_page_size=8)
        eng.set_telemetry_identity("fr0", "solo-test")
        rs = np.random.RandomState(3)
        reqs = eng.run([rs.randint(1, VOCAB, (n,)).astype(np.int32)
                        for n in (5, 9)], max_new_tokens=3)
        assert all(r.state == "done" for r in reqs)
        flightrec.hbm_ledger().add_source(ff._hbm_source)
        path = flightrec.dump(cause="manual")
        engines = json.load(open(os.path.join(path, "engines.json")))
        row = engines["engine-fr0"]
        assert row["stats"]["completed"] == 2
        assert row["health"]["status"] == "idle"
        hbm = json.load(open(os.path.join(path, "hbm.json")))
        assert hbm["sources"]["engine-fr0"]["kv_pool"] > 0
        model_rows = [v for k, v in hbm["sources"].items()
                      if k.startswith("model-")]
        assert model_rows and model_rows[0]["params"] > 0
        # the health rollup sees the engine's lock-free probe
        roll = flightrec.health_rollup()
        kinds = [r.get("kind") for r in roll["fleet"]]
        assert "engine" in kinds
    finally:
        ff.config.flight_recorder_dir = prev


def test_model_dump_flight_record_and_off_contract(ff, tmp_path):
    path = ff.dump_flight_record(directory=str(tmp_path), note="drill")
    assert path and os.path.isdir(path)
    flightrec.verify_bundle(path)
    trig = json.load(open(os.path.join(path, "trigger.json")))
    assert trig["cause"] == "manual" and trig["args"]["source"] == "model"
    prev = ff.config.telemetry
    ff.config.telemetry = "off"
    try:
        assert ff.dump_flight_record(directory=str(tmp_path)) is None
    finally:
        ff.config.telemetry = prev


# ------------------------------------------------------- config knobs


def test_config_validation_and_flags():
    with pytest.raises(ValueError):
        _cfg(flight_keep=0)
    with pytest.raises(ValueError):
        _cfg(flight_cooldown_s=-1)
    with pytest.raises(ValueError):
        _cfg(flight_window_s=0)
    with pytest.raises(ValueError):
        _cfg(slo_ttft_p99_s=-0.1)
    with pytest.raises(ValueError):
        _cfg(slo_prefix_hit_rate_min=1.5)
    with pytest.raises(ValueError):
        _cfg(slo_window_s=0)
    with pytest.raises(ValueError):
        _cfg(slo_clear_windows=0)
    cfg = FFConfig.parse_args([
        "--flight-recorder-dir", "/tmp/fr", "--flight-keep", "7",
        "--flight-cooldown-s", "2.5", "--flight-debounce-s", "0.2",
        "--flight-window-s", "33", "--slo-ttft-p99-s", "0.25",
        "--slo-prefix-hit-rate-min", "0.6", "--slo-window-s", "3",
        "--slo-clear-windows", "3", "--slo-trip-recorder"])
    assert cfg.flight_recorder_dir == "/tmp/fr"
    assert cfg.flight_keep == 7 and cfg.flight_cooldown_s == 2.5
    assert cfg.flight_debounce_s == 0.2 and cfg.flight_window_s == 33.0
    assert cfg.slo_ttft_p99_s == 0.25
    assert cfg.slo_prefix_hit_rate_min == 0.6
    assert cfg.slo_window_s == 3.0 and cfg.slo_clear_windows == 3
    assert cfg.slo_trip_recorder
