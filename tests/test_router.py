"""Fleet serving router (runtime/router.py ServingRouter).

Correctness anchors:
  * the router moves work, never changes it: greedy fleet output is
    token-identical to solo generate, at any replica count, through any
    failover — a resubmitted request's final stream is ONE replica's
    complete greedy decode, never a splice;
  * failover is exactly-once: a crashed/hung replica is fenced, its
    in-flight and queued requests resubmit to survivors at most once
    (losses cap at 2; attempts == 2 on a mixed fleet, where no
    handoff double-dispatch exists), nothing is lost, nothing is
    duplicated;
  * deadlines are honored at the cheapest point: expired-while-queued
    requests retire as "timeout" with zero dispatch (and zero compiles);
    expired in-flight work on a fenced replica is NOT resubmitted;
  * shedding is fast: a full router queue rejects in microseconds with
    state "rejected" — accepted work is unaffected;
  * prefix affinity sends shared-prompt traffic to the replica whose
    trie already holds the pages (hits concentrate on one engine).

Every failure drill is deterministic via FF_FAULT (crash@replica,
hang@replica, slow@serve — runtime/faultinject.py).
"""

import time

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject

VOCAB = 89


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=64, layers=2,
                         heads=4, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


@pytest.fixture(scope="module")
def draft(ff):
    """A smaller draft LM over the SAME vocabulary (random weights — the
    reject path runs hard), for the prefix+speculation failover test."""
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=32, layers=1,
                         heads=2, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model


def _prompts(seed, lengths):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB, (L,)).astype(np.int32) for L in lengths]


def _solo_check(ff, reqs, max_new):
    for r in reqs:
        solo = ff.generate(r.prompt[None, :], max_new_tokens=max_new)
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), solo[0, r.prompt.size:],
            err_msg=f"request {r.rid} (attempts {r.attempts}, replica "
                    f"{r.replica}) diverged from its solo run")


def _arm_fault(monkeypatch, spec):
    monkeypatch.setenv("FF_FAULT", spec)
    faultinject.reset()


def _disarm_fault(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()


# ---- host-side semantics (no decode, no compiles: tier-1 fast) -----------


def test_router_validation_and_rejection_is_fast(ff):
    """Malformed submits raise synchronously; a full queue rejects in
    well under a millisecond of work (shedding must be cheaper than the
    work it sheds); constructor guards hold."""
    router = ff.make_serving_router(replicas=1, serve_slots=2,
                                    kv_page_size=4, max_seq_len=32,
                                    max_queue=2, start=False)
    try:
        with pytest.raises(ValueError, match="empty"):
            router.submit(np.zeros((0,), np.int32), 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            router.submit(np.arange(1, 5, dtype=np.int32), 0)
        with pytest.raises(ValueError, match="max_seq_len"):
            router.submit(np.arange(1, 30, dtype=np.int32), 16)
        with pytest.raises(ValueError, match="deadline_s"):
            router.submit(np.arange(1, 5, dtype=np.int32), 4,
                          deadline_s=-1.0)
        a = router.submit(np.arange(1, 5, dtype=np.int32), 4)
        b = router.submit(np.arange(1, 6, dtype=np.int32), 4)
        t0 = time.perf_counter()
        shed = [router.submit(np.arange(1, 5, dtype=np.int32), 4)
                for _ in range(20)]
        dt = time.perf_counter() - t0
        assert [r.state for r in shed] == ["rejected"] * 20
        assert all(r.attempts == 0 and r.t_done for r in shed)
        assert dt < 0.1, f"20 rejections took {dt:.3f}s — not 'fast'"
        assert a.state == "queued" and b.state == "queued"
        st = router.stats()
        assert st["rejected"] == 20 and st["queued"] == 2
        assert st["submitted"] == 22 and st["max_queue"] == 2
    finally:
        router.close()
    with pytest.raises(ValueError, match="replicas"):
        ff.make_serving_router(replicas=0, start=False)
    with pytest.raises(ValueError, match="max_queue"):
        ff.make_serving_router(replicas=1, max_queue=-1, start=False)
    with pytest.raises(ValueError, match="health_timeout_s"):
        ff.make_serving_router(replicas=1, health_timeout_s=0.0,
                               start=False)
    with pytest.raises(ValueError):
        FFConfig(batch_size=2, mesh_shape={"data": 1}, serve_max_queue=-1)
    cfg = FFConfig.parse_args(["--batch-size", "2",
                               "--serve-max-queue", "9"])
    assert cfg.serve_max_queue == 9


def test_deadline_expired_while_queued_never_dispatches(ff):
    """A request whose deadline passes in the router queue retires as
    "timeout" with zero dispatch — and therefore zero compiles: the
    cheapest possible retirement."""
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=32,
                                    start=False)
    try:
        req = router.submit(np.arange(1, 6, dtype=np.int32), 4,
                            deadline_s=0.0)
        time.sleep(0.005)
        router.start()
        router.wait([req], timeout=30)
        assert req.state == "timeout" and req.attempts == 0
        assert "router queue" in req.error
        st = router.stats()
        assert st["timeouts"] == 1 and st["dispatched"] == 0
        assert all(e.recompile_count == 0 for e in router.engines), \
            "an expired-in-queue request must never reach a device"
        assert router.health()["status"] == "idle"
    finally:
        router.close()


def test_router_stats_and_health_keys(ff):
    """The fleet observability surface: counters + per-replica rows in
    stats(), a cheap health() that never touches an engine lock."""
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=32,
                                    start=False)
    try:
        st = router.stats()
        for key in ("replicas", "alive", "submitted", "dispatched",
                    "completed", "failed", "timeouts", "rejected",
                    "fenced", "resubmitted", "queued", "max_queue",
                    "ttft_p50_ms", "ttft_p99_ms", "affinity_keys",
                    "per_replica"):
            assert key in st, f"stats() missing {key}"
        assert len(st["per_replica"]) == 2
        for row in st["per_replica"]:
            for key in ("replica", "fenced", "fence_reason",
                        "outstanding", "active_slots", "queued"):
                assert key in row, f"per_replica row missing {key}"
        h = router.health()
        for key in ("status", "admitting", "alive", "replicas", "queued",
                    "outstanding", "fenced", "max_queue"):
            assert key in h, f"health() missing {key}"
        assert h["status"] == "idle" and h["alive"] == 2
        assert all(e.recompile_count == 0 for e in router.engines)
    finally:
        router.close()


# ---- fleet semantics (decode on both replicas) ----------------------------


@pytest.mark.slow  # 25 s
def test_fleet_token_identity_and_both_replicas_serve(ff):
    """More requests than one replica's capacity, mixed lengths: every
    stream equals its solo generate run, and least-loaded dispatch
    actually spreads work across BOTH replicas."""
    prompts = _prompts(3, [5, 9, 3, 12, 7, 6, 17, 2, 11, 4])
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64)
    try:
        reqs = router.run(prompts, max_new_tokens=6, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        _solo_check(ff, reqs, 6)
        st = router.stats()
        assert st["completed"] == len(prompts)
        assert st["fenced"] == 0 and st["resubmitted"] == 0
        served = [e.stats()["completed"] for e in router.engines]
        assert all(c > 0 for c in served), \
            f"least-loaded dispatch left a replica idle: {served}"
        assert sum(served) == len(prompts), "requests duplicated or lost"
        assert 0 < st["ttft_p50_ms"] <= st["ttft_p99_ms"]
    finally:
        router.close()


@pytest.mark.parametrize("sanitize", ["off", "on"])
def test_crash_failover_exactly_once_token_identity(ff, monkeypatch,
                                                    sanitize):
    """FF_FAULT crash@replica:0 mid-flight: the replica is fenced, its
    in-flight and queued work resubmits to the survivor exactly once,
    every request completes with its solo tokens, none is duplicated.
    Under the sanitizer (every runtime lock an order-asserting proxy, every
    engine's retrace sentinel armed by a warmup over the run's own
    buckets) the same drill leaves no lock-order violation and no
    post-warmup retrace."""
    from flexflow_tpu.runtime import locks

    prompts = _prompts(5, [5, 9, 3, 12, 7, 6])
    prev = locks.set_mode(sanitize)
    locks.reset()
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    decode_chunk=2, start=False)
    try:
        router.warmup(prompts if sanitize == "on" else _prompts(6, [5, 9]),
                      max_new_tokens=12 if sanitize == "on" else 2)
        warm_done = router.engines[1].stats()["completed"]
        _arm_fault(monkeypatch, "crash(3)@replica:0")
        reqs = router.run(prompts, max_new_tokens=12, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        _solo_check(ff, reqs, 12)
        st = router.stats()
        assert st["fenced"] == 1 and st["resubmitted"] >= 1
        assert st["completed"] == len(prompts), "lost or duplicated"
        assert all(1 <= r.attempts <= 2 for r in reqs), \
            "resubmission must happen at most once"
        assert any(r.attempts == 2 for r in reqs), \
            "the crash was supposed to catch work in flight"
        # the fenced replica's engine is abandoned; the survivor did the
        # failover work (delta past its warmup traffic)
        assert router.engines[1].stats()["completed"] - warm_done == sum(
            1 for r in reqs if r.replica == 1)
        assert router.health()["alive"] == 1
        if sanitize == "on":
            assert hasattr(router.engines[1]._lock, "rank"), "no proxy"
            assert locks.violations() == [], [
                (v["outer"], v["inner"]) for v in locks.violations()]
            assert locks.retrace_log() == [], [
                (r["program"], r["signature"]) for r in locks.retrace_log()]
            assert [e.stats()["sanitizer_retraces"]
                    for e in router.engines] == [0, 0]
    finally:
        _disarm_fault(monkeypatch)
        router.close()
        locks.set_mode(prev)
        locks.reset()


@pytest.mark.slow  # 45 s — the
# satellite pin: failover token identity with prefix cache AND
# speculation live on both replicas
def test_requeue_after_crash_token_identity_with_prefix_and_spec(
        ff, draft, monkeypatch):
    """A request resubmitted to a second replica mid-stream produces the
    SAME greedy tokens as an uninterrupted single-replica run, with the
    radix prefix cache and speculative decoding enabled on both
    replicas: the failover path composes with every serving feature
    without touching the stream."""
    rs = np.random.RandomState(11)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)  # 2 full pages
    prompts = [np.concatenate([system,
                               rs.randint(1, VOCAB, (L,)).astype(np.int32)])
               for L in (2, 6, 4, 3, 5)]
    kwargs = dict(serve_slots=2, kv_page_size=4, max_seq_len=64,
                  decode_chunk=2, draft_model=draft, speculate_k=2)

    # the uninterrupted single-replica reference run
    ref = ff.make_serving_engine(**kwargs)
    want = [np.asarray(r.tokens, np.int32)
            for r in ref.run(prompts, max_new_tokens=10)]

    router = ff.make_serving_router(replicas=2, start=False, **kwargs)
    try:
        router.warmup(prompts[:2], max_new_tokens=2)
        _arm_fault(monkeypatch, "crash(3)@replica:0")
        reqs = router.run(prompts, max_new_tokens=10, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        st = router.stats()
        assert st["fenced"] == 1 and st["resubmitted"] >= 1
        assert any(r.attempts == 2 for r in reqs), \
            "no request was actually resubmitted mid-stream"
        for w, r in zip(want, reqs):
            np.testing.assert_array_equal(
                w, np.asarray(r.tokens, np.int32),
                err_msg=f"request {r.rid} (attempts {r.attempts}) "
                        f"diverged from the uninterrupted run")
        # the survivor's prefix cache and speculation genuinely ran
        sst = router.engines[1].stats()
        assert sst["prefix_hits"] > 0 and sst["spec_proposed"] > 0
    finally:
        _disarm_fault(monkeypatch)
        router.close()


def test_hang_detected_fenced_and_survivor_completes(ff, monkeypatch):
    """FF_FAULT hang@replica:1: the wedged driver stops heartbeating,
    the health sweep fences it within health_timeout_s, its work moves
    to the survivor, every stream stays solo-identical. Warm programs
    first — a tight timeout is only meaningful when a healthy tick is
    milliseconds (a cold tick legitimately compiles for seconds)."""
    prompts = _prompts(7, [5, 9, 3, 12])
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    decode_chunk=2, prefix_cache=False,
                                    health_timeout_s=1.0, start=False)
    try:
        router.warmup(_prompts(8, [6, 10]), max_new_tokens=2)
        _arm_fault(monkeypatch, "hang@replica:1")
        t0 = time.monotonic()
        reqs = router.run(prompts, max_new_tokens=10, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        _solo_check(ff, reqs, 10)
        st = router.stats()
        assert st["fenced"] == 1
        assert "hang" in router.stats()["per_replica"][1]["fence_reason"]
        # detection is bounded by the timeout, not by luck
        assert time.monotonic() - t0 < 60
    finally:
        _disarm_fault(monkeypatch)
        router.close()


def test_slow_replica_expired_inflight_not_resubmitted(ff, monkeypatch):
    """FF_FAULT slow(400)@serve:1 stalls replica 0's first admission past
    the request's 150 ms deadline; when the replica is then crashed, the
    expired in-flight request retires as "timeout" WITHOUT being
    resubmitted (the work is already worthless) while non-expired work
    fails over normally."""
    prompts = _prompts(9, [5, 9])
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    decode_chunk=2, prefix_cache=False,
                                    start=False)
    try:
        router.warmup(_prompts(10, [6, 10]), max_new_tokens=2)
        _arm_fault(monkeypatch, "slow(400)@serve:1,crash(3)@replica:0")
        # submit a ALONE and wait for its dispatch (least-loaded
        # tie-break -> replica 0) so the process-global slow@serve
        # occurrence 1 deterministically lands on ITS admission, then
        # send b (replica 0 now loaded -> replica 1)
        a = router.submit(prompts[0], 12, deadline_s=0.15)
        router.start()
        t0 = time.monotonic()
        while a.attempts == 0 and time.monotonic() - t0 < 60:
            time.sleep(0.002)
        assert a.replica == 0, "tie-break must send the first request to 0"
        time.sleep(0.1)   # replica 0 is now inside its slow admission
        b = router.submit(prompts[1], 12)
        router.wait([a, b], timeout=300)
        assert a.state == "timeout" and a.attempts == 1
        assert "fenced replica" in a.error
        assert b.state == "done"
        st = router.stats()
        assert st["fenced"] == 1
        assert st["resubmitted"] == 0, \
            "expired in-flight work must not burn survivor capacity"
        assert st["timeouts"] == 1
    finally:
        _disarm_fault(monkeypatch)
        router.close()


def test_prefix_affinity_concentrates_shared_prompts(ff):
    """Shared-prefix traffic lands on the replica that already holds the
    prefix pages: after the first shared-prompt request homes, the rest
    follow it (prefix hits concentrate on ONE engine) while background
    traffic still balances."""
    rs = np.random.RandomState(13)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)  # 2 full pages
    shared = [np.concatenate([system,
                              rs.randint(1, VOCAB, (L,)).astype(np.int32)])
              for L in (2, 5, 3, 4)]
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64)
    try:
        # home the prefix: run the first shared prompt alone
        first = router.run([shared[0]], max_new_tokens=4, timeout=300)[0]
        home = first.replica
        reqs = router.run(shared[1:], max_new_tokens=4, timeout=300)
        assert all(r.state == "done" for r in reqs)
        assert all(r.replica == home for r in reqs), (
            f"shared-prefix requests scattered: "
            f"{[r.replica for r in reqs]}, home {home}")
        hits = [e.stats()["prefix_hits"] for e in router.engines]
        assert hits[home] == len(shared) - 1
        assert hits[1 - home] == 0
        _solo_check(ff, [first] + reqs, 4)
        assert router.stats()["affinity_keys"] >= 1
    finally:
        router.close()


def test_shedding_accepted_work_unaffected_and_fleet_drains(ff):
    """With a bounded queue, shed load never touches accepted work:
    accepted requests all complete solo-identical; drain() settles the
    fleet and leaves every surviving engine drained."""
    prompts = _prompts(15, [5, 9, 3, 12, 7, 6, 4, 8])
    router = ff.make_serving_router(replicas=1, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    max_queue=3, start=False)
    try:
        reqs = [router.submit(p, max_new_tokens=5) for p in prompts]
        accepted = [r for r in reqs if r.state == "queued"]
        shed = [r for r in reqs if r.state == "rejected"]
        assert len(accepted) == 3 and len(shed) == len(prompts) - 3
        snap = router.drain()   # starts the drivers, finishes the queue
        assert snap["drained"] and snap["rejected"] == len(shed)
        assert [r.state for r in accepted] == ["done"] * len(accepted)
        _solo_check(ff, accepted, 5)
        assert router.health()["status"] == "drained"
        assert router.engines[0].health()["status"] == "drained"
        with pytest.raises(RuntimeError, match="draining"):
            router.submit(prompts[0], 4)
    finally:
        router.close()


@pytest.mark.slow  # 15 s
def test_serve_fleet_api(ff):
    """FFModel.serve_fleet: the one-shot fleet surface returns outputs
    aligned with prompts (None for shed/expired) plus the fleet ledger."""
    prompts = _prompts(17, [5, 9, 3, 12])
    outs, st = ff.serve_fleet(prompts, max_new_tokens=5, replicas=2,
                              serve_slots=2, kv_page_size=4,
                              max_seq_len=64)
    assert st["completed"] == len(prompts) and st["alive"] == 2
    for p, out in zip(prompts, outs):
        solo = ff.generate(p[None, :], max_new_tokens=5)
        np.testing.assert_array_equal(out, solo[0, :p.size + 5])
