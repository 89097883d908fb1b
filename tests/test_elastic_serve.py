"""Elastic fleet (ISSUE 20): SLO-driven autoscaling + preemption-tolerant
serving with exactly-once state evacuation.

Correctness anchors:
  * the AutoscalePolicy is a windowed hysteresis controller: a breach
    must PERSIST across ``breach_windows`` consecutive windows before
    the fleet grows, idleness must persist across ``idle_windows``
    before it shrinks, every action starts a cooldown, and min/max
    bounds always win — a breach storm thrashes counters, never
    replicas;
  * live membership preserves every existing contract: a scaled-out
    replica serves token-identical greedy streams, a scale-in requeues
    queued-never-admitted work automatically (the PR-5 drain contract
    left it parked on the retiring engine — the regression pinned
    here), and no request is stranded when remove_replica() races
    fresh submissions;
  * survivors inherit the retiree's state: hot prefix pages land
    bitwise-identical (per-namespace) on a survivor and serve warm
    hits, and registered LoRA adapters keep serving with no caller
    re-register;
  * preemption is exactly-once: every queued/in-flight request on the
    preempted replica completes exactly once on a survivor with its
    solo-identical stream (losses NOT counted — a later real failover
    still fits the cap), and a deadline-starved evacuation degrades to
    a clean fence, never a stall, duplicate, or lost request.

Drills are deterministic via FF_FAULT (preempt(<deadline_ms>)@replica:<r>,
slow_evac(<ms>)@evacuate:<n> — runtime/faultinject.py).
"""

import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject
from flexflow_tpu.runtime.autoscale import AutoscalePolicy, PlacementAdvisor

VOCAB = 89


@pytest.fixture(scope="module")
def ff():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=64, layers=2,
                         heads=4, kv_heads=2, vocab_size=VOCAB)
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


def _assert_slab_bitwise(got, ref):
    """Two page slabs carry the SAME prefix: tokens, salted namespace,
    and every pool array of every page, bitwise."""
    np.testing.assert_array_equal(got["tokens"], ref["tokens"])
    assert got["ns"] == ref["ns"], "namespace changed in evacuation"
    assert len(got["payload"]) == len(ref["payload"])
    for gp, rp in zip(got["payload"], ref["payload"]):
        assert gp.keys() == rp.keys()
        for key in gp:
            assert gp[key].keys() == rp[key].keys()
            for name in gp[key]:
                np.testing.assert_array_equal(
                    gp[key][name], rp[key][name],
                    err_msg=f"page array {key}/{name} not bitwise")


def _arm_fault(monkeypatch, spec):
    monkeypatch.setenv("FF_FAULT", spec)
    faultinject.reset()


def _disarm_fault(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()


# ---- policy state machine (fake fleet, no model: tier-1 fast) ------------


class _FakeCfg:
    telemetry = "off"           # keep the fake off the global registries
    slo_window_s = 10.0
    dcn_mesh_shape = {"data": 2}
    autoscale_min_replicas = 1
    autoscale_max_replicas = 3
    autoscale_breach_windows = 2
    autoscale_idle_windows = 3
    autoscale_cooldown_s = 30.0


class _FakeModel:
    config = _FakeCfg()


class _FakeRouter:
    """Just enough fleet for the policy: health(), stats(), and the two
    actuators, with a scriptable load signal."""

    def __init__(self, replicas=2):
        self.model = _FakeModel()
        self.alive = replicas
        self.queued = 0
        self.outstanding = 0
        self.added = []
        self.removed = []

    def health(self):
        return {"alive": self.alive, "queued": self.queued,
                "outstanding": self.outstanding}

    def stats(self):
        rows = [{"replica": r, "fenced": False, "retired": False,
                 "suspended": False, "outstanding": r, "queued": 0}
                for r in range(self.alive)]
        return {"alive": self.alive, "per_replica": rows,
                "fleet": {"pages_by_tier": {"hbm": 8, "host": 0}},
                "evacuated_pages": 0, "evacuation_bytes": 0}

    def add_replica(self):
        self.alive += 1
        self.added.append(self.alive - 1)
        return self.alive - 1

    def remove_replica(self, r, **kw):
        self.alive -= 1
        self.removed.append(r)
        return {"replica": r, "requeued": 0, "fenced": False}


class _FakeSLO:
    def __init__(self):
        self.rows = []

    def maybe_evaluate(self, now=None):
        return []

    def breaches(self):
        return self.rows

    def __getattr__(self, name):
        # the monkeypatched accessor is global: engines/routers under
        # test call rebaseline()/add_source()/... on membership changes
        # too — absorb everything that is not the scripted read surface
        return lambda *a, **kw: None


def _policy(monkeypatch, router, **kw):
    slo = _FakeSLO()
    from flexflow_tpu.runtime import autoscale as A
    monkeypatch.setattr(A.flightrec, "slo_monitor", lambda: slo)
    return AutoscalePolicy(router, **kw), slo


def test_autoscale_breach_streak_hysteresis_and_cooldown(monkeypatch):
    """One bad window never scales; a persistent queue_wait breach does;
    the action zeroes the streak and starts a cooldown that suppresses
    (and counts) the next trigger; an unrelated SLO never triggers."""
    rt = _FakeRouter(replicas=2)
    pol, slo = _policy(monkeypatch, rt, max_replicas=5)
    breach = [{"slo": "queue_wait_p99", "replica": -1, "value": 2.0,
               "bound": 0.5, "ok_streak": 0, "windows": 3}]
    slo.rows = breach
    assert pol.tick() is None, "breach window 1 of 2 must not act"
    assert pol.tick() == "scale_out" and rt.added == [2]
    st = pol.state()
    assert st["breach_streak"] == 0 and st["scale_outs"] == 1
    # cooldown: the streak re-arms but the action is suppressed
    assert pol.tick() is None and pol.tick() is None
    assert pol.state()["cooldown_blocks"] >= 1 and rt.alive == 3
    # a quality SLO (hit rate) is NOT a capacity signal
    slo.rows = [{"slo": "prefix_hit_rate", "replica": -1, "value": 0.1,
                 "bound": 0.5, "ok_streak": 0, "windows": 3}]
    pol2, _ = _policy(monkeypatch, _FakeRouter(replicas=1))
    for _ in range(5):
        assert pol2.tick() is None
    assert pol2.state()["breach_streak"] == 0


def test_autoscale_max_bound_blocks_scale_out(monkeypatch):
    rt = _FakeRouter(replicas=3)         # already at max_replicas
    pol, slo = _policy(monkeypatch, rt)
    slo.rows = [{"slo": "ttft_p99", "replica": 0, "value": 9.0,
                 "bound": 1.0, "ok_streak": 0, "windows": 2}]
    for _ in range(4):
        assert pol.tick() is None
    assert rt.added == [] and pol.state()["bound_blocks"] >= 1


def test_autoscale_idle_streak_scale_in_and_min_bound(monkeypatch):
    """Sustained idleness retires the least-loaded replica; busy-but-ok
    windows reset the idle streak; min_replicas always wins."""
    rt = _FakeRouter(replicas=2)
    pol, _ = _policy(monkeypatch, rt, cooldown_s=0.0)
    assert pol.tick() is None and pol.tick() is None
    # a busy window resets the calm
    rt.queued = 3
    assert pol.tick() is None and pol.state()["idle_streak"] == 0
    rt.queued = 0
    for _ in range(2):
        assert pol.tick() is None
    assert pol.tick() == "scale_in"
    assert rt.removed == [0], "least-outstanding replica retires first"
    # now at min_replicas: idleness can never empty the fleet
    for _ in range(6):
        pol.tick()
    assert rt.alive == 1 and pol.state()["bound_blocks"] >= 1


def test_autoscale_knob_validation_and_state_keys():
    rt = _FakeRouter()
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalePolicy(rt, min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscalePolicy(rt, min_replicas=4, max_replicas=2)
    st = AutoscalePolicy(rt).state()
    for k in ("breach_streak", "idle_streak", "cooldown_remaining_s",
              "scale_outs", "scale_ins", "cooldown_blocks",
              "bound_blocks", "last_action", "events"):
        assert k in st


def test_placement_advisor_prices_ici_vs_dcn():
    """The advisor reuses the search's interconnect constants: ICI while
    the modeled transfer fits the budget, DCN (with the penalty ratio
    recorded) once it does not — the decision is priced, not guessed."""
    adv = PlacementAdvisor(budget_s=1.0)
    small = adv.place(1 << 20)
    assert small["tier"] == "ici" and small["dcn_s"] > small["ici_s"]
    assert small["dcn_penalty_x"] > 1.0
    huge = adv.place(10 ** 12)          # ~22 s on ICI: over any warmup budget
    assert huge["tier"] == "dcn"
    assert huge["ici_s"] > 1.0


def test_config_elastic_knob_validation():
    base = dict(batch_size=2, mesh_shape={"data": 1})
    with pytest.raises(ValueError, match="autoscale_min_replicas"):
        FFConfig(autoscale_min_replicas=0, **base)
    with pytest.raises(ValueError, match="autoscale_max_replicas"):
        FFConfig(autoscale_min_replicas=3, autoscale_max_replicas=2, **base)
    with pytest.raises(ValueError, match="autoscale_breach_windows"):
        FFConfig(autoscale_breach_windows=0, **base)
    with pytest.raises(ValueError, match="autoscale_cooldown_s"):
        FFConfig(autoscale_cooldown_s=-1.0, **base)
    with pytest.raises(ValueError, match="preempt_deadline_s"):
        FFConfig(preempt_deadline_s=0.0, **base)
    cfg = FFConfig.parse_args([
        "--autoscale-min-replicas", "2",
        "--autoscale-max-replicas", "5",
        "--autoscale-breach-windows", "3",
        "--autoscale-idle-windows", "9",
        "--autoscale-cooldown-s", "7.5",
        "--preempt-deadline-s", "2.0"])
    assert (cfg.autoscale_min_replicas, cfg.autoscale_max_replicas) \
        == (2, 5)
    assert (cfg.autoscale_breach_windows, cfg.autoscale_idle_windows) \
        == (3, 9)
    assert cfg.autoscale_cooldown_s == 7.5
    assert cfg.preempt_deadline_s == 2.0


def test_engine_reclaim_queued_drains_parked_queue(ff):
    """The PR-5 drain contract left queued-never-admitted requests
    parked on a draining engine; reclaim_queued() hands them back so a
    scale-in can requeue them (the ISSUE-20 bugfix)."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64)
    ps = _prompts(21, [5, 7, 3])
    reqs = [eng.submit(p, max_new_tokens=4) for p in ps]
    got = eng.reclaim_queued()
    assert [id(r) for r in got] == [id(r) for r in reqs]
    assert eng.load()["queued"] == 0
    assert eng.reclaim_queued() == []


# ---- live membership + preemption drills (model fixture: slow) -----------


@pytest.mark.slow  # 20 s
def test_scale_out_serves_token_identical(ff):
    """add_replica() on a live, mid-flood fleet: the newcomer is warmed
    before admission, takes real work, and every stream stays
    solo-identical; the ledger and /healthz see the grown fleet."""
    router = ff.make_serving_router(replicas=1, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    start=False)
    try:
        router.warmup(_prompts(6, [5, 9]), max_new_tokens=2)
        prompts = _prompts(31, [5, 9, 3, 12, 7, 6, 11, 4])
        reqs = [router.submit(p, max_new_tokens=5) for p in prompts]
        router.start()
        r_new = router.add_replica()
        assert r_new == 1
        router.wait(reqs, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        _solo_check(ff, reqs, 5)
        st = router.stats()
        assert st["scale_outs"] == 1 and st["alive"] == 2
        assert router.health()["replicas"] == 2
        # the newcomer genuinely served (warmup on the new engine plus
        # dispatched flood work)
        more = router.run(_prompts(32, [6, 8, 5, 9]), max_new_tokens=4,
                          timeout=300)
        assert any(r.replica == r_new for r in reqs + more), \
            "scaled-out replica never took work"
        _solo_check(ff, more, 4)
    finally:
        router.close()


@pytest.mark.slow  # 30 s
def test_scale_in_requeues_and_survivor_inherits(ff):
    """remove_replica() racing fresh submissions strands nothing: parked
    never-admitted work is requeued automatically and completes
    solo-identical on survivors. The retiree's hot prefix pages land
    BITWISE on a survivor (namespace preserved) and serve warm hits."""
    rs = np.random.RandomState(13)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)  # 2 full pages
    shared = [np.concatenate([system,
                              rs.randint(1, VOCAB, (L,)).astype(np.int32)])
              for L in (2, 5, 3)]
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64)
    try:
        first = router.run([shared[0]], max_new_tokens=4, timeout=300)[0]
        home = first.replica
        survivor = 1 - home
        ref_slab = router.engines[home].export_prefix_slab(system)
        assert ref_slab is not None and ref_slab["tokens"].size == 8
        # race the retirement against a fresh flood
        prompts = _prompts(41, [5, 9, 3, 12, 7, 6])
        reqs = [router.submit(p, max_new_tokens=5) for p in prompts]
        snap = router.remove_replica(home)
        router.wait(reqs, timeout=300)
        assert not snap["fenced"] and snap["pages"] >= 2
        assert [r.state for r in reqs] == ["done"] * len(prompts), \
            "scale-in stranded submitted work"
        _solo_check(ff, reqs, 5)
        assert all(r.replica == survivor for r in reqs)
        st = router.stats()
        assert st["scale_ins"] == 1 and st["alive"] == 1
        assert st["fenced"] == 0, "clean scale-in must not count a loss"
        assert st["replicas"] == 1 and st["retired"] == 1
        assert router.health()["status"] in ("idle", "busy")
        # inherited pages are bitwise the retiree's, namespace intact
        got = router.engines[survivor].export_prefix_slab(system)
        assert got is not None
        _assert_slab_bitwise(got, ref_slab)
        # and they serve warm hits: the shared prefix re-runs hot
        h0 = router.engines[survivor].stats()["prefix_hits"]
        more = router.run(shared[1:], max_new_tokens=4, timeout=300)
        assert all(r.state == "done" for r in more)
        _solo_check(ff, more, 4)
        assert router.engines[survivor].stats()["prefix_hits"] > h0, \
            "evacuated prefix pages never served a warm hit"
    finally:
        router.close()


def test_scale_in_inherits_adapters_no_reregister(ff):
    """After the adapter-holding replica retires, the tenant keeps
    serving from survivors with NO caller re-register; a later
    add_replica() replays the registry onto the newcomer too."""
    from tests.test_tenancy import RANK, _adapter_weights
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    adapter_pool_pages=2, lora_rank=RANK)
    try:
        geo = router.engines[0].lora.geometry
        router.register_adapter("t", _adapter_weights(geo, 3))
        p = _prompts(51, [7])[0]
        want = router.run([p], max_new_tokens=4, adapter="t",
                          timeout=300)[0]
        assert want.state == "done"
        router.remove_replica(0)
        got = router.run([p], max_new_tokens=4, adapter="t",
                         timeout=300)[0]
        assert got.state == "done" and got.replica == 1
        assert got.tokens == want.tokens, \
            "adapter stream changed across scale-in"
        r_new = router.add_replica()
        assert "t" in router.engines[r_new].lora.registry, \
            "newcomer missed the adapter registry replay"
    finally:
        router.close()


@pytest.mark.slow  # 30 s
def test_preempt_exactly_once_and_prefix_evacuation(ff, monkeypatch):
    """FF_FAULT preempt(800)@replica:0 mid-flood: the replica evacuates
    queued + in-flight work and its hot prefix pages inside the
    deadline, retires WITHOUT a fence (no loss counted — the router
    ledger equals the per-engine completion sum), every request
    completes exactly once solo-identical, and the evacuated prefix
    serves warm on the survivor."""
    rs = np.random.RandomState(17)
    system = rs.randint(1, VOCAB, (8,)).astype(np.int32)
    shared = [np.concatenate([system,
                              rs.randint(1, VOCAB, (L,)).astype(np.int32)])
              for L in (2, 5, 3, 4)]
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    decode_chunk=2, start=False)
    try:
        router.warmup(_prompts(6, [5, 9]), max_new_tokens=2)
        base = [e.stats()["completed"] for e in router.engines]
        _arm_fault(monkeypatch, "preempt(800)@replica:0")
        prompts = shared + _prompts(61, [5, 9, 12, 7, 6])
        reqs = router.run(prompts, max_new_tokens=8, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        _solo_check(ff, reqs, 8)
        st = router.stats()
        assert st["preempts"] == 1
        assert st["fenced"] == 0, \
            "a clean preemption must not count as a replica loss"
        assert st["evac_deadline_misses"] == 0
        assert st["completed"] == len(prompts)
        # exactly-once: router ledger == sum of per-engine completions
        done = [e.stats()["completed"] - b
                for e, b in zip(router.engines, base)]
        assert sum(done) == len(prompts), \
            f"duplicated or lost across preemption: {done}"
        assert st["per_replica"][0]["retired"]
        assert router.health()["replicas"] == 1
        # a replica that evacuated everything moved its state over
        if st["evacuated_slabs"]:
            assert st["evacuated_pages"] > 0 and st["evacuation_bytes"] > 0
        # round 2: the shared prefix serves warm from the survivor
        h0 = router.engines[1].stats()["prefix_hits"]
        more = router.run([shared[0]], max_new_tokens=4, timeout=300)
        assert more[0].state == "done" and more[0].replica == 1
        assert router.engines[1].stats()["prefix_hits"] > h0
        # exactly-once survives a LATER real failover: evacuation did
        # not burn a loss, so the losses cap still has headroom
        assert all(r.losses == 0 for r in reqs)
    finally:
        _disarm_fault(monkeypatch)
        router.close()


@pytest.mark.slow  # 25 s
def test_preempt_deadline_starved_degrades_to_clean_fence(ff, monkeypatch):
    """slow_evac stalls the first slab export past a tiny preemption
    deadline: evacuation aborts, the replica is FENCED (this one IS a
    loss) and its work resubmits cold through the existing exactly-once
    machinery — never a stall, duplicate, or lost request."""
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    decode_chunk=2, start=False)
    try:
        router.warmup(_prompts(6, [5, 9]), max_new_tokens=2)
        _arm_fault(monkeypatch,
                   "preempt(150)@replica:0,slow_evac(400)@evacuate:1")
        prompts = _prompts(71, [5, 9, 3, 12, 7, 6])
        reqs = router.run(prompts, max_new_tokens=8, timeout=300)
        assert [r.state for r in reqs] == ["done"] * len(prompts)
        _solo_check(ff, reqs, 8)
        st = router.stats()
        assert st["preempts"] == 1
        assert st["evac_deadline_misses"] == 1
        assert st["fenced"] == 1, \
            "a starved evacuation must degrade to a fence"
        assert st["completed"] == len(prompts), "lost or duplicated"
        assert all(1 <= r.attempts <= 2 for r in reqs)
        assert st["per_replica"][0]["retired"]
    finally:
        _disarm_fault(monkeypatch)
        router.close()


@pytest.mark.slow  # 15 s
def test_autoscaler_drives_real_router(ff, monkeypatch):
    """The policy wired to a REAL fleet: a scripted breach grows it via
    add_replica (newcomer serves token-identical), scripted idleness
    shrinks it back — actuators run outside the policy lock, so a tick
    can run concurrently with serving."""
    from flexflow_tpu.runtime import autoscale as A
    router = ff.make_serving_router(replicas=1, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    start=False)
    slo = _FakeSLO()
    monkeypatch.setattr(A.flightrec, "slo_monitor", lambda: slo)
    pol = AutoscalePolicy(router, min_replicas=1, max_replicas=2,
                          breach_windows=2, idle_windows=2,
                          cooldown_s=0.0)
    try:
        router.warmup(_prompts(6, [5, 9]), max_new_tokens=2)
        router.start()
        slo.rows = [{"slo": "queue_wait_p99", "replica": -1, "value": 2.0,
                     "bound": 0.5, "ok_streak": 0, "windows": 2}]
        assert pol.tick() is None
        assert pol.tick() == "scale_out"
        assert router.stats()["alive"] == 2
        reqs = router.run(_prompts(81, [5, 9, 3, 7]), max_new_tokens=4,
                          timeout=300)
        assert all(r.state == "done" for r in reqs)
        _solo_check(ff, reqs, 4)
        slo.rows = []
        assert pol.tick() is None
        assert pol.tick() == "scale_in"
        assert router.stats()["alive"] == 1
        assert pol.state()["events"][-1]["placement"]["tier"] in (
            "ici", "dcn")
    finally:
        pol.close()
        router.close()
