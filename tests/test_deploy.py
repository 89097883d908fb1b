"""SLO-gated rolling deployment (runtime/deploy.py + serving/router hooks).

Correctness anchors:
  * drain() is no longer terminal: drain -> reopen -> serve works, and
    the reopened engine's tokens still equal solo generate;
  * weight versions partition the KV world: a prompt cached under
    version A admits COLD under version B (zero cross-version prefix
    hits — the version_ns salt, the ISSUE-14 adapter mechanism extended
    to ``(version, adapter)``), and post-swap tokens are identical to a
    reference model holding the new weights;
  * the registry refuses what it cannot prove: a corrupt/torn artifact
    (FF_FAULT corrupt_ckpt@publish) fails manifest verify and the deploy
    is REFUSED before any replica is touched;
  * a torn swap (FF_FAULT swap_fail@deploy) rolls the whole deploy back
    — the fleet ends on the version it started on, exactly-once;
  * swap_weights refuses an engine with live slots (a mid-stream weight
    change would corrupt in-flight decodes).

The last test rolls a fleet under a closed-loop flood, where real traffic
feeds the SLO windows: a clean roll, then the canary-breach -> automatic
rollback drill (slow@canary).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import checkpoint, faultinject
from flexflow_tpu.runtime.deploy import (RollingDeployer,
                                         WeightArtifactRegistry)
from flexflow_tpu.runtime.kv_pool import RadixPrefixCache
from flexflow_tpu.runtime.serving import DEFAULT_WEIGHT_VERSION, version_ns

VOCAB = 61


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


def _bumped(params, scale=1.25):
    """A same-geometry tree with visibly different weights — 'v1'."""
    return jax.tree_util.tree_map(
        lambda x: (np.asarray(x) * scale).astype(np.asarray(x).dtype),
        params)


def _publish_bumped(ff, watch_dir, step, scale=1.25):
    """Publish a perturbed copy of the model's weights as v<step> and
    restore the model untouched — the test's 'new training run'."""
    reg = WeightArtifactRegistry(str(watch_dir))
    keep = ff.params
    ff.params = ff.executor.reshard_params(_bumped(keep, scale))
    try:
        version = reg.publish(ff, step=step)
    finally:
        ff.params = keep
    return reg, version


def _arm_fault(monkeypatch, spec):
    monkeypatch.setenv("FF_FAULT", spec)
    faultinject.reset()


# ---- version salt (pure host-side, no decode) -----------------------------


def test_version_ns_default_is_unsalted():
    """The construction version (and None/"") must produce the EXACT
    pre-deploy namespace — bare adapter — so a fleet that never deploys
    is bit-identical to the pre-ISSUE-17 trie; any other version salts
    the namespace (and thus the trie's first edge and the router's
    affinity key) per version."""
    for v in (None, "", DEFAULT_WEIGHT_VERSION):
        assert version_ns(v) is None
        assert version_ns(v, "lora-a") == "lora-a"
    assert version_ns("v3") == ("v3", None)
    assert version_ns("v3", "lora-a") == ("v3", "lora-a")
    toks = np.arange(1, 5, dtype=np.int32)
    keys = {RadixPrefixCache.first_chunk(toks, version_ns(v, None))
            for v in (DEFAULT_WEIGHT_VERSION, "v1", "v2")}
    assert len(keys) == 3, "versions must never collide on the trie key"
    # adapter x version compose: four distinct worlds
    keys = {RadixPrefixCache.first_chunk(toks, version_ns(v, a))
            for v in ("v0", "v1") for a in (None, "lora-a")}
    assert len(keys) == 4


def test_registry_publish_verify_load(ff, tmp_path):
    reg = WeightArtifactRegistry(str(tmp_path))
    assert reg.versions() == [] and reg.latest() is None
    assert reg.latest_intact() is None
    with pytest.raises(ValueError, match="reserved"):
        reg.publish(ff, step=0)  # v0 = construction weights
    v = reg.publish(ff, step=3)
    assert v == "v3"
    assert reg.versions() == ["v3"] and reg.latest() == "v3"
    assert reg.latest_intact() == "v3"
    reg.verify(v)  # intact
    host = reg.load_params(v)
    ref_leaves = jax.tree_util.tree_leaves(ff.params)
    got_leaves = jax.tree_util.tree_leaves(host)
    assert len(got_leaves) == len(ref_leaves)
    np.testing.assert_array_equal(np.asarray(got_leaves[0]),
                                  np.asarray(ref_leaves[0]))
    with pytest.raises(ValueError, match="v<step>"):
        reg.step_dir("release-candidate")
    with pytest.raises(ValueError, match="watch directory"):
        WeightArtifactRegistry("")


def test_corrupt_publish_refuses_deploy(ff, tmp_path, monkeypatch):
    """FF_FAULT corrupt_ckpt@publish:1 tears the artifact after it
    lands; verify must fail and the deploy must be REFUSED with zero
    replicas touched."""
    _arm_fault(monkeypatch, "corrupt_ckpt@publish:1")
    reg = WeightArtifactRegistry(str(tmp_path))
    v = reg.publish(ff, step=1)
    with pytest.raises(checkpoint.CheckpointCorruptError):
        reg.verify(v)
    assert reg.latest() == "v1" and reg.latest_intact() is None
    monkeypatch.delenv("FF_FAULT")
    faultinject.reset()

    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    start=False)
    try:
        dep = RollingDeployer(router, reg, canary_windows=0)
        report = dep.deploy("v1")
        assert report["state"] == "refused"
        assert "manifest" in report["error"] or report["error"]
        for eng in router.engines:
            assert eng.weight_version == DEFAULT_WEIGHT_VERSION
            assert eng.deploy_state == "serving"
            assert eng.stats()["weight_swaps"] == 0
        st = router.stats()
        assert st["swaps_completed"] == 0 and st["rollbacks"] == 0
        assert not st["deploying"]
    finally:
        router.close()


def test_deploy_completes_and_torn_swap_rolls_back(ff, tmp_path,
                                                   monkeypatch):
    """Idle-fleet state machine, no decode: a clean deploy moves every
    replica to v1 (one swap each, counters pinned); re-deploying the
    same version is a noop; a torn swap (swap_fail@deploy:1) on a later
    deploy rolls the fleet back to v1 exactly."""
    reg, v1 = _publish_bumped(ff, tmp_path, step=1)
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    start=False)
    try:
        dep = RollingDeployer(router, reg, canary_windows=0)
        report = dep.deploy(v1)
        assert report["state"] == "completed"
        assert report["swapped"] == [0, 1]
        assert report["prior_versions"] == [DEFAULT_WEIGHT_VERSION] * 2
        for eng in router.engines:
            assert eng.weight_version == "v1"
            assert eng.deploy_state == "serving"
            assert eng._cache_ns(None) == ("v1", None)
            st = eng.stats()
            assert st["weight_swaps"] == 1
            assert st["weight_version"] == "v1"
        # the override actually carries the bumped weights
        leaf0 = jax.tree_util.tree_leaves(
            router.engines[0].gen._source_params())[0]
        ref0 = jax.tree_util.tree_leaves(ff.params)[0]
        np.testing.assert_allclose(np.asarray(leaf0),
                                   np.asarray(ref0) * 1.25, rtol=1e-5)
        st = router.stats()
        assert st["swaps_completed"] == 2 and st["rollbacks"] == 0
        assert [row["weight_version"] for row in st["per_replica"]] \
            == ["v1", "v1"]
        h = router.health()
        assert h["weight_versions"] == ["v1", "v1"]
        assert not h["deploying"]

        assert dep.deploy(v1)["state"] == "noop"

        # torn swap on the roll to v2: replica 0 restores itself, the
        # deployer rolls the fleet back — everyone ends on v1
        reg2, v2 = _publish_bumped(ff, tmp_path, step=2, scale=1.5)
        _arm_fault(monkeypatch, "swap_fail@deploy:1")
        report = dep.deploy(v2)
        assert report["state"] == "rolled_back"
        assert "swap on replica 0" in report["error"]
        assert report["bundle"] is None  # no flight-recorder dir set
        for eng in router.engines:
            assert eng.weight_version == "v1"
            assert eng.deploy_state == "serving"
        st = router.stats()
        assert st["rollbacks"] == 1
        assert not router._suspended[0] and not router._suspended[1]
    finally:
        monkeypatch.delenv("FF_FAULT", raising=False)
        faultinject.reset()
        router.close()


def test_drain_reopen_gate_without_decode(ff):
    """The admission-gate half of the reopen regression: drain() on an
    idle engine closes submit(), reopen() lifts it — no decode needed."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=32)
    snap = eng.drain()
    assert snap["drained"] and snap["completed"] == 0
    with pytest.raises(RuntimeError, match="draining"):
        eng.submit(np.arange(1, 5, dtype=np.int32), 4)
    eng.reopen()
    req = eng.submit(np.arange(1, 5, dtype=np.int32), 4)
    assert req.state == "queued"
    eng.reopen()  # idempotent
    assert eng.stats()["weight_version"] == DEFAULT_WEIGHT_VERSION
    assert eng.stats()["deploy_state"] == "serving"


# ---- decode-carrying paths ------------------------------------------------


@pytest.mark.slow  # 20 s
def test_drain_reopen_serve_token_identity(ff):
    """drain -> reopen -> serve: the reopened engine serves again and
    its tokens still equal solo generate (ISSUE 17 satellite — drain
    used to be terminal)."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64)
    first = eng.run(_prompts(0, [5, 9]), max_new_tokens=4)
    assert [r.state for r in first] == ["done", "done"]
    eng.drain()
    eng.reopen()
    prompts = _prompts(1, [6, 11, 4])
    reqs = eng.run(prompts, max_new_tokens=6)
    assert [r.state for r in reqs] == ["done"] * 3
    for r in reqs:
        solo = ff.generate(r.prompt[None, :], max_new_tokens=6)
        np.testing.assert_array_equal(
            np.asarray(r.tokens, np.int32), solo[0, r.prompt.size:],
            err_msg=f"request {r.rid} diverged after drain->reopen")
    assert eng.stats()["completed"] == 5


@pytest.mark.slow  # 15 s
def test_swap_weights_refuses_live_slots(ff):
    """A mid-stream weight change corrupts in-flight decodes: swapping
    with live slots must raise, and the engine must finish serving the
    in-flight request untouched afterwards."""
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64, decode_chunk=2)
    req = eng.submit(np.arange(1, 7, dtype=np.int32), 8)
    eng.step()  # admit + first chunk: the slot is live now
    assert eng.active.any()
    with pytest.raises(RuntimeError, match="live slots"):
        eng.swap_weights(None, "v9")
    assert eng.weight_version == DEFAULT_WEIGHT_VERSION
    while eng.step():
        pass
    assert req.state == "done"
    solo = ff.generate(req.prompt[None, :], max_new_tokens=8)
    np.testing.assert_array_equal(np.asarray(req.tokens, np.int32),
                                  solo[0, req.prompt.size:])


@pytest.mark.slow  # 40 s
def test_version_salt_isolates_prefix_cache(ff, tmp_path):
    """The stale-KV kill shot: a prompt whose prefix is HOT under v0
    admits COLD after the swap to v1 (zero cross-version hits — new
    namespace AND the old one flushed), its tokens equal a reference
    model holding the v1 weights, and re-serving it under v1 hits its
    own freshly-cached pages."""
    reg, v1 = _publish_bumped(ff, tmp_path, step=1)
    eng = ff.make_serving_engine(serve_slots=2, kv_page_size=4,
                                 max_seq_len=64)
    shared = _prompts(7, [8])[0]
    eng.run([shared], max_new_tokens=4)
    base = eng.stats()
    eng.run([shared], max_new_tokens=4)
    warm = eng.stats()
    assert warm["prefix_hits"] == base["prefix_hits"] + 1, \
        "the v0 prefix must be hot before the swap"

    host = reg.load_params(v1)
    tree = ff.executor.reshard_params(host)
    eng.drain()
    eng.swap_weights(tree, v1)
    eng.reopen()
    assert eng.stats()["kv_pages_cached"] == 0, \
        "the swap must flush every v0 page"

    post = eng.stats()
    r1 = eng.run([shared], max_new_tokens=4)[0]
    after = eng.stats()
    assert after["prefix_hits"] == post["prefix_hits"], \
        "a v0-cached prefix must NOT hit under v1"
    # token identity vs a reference holding the v1 weights
    keep = ff.params
    ff.params = tree
    try:
        solo = ff.generate(shared[None, :], max_new_tokens=4)
    finally:
        ff.params = keep
    np.testing.assert_array_equal(np.asarray(r1.tokens, np.int32),
                                  solo[0, shared.size:],
                                  err_msg="post-swap tokens diverged "
                                          "from the v1 reference")
    # and v1's own cache works: the SAME prompt now hits under v1
    eng.run([shared], max_new_tokens=4)
    assert eng.stats()["prefix_hits"] == after["prefix_hits"] + 1


@pytest.mark.slow  # 45 s
def test_ab_fleet_per_version_hit_accounting(ff, tmp_path):
    """Mid-roll A/B window: replica 0 on v1, replica 1 still on v0
    behind one router. Identical prompts route to a consistent home via
    the version-salted affinity key, prefix hits accrue ONLY inside one
    version's world, and streams are token-identical to that version's
    reference — never a splice of the two."""
    reg, v1 = _publish_bumped(ff, tmp_path, step=1)
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64)
    try:
        tree = ff.executor.reshard_params(reg.load_params(v1))
        # half a roll, by hand: replica 0 -> v1
        router.suspend_replica(0)
        while not router.replica_quiesced(0):
            pass
        router.engines[0].drain()
        router.engines[0].swap_weights(tree, v1)
        router.engines[0].reopen()
        router.resume_replica(0)
        assert [e.weight_version for e in router.engines] == ["v1", "v0"]

        shared = _prompts(9, [8])[0]
        first = router.run([shared], max_new_tokens=4, timeout=300)[0]
        home = first.replica
        rest = router.run([shared, shared], max_new_tokens=4,
                          timeout=300)
        assert [r.replica for r in rest] == [home, home], \
            "version-salted affinity must keep the prompt on its home"
        other = 1 - home
        assert router.engines[other].stats()["prefix_hits"] == 0, \
            "cross-version world leaked a prefix hit"
        assert router.engines[home].stats()["prefix_hits"] >= 1
        # token identity against the HOME replica's weights
        keep = ff.params
        if router.engines[home].weight_version == v1:
            ff.params = tree
        try:
            solo = ff.generate(shared[None, :], max_new_tokens=4)
        finally:
            ff.params = keep
        for r in [first] + rest:
            np.testing.assert_array_equal(
                np.asarray(r.tokens, np.int32), solo[0, shared.size:],
                err_msg=f"request {r.rid} spliced versions")
        st = router.stats()
        assert sorted(row["weight_version"]
                      for row in st["per_replica"]) == ["v0", "v1"]
    finally:
        router.close()


@pytest.mark.slow  # 35 s
def test_rolling_deploy_on_live_fleet(ff, tmp_path):
    """End-to-end roll on a STARTED fleet (no flood: the last test has
    one): warmup re-runs under the new weights, both replicas end on
    v1, zero recompiles during the swaps (same-geometry override), and
    post-deploy traffic matches the v1 reference."""
    reg, v1 = _publish_bumped(ff, tmp_path, step=1)
    router = ff.make_serving_router(replicas=2, serve_slots=2,
                                    kv_page_size=4, max_seq_len=64,
                                    start=False)
    try:
        warm = _prompts(3, [5, 9])
        router.warmup(warm, max_new_tokens=2)
        router.start()
        pre = [e.stats()["recompiles"] for e in router.engines]
        dep = RollingDeployer(router, reg, canary_windows=0)
        report = dep.deploy(v1, warmup_prompts=warm, max_new_tokens=2)
        assert report["state"] == "completed"
        assert [e.weight_version for e in router.engines] == ["v1", "v1"]
        post = [e.stats()["recompiles"] for e in router.engines]
        assert post == pre, \
            f"same-geometry swap must not retrace: {pre} -> {post}"
        prompts = _prompts(11, [6, 10, 4])
        reqs = router.run(prompts, max_new_tokens=4, timeout=300)
        assert [r.state for r in reqs] == ["done"] * 3
        tree = ff.executor.reshard_params(reg.load_params(v1))
        keep = ff.params
        ff.params = tree
        try:
            for r in reqs:
                solo = ff.generate(r.prompt[None, :], max_new_tokens=4)
                np.testing.assert_array_equal(
                    np.asarray(r.tokens, np.int32),
                    solo[0, r.prompt.size:],
                    err_msg=f"request {r.rid} not serving v1 weights")
        finally:
            ff.params = keep
        assert router.stats()["swaps_completed"] == 2
    finally:
        router.close()


class _Flood(threading.Thread):
    """Closed-loop skewed flood: keeps up to `max_inflight` requests open
    (80 % share a system prompt) until stopped, sampling how many replicas
    are suspended at once: the capacity >= N - 1 witness."""

    def __init__(self, router, rs, system, max_new, max_inflight=12):
        super().__init__(daemon=True)
        self.router, self.rs, self.system = router, rs, system
        self.max_new, self.max_inflight = max_new, max_inflight
        self.reqs, self.max_suspended = [], 0
        self._halt = threading.Event()
        self._done_before = self._engines_done()

    def _engines_done(self):
        return sum(e.stats()["completed"] for e in self.router.engines)

    def run(self):
        rs = self.rs
        while not self._halt.is_set():
            self.max_suspended = max(self.max_suspended,
                                     sum(self.router._suspended))
            if sum(not r.settled for r in self.reqs) >= self.max_inflight:
                time.sleep(0.004)
                continue
            if rs.randint(5) < 4:
                prompt = np.concatenate([self.system, rs.randint(
                    1, VOCAB, (int(rs.randint(1, 8)),)).astype(np.int32)])
            else:
                prompt = rs.randint(
                    1, VOCAB, (int(rs.randint(3, 25)),)).astype(np.int32)
            self.reqs.append(self.router.submit(prompt, self.max_new))

    def settle(self, warmups_since):
        """Stop, wait everything out, and hold the exactly-once ledger:
        every request done on its first attempt, and the engines'
        completions since the flood was made = the flood + the deploy's
        own warm-up passes."""
        self._halt.set()
        self.join(timeout=60)
        self.router.wait(self.reqs, timeout=600)
        n = len(self.reqs)
        assert [r.state for r in self.reqs] == ["done"] * n
        assert all(r.attempts == 1 for r in self.reqs)
        assert self._engines_done() - self._done_before \
            == n + warmups_since, "duplicated or dropped work"


@pytest.mark.slow  # ~1 min: two deploys under a flood, canary soaks of 1 s
def test_flood_roll_then_canary_breach_rolls_back(ff, tmp_path,
                                                  monkeypatch):
    """A 2-replica fleet under a closed-loop skewed flood.
    Leg 1, a version published mid-flood rolls through: every request
    served exactly once, at most one replica suspended at any sampled
    instant and none fenced, zero recompiles (a same-geometry swap keeps
    every program valid), the tries salted with the new version, post-roll
    streams those of a model holding the new weights.
    Leg 2, `slow@canary` stalls the freshly swapped canary's admissions and
    breaches its rebaselined TTFT SLO: the deployer rolls the fleet BACK,
    traffic still exactly-once and token-identical to the prior version,
    and exactly ONE manifest-intact flight bundle names the breached SLO."""
    from flexflow_tpu.runtime import flightrec

    max_new = 12
    monkeypatch.setattr(ff.config, "slo_window_s", 1.0)
    reg = WeightArtifactRegistry(str(tmp_path / "watch"))
    flight = tmp_path / "flight"
    flight.mkdir()
    rs = np.random.RandomState(0)
    system = rs.randint(1, VOCAB, (32,)).astype(np.int32)   # 4 full pages
    router = ff.make_serving_router(
        replicas=2, serve_slots=4, kv_page_size=8, max_seq_len=80,
        decode_buckets=[32, 64], start=False)

    def publish(step, scale):
        keep = ff.params
        ff.params = ff.executor.reshard_params(_bumped(keep, scale))
        try:
            return reg.publish(ff, step=step)
        finally:
            ff.params = keep

    def assert_serves(version, probe):
        keep = ff.params
        ff.params = ff.executor.reshard_params(reg.load_params(version))
        try:
            solo = ff.generate(probe[None, :], max_new_tokens=max_new)
        finally:
            ff.params = keep
        got = router.run([probe], max_new_tokens=max_new, timeout=600)[0]
        np.testing.assert_array_equal(np.asarray(got.tokens, np.int32),
                                      solo[0, probe.size:])

    try:
        tail = rs.randint(1, VOCAB, (3,)).astype(np.int32)
        warm = [rs.randint(1, VOCAB, (10,)).astype(np.int32),
                np.concatenate([system, tail]),
                np.concatenate([system, tail + 1])]
        router.warmup(warm, max_new_tokens=4)
        warm_compiles = [e.recompile_count for e in router.engines]
        router.start()
        dep = RollingDeployer(router, reg, canary_windows=2)

        # ---- leg 1: a clean roll under load
        v1 = publish(1, 1.25)
        flood = _Flood(router, rs, system, max_new)
        flood.start()
        while len(flood.reqs) < 8:                     # the flood is live
            time.sleep(0.01)
        report = dep.deploy(v1, warmup_prompts=warm, max_new_tokens=4)
        while len(flood.reqs) < 40:                    # post-roll traffic
            time.sleep(0.01)
        # each swapped engine's warm-up drives 2 passes over the prompts
        flood.settle(warmups_since=2 * 2 * len(warm))
        assert report["state"] == "completed", report
        assert report["swapped"] == [0, 1] and report["canary"] == 0
        st = router.stats()
        assert st["fenced"] == 0 and flood.max_suspended <= 1
        assert (st["swaps_completed"], st["rollbacks"]) == (2, 0)
        assert not st["deploying"]
        assert [row["weight_version"] for row in st["per_replica"]] \
            == router.health()["weight_versions"] == [v1, v1]
        for eng, warm_count in zip(router.engines, warm_compiles):
            assert eng._cache_ns(None) == (v1, None)
            assert eng.recompile_count == warm_count
        assert_serves(v1, np.concatenate(
            [system, rs.randint(1, VOCAB, (4,)).astype(np.int32)]))

        # ---- leg 2: the canary breaches, the fleet rolls back
        v2 = publish(2, 1.5)
        # a tight TTFT ceiling over 1 s windows; the debounce parked high,
        # so the ONLY bundle is the rollback's own synchronous dump
        flightrec.configure(FFConfig(
            batch_size=2, mesh_shape={"data": 1}, slo_ttft_p99_s=0.25,
            slo_window_s=1.0, flight_recorder_dir=str(flight),
            flight_debounce_s=600.0))
        _arm_fault(monkeypatch, "slow(600)@canary:1-400")
        flood = _Flood(router, rs, system, max_new)
        flood.start()
        while len(flood.reqs) < 8:
            time.sleep(0.01)
        try:
            report = dep.deploy(v2, warmup_prompts=warm, max_new_tokens=4)
        finally:
            monkeypatch.delenv("FF_FAULT")
            faultinject.reset()
        # only the canary's warm-up ran engine-side; the rollback swap
        # rebaselines without warming again
        flood.settle(warmups_since=2 * len(warm))
        assert report["state"] == "rolled_back", report
        assert report["breach"]["slo"] == "ttft_p99", report["breach"]
        assert str(report["breach"]["replica"]) == str(report["canary"])
        assert report["rollback_s"] > 0
        assert [e.weight_version for e in router.engines] == [v1, v1]
        st = router.stats()
        assert (st["rollbacks"], st["fenced"]) == (1, 0)
        bundle, = [str(flight / d) for d in os.listdir(flight)]
        assert report["bundle"] == bundle
        flightrec.verify_bundle(bundle)
        with open(os.path.join(bundle, "trigger.json")) as f:
            blob = json.dumps(json.load(f))
        assert "canary_rollback" in blob and "ttft_p99" in blob
        assert_serves(v1, np.concatenate(
            [system, rs.randint(1, VOCAB, (5,)).astype(np.int32)]))
    finally:
        router.close()
        flightrec.reset()
