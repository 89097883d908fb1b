"""Search v2 tests: persistent op-cost DB, warm-started search, and the
multi-objective (time x HBM) objective (ISSUE 19).

Covers the contracts the PR pins:
  * table_store round-trip + atomic publish (no .tmp debris, valid JSON);
  * measured vs analyzed entries for ONE op signature can never collide
    or shadow (the ("analyze",) tuple-prefix bug, satellite 2);
  * a jax-version/backend bump invalidates by key mismatch;
  * a warm-started search re-measures ZERO already-keyed ops
    (cost_db.stats()["misses"] == 0);
  * a tight per-chip HBM cap makes the multi-objective search choose
    remat/ZeRO/offload relief, and the chosen strategy lints UNDER cap
    where the time-only objective lints over (and fflint escalates);
  * sequence-parallel and expert-parallel axes appear in the SOAP
    candidate space (legal_axis_maps);
  * an N-chip strategy warm-starts the M-chip search
    (warm_start_seed / rank_mesh_candidates / research path).
"""

import json
import os

import pytest

from flexflow_tpu import ActiMode, FFConfig, FFModel
from flexflow_tpu.parallel.pconfig import EXPERT, ParallelConfig
from flexflow_tpu.search import cost_db, measure, table_store
from flexflow_tpu.search.cost_model import MEM_MODES, CostModel
from flexflow_tpu.search.driver import (legal_axis_maps, optimize_strategies,
                                        optimize_strategies_multi,
                                        rank_mesh_candidates, warm_start_seed)
from flexflow_tpu.search.machine import MachineModel

MESH = {"data": 2, "model": 2}


@pytest.fixture(autouse=True)
def _fresh_caches():
    """Every test starts as a fresh process would: no in-memory signature
    cache, no table cache, zeroed counters."""
    measure._SIGNATURE_CACHE.clear()
    table_store.clear_cache()
    cost_db.reset_stats()
    yield
    measure._SIGNATURE_CACHE.clear()
    table_store.clear_cache()
    cost_db.reset_stats()


def build_mlp(mesh_shape=MESH, batch=16):
    cfg = FFConfig(batch_size=batch, mesh_shape=mesh_shape)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 32], name="x")
    t = ff.dense(x, 64, ActiMode.AC_MODE_RELU, name="fc1")
    t = ff.dense(t, 64, ActiMode.AC_MODE_RELU, name="fc2")
    ff.dense(t, 8, name="out")
    return ff


def build_moe(mesh_shape=MESH, batch=8):
    cfg = FFConfig(batch_size=batch, mesh_shape=mesh_shape)
    ff = FFModel(cfg)
    x = ff.create_tensor([batch, 16, 32], name="x")
    ff.moe(x, num_experts=4, hidden_dim=64, name="moe")
    return ff


# ---- table_store ------------------------------------------------------------

def test_table_store_roundtrip_and_atomicity(tmp_path):
    path = str(tmp_path / "sub" / "t.json")
    table_store.publish(path, {"a": {"v": 1}, "b": {"v": 2}})
    # atomic publish: final file only, no tmp debris
    names = os.listdir(os.path.dirname(path))
    assert names == ["t.json"]
    with open(path) as f:
        data = json.load(f)
    assert data["version"] == 1
    assert data["entries"]["a"] == {"v": 1}
    # cached load serves without re-reading; reload matches
    assert table_store.load(path) == {"a": {"v": 1}, "b": {"v": 2}}
    assert table_store.load(path, reload=True) == table_store.load(path)
    # a rewrite behind the cache's back is picked up via (mtime,size)
    table_store.publish(path, {"c": {"v": 3}})
    assert table_store.load(path) == {"c": {"v": 3}}


def test_table_store_missing_and_corrupt(tmp_path):
    assert table_store.load(str(tmp_path / "nope.json")) == {}
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert table_store.load(str(bad)) == {}


# ---- keying -----------------------------------------------------------------

def test_measure_analyze_entries_never_collide(tmp_path):
    """Satellite 2: one op signature, both a measured and an analyzed
    entry — each round-trips to its own value, neither shadows the other
    (the old in-memory table prefixed analyze rows with ("analyze",),
    which a flat persisted keyspace could collide with)."""
    db = str(tmp_path / "db.json")
    sig = ("Dense", (("units", 64),), ((16, 32),), ((32, 64),),
           ("float32",), measure._env_signature())
    cost_db.record_measured(sig, 0.125, path=db)
    cost_db.record_analyzed(sig, 1e9, 2e6, path=db)
    assert cost_db.get_measured(sig, path=db) == 0.125
    assert cost_db.get_analyzed(sig, path=db) == (1e9, 2e6)
    # distinct keys on disk, env identity in the readable prefix
    entries = table_store.load(db, reload=True)
    keys = sorted(entries)
    assert len(keys) == 2
    assert keys[0].startswith("analyze|") and keys[1].startswith("measure|")
    assert all(table_store.env_key() in k for k in keys)


def test_signature_cache_kinds_distinct():
    """The in-memory cache keys are structurally distinct nested tuples —
    ("measure", sig) vs ("analyze", sig) — not flat concatenations that
    an adversarial signature could alias."""
    sig = ("Dense", (("units", 8),))
    measure._SIGNATURE_CACHE[("measure", sig)] = 0.5
    measure._SIGNATURE_CACHE[("analyze", sig)] = (1.0, 2.0)
    assert measure._SIGNATURE_CACHE[("measure", sig)] == 0.5
    assert measure._SIGNATURE_CACHE[("analyze", sig)] == (1.0, 2.0)


def test_env_bump_invalidates(tmp_path, monkeypatch):
    db = str(tmp_path / "db.json")
    sig = ("Dense", (("units", 64),), measure._env_signature())
    cost_db.record_measured(sig, 0.25, path=db)
    assert cost_db.get_measured(sig, path=db) == 0.25
    # simulate a jax upgrade: the env signature changes, the entry written
    # under the old env must MISS (key mismatch), never serve stale
    monkeypatch.setattr(measure, "_ENV_SIG",
                        ("cpu", "host-cpu", "jax-99.0.0-bumped"))
    cost_db.reset_stats()
    new_sig = sig[:-1] + (measure._env_signature(),)
    assert cost_db.get_measured(new_sig, path=db) is None
    assert cost_db.stats()["misses"] == 1
    assert cost_db.stats()["hits"] == 0


def test_malformed_entry_is_illegal_not_hit(tmp_path):
    db = str(tmp_path / "db.json")
    sig = ("Dense", (("units", 64),), measure._env_signature())
    key = cost_db.record_measured(sig, 1.0, path=db)
    entries = table_store.load(db, reload=True)
    entries[key] = {"seconds": "NaN-ish garbage"}
    table_store.publish(db, entries)
    assert cost_db.get_measured(sig, path=db) is None
    assert cost_db.stats()["illegal"] == 1


def test_db_off_without_path():
    """No path, no FF_COST_DB: the DB must stay inert (hermetic runs)."""
    assert cost_db.resolve_path(None) is None or os.environ.get("FF_COST_DB")
    sig = ("Dense", (("units", 1),))
    assert cost_db.record_measured(sig, 1.0, path=None) is None \
        or os.environ.get("FF_COST_DB")


# ---- warm start: zero re-measures ------------------------------------------

def test_warm_start_analyze_zero_remeasures(tmp_path):
    db = str(tmp_path / "db.json")
    ff = build_mlp()
    cold = measure.analyze_op_costs(ff, MESH, db_path=db)
    assert len(cold) > 0
    n = cost_db.entry_count(db)
    assert n > 0
    cold_stats = cost_db.stats()
    assert cold_stats["stores"] == n

    # fresh process simulation
    measure._SIGNATURE_CACHE.clear()
    table_store.clear_cache()
    cost_db.reset_stats()

    warm = measure.analyze_op_costs(ff, MESH, db_path=db)
    s = cost_db.stats()
    assert s["misses"] == 0, s  # ZERO re-compiles for already-keyed ops
    assert s["hits"] > 0
    assert s["stores"] == 0  # nothing new to write
    assert set(warm) == set(cold)
    for k in cold:
        assert warm[k] == pytest.approx(cold[k], rel=1e-9)


# ---- multi-objective: time subject to HBM cap -------------------------------

def _drill_cap(ff, strategies):
    """A cap strictly between the strategy's unrelieved footprint and its
    best-relief floor: time-only lands over it, relief can get under."""
    cost = CostModel(ff, MESH)
    ops = {op.name: op for op in ff.ops if op.name in strategies}
    peak = sum(cost.op_mem_bytes(ops[n], strategies[n].axis_map or {})
               for n in ops)
    floor = sum(min(cost.op_mem_bytes(ops[n], strategies[n].axis_map or {},
                                      mem_mode=mm) for mm in MEM_MODES)
                for n in ops)
    assert floor < peak
    return (floor + peak) / 2.0


def test_multi_objective_drill_chooses_relief_and_lints_clean():
    from flexflow_tpu.analysis import analyze

    ff = build_mlp()
    time_only = optimize_strategies(ff, budget=80, mesh_shape=MESH, seed=3,
                                    use_native=False)
    cap = _drill_cap(ff, time_only)
    tiny = MachineModel(hbm_bytes=cap)

    # time-only objective: over cap, and fflint ESCALATES to error because
    # the relief modes could have brought it under (satellite 3)
    rep = analyze(ff, strategies=time_only, mesh_shape=MESH, machine=tiny,
                  passes=("legality", "perf"))
    over = rep.by_code("hbm-over-capacity")
    assert over and over[0].severity == "error"
    assert "multi-objective" in over[0].message

    # multi-objective search with the same budget/seed: picks relief modes
    multi = optimize_strategies_multi(ff, budget=80, mesh_shape=MESH, seed=3,
                                      hbm_cap_bytes=cap, use_native=False)
    chosen = {n: pc.mem_mode for n, pc in multi.items()
              if pc.mem_mode != "none"}
    assert chosen, "tight cap must force at least one relief mode"
    assert all(m in MEM_MODES for m in chosen.values())
    summary = ff._search_summary
    assert summary["over_cap"] is False
    assert summary["peak_hbm_bytes"] <= cap
    assert summary["predicted_step_s"] >= summary["base_step_s"]
    assert ff._predicted_step_time == summary["predicted_step_s"]

    # the chosen strategy lints UNDER cap (footprint pass audits mem_mode)
    rep2 = analyze(ff, strategies=multi, mesh_shape=MESH, machine=tiny,
                   passes=("legality", "perf"))
    assert not rep2.by_code("hbm-over-capacity")


def test_multi_objective_no_cap_is_time_only():
    """With the default (real) capacity a small model fits: the relief
    loop must be a no-op and the result identical to the time objective."""
    ff = build_mlp()
    time_only = optimize_strategies(ff, budget=60, mesh_shape=MESH, seed=7,
                                    use_native=False)
    multi = optimize_strategies_multi(ff, budget=60, mesh_shape=MESH, seed=7,
                                      use_native=False)
    assert all(pc.mem_mode == "none" for pc in multi.values())
    assert {n: pc.axis_map for n, pc in multi.items()} \
        == {n: pc.axis_map for n, pc in time_only.items()}
    assert ff._search_summary["over_cap"] is False


def test_mem_mode_accounting_monotone():
    """Relief modes must actually relieve (bytes strictly drop vs none for
    a weighted op) and cost time where physics says they must."""
    ff = build_mlp()
    cost = CostModel(ff, MESH)
    op = ff.get_op_by_name("fc1")
    am = {"data": 0}  # replicated over 'model' => relief degree 2
    base = cost.op_mem_bytes(op, am)
    for mm in ("zero1", "zero3", "offload", "remat"):
        assert cost.op_mem_bytes(op, am, mem_mode=mm) < base, mm
        assert cost.mem_mode_time(op, am, mm) > 0.0, mm
    assert cost.mem_mode_time(op, am, "none") == 0.0


# ---- SOAP space extensions --------------------------------------------------

def test_sequence_parallel_axis_in_candidates():
    cfg = FFConfig(batch_size=8, mesh_shape=MESH)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 16, 64], name="x")
    ff.transformer_pipeline_stack(x, 4, 4, name="stack")
    op = ff.get_op_by_name("stack")
    assert op.partitionable_output_dims() == [0, 1]
    assert op.single_axis_dims() == [1]
    maps = legal_axis_maps(op, MESH)
    seq = [m for m in maps if 1 in m.values()]
    assert seq, "sequence-parallel candidates missing"
    # single-axis dim: no candidate shards seq over two axes
    for m in seq:
        assert sum(1 for d in m.values() if d == 1) == 1


def test_expert_parallel_axis_in_candidates_and_pricing():
    ff = build_moe()
    op = ff.get_op_by_name("moe")
    assert op.expert_parallel_size() == 4
    maps = legal_axis_maps(op, MESH)
    ep = [m for m in maps if EXPERT in m.values()]
    assert ep, "expert-parallel candidates missing"
    cost = CostModel(ff, MESH)
    t_dp = cost.op_compute_time(op, {"data": 0})
    t_ep = cost.op_compute_time(op, {"data": 0, "model": EXPERT})
    assert t_ep > 0.0 and t_dp > 0.0
    # EXPERT shards the weights, not the output
    wp = op.weight_partition({"data": 0, "model": EXPERT})
    assert wp["w_in"][0] == "model"
    assert op.output_axis_map({"data": 0, "model": EXPERT}) \
        == {"data": 0, "model": None}
    # ...and the EXPERT strategy survives legality + serialization checks
    pc = ParallelConfig.from_axis_map(3, MESH, {"data": 0, "model": EXPERT})
    assert pc.device_ids == tuple(range(4))
    from flexflow_tpu.analysis import analyze

    rep = analyze(ff, strategies={"moe": pc}, mesh_shape=MESH,
                  passes=("legality",))
    assert not rep.by_code("dim-out-of-range")
    assert not rep.by_code("axis-unknown")


def test_expert_gated_by_parameter_parallel_flag():
    ff = build_moe()
    op = ff.get_op_by_name("moe")
    maps = legal_axis_maps(op, MESH, enable_parameter_parallel=False)
    assert not any(EXPERT in m.values() for m in maps)


# ---- elastic N -> M transfer ------------------------------------------------

def test_warm_start_seed_carries_legal_maps():
    ff = build_mlp()
    saved = {"fc1": ParallelConfig(axis_map={"data": 0, "model": 1}),
             "fc2": ParallelConfig(axis_map={"gone_axis": 0}),
             "out": ParallelConfig(axis_map={"data": 0, "model": 99})}
    seed = warm_start_seed(ff, MESH, saved)
    assert seed is not None
    assert seed["fc1"] == {"data": 0, "model": 1}  # legal: carried
    # axis absent from the new mesh / illegal dim: DP fallback, not crash
    assert seed["fc2"] == {"data": 0}
    assert seed["out"] == {"data": 0}
    # nothing carries -> None (caller skips the seed entirely)
    assert warm_start_seed(ff, MESH, {"fc1": ParallelConfig(
        axis_map={"gone": 0})}) is None
    assert warm_start_seed(ff, MESH, None) is None


def test_n_to_m_warm_start_search_and_ranking(tmp_path):
    """Strategy searched at N=4 chips warm-starts the M=2 search through
    rank_mesh_candidates, sharing cost-DB-backed measured entries."""
    db = str(tmp_path / "db.json")
    ff = build_mlp()
    measured = measure.analyze_op_costs(ff, MESH, db_path=db)
    at_n = optimize_strategies(ff, budget=60, mesh_shape=MESH, seed=5,
                               measured=measured, use_native=False)
    # M-chip candidates ranked under the SAME measured table
    ranked = rank_mesh_candidates(ff, [{"data": 2}, {"data": 4}],
                                  strategies=at_n, measured=measured)
    assert len(ranked) == 2
    assert ranked[0][0] <= ranked[1][0]
    # the M-chip search accepts the N-chip table as a warm seed and must
    # do no worse than a cold search of the same budget
    cold = optimize_strategies(ff, budget=40, mesh_shape={"data": 2}, seed=5,
                               use_native=False)
    warm = optimize_strategies(ff, budget=40, mesh_shape={"data": 2}, seed=5,
                               warm_start=at_n, use_native=False)
    cost = CostModel(ff, {"data": 2})
    t_cold = cost.iteration_time({n: pc.axis_map for n, pc in cold.items()})
    t_warm = cost.iteration_time({n: pc.axis_map for n, pc in warm.items()})
    assert t_warm <= t_cold * 1.0001


# ---- calibration ------------------------------------------------------------

def test_export_calibration_gauges_and_lint(tmp_path):
    from flexflow_tpu.analysis import analyze
    from flexflow_tpu.runtime import telemetry

    telemetry.reset()
    try:
        db = str(tmp_path / "db.json")
        ff = build_mlp()
        ff._predicted_step_time = 0.012
        hist = telemetry.registry().histogram(
            "ff_train_step_seconds", "fit() per-step wall time")
        for _ in range(8):
            hist.observe(0.010)
        rec = cost_db.export_calibration(ff, path=db)
        assert rec is not None
        assert rec["source"] == "telemetry"
        assert rec["predicted_s"] == pytest.approx(0.012)
        assert rec["ratio"] == pytest.approx(0.012 / rec["observed_s"])
        scrape = telemetry.registry().to_prometheus()
        assert "ff_csim_error_ratio" in scrape
        assert "ff_csim_predicted_step_seconds" in scrape
        assert "ff_csim_observed_step_seconds" in scrape
        # persisted as a telemetry-tagged calib entry
        entries = table_store.load(db, reload=True)
        assert any(k.startswith("calib|") for k in entries)
        # fflint surfaces the same drift as a csim-calibration info note
        rep = analyze(ff, strategies={}, mesh_shape=MESH,
                      passes=("legality", "perf"))
        cal = rep.by_code("csim-calibration")
        assert cal and cal[0].severity == "info"
        assert "ratio" in cal[0].message
    finally:
        telemetry.reset()


def test_export_calibration_absent_without_signals(tmp_path):
    from flexflow_tpu.runtime import telemetry

    telemetry.reset()
    try:
        ff = build_mlp()
        assert cost_db.export_calibration(ff) is None  # no prediction
        ff._predicted_step_time = 0.01
        assert cost_db.export_calibration(ff) is None  # no observations
    finally:
        telemetry.reset()


def test_time_scalar_program_primitive():
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: jnp.sum(x * 2.0))
    dt = measure.time_scalar_program(fn, jnp.ones((64, 64)), warmup=1,
                                     iters=2)
    assert dt > 0.0


def test_measure_signature_records_dtype_and_env():
    """ISSUE-7 bugfix: the cost-table signature must carry input dtypes
    and the (backend, device kind, jax version) environment — shapes
    alone let a bf16 timing serve an fp32 query across version bumps."""
    import jax

    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    x = ff.create_tensor([2, 8], name="x")
    ff.dense(x, 4, ActiMode.AC_MODE_RELU, name="d0")
    op = next(o for o in ff.ops if o.name == "d0")
    sig = measure._op_signature(op, [(2, 8)], [(8, 4)])
    env = measure._env_signature()
    assert env == (jax.default_backend(),) + env[1:]
    assert env[2] == jax.__version__
    assert sig[-1] == env, "environment signature missing from cost key"
    dtypes = sig[-2]
    assert len(dtypes) == len(op.inputs) and "FLOAT" in dtypes[0].upper()
