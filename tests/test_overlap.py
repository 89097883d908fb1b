"""Host-overlap step engine tests (runtime/pipeline_loader.py + the
dispatch-ahead fit() loop).

The contract under test: turning the overlap engine on changes WHERE the
host blocks, never WHAT gets computed — the loss trajectory is bitwise
identical to the synchronous loop, checkpoints taken mid-prefetch record
the exact consumed dataloader cursor (so resume stays bitwise too),
injected loader IO failures retry inside the worker without reordering
batches or deadlocking, and the warm step program never retraces across
prefetched committed batches.
"""

import os
import time

import numpy as np
import pytest

from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType, MetricsType,
                          SGDOptimizer, SingleDataLoader)
from flexflow_tpu.runtime import faultinject, resilience
from flexflow_tpu.runtime.checkpoint import latest_step, load_meta
from flexflow_tpu.runtime.pipeline_loader import PipelineLoader


@pytest.fixture(autouse=True)
def _fresh_fault_state(monkeypatch):
    monkeypatch.delenv("FF_FAULT", raising=False)
    faultinject.reset()
    resilience.reset_counters()
    yield
    faultinject.reset()


def _build(prefetch_depth, *, ckpt_dir="", dispatch_ahead=2, epochs=2,
           n=64, checkpoint_every=0, step_timeout_s=0.0):
    # device_resident_data=False pins the host-resident path the overlap
    # engine targets (device-resident datasets already slice on device);
    # native off so the SingleDataLoader cursor contract is what's tested
    cfg = FFConfig(batch_size=16, epochs=epochs, seed=3,
                   device_resident_data=False, native_dataloader=False,
                   prefetch_depth=prefetch_depth,
                   dispatch_ahead=dispatch_ahead,
                   checkpoint_dir=str(ckpt_dir),
                   checkpoint_every=checkpoint_every,
                   step_timeout_s=step_timeout_s)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, 16, ActiMode.AC_MODE_RELU, name="fc1")
    ff.dense(t, 4, name="out")
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY])
    rs = np.random.RandomState(7)
    SingleDataLoader(ff, x, rs.randn(n, 8).astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 4, (n, 1)).astype(np.int32))
    return ff


def _fit_recording_losses(ff, **kw):
    """Run fit() recording every step's loss as a host float (the record
    wrapper syncs per step — it perturbs timing, never numerics)."""
    losses = []
    orig = ff._run_train_step

    def rec(batch, **kwargs):
        loss, mets = orig(batch, **kwargs)
        losses.append(float(loss))
        return loss, mets

    ff._run_train_step = rec
    ff.fit(verbose=False, **kw)
    ff._run_train_step = orig
    return losses


# ------------------------------------------------------- bitwise identity


def test_overlap_bitwise_identical_to_sync():
    ls_sync = _fit_recording_losses(_build(0))
    ls_overlap = _fit_recording_losses(_build(2))
    assert len(ls_sync) == 8  # 2 epochs x 4 batches
    assert ls_sync == ls_overlap, \
        "overlap loop must train the exact synchronous trajectory"
    # and with a different in-flight bound (including fully-throttled 0)
    assert _fit_recording_losses(_build(3, dispatch_ahead=0)) == ls_sync


def test_overlap_final_state_and_cursors_match_sync():
    ff_s, ff_o = _build(0), _build(2)
    ff_s.fit(verbose=False)
    ff_o.fit(verbose=False)
    np.testing.assert_array_equal(ff_s.get_weights("fc1"),
                                  ff_o.get_weights("fc1"))
    # stop() rewinds the pulled-ahead cursors to the consumed position:
    # after fit the loaders sit exactly where the sync loop left them
    assert ([dl.next_index for dl in ff_o._dataloaders]
            == [dl.next_index for dl in ff_s._dataloaders])
    assert ff_o._pipeline is None, "pipeline torn down at the end of fit"
    bd = ff_o.last_step_breakdown
    assert bd is not None and bd["overlap"] and bd["steps"] > 0
    assert 0.0 <= bd["host_wait_fraction"] <= 1.0


# ------------------------------------------- checkpoint / resume exactness


def test_kill_and_resume_under_prefetch_restores_exact_cursor(tmp_path,
                                                              monkeypatch):
    # preempt at step 5 = mid-epoch 2 (4 batches/epoch): the checkpoint
    # must record the CONSUMED cursor, not the prefetch worker's
    # pulled-ahead dl.next_index
    monkeypatch.setenv("FF_FAULT", "sigterm@step:5")
    faultinject.reset()
    ff = _build(2, ckpt_dir=tmp_path / "ov", epochs=4)
    ff.fit(verbose=False)
    assert ff._step_count == 5
    assert latest_step(str(tmp_path / "ov")) == 5
    meta = load_meta(str(tmp_path / "ov"), 5)
    assert meta["reason"] == "preempt"
    # sync-loop cursor after 5 batches of 16 over 64 samples: wrapped to 16
    assert meta["dataloaders"] == {"x": 16, "label": 16}

    # the same preemption on the SYNC loop records the identical cursor
    monkeypatch.setenv("FF_FAULT", "sigterm@step:5")
    faultinject.reset()
    ff_s = _build(0, ckpt_dir=tmp_path / "sync", epochs=4)
    ff_s.fit(verbose=False)
    assert load_meta(str(tmp_path / "sync"), 5)["dataloaders"] \
        == meta["dataloaders"]

    # resume under prefetch: remaining 11 steps bitwise-match an
    # uninterrupted synchronous run
    monkeypatch.delenv("FF_FAULT")
    faultinject.reset()
    ff2 = _build(2, ckpt_dir=tmp_path / "ov", epochs=4)
    ff2.fit(verbose=False)
    assert ff2._step_count == 16
    ref = _build(0, epochs=4)
    ref.fit(verbose=False)
    np.testing.assert_array_equal(ff2.get_weights("fc1"),
                                  ref.get_weights("fc1"))


def test_periodic_checkpoint_mid_prefetch_consistent(tmp_path):
    # a periodic save while the worker is pulled ahead must be internally
    # consistent: step counter, cursor and params all "as of step N"
    ff = _build(2, ckpt_dir=tmp_path, epochs=2, checkpoint_every=3)
    ff.fit(verbose=False)
    # periodic saves land at steps 1/4/7 (+ final 8); keep=3 retains 4/7/8
    meta = load_meta(str(tmp_path), 7)
    assert meta["step"] == 7
    # sync-loop cursor after 7 batches of 16 over 64 samples (3 into the
    # second epoch), NOT the worker's pulled-ahead position
    assert meta["dataloaders"] == {"x": 48, "label": 48}


# -------------------------------------------- fault injection in the worker


def test_io_fail_in_prefetch_thread_retries_in_order(monkeypatch):
    monkeypatch.setenv("FF_FAULT", "io_fail@loader:3")
    faultinject.reset()
    ff = _build(2)
    ff.fit(verbose=False)
    assert resilience.COUNTERS["retries"] >= 1
    assert ff._step_count == 8, "retry must not drop or duplicate batches"
    # the retried pull re-pulls the SAME batch: trajectory == no-fault run
    monkeypatch.delenv("FF_FAULT")
    faultinject.reset()
    ref = _build(0)
    ref.fit(verbose=False)
    np.testing.assert_array_equal(ff.get_weights("fc1"),
                                  ref.get_weights("fc1"))


def test_io_fail_exhausted_surfaces_on_training_thread(monkeypatch):
    # every retry attempt of one pull fails -> the worker parks the error
    # and fit raises instead of deadlocking on an empty queue
    monkeypatch.setenv("FF_FAULT", "io_fail@loader:2-5")
    faultinject.reset()
    ff = _build(2)
    with pytest.raises(RuntimeError, match="prefetch worker died"):
        ff.fit(verbose=False)
    assert ff._pipeline is None, "fit's finally must tear the pipeline down"


# -------------------------------------------------------- retrace flatness


def test_warm_step_program_never_retraces_across_prefetched_batches():
    ff = _build(2, epochs=4, n=96)
    if not hasattr(ff._train_step, "_cache_size"):
        pytest.skip("jit cache size introspection unavailable on this jax")
    # warmup: the first step traces once more when the freshly-initialized
    # (uncommitted) opt_state becomes the step's committed output — that
    # is the known pre-existing warmup shape, identical under sync
    ff._run_train_step(ff.executor.shard_batch(ff._stage_batch()))
    ff._run_train_step(ff.executor.shard_batch(ff._stage_batch()))
    warm = ff._train_step._cache_size()
    ff._reset_dataloaders()
    ff.fit(verbose=False)  # 4 epochs x 6 batches through the pipeline
    assert ff._train_step._cache_size() == warm, \
        "prefetched committed batches must reuse the warm executable"


def test_shard_batch_is_cached_and_pass_through():
    import jax

    ff = _build(0)
    raw = ff._stage_batch()
    sharded = ff.executor.shard_batch(raw)
    for v in sharded.values():
        assert isinstance(v, jax.Array) and v.committed
    # cached NamedSharding objects: same instance across calls
    sh1 = ff.executor.batch_sharding("x", 2)
    sh2 = ff.executor.batch_sharding("x", 2)
    assert sh1 is sh2
    # already-committed-correct arrays pass through untouched (no new put)
    again = ff.executor.shard_batch(sharded)
    for k in sharded:
        assert again[k] is sharded[k]


# ------------------------------------------------- pipeline loader directly


def test_pipeline_loader_order_epoch_break_and_cursor_rewind():
    ff = _build(0, n=96)
    pipe = PipelineLoader.from_loaders(ff, depth=3).start()
    try:
        ref = _build(0, n=96)
        expect = [ref._stage_batch() for _ in range(4)]
        for i in range(4):
            got = pipe.get(timeout=30)
            np.testing.assert_array_equal(np.asarray(got["x"]),
                                          expect[i]["x"])
        assert pipe.consumed_cursors() == {"x": 64, "label": 64}
        # give the worker a moment to prefetch ahead, then break the
        # epoch: cursors rewind to consumed, reset runs, prefetch resumes
        time.sleep(0.2)
        pipe.epoch_break(ff._reset_dataloaders)
        assert all(dl.next_index == 0 for dl in ff._dataloaders)
        got = pipe.get(timeout=30)
        np.testing.assert_array_equal(np.asarray(got["x"]), expect[0]["x"])
    finally:
        pipe.stop()
    # stop() after one consumed batch post-reset: cursor sits at 16
    assert all(dl.next_index == 16 for dl in ff._dataloaders)


def test_pipeline_depth_validation_and_config_knobs():
    with pytest.raises(ValueError, match="depth"):
        PipelineLoader(lambda: None, lambda b: b, depth=0)
    with pytest.raises(ValueError):
        FFConfig(prefetch_depth=-1)
    with pytest.raises(ValueError):
        FFConfig(dispatch_ahead=-1)


def test_native_loader_through_pipeline_multi_epoch():
    """The pipeline wraps the native threaded loader too (prefetch-shard
    on top of its host prefetch): end-of-epoch Nones park the worker,
    epoch_break resets + resumes it — 3 epochs must deliver exactly
    3 x num_batches steps."""
    from flexflow_tpu.runtime.native_loader import load_lib

    if load_lib() is None:
        pytest.skip("native dataloader unavailable (no g++)")
    cfg = FFConfig(batch_size=16, epochs=3, seed=3,
                   device_resident_data=False, native_dataloader=True,
                   dataloader_shuffle=True, prefetch_depth=2)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, 16, ActiMode.AC_MODE_RELU, name="fc1")
    ff.dense(t, 4, name="out")
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY])
    rs = np.random.RandomState(7)
    SingleDataLoader(ff, x, rs.randn(64, 8).astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 4, (64, 1)).astype(np.int32))
    ff.fit(verbose=False)
    assert ff._step_count == 12
    assert ff.last_step_breakdown["overlap"]


# --------------------------------------- barriers / watchdog documentation


def test_sync_fit_has_single_warmup_barrier(monkeypatch):
    """Satellite contract: the epoch loop takes ONE warmup barrier (on the
    first step's loss) plus the single end-of-fit barrier — the former
    duplicated per-branch `block_until_ready(self.params)` syncs are
    gone."""
    import jax

    ff = _build(0)
    calls = []
    orig = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or orig(x))
    ff.fit(verbose=False)
    # (the step program's FIRST call is a `compile` span that ends when its
    # outputs are ready: one barrier a program's life, telemetry on)
    assert len(calls) == 3, \
        f"expected first call + warm + final barriers only, saw {len(calls)}"
    del calls[:]
    ff.fit(verbose=False)
    assert len(calls) == 2, \
        f"expected warm + final barriers only, saw {len(calls)}"


def test_overlap_fit_healthy_under_watchdog(tmp_path):
    """The dispatch-ahead drain arms the supervisor watchdog on DEVICE
    progress; a healthy overlapped run completes without firing it."""
    ff = _build(2, ckpt_dir=tmp_path, step_timeout_s=30.0)
    ff.fit(verbose=False)
    assert ff._step_count == 8
    assert resilience.COUNTERS["watchdog_fires"] == 0
    assert latest_step(str(tmp_path)) == 8  # final checkpoint landed
