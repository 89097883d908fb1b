"""The training cell's update check and the trace slice's place: what
`correct` can see, shown on small arrays on the CPU (no device number)."""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark import spec
from benchmark.reference import train_check

OPT = {"type": "AdamOptimizer", "alpha": 1e-4, "beta1": 0.9, "beta2": 0.999,
       "epsilon": 1e-8}


def test_adam_first_step_is_the_papers():
    g = jnp.asarray([2e-3, -5e-7, 0.0], jnp.float32)
    got = np.asarray(train_check.adam_first_step(g, 1e-4))
    # m / sqrt(v) = g / |g| sqrt(0.1^2 / 0.001); epsilon counts where g is
    # small: -alpha g / (|g| + epsilon / sqrt(1 - beta2))
    want = -1e-4 * np.asarray(g) / (np.abs(g) + 1e-8 / np.sqrt(1e-3))
    assert got == pytest.approx(want, rel=1e-5)
    assert got[2] == 0.0


def _update_error(after, grads, before):
    h = types.SimpleNamespace(cut={"optimizer": OPT}, log=lambda msg: None)
    ff = types.SimpleNamespace(params={"op": {"w": after}})
    return train_check.update_error(
        h, ff, {"grads": {"op": {"w": grads}}, "before": {"op": {"w": before}}})


@pytest.mark.parametrize("fault, low, high", [
    ("none", 0.0, 1e-4),                    # f32 master: rounding only
    ("bf16_master", 0.05, 1.0),             # the update is lost in 8 bits
    ("half_the_gradient_dropped", 0.4, 0.6),
    ("gradient_noise_2_percent", 0.0, 2e-2),    # what bf16 compute may do
])
def test_update_error_sees_the_update(fault, low, high):
    rng = np.random.default_rng(0)
    w0 = rng.normal(0, 0.015, 20000).astype(np.float32)
    g = (rng.normal(0, 2e-6, 20000)).astype(np.float32)
    seen = g.copy()
    if fault == "half_the_gradient_dropped":
        seen[::2] = 0.0
    if fault == "gradient_noise_2_percent":
        seen *= 1 + rng.normal(0, 0.02, g.size).astype(np.float32)
    if fault == "bf16_master":
        w0 = np.asarray(jnp.asarray(w0).astype(jnp.bfloat16).astype(
            jnp.float32))
    w1 = jnp.asarray(w0) + train_check.adam_first_step(jnp.asarray(seen),
                                                       1e-4)
    if fault == "bf16_master":
        w1 = w1.astype(jnp.bfloat16)
    err = _update_error(w1, jnp.asarray(g), jnp.asarray(w0))
    assert low <= err <= high


def test_without_checked_weights_there_is_no_update_check():
    assert train_check.update_error(None, None, {"grads": None}) is None


@pytest.mark.parametrize("cell, seconds, start, stop", [
    ("chat-steady", 51, 46.0, 51.0),            # the window's last 5 s
    ("train-4k", 3, 0.0, 3.0),                  # a window shorter than it
    ("train-4k-search-4chip", 51, 18.0, 23.0),  # the file's max_seconds
])
def test_the_slice_is_the_end_of_the_window(cell, seconds, start, stop):
    h = bench_run.load_cell(spec.load_benchmark(), cell, seconds=seconds,
                            trace=1)
    assert (h._trace["start"], h._trace["stop"]) == (start, stop)
    assert h.seconds == stop


def test_the_profiler_is_stopped_outside_the_loop(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    h = bench_run.load_cell(spec.load_benchmark(), "chat-steady", seconds=51,
                            trace=1)
    h.trace_poll(10.0)
    assert calls == [] and h._trace["state"] == "idle"
    h.trace_poll(46.2)
    assert calls == ["start"] and h._trace["state"] == "tracing"
    h.trace_poll(51.3)      # the slice closes; the loop is not held
    assert calls == ["start"] and h._trace["state"] == "sliced"
    h.window_done()
    assert calls == ["start", "stop"] and h._trace["state"] == "done"
