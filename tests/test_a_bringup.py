"""Bring-up contracts (ISSUE 21): how the process starts, where the compile
cache goes, and what refuses loudly instead of falling back. CPU-only and
sub-second each; the file sorts early so the tier-1 window always reaches it.
The chip side of the same contracts is `python chip_smoke.py`.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu import _env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def no_persistent_cache():
    """Whatever a test does to the cache config, the rest of the suite keeps
    running without a persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache

    yield
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def test_cache_from_env_is_left_alone(tmp_path, monkeypatch,
                                      no_persistent_cache):
    """JAX_COMPILATION_CACHE_DIR set: jax read it at import (replayed here),
    the resolver updates no config and reports the env path."""
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", placed)  # jax's import
    monkeypatch.setattr(_env, "CHECKOUT", str(tmp_path / "checkout"))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    assert _env.resolve_compilation_cache() == placed
    assert updates == []
    assert jax.config.jax_compilation_cache_dir == placed
    assert not (tmp_path / "checkout" / ".xla_cache").exists()


def test_cache_default_is_fixed_under_the_checkout(tmp_path, monkeypatch,
                                                   no_persistent_cache):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(_env, "CHECKOUT", str(tmp_path))
    first = _env.resolve_compilation_cache()
    second = _env.resolve_compilation_cache()
    assert first == second == str(tmp_path / ".xla_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert _env.compilation_cache_dir() == first
    assert _env.CHECKOUT != REPO  # monkeypatched; the real default:
    monkeypatch.undo()
    assert _env.CHECKOUT == REPO


def test_unusable_cache_directory_is_an_error(tmp_path, monkeypatch,
                                              no_persistent_cache):
    blocker = tmp_path / "a-file"
    blocker.write_text("")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(blocker / "cache"))
    with pytest.raises(OSError):
        _env.resolve_compilation_cache()


def test_library_code_places_no_cache():
    """FFModel.compile()/the test suite run without a persistent cache: only
    the entry scripts call the resolver."""
    assert not _env.compilation_cache_dir()
    from flexflow_tpu import FFConfig

    assert not hasattr(FFConfig(), "compilation_cache_dir")


def test_peak_table_raises_on_unknown_device_kind():
    from benchmark.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    for kind in ("TPU v99", "cpu", ""):
        with pytest.raises(KeyError, match="benchmark/peaks.py"):
            peaks_for(kind)


def test_entry_scripts_refuse_a_non_tpu_platform():
    """chip_smoke.py names the platform and stops — no CPU tier, no
    fallback row, no JSON verdict — and places no cache on the way."""
    import chip_smoke

    with pytest.raises(RuntimeError, match="'cpu', not a TPU"):
        chip_smoke.main([])
    assert not _env.compilation_cache_dir()


def test_interpret_mode_is_asked_for(monkeypatch):
    from flexflow_tpu.ops import pallas_kernels as pk

    monkeypatch.delenv("FF_PALLAS_INTERPRET", raising=False)
    assert pk._interpret() is False  # a CPU process does not fall into it
    monkeypatch.setenv("FF_PALLAS_INTERPRET", "1")
    assert pk._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="FF_PALLAS_INTERPRET"):
        pk._interpret()


def test_compiler_params_raise_rather_than_return_none(monkeypatch):
    from flexflow_tpu.ops import pallas_kernels as pk

    params = pk._compiler_params(("parallel", "arbitrary"))
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
    monkeypatch.delattr(pk.pltpu, "CompilerParams")
    with pytest.raises(AttributeError):
        pk._compiler_params()


def test_kernel_selectors_refuse_by_name():
    """What the kernels cannot take is refused where they are chosen, with a
    reason — never a Mosaic failure at the first compile. After ISSUE 21
    the paged kernels take every class the engine routes to them (S > 1,
    quantized pools), so the only shape refusals left are the add+LN
    kernel's."""
    from flexflow_tpu.ops import pallas_kernels as pk
    from flexflow_tpu.ops.attention import resolve_paged_attention_impl
    from flexflow_tpu.ops.norm import fused_add_ln_refusal

    assert fused_add_ln_refusal(4096, 4096, jnp.bfloat16) is None
    assert fused_add_ln_refusal(2048, 2048, jnp.bfloat16) is None
    assert "128-lane" in fused_add_ln_refusal(64, 100, jnp.float32)
    assert "VMEM" in fused_add_ln_refusal(4096, 65536, jnp.bfloat16)
    # rows no legal block divides, wider than the budget holds whole
    assert "VMEM" in fused_add_ln_refusal(1001, 4096, jnp.bfloat16)
    assert pk.add_ln_block_rows(4096, 4096, jnp.bfloat16) == 64
    x = jax.ShapeDtypeStruct((8, 65536), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((65536,), jnp.float32)
    with pytest.raises(ValueError, match="fits VMEM"):
        jax.eval_shape(lambda x, w: pk.fused_add_layernorm_fwd_pallas(
            x, x, w, w, 1e-5), x, w)
    with pytest.raises(ValueError, match="paged_attention_impl"):
        resolve_paged_attention_impl("mosaic")
    assert resolve_paged_attention_impl("auto") == "einsum"  # off-TPU


def test_native_libs_build_by_content(tmp_path):
    """The binary a process loads is keyed by the hash of the tracked source:
    a changed source builds a new file and the stale one goes."""
    from flexflow_tpu._native import build_native_lib

    src = tmp_path / "k.cc"
    src.write_text('extern "C" int k() { return 1; }\n')
    first = build_native_lib(str(src), "libk")
    assert build_native_lib(str(src), "libk") == first
    stray = tmp_path / "libk.so"       # an unhashed leftover plays no part
    stray.write_text("not a library")
    src.write_text('extern "C" int k() { return 2; }\n')
    second = build_native_lib(str(src), "libk")
    assert second != first and os.path.exists(second)
    assert not os.path.exists(first)
    import ctypes

    assert ctypes.CDLL(second).k() == 2


@pytest.mark.slow  # ~30 s: compiles every sweep class for the v5e topology
def test_aot_kernel_check():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "aot_kernel_check.py")],
        capture_output=True, text=True, timeout=900)
    if r.returncode == 77:
        pytest.skip(r.stdout.strip().splitlines()[-1])
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]


@pytest.mark.slow  # ~45 s: the whole smoke at a tiny size, kernels interpreted
def test_chip_smoke_cpu_rehearsal():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--cpu-rehearsal"], capture_output=True, text=True, timeout=900)
    assert r.returncode == 64, r.stdout[-4000:] + r.stderr[-2000:]
    assert "NOT A CHIP RESULT" in r.stdout
    assert '"ok"' not in r.stdout  # no verdict without a chip
