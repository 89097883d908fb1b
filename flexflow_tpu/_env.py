"""Process start-up shared by every entry point: virtual CPU devices for
tests and dry runs, and the one resolver that places jax's persistent
compilation cache.

One implementation of each serves the package import hook
(FLEXFLOW_FORCE_CPU_DEVICES), the entry scripts (chip_smoke.py,
benchmark/run.py, __graft_entry__.py), the launcher, and the C API
(FFT_JAX_PLATFORMS/FFT_NUM_CPU_DEVICES).
"""

from __future__ import annotations

import os

#: the checkout this package was imported from — the compile cache's
#: default home is a fixed path under it (the path is part of the cache
#: key, so a directory that moves never hits)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def force_cpu_devices(n: int) -> bool:
    """Point jax at an n-device virtual CPU platform. Must run before the
    first backend query (jax.devices() locks platform selection). Returns
    True if the config was applied, False if the backend was already
    initialized (in which case the caller should check device count)."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        if n > 0:
            jax.config.update("jax_num_cpu_devices", int(n))
        return True
    except RuntimeError:
        return False


def force_cpu_devices_from_env(value: str) -> bool:
    """Env-var flavored wrapper: accepts '8', '1', or truthy junk ('true',
    'yes' -> platform forced, device count left at default)."""
    try:
        n = int(value)
    except ValueError:
        n = 0
    return force_cpu_devices(n)


def resolve_compilation_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.
    The ONE place the cache directory is decided; entry scripts call it
    before their first trace, library code never does (tests run without
    a persistent cache).

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already read it at import and
    this function sets nothing — the cache is placed from outside.
    Unset: ``<checkout>/.xla_cache`` (git-ignored), the same path on every
    call and in every process. A directory that cannot be created or
    written raises OSError: a run that believes it is cached and is not
    costs its whole compile time on every call."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    from_env = bool(cache_dir)
    if not from_env:
        cache_dir = os.path.join(CHECKOUT, ".xla_cache")
    os.makedirs(cache_dir, exist_ok=True)
    if not os.access(cache_dir, os.W_OK | os.X_OK):
        raise OSError(f"compilation cache directory {cache_dir} is not "
                      f"writable")
    if not from_env:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # jax latches "no cache" at the first compile of the process;
        # clearing the latch makes the next compile re-check, so a jit
        # that ran before this call cannot disable persistence for good
        compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir


def compilation_cache_dir() -> str:
    """The directory jax's persistent compilation cache currently uses
    ('' when it has none) — for hit/miss logging around a compile."""
    import jax

    return jax.config.jax_compilation_cache_dir or ""


def compilation_cache_entries(cache_dir: str) -> int:
    """Number of entries in the persistent compilation cache directory —
    sampled before/after a compile to log hit (count unchanged) vs miss
    (new entry written). Zero for a missing dir."""
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if not n.startswith("."))
    except OSError:
        return 0
