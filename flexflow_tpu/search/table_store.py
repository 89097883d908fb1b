"""Shared atomic-JSON table persistence for measured-cost stores.

The on-disk discipline of the op-cost database (`search/cost_db.py`):

  * atomic publish: write ``<path>.tmp`` then ``os.replace`` so a reader
    (or a crash mid-write) can never observe a torn table;
  * in-process cache keyed by the file's ``(mtime_ns, size)`` so an
    out-of-process update (another worker's re-tune / re-measure) is
    picked up by the NEXT lookup without a restart, while warm lookups
    never stat() twice for the same generation;
  * one environment key — ``measure._env_signature()``'s
    (backend, device kind, jax version) — stamped into every persisted
    key, so a timing taken on one backend/jax build can never be served
    on another: it must MISS, not mislead.

File format (shared by every consumer)::

    {"version": 1, "entries": {"<key>": {...}, ...}}
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Tuple

# {path: (file_stat_sig, entries)} — shared by every table on disk; keys
# are file paths so distinct tables never collide.
_CACHE: Dict[str, Tuple] = {}


def stat_sig(path: str):
    """(mtime_ns, size) of the file, or None when absent — the cache
    invalidation token: any out-of-process rewrite changes it."""
    try:
        st = os.stat(path)
        return (st.st_mtime_ns, st.st_size)
    except OSError:
        return None


def env_key() -> str:
    """Device-identity half of every persisted key: backend, chip kind,
    jax version — measure._env_signature, the ONE environment probe all
    persisted cost keys share. A version bump (jax or the libtpu it
    pins) changes codegen, so old entries stop matching new programs by
    key mismatch instead of silently serving stale numbers."""
    from flexflow_tpu.search.measure import _env_signature

    backend, kind, version = _env_signature()
    return f"{backend}|{kind}|jax-{version}"


def load(path: str, reload: bool = False) -> Dict:
    """Entries dict for `path`, cached in-process and invalidated by the
    file's (mtime, size) — a table written after this process's first
    lookup is served on the next call, never shadowed by a cached empty
    read. ``reload=True`` forces the re-read regardless."""
    sig = stat_sig(path)
    if not reload and path in _CACHE and _CACHE[path][0] == sig:
        return _CACHE[path][1]
    entries: Dict = {}
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict):
            entries = data.get("entries", {})
    except (OSError, ValueError):
        entries = {}
    _CACHE[path] = (sig, entries)
    return entries


def publish(path: str, entries: Dict) -> None:
    """Atomic tmp+rename write (the checkpoint.py discipline) and cache
    refresh: after this returns, every reader — this process or another
    — sees either the old complete table or the new complete table."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"version": 1, "entries": entries}, f, indent=1,
                  sort_keys=True)
    os.replace(tmp, path)
    _CACHE[path] = (stat_sig(path), entries)


def clear_cache() -> None:
    """Drop every in-process cached table (test fixtures simulating a
    fresh process). On-disk state is untouched."""
    _CACHE.clear()
