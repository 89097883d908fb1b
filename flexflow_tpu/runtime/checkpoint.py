"""Checkpoint / resume.

The reference has NO model checkpointing (SURVEY §5.4) — only strategy files
persist (strategy.cc) and weights can be moved via set/get_tensor. The TPU
build makes checkpointing first-class: orbax saves the sharded params /
optimizer state / batch-norm stats / step counter (each chip writes its own
shard — no host gather), and the strategy table is saved alongside in the
reference text schema so a resumed job re-shards identically.

Crash consistency (the preemption story, runtime/resilience.py): each save
lands in ``<dir>/.tmp-step_N`` and becomes ``<dir>/step_N`` via one
``os.replace`` — a kill mid-save leaves only an ignored tmp dir, never a
half-written checkpoint. ``ff_meta.json`` (step, layout guards, supervisor
extras: RNG key, dataloader cursors) is written INSIDE the step dir before
the rename, so a renamed checkpoint is always self-contained; the top-level
``meta.json``/``strategy.txt`` mirror the newest step for older readers.
``latest_step`` scans the ``step_*`` dirs (tmp dirs skipped), and orbax
save/load run under ``resilience.retry`` with ``io_fail`` fault-injection
hooks (FF_FAULT) so the retry path is tier-1-testable.

Integrity (the elastic-recovery story, runtime/elastic.py): every step dir
carries a content-hash manifest ``ff_manifest.json`` (relative path ->
sha256 + byte size over every other file in the dir), written INSIDE the
tmp dir before the publish rename so a published checkpoint always carries
its own proof. ``verify_step`` recomputes the hashes; resume paths
(``auto_resume``, ``TrainSupervisor.resume``, ``restore_checkpoint`` with
``step=None``) fall back to the newest *intact* step when the latest one
fails verification (torn write, bitrot, FF_FAULT ``corrupt_ckpt@save:<n>``
injection), and keep-K retention never deletes the last intact checkpoint
even when every newer step is corrupt.

Topology: single-controller checkpoints are host numpy, so a restore
re-shards onto whatever mesh the restoring model compiled with
(``executor.reshard_params``) — the checkpoint itself is topology-free and
a job killed on N devices resumes on N-1 (see runtime/elastic.py for the
policy and mesh-refit side).
"""

from __future__ import annotations

import collections
import hashlib
import json
import re
import os
import threading
from typing import List, Optional

import jax
import numpy as np

from flexflow_tpu.parallel.strategy import (load_strategies_from_file,
                                            save_strategies_to_file)
from flexflow_tpu.runtime import faultinject, locks
from flexflow_tpu.runtime.resilience import retry


MANIFEST_NAME = "ff_manifest.json"


class CheckpointCorruptError(RuntimeError):
    """A checkpoint's payload no longer matches its content-hash manifest
    (torn write, bitrot, injected corruption). Resume paths catch this and
    fall back to the newest intact step."""


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer()


# ------------------------------------------------------ integrity manifest


def _manifest_files(step_dir: str):
    """Every regular file under `step_dir` except the manifest itself, as
    (relative posix path, absolute path) sorted for determinism."""
    out = []
    for root, _dirs, files in os.walk(step_dir):
        for name in files:
            full = os.path.join(root, name)
            rel = os.path.relpath(full, step_dir).replace(os.sep, "/")
            if rel == MANIFEST_NAME:
                continue
            out.append((rel, full))
    out.sort()
    return out


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def write_manifest(step_dir: str) -> str:
    """Write the content-hash manifest for a (not yet published) step dir:
    ``{"algo": "sha256", "files": {relpath: {"sha256": ..., "bytes": n}}}``.
    Called inside the tmp dir BEFORE the publish rename, so every published
    checkpoint is born with its proof."""
    manifest = {"algo": "sha256", "files": {}}
    for rel, full in _manifest_files(step_dir):
        manifest["files"][rel] = {"sha256": _sha256(full),
                                  "bytes": os.path.getsize(full)}
    path = os.path.join(step_dir, MANIFEST_NAME)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


def verify_dir_manifest(step_dir: str, label: Optional[str] = None,
                        require: bool = False):
    """Recompute the content-hash manifest of any published directory
    (checkpoint step dirs AND flight-recorder post-mortem bundles share
    this verifier) and raise ``CheckpointCorruptError`` naming the first
    mismatching file. Without a manifest: passes when ``require`` is
    False (pre-manifest checkpoints), raises when True (a bundle is
    born with its proof — a manifest-less one IS a torn write)."""
    label = label or step_dir
    mpath = os.path.join(step_dir, MANIFEST_NAME)
    if not os.path.exists(mpath):
        if require:
            raise CheckpointCorruptError(
                f"{label}: no {MANIFEST_NAME} — torn or foreign write")
        return
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise CheckpointCorruptError(
            f"{label}: unreadable manifest {mpath}: {e}")
    files = manifest.get("files", {})
    present = {rel: full for rel, full in _manifest_files(step_dir)}
    missing = [rel for rel in files if rel not in present]
    if missing:
        raise CheckpointCorruptError(
            f"{label}: {len(missing)} manifest file(s) "
            f"missing, first {missing[0]!r}")
    for rel, rec in files.items():
        full = present[rel]
        if os.path.getsize(full) != rec.get("bytes"):
            raise CheckpointCorruptError(
                f"{label}: {rel!r} is "
                f"{os.path.getsize(full)} bytes, manifest records "
                f"{rec.get('bytes')}")
        if _sha256(full) != rec.get("sha256"):
            raise CheckpointCorruptError(
                f"{label}: content hash mismatch on {rel!r} "
                f"— payload corrupted after save")


def verify_checkpoint(directory: str, step: int):
    """Recompute the manifest hashes of ``step_<step>`` and raise
    ``CheckpointCorruptError`` naming the first mismatching file. A
    checkpoint predating the manifest layer (no ff_manifest.json) passes —
    there is nothing to verify it against, and refusing every pre-existing
    checkpoint would turn an upgrade into data loss."""
    step_dir = os.path.join(os.path.abspath(directory), f"step_{step}")
    verify_dir_manifest(step_dir, label=f"checkpoint step {step}")


def verify_step(directory: str, step: int) -> bool:
    """Boolean flavor of verify_checkpoint (plus meta readability) for
    scan loops; corruption details go through verify_checkpoint."""
    if not _meta_readable(directory, step):
        return False
    try:
        verify_checkpoint(directory, step)
        return True
    except CheckpointCorruptError:
        return False


def _meta_readable(directory: str, step: int) -> bool:
    """Is the step's metadata usable? A per-step ff_meta.json that exists
    but fails to parse marks a damaged dir; a dir with NO per-step meta is
    only usable through a readable top-level meta.json (pre-atomic-write
    layout)."""
    per_step = os.path.join(directory, f"step_{step}", "ff_meta.json")
    target = per_step if os.path.exists(per_step) \
        else os.path.join(directory, "meta.json")
    try:
        with open(target) as f:
            json.load(f)
        return True
    except (OSError, ValueError):
        return False


def _intact_with_warning(directory: str, step: int, verify: bool) -> bool:
    from flexflow_tpu.logger import fflogger

    if not _meta_readable(directory, step):
        fflogger.warning(
            "checkpoint step %d in %s: unreadable metadata — skipping "
            "(torn write or damaged dir)", step, directory)
        return False
    if verify:
        try:
            verify_checkpoint(directory, step)
        except CheckpointCorruptError as e:
            fflogger.warning(
                "checkpoint step %d in %s failed integrity "
                "verification — skipping: %s", step, directory, e)
            return False
    return True


def iter_intact_steps(directory: str, verify: bool = True, on_skip=None,
                      trusted_step: Optional[int] = None):
    """Lazily yield published checkpoint steps newest-first, skipping
    (with a warning, and an ``on_skip(step)`` callback for counters) any
    whose metadata is unreadable or — when `verify` — whose manifest
    fails verification. LAZY on purpose: verification hashes the full
    payload, so the resume paths (which stop at the first restorable
    step) pay one hash pass over one checkpoint, not K. ``trusted_step``
    names a step the caller already verified in this process (the
    compile-time elastic hook records one) — its payload is not hashed
    again, only its metadata re-checked."""
    directory = os.path.abspath(directory)
    for step in sorted(_step_dirs(directory), reverse=True):
        if _intact_with_warning(directory, step,
                                verify and step != trusted_step):
            yield step
        elif on_skip is not None:
            on_skip(step)


def trusted_step_for(model, directory: str) -> Optional[int]:
    """The step the compile-time elastic hook verified, or None — honored
    ONLY when ``directory`` is the one the hook actually hashed, so a
    resume pointed at a different directory never inherits the trust."""
    step = getattr(model, "_elastic_verified_step", None)
    if step is None:
        return None
    recorded = getattr(model, "_elastic_verified_dir", None)
    if recorded is not None and \
            os.path.abspath(recorded) != os.path.abspath(directory):
        return None
    return step


def has_checkpoints(directory: str) -> bool:
    """Any published step dir at all in `directory`, intact or not — the
    'is there evidence of prior training' test the resume paths use to
    distinguish a fresh start from a directory of damaged checkpoints."""
    return bool(_step_dirs(os.path.abspath(directory)))


def intact_steps(directory: str, verify: bool = True) -> List[int]:
    """Eager flavor of ``iter_intact_steps`` — the full fallback chain,
    for callers that genuinely need every intact step."""
    return list(iter_intact_steps(directory, verify=verify))


def latest_intact_step(directory: str, verify: bool = True) -> Optional[int]:
    return next(iter_intact_steps(directory, verify=verify), None)


def _inject_corruption(step_dir: str):
    """FF_FAULT ``corrupt_ckpt@save:<n>``: flip bytes in the middle of the
    step's largest payload file AFTER the publish rename — the
    deterministic stand-in for bitrot / a torn write that slipped past
    rename atomicity. The manifest is left intact so verification can
    catch the damage."""
    from flexflow_tpu.logger import fflogger

    skip = {MANIFEST_NAME, "ff_meta.json", "strategy.txt"}
    candidates = [(os.path.getsize(full), rel, full)
                  for rel, full in _manifest_files(step_dir)
                  if rel.split("/")[-1] not in skip
                  and os.path.getsize(full) > 0]
    if not candidates:  # nothing but metadata: corrupt the meta instead
        candidates = [(os.path.getsize(full), rel, full)
                      for rel, full in _manifest_files(step_dir)
                      if os.path.getsize(full) > 0]
    if not candidates:
        return
    size, rel, full = max(candidates)
    with open(full, "r+b") as f:
        f.seek(size // 2)
        chunk = f.read(8) or b"\x00"
        f.seek(size // 2)
        f.write(bytes(b ^ 0xFF for b in chunk))
    fflogger.warning(
        "faultinject: corrupted checkpoint payload %s in %s (FF_FAULT "
        "corrupt_ckpt@save)", rel, step_dir)


def _opt_layout(model) -> str:
    """Optimizer-state pytree layout: the fused wrappers store state as
    flat per-dtype vectors, so a checkpoint written under one layout
    cannot restore into another (the tree structures differ). Recorded in
    meta.json; restore refuses a mismatch with a clear error instead of
    an opaque tree-structure failure."""
    from flexflow_tpu.runtime.optimizer import (FusedUpdate,
                                                ShardedFusedUpdate)

    opt = model.optimizer
    if isinstance(opt, ShardedFusedUpdate):
        return "sharded_fused"
    if isinstance(opt, FusedUpdate):
        return "fused"
    return "per_leaf"


def _sharded_fused_shardings(model):
    """The sharded-fused flat vector's element order is a pure function
    of (tree structure, leaf shardings, mesh) — record all three so a
    restore onto a DIFFERENT topology is refused instead of silently
    scrambling the moments (same per-dtype length, different
    (leaf, element) mapping)."""
    return {op: {w: str(spec) for w, spec in ws.items()}
            for op, ws in model.optimizer.specs.items()}


def _is_multihost() -> bool:
    return jax.process_count() > 1


def save_checkpoint(model, directory: str, step: Optional[int] = None,
                    extra_meta: Optional[dict] = None,
                    keep: Optional[int] = None,
                    async_save: bool = False) -> str:
    """Save model state. Returns the checkpoint path.

    Atomic: orbax writes into ``<directory>/.tmp-step_N``; meta + strategy
    land inside it; ONE ``os.replace`` publishes ``step_N``. A kill at any
    point leaves either the previous checkpoints intact plus a stale tmp
    dir (ignored by latest_step and cleaned on the next save of that
    step), or the complete new checkpoint — never a torn one.

    ``extra_meta`` merges into the per-step ``ff_meta.json`` (the
    supervisor records RNG key + dataloader cursors there); ``keep``
    prunes all but the newest ``keep`` step dirs after a successful
    publish.

    Single-controller: arrays are gathered to host numpy before writing, so
    checkpoints are topology-free — a restore re-shards onto whatever mesh
    the restoring model compiled with.

    ``async_save`` (FFConfig.async_checkpointing): the host snapshot is
    still taken on THIS thread before returning — the training loop
    donates param buffers to the next step, so the D2H copy cannot be
    deferred (leaf transfers are started asynchronously and collected
    once) — but everything after it (orbax serialization, manifest
    hashing, fsync, the publish rename, retention) runs on ONE background
    publisher thread, so ``checkpoint_every`` stops costing step time.
    Submissions publish strictly in order; ``wait_pending_saves``
    quiesces and re-raises the first failure; a publisher slower than the
    save cadence applies BACKPRESSURE (at most one snapshot queued behind
    the in-flight publish — the submit blocks rather than growing host
    memory without bound); the atomicity story is unchanged (a process
    exit mid-publish leaves a stale tmp dir, never a torn step).
    Single-controller only — multihost saves are collective and fall
    back to synchronous with a warning.

    Multi-controller (jax.process_count() > 1): arrays are handed to orbax
    as sharded jax.Arrays and EVERY process participates in the save — each
    host writes only its addressable shards (no host gather; a vocab-sharded
    embedding never materializes on one host). All processes must call this
    collectively; process 0 does the rename/prune between the barriers.
    Saving the same step twice overwrites (idempotent)."""
    directory = os.path.abspath(directory)
    step = int(step if step is not None else model._step_count)
    if _is_multihost():
        if async_save:
            from flexflow_tpu.logger import fflogger

            fflogger.warning(
                "async checkpointing is single-controller only (the "
                "multihost orbax save is collective) — saving step %d "
                "synchronously", step)
        return _save_multihost(model, directory, step, extra_meta, keep)

    state = _host_state(model)
    meta = _build_meta(model, step, with_opt="opt_state" in state,
                       multihost=False)
    if extra_meta:
        meta.update(extra_meta)
    strategies = dict(model.config.strategies)
    path = os.path.join(directory, f"step_{step}")
    if async_save:
        import functools

        # backpressure: each queued save holds a FULL host snapshot, so a
        # publisher slower than the save cadence must slow the caller
        # down (degrading toward a synchronous save), not grow host
        # memory without bound — at most one snapshot in flight plus the
        # one being submitted
        _SAVER.wait_below(directory, 1)
        _SAVER.submit(directory, step, functools.partial(
            _publish_step, directory, step, state, meta, strategies, keep))
        return path
    _publish_step(directory, step, state, meta, strategies, keep)
    return path


def _host_state(model) -> dict:
    """Snapshot params / optimizer state / bn stats to host numpy. Every
    leaf's D2H transfer is STARTED before the first blocking conversion,
    so the copies overlap instead of serializing leaf by leaf."""
    state = {"params": _strip_none(model.params)}
    if model.opt_state is not None:
        state["opt_state"] = _strip_none(model.opt_state)
    if model.bn_state:
        state["bn_state"] = _strip_none(model.bn_state)
    for leaf in jax.tree_util.tree_leaves(state):
        try:
            leaf.copy_to_host_async()
        except AttributeError:
            pass  # already host numpy / older array type
    return jax.tree_util.tree_map(lambda a: np.asarray(a), state)


def _build_meta(model, step: int, *, with_opt: bool,
                multihost: bool) -> dict:
    """Per-step ff_meta.json: topology + batch math recorded for elastic
    resume (runtime/elastic.py) — a restart on a different device count
    reads these to refit the mesh and preserve the global batch via
    grad-accum adjustment."""
    meta = {"step": int(step),
            "mesh_shape": model.config.mesh_shape,
            "num_devices": int(model.config.num_devices or 0),
            "process_count": jax.process_count(),
            "batch_size": int(model.config.batch_size),
            "grad_accum_steps": int(getattr(model.config,
                                            "grad_accum_steps", 1)),
            "multihost": multihost,
            "loss_type": model.loss_type.name if model.loss_type else None}
    if with_opt:  # layout only meaningful when state saved
        meta["opt_layout"] = _opt_layout(model)
        if meta["opt_layout"] == "sharded_fused":
            meta["opt_state_shardings"] = _sharded_fused_shardings(model)
    return meta


def _publish_step(directory: str, step: int, state: dict, meta: dict,
                  strategies: dict, keep: Optional[int]):
    """The write-and-publish half of a single-controller save: orbax the
    host state into the tmp dir (retried), then finalize. Runs on the
    caller's thread for a synchronous save, on the publisher thread for an
    async one — the inputs are already host-resident snapshots, so it
    never touches the model or the device."""
    import shutil

    tmp = os.path.join(directory, f".tmp-step_{step}")
    os.makedirs(directory, exist_ok=True)
    # only the TMP dir is cleared up front (orbax refuses to overwrite); a
    # pre-existing published step_N stays live until the new one is ready
    # — clearing it here would lose the checkpoint if the process dies
    # during the orbax write
    if os.path.exists(tmp):
        shutil.rmtree(tmp)

    def _save():
        faultinject.maybe_fail("io_fail", "save")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)  # half-written tmp from a failed attempt
        _checkpointer().save(tmp, state)

    retry(attempts=3, base_delay=0.05, retryable=(OSError,),
          name="orbax save")(_save)()
    _finalize_step_dir(directory, step, meta, strategies, keep)


def _save_multihost(model, directory: str, step: int,
                    extra_meta: Optional[dict], keep: Optional[int]) -> str:
    """Collective multi-controller save: orbax writes sharded jax.Arrays
    (each host only its addressable shards), process 0 finalizes between
    the two global barriers."""
    import shutil

    path = os.path.join(directory, f"step_{step}")
    tmp = os.path.join(directory, f".tmp-step_{step}")
    is_writer = jax.process_index() == 0
    if is_writer:
        os.makedirs(directory, exist_ok=True)
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("ff_ckpt_clean")
    state = {"params": _strip_none(model.params)}
    if model.opt_state is not None:
        state["opt_state"] = _strip_none(model.opt_state)
    if model.bn_state:
        state["bn_state"] = _strip_none(model.bn_state)
    # the orbax save is COLLECTIVE: a per-host retry would re-enter it on
    # one process only (different op counts per host -> the job deadlocks
    # at orbax's internal syncs, or the writer rmtrees shards peers just
    # wrote). A failed collective save must be retried collectively by the
    # caller on every host.
    faultinject.maybe_fail("io_fail", "save")
    _checkpointer().save(tmp, state)
    if is_writer:
        meta = _build_meta(model, step, with_opt="opt_state" in state,
                           multihost=True)
        if extra_meta:
            meta.update(extra_meta)
        _finalize_step_dir(directory, step, meta,
                           dict(model.config.strategies), keep)
    multihost_utils.sync_global_devices("ff_ckpt_done")
    return path


def _finalize_step_dir(directory: str, step: int, meta: dict,
                       strategies: dict, keep: Optional[int]):
    """Meta + strategy + manifest into the tmp dir, the publish rename,
    the top-level mirrors, the corruption drill, and retention — shared by
    the sync, async, and multihost writer paths."""
    import shutil

    path = os.path.join(directory, f"step_{step}")
    tmp = os.path.join(directory, f".tmp-step_{step}")
    with open(os.path.join(tmp, "ff_meta.json"), "w") as f:
        json.dump(meta, f)
    save_strategies_to_file(os.path.join(tmp, "strategy.txt"), strategies)
    # the manifest is the LAST write into tmp: it covers every other
    # file (orbax payload, meta, strategy), so a published dir always
    # carries a complete proof of its own contents
    write_manifest(tmp)
    if os.path.exists(path):
        # same-step overwrite: the old dir must vanish for the rename
        # (os.replace cannot clobber a non-empty dir). The unprotected
        # window shrinks to this instant — the complete replacement is
        # already on disk in tmp, so a kill here leaves tmp salvageable
        # rather than nothing mid-write
        shutil.rmtree(path)
    os.replace(tmp, path)  # the publish point
    # top-level mirrors (older readers + import_strategy_file): written
    # atomically too, AFTER the step dir is live
    mtmp = os.path.join(directory, f".meta.json.tmp-{os.getpid()}")
    with open(mtmp, "w") as f:
        json.dump(meta, f)
    os.replace(mtmp, os.path.join(directory, "meta.json"))
    stmp = os.path.join(directory, f".strategy.txt.tmp-{os.getpid()}")
    save_strategies_to_file(stmp, strategies)
    os.replace(stmp, os.path.join(directory, "strategy.txt"))
    if faultinject.active_plan().fire("corrupt_ckpt", "save"):
        # deterministic bitrot drill: damage the JUST-PUBLISHED payload
        # (before retention runs, so the intact-preservation rule below
        # is what keeps an older recoverable step alive)
        _inject_corruption(path)
    if keep is not None and keep > 0:
        steps_sorted = sorted(_step_dirs(directory))
        doomed = steps_sorted[:-keep]

        # the step THIS call just wrote (and fully hashed in
        # write_manifest) is intact by construction — don't pay a
        # second hash pass on the save critical path. The exception is
        # the corruption drill, whose whole point is that the fresh
        # step may no longer match its manifest.
        drill = any(k == "corrupt_ckpt"
                    for k, _s, _i in faultinject.active_plan().events)

        def _survivor_intact(s: int) -> bool:
            if s == int(step) and not drill:
                return True
            return verify_step(directory, s)

        # newest-first so an intact newest survivor short-circuits
        if doomed and not any(_survivor_intact(s)
                              for s in reversed(steps_sorted[-keep:])):
            # every survivor is corrupt/unreadable: deleting the whole
            # tail would leave NO restorable checkpoint — spare the
            # newest intact one (retention resumes normally once an
            # intact step re-enters the survivor window)
            for s in reversed(doomed):
                if verify_step(directory, s):
                    doomed.remove(s)
                    from flexflow_tpu.logger import fflogger

                    fflogger.warning(
                        "checkpoint retention: every surviving step of "
                        "keep=%d fails verification — keeping intact "
                        "step %d beyond the retention window", keep, s)
                    break
        for old in doomed:
            shutil.rmtree(os.path.join(directory, f"step_{old}"),
                          ignore_errors=True)


# ------------------------------------------------------ async publisher


class _AsyncSaver:
    """ONE background publisher thread for async checkpointing: saves to
    any directory publish strictly in submission order (step N can never
    rename after step N+1), pending work is awaitable per directory, and
    the first failure is re-raised at the next wait — callers treat it
    exactly like a synchronous save failure. The thread is a daemon: a
    process exit mid-publish leaves only a stale tmp dir (the publish
    rename is atomic), never a torn checkpoint; callers that need the
    save DURABLE (supervisor preempt/final, rewind's intact scan) call
    ``wait_pending_saves`` first."""

    def __init__(self):
        self._cond = locks.make_condition("checkpoint-saver")
        self._queue: collections.deque = collections.deque()
        self._active: Optional[str] = None  # directory being published
        self._errors: List[tuple] = []
        self._thread: Optional[threading.Thread] = None

    def submit(self, directory: str, step: int, fn):
        with self._cond:
            self._queue.append((directory, step, fn))
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, name="ff-ckpt-publisher", daemon=True)
                self._thread.start()
            self._cond.notify_all()

    def _run(self):
        while True:
            with self._cond:
                while not self._queue:
                    self._cond.wait()
                directory, step, fn = self._queue.popleft()
                self._active = directory
            try:
                fn()
            except BaseException as e:  # surfaced at the next wait()
                from flexflow_tpu.logger import fflogger

                fflogger.error(
                    "async checkpoint: publishing step %d in %s failed: "
                    "%s: %s", step, directory, type(e).__name__, e)
                # drop the traceback chain BEFORE retaining: its frames
                # reference the publish closure and with it the full
                # model host snapshot — a retained error must not pin
                # model-sized memory until someone waits on it
                e.__traceback__ = None
                with self._cond:
                    self._errors.append((directory, step, e))
            finally:
                with self._cond:
                    self._active = None
                    self._cond.notify_all()

    def _matches(self, d: Optional[str], directory: Optional[str]) -> bool:
        return directory is None or d == directory

    def pending(self, directory: Optional[str] = None) -> int:
        with self._cond:
            return self._pending_locked(directory)

    def wait_below(self, directory: Optional[str], n: int):
        """Block until fewer than ``n`` matching saves are queued or in
        flight — the submit-side backpressure primitive. Never raises:
        retained failures keep surfacing at wait()."""
        with self._cond:
            while self._pending_locked(directory) > n:
                self._cond.wait()

    def _pending_locked(self, directory: Optional[str]) -> int:
        n = sum(1 for d, _s, _f in self._queue
                if self._matches(d, directory))
        if self._active is not None and self._matches(self._active,
                                                      directory):
            n += 1
        return n

    def wait(self, directory: Optional[str] = None):
        with self._cond:
            while self._pending_locked(directory) > 0:
                self._cond.wait()
            errs = [e for e in self._errors
                    if self._matches(e[0], directory)]
            if errs:
                self._errors = [e for e in self._errors if e not in errs]
                if len(errs) > 1:
                    from flexflow_tpu.logger import fflogger

                    fflogger.warning(
                        "async checkpoint: %d further save failure(s) "
                        "consumed alongside the one re-raised (each was "
                        "logged at failure time)", len(errs) - 1)
                d, s, exc = errs[0]
                raise RuntimeError(
                    f"async checkpoint save of step {s} in {d} "
                    f"failed") from exc


_SAVER = _AsyncSaver()


def wait_pending_saves(directory: Optional[str] = None):
    """Quiesce async checkpointing: block until every pending async save
    (to ``directory``, or anywhere when None) has published, then
    re-raise the first failure among them. A no-op when nothing is
    pending — safe to call unconditionally before reading a checkpoint
    directory the training loop writes asynchronously."""
    _SAVER.wait(os.path.abspath(directory) if directory else None)


def pending_saves(directory: Optional[str] = None) -> int:
    """Number of async saves still queued or publishing."""
    return _SAVER.pending(os.path.abspath(directory) if directory else None)


def restore_checkpoint(model, directory: str, step: Optional[int] = None,
                       verify: Optional[bool] = None):
    """Restore into a compiled model. Single-controller checkpoints are
    stored as host numpy (see save_checkpoint), so restore re-shards onto
    the restoring model's own mesh regardless of the topology that saved
    them — including a DIFFERENT device count (the elastic path,
    runtime/elastic.py). Under multi-controller, every process calls this
    collectively and orbax restores each array directly into the model's
    current sharding (each host reads only its shards).

    ``verify`` (default: FFConfig.verify_checkpoints) recomputes the step's
    content-hash manifest first and raises ``CheckpointCorruptError`` on a
    mismatch; with ``step=None`` the newest INTACT step is chosen, so a
    corrupted latest falls back automatically. Even with ``verify=False``
    a restore that fails mid-read is re-checked against the manifest: if
    the step no longer verifies (damage or retention raced the caller's
    intact scan) the failure is reclassified as ``CheckpointCorruptError``
    so the resume fallback chains engage; a genuine error over an intact
    step propagates untouched."""
    directory = os.path.abspath(directory)
    if verify is None:
        verify = bool(getattr(model.config, "verify_checkpoints", True))
    if step is None:
        step = latest_intact_step(directory, verify=verify)
        if step is None:
            raise FileNotFoundError(
                f"no (intact) checkpoint found in {directory}")
    elif verify:
        verify_checkpoint(directory, step)
    try:
        return _restore_into(model, directory, step)
    except CheckpointCorruptError:
        raise
    except Exception as err:
        _reclassify_raced_damage(directory, step, err)
        raise


def _reclassify_raced_damage(directory: str, step: int, err: Exception):
    """A restore that failed AFTER the caller's intact scan may be raced
    damage (concurrent retention pruned the step, corruption landed after
    the hash pass) rather than a code bug. Re-check the step: a vanished
    dir, unreadable metadata, or a manifest that no longer verifies
    reclassifies the failure as ``CheckpointCorruptError`` — the exception
    the documented fallbacks (auto_resume, TrainSupervisor.resume) catch.
    An intact step means the error is real; return and let it propagate."""
    step_dir = os.path.join(directory, f"step_{step}")
    if not os.path.isdir(step_dir):
        raise CheckpointCorruptError(
            f"checkpoint step {step} disappeared mid-restore "
            f"({type(err).__name__}: {err})") from err
    if not _meta_readable(directory, step):
        raise CheckpointCorruptError(
            f"checkpoint step {step}: metadata became unreadable "
            f"mid-restore ({type(err).__name__}: {err})") from err
    try:
        verify_checkpoint(directory, step)
    except CheckpointCorruptError as ce:
        raise CheckpointCorruptError(
            f"{ce} (surfaced as {type(err).__name__} mid-restore)") from err


def _restore_into(model, directory: str, step: int) -> int:
    """Read + re-shard a chosen, published step into the model — the body
    of ``restore_checkpoint`` after step selection/verification, separated
    so the wrapper can reclassify raced-damage read failures."""
    meta = load_meta(directory, step)
    path = os.path.join(directory, f"step_{step}")

    # absent on pre-r5 and params-only checkpoints (no opt state to
    # mismatch — a weights-export -> fine-tune restore must not be blocked)
    saved_layout = meta.get("opt_layout")
    if saved_layout is not None and model.optimizer is not None:
        if saved_layout != _opt_layout(model):
            raise ValueError(
                f"checkpoint at {directory} stores optimizer state in the "
                f"{saved_layout!r} layout but this model uses "
                f"{_opt_layout(model)!r} (FFConfig.fused_optimizer and the "
                f"sharding strategy determine the layout). Re-compile with "
                f"a matching fused_optimizer setting to restore.")
        if saved_layout == "sharded_fused":
            # same layout kind is not enough: the flat vector's element
            # order depends on (mesh, leaf shardings) — a cross-topology
            # restore would silently scramble the moments
            saved_sh = meta.get("opt_state_shardings")
            cur_sh = _sharded_fused_shardings(model)
            # ordered compare: the flat layout follows mesh AXIS ORDER
            # (P(tuple(axis_names))), so {'data':2,'model':2} and
            # {'model':2,'data':2} are different layouts even though the
            # dicts compare equal (JSON preserves key order)
            mesh_saved = list((meta.get("mesh_shape") or {}).items())
            mesh_cur = list(model.config.mesh_shape.items())
            if (mesh_saved != mesh_cur
                    or (saved_sh is not None and saved_sh != cur_sh)):
                raise ValueError(
                    f"checkpoint at {directory} stores sharded-fused "
                    f"optimizer state for mesh {meta.get('mesh_shape')} "
                    f"with different parameter shardings — the flat state "
                    f"layout is topology-dependent. Re-compile with the "
                    f"saved mesh/strategy, or restore weights only "
                    f"(optimizer=None) and start the optimizer fresh.")

    if _is_multihost():
        import orbax.checkpoint as ocp

        template = {"params": model.params}
        if model.opt_state is not None:
            template["opt_state"] = _strip_none(model.opt_state)
        if model.bn_state:
            template["bn_state"] = model.bn_state
        restore_args = ocp.checkpoint_utils.construct_restore_args(template)
        # no per-host retry around the COLLECTIVE restore (see _save):
        # one host re-entering it would desync the participants
        faultinject.maybe_fail("io_fail", "load")
        restored = _checkpointer().restore(path, restore_args=restore_args)
        model.params = restored["params"]
        if "opt_state" in restored and model.optimizer is not None:
            fresh = model.optimizer.init_state(model.params)
            model.opt_state = _merge_sharded(fresh, restored["opt_state"])
        if "bn_state" in restored:
            model.bn_state = restored["bn_state"]
        model._step_count = step
        return step

    # a checkpoint written by a multi-controller job stores SHARDED jax
    # arrays; deserializing those into a single-controller process needs
    # explicit numpy restore args (orbax refuses without a sharding) —
    # the N-hosts -> 1-host elastic resume path
    restored = (_orbax_restore_as_numpy(path) if meta.get("multihost")
                else _orbax_restore(path))
    # re-shard the host tree onto the CURRENT executor's placement — the
    # mesh the restoring process actually built, which need not match the
    # one that saved (executor.reshard_params; elastic resume rides this)
    model.params = model.executor.reshard_params(restored["params"])
    if "opt_state" in restored and model.optimizer is not None:
        fresh = model.optimizer.init_state(model.params)
        model.opt_state = _merge_restored(fresh, restored["opt_state"])
    if "bn_state" in restored:
        # ffsan: allow(uncommitted-device-put) — one-time restore
        # placement of replicated BN state, matching how init
        # placed it; the post-restore step compiles fresh anyway
        model.bn_state = {k: {n: jax.device_put(np.asarray(v))
                              for n, v in s.items()}
                          for k, s in restored["bn_state"].items()}
    model._step_count = step
    # NOTE: the checkpointed strategy file is NOT silently applied — sharding
    # was already resolved in compile(). To resume with the checkpointed
    # strategy, pass import_strategy_file=<dir>/strategy.txt in FFConfig
    # BEFORE compile(). We only warn on divergence here.
    try:
        per_step = os.path.join(path, "strategy.txt")
        saved = load_strategies_from_file(
            per_step if os.path.exists(per_step)
            else os.path.join(directory, "strategy.txt"))
        current = model.config.strategies
        def differs(a, b):
            if a.dims != b.dims:
                return True
            # dims alone miss CONTRACT/STAGE divergence (they shard
            # weights, not the output) — compare axis maps when both known
            if a.axis_map is not None and b.axis_map is not None:
                na = {k: v for k, v in a.axis_map.items() if v is not None}
                nb = {k: v for k, v in b.axis_map.items() if v is not None}
                return na != nb
            return False

        diff = [k for k in saved
                if k in current and differs(saved[k], current[k])]
        if diff:
            import sys

            print(f"[checkpoint] WARNING: strategy mismatch vs checkpoint for "
                  f"ops {diff[:5]}{'...' if len(diff) > 5 else ''}; set "
                  f"import_strategy_file before compile() to resume with the "
                  f"saved strategy", file=sys.stderr)
    except FileNotFoundError:
        pass
    return step


@retry(attempts=3, base_delay=0.05, retryable=(OSError,), name="orbax load")
def _orbax_restore(path, **kw):
    faultinject.maybe_fail("io_fail", "load")
    return _checkpointer().restore(path, **kw)


@retry(attempts=3, base_delay=0.05, retryable=(OSError,), name="orbax load")
def _orbax_restore_as_numpy(path):
    """Restore a multi-controller (sharded-array) checkpoint as plain host
    numpy: every leaf gets RestoreArgs(restore_type=np.ndarray), built
    from the checkpoint's own structure metadata. The full arrays
    materialize on this host — exactly what the cross-topology re-shard
    needs."""
    faultinject.maybe_fail("io_fail", "load")
    import orbax.checkpoint as ocp

    ckptr = _checkpointer()
    # the step's metadata wraps the saved tree's own (orbax 0.11)
    structure = ckptr.metadata(path).item_metadata.tree
    restore_args = jax.tree_util.tree_map(
        lambda _m: ocp.RestoreArgs(restore_type=np.ndarray), structure)
    return ckptr.restore(path, restore_args=restore_args)


def _step_dirs(directory: str):
    """Published checkpoint step numbers in `directory` (tmp dirs from an
    interrupted save are skipped — they never became checkpoints)."""
    try:
        names = os.listdir(directory)
    except (FileNotFoundError, NotADirectoryError):
        return []
    out = []
    for n in names:
        m = re.fullmatch(r"step_(\d+)", n)
        if m and os.path.isdir(os.path.join(directory, n)):
            out.append(int(m.group(1)))
    return out


def load_meta(directory: str, step: Optional[int] = None) -> dict:
    """Checkpoint metadata: the per-step ``step_N/ff_meta.json`` when
    present (self-contained checkpoints), else the top-level ``meta.json``
    (pre-atomic-write layout)."""
    directory = os.path.abspath(directory)
    if step is not None:
        per_step = os.path.join(directory, f"step_{step}", "ff_meta.json")
        if os.path.exists(per_step):
            with open(per_step) as f:
                return json.load(f)
    with open(os.path.join(directory, "meta.json")) as f:
        return json.load(f)


def latest_step(directory: str) -> Optional[int]:
    """Newest published checkpoint step in `directory` with READABLE
    metadata, or None. Scans the ``step_*`` dirs ONLY: trusting
    ``meta.json`` would return steps whose dir is gone (a kill inside the
    same-step overwrite window, retention pruning) and turn auto-resume
    into a restore-of-nothing crash loop — no dir means fresh start.
    ``.tmp-*`` leftovers from an interrupted save are ignored, and a dir
    whose ``ff_meta.json`` exists but no longer parses is skipped (a
    damaged dir used to raise mid-resume here) — payload verification is
    ``latest_intact_step``'s stricter job."""
    directory = os.path.abspath(directory)
    for step in sorted(_step_dirs(directory), reverse=True):
        if _meta_readable(directory, step):
            return step
    return None


def _strip_none(tree):
    if isinstance(tree, dict):
        return {k: _strip_none(v) for k, v in tree.items() if v is not None}
    return tree


def _merge_sharded(fresh, restored):
    """Refill None leaves stripped before a sharded save (restored arrays
    already carry the model's shardings via construct_restore_args)."""
    if isinstance(fresh, dict):
        return {k: _merge_sharded(v, restored[k]) if k in restored else v
                for k, v in fresh.items()}
    if fresh is None:
        return None
    return restored


def _merge_restored(fresh, restored):
    from jax.sharding import NamedSharding

    if isinstance(fresh, dict):
        return {k: _merge_restored(v, restored[k]) if k in restored else v
                for k, v in fresh.items()}
    if fresh is None:
        return None
    arr = np.asarray(restored).astype(np.asarray(fresh).dtype)
    sh = getattr(fresh, "sharding", None)
    if isinstance(sh, NamedSharding):
        return jax.device_put(arr, sh)
    # uncommitted: let jit place it alongside the mesh-sharded params
    import jax.numpy as jnp

    return jnp.asarray(arr)


def scan_and_restore(model, directory: str, *, restore, on_skip=None,
                     who: str = "auto_resume") -> Optional[int]:
    """The ONE newest-intact-first resume policy (``auto_resume`` and
    ``TrainSupervisor.resume`` both ride it): lazily scan intact steps
    (one payload hash per step actually examined, none for the step the
    compile-time elastic hook verified for this directory), call
    ``restore(step)`` on each candidate, fall back past raced
    mid-restore damage with a warning (and ``on_skip``), and return the
    restored step. Returns None when the directory holds no steps at
    all; raises CheckpointCorruptError when every existing step fails —
    silently starting fresh over damaged checkpoints would destroy the
    evidence."""
    from flexflow_tpu.logger import fflogger

    verify = bool(getattr(model.config, "verify_checkpoints", True))
    for step in iter_intact_steps(
            directory, verify=verify, on_skip=on_skip,
            trusted_step=trusted_step_for(model, directory)):
        try:
            restore(step)
            return step
        except CheckpointCorruptError as e:
            # raced corruption between the scan's hash pass and the
            # restore itself
            fflogger.warning(
                "%s: checkpoint step %d became unreadable mid-restore "
                "(%s); falling back to the next intact step", who, step, e)
            if on_skip is not None:
                on_skip(step)
    if _step_dirs(directory):
        raise CheckpointCorruptError(
            f"every checkpoint in {directory} fails metadata/manifest "
            f"verification — refusing to silently start fresh over "
            f"damaged checkpoints")
    return None


def auto_resume(model, directory: str) -> int:
    """Slice-preemption recovery (the capability gap SURVEY §5.3 notes in the
    reference: a failed node kills the job with no recovery). Call after
    compile(): restores the newest INTACT checkpoint in `directory` when
    one exists and returns its step; returns 0 on a fresh start (no step
    dirs at all). A corrupted/unreadable newer step is skipped with a
    warning instead of raising mid-resume; when every existing step fails
    verification the corruption error propagates — silently training from
    scratch on top of a directory full of damaged checkpoints would
    destroy the evidence."""
    def _restore(step):
        # the scan just verified this step — don't hash it again; raced
        # damage inside the restore itself still surfaces
        restore_checkpoint(model, directory, step=step, verify=False)

    step = scan_and_restore(model, directory, restore=_restore)
    return 0 if step is None else step
