"""ffsan ``tracestability`` pass — retrace hazards the repo has
relearned at runtime four times (PRs 3/7/10/11), rejected statically.

Rules (codes):
  uncommitted-device-put (warning)  ``jax.device_put(x)`` with no
        device/sharding: the result is UNCOMMITTED, and an uncommitted
        array feeding a jitted program gives it a different argument
        signature than a committed one — the warm program silently
        retraces (minutes on a real TPU) with recompile_count none the
        wiser. Pass the placement explicitly.
  shape-dependent-slice  (warning)  Python-level slicing of a device
        array with non-constant bounds in the serving/migration hot
        path (serving.py, router.py): each distinct bound is a new
        trace shape downstream, and the slice itself forces a transfer.
        Slice on the host (numpy) or inside the program (lax.dynamic_slice
        with a fixed output shape).
  jnp-under-lock         (warning)  A statement-level ``jnp.*`` call
        while holding a registered lock: op-by-op dispatch (tracing,
        potentially compiling) inside a critical section, every tick.
        ``jnp`` inside a nested ``def``/``lambda`` is NOT flagged —
        that's a traced-program builder, executed by jit, which is the
        correct place for jnp.

The runtime complement is the retrace sentinel (runtime/locks.py):
after ``warmup()`` any jit cache miss on a warm program is recorded
with the argument signature that diverged — what these rules catch
statically, it catches dynamically, including hazards that arrive via
data rather than code.
"""

from __future__ import annotations

from typing import List

from flexflow_tpu.analysis.report import Violation
from flexflow_tpu.analysis.sanitize.lockgraph import LockGraph

# rule 2's scope: the serving/migration hot paths named by the issue —
# a shape-dependent slice in offline checkpoint code is not a per-tick
# hazard
_HOT_MODULES = ("serving", "router", "kv_pool")


def check_tracestability(graph: LockGraph) -> List[Violation]:
    out: List[Violation] = []
    seen = set()

    def emit(code, msg, path, line, qual):
        key = (code, path, line)
        if key in seen or graph.allowed_at(code, path, line):
            return
        seen.add(key)
        out.append(Violation(code=code, pass_name="tracestability",
                             severity="warning", message=msg,
                             op_name=qual, file=path, line=line))

    for info in graph.functions.values():
        for path, line in info.uncommitted_puts:
            emit("uncommitted-device-put",
                 "jax.device_put without a device/sharding leaves the "
                 "array UNCOMMITTED — feeding it to a warm jitted "
                 "program silently retraces it (the PR-3 bug class); "
                 "pass the placement explicitly",
                 path, line, info.qualname)
        if info.module in _HOT_MODULES:
            for var, path, line in info.device_slices:
                emit("shape-dependent-slice",
                     f"Python-level slice of device array {var!r} with "
                     f"non-constant bounds in a serving hot path: each "
                     f"distinct bound is a new downstream trace shape "
                     f"and the slice forces a device sync — slice on "
                     f"the host or via lax.dynamic_slice",
                     path, line, info.qualname)
        for held, callee_key, text, path, line in info.calls_under:
            if text.startswith(("jnp.", "jax.numpy.")):
                emit("jnp-under-lock",
                     f"{text} dispatched while holding {list(held)}: "
                     f"op-by-op tracing inside a critical section — "
                     f"move it into the jitted program (nested def) or "
                     f"outside the lock",
                     path, line, info.qualname)
    return out
