"""Profiling / tracing.

Reference observability (SURVEY §5.1): per-op cudaEvent timing behind
--profiling (linear.cu:526-553), simulator DOT export (--taskgraph), Legion
-lg:prof logs. TPU equivalents:

  * IN-SITU attribution: the executors trace every op under
    jax.named_scope(op.name), so each instruction of the PRODUCTION jitted
    program carries the op name in its HLO metadata — a
    jax.profiler.start_trace() trace attributes device ops back to graph
    ops (the host's ff.* spans of runtime/telemetry.py lie in the same
    trace), and in_situ_op_summary reads the
    optimized program's per-op instruction breakdown without running
    anything unfused
  * profile_step: op-by-op eager execution with wall timers — the analog of
    the per-op printf path, for wall-clock per op at the price of fusion
  * export_taskgraph: the op graph + strategy as Graphviz DOT (the
    simulator's DotFile analog, simulator.h:78-131)
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax

from flexflow_tpu.runtime.executor import resolve_tied_params


def profile_step(model, batch: Dict, iters: int = 3) -> List[dict]:
    """Run the forward graph op-by-op (unfused) and time each op.
    Returns [{op, type, ms, output_shape}] sorted by cost."""
    from flexflow_tpu.ops.base import InputOp

    ex = model.executor
    sharded = ex.shard_batch(batch)
    input_ops = {op.name: op for op in model.ops if isinstance(op, InputOp)}
    vals = {}
    for name, op in input_ops.items():
        if name in sharded:
            vals[op.outputs[0]] = sharded[name]
    rows = []
    rng = jax.random.PRNGKey(0)
    for idx, op in enumerate(model.ops):
        if isinstance(op, InputOp):
            continue
        xs = [vals[t] for t in op.inputs]
        p = resolve_tied_params(model, model.params, op.name,
                                model.params.get(op.name, {}))
        op_rng = jax.random.fold_in(rng, idx) if op.needs_rng else None

        def run():
            if op.stateful:
                outs, _ = op.forward_stateful(
                    p, model.bn_state.get(op.name, {}), xs,
                    training=False, rng=op_rng)
            else:
                kwargs = {}
                if getattr(op, "wants_shard_ctx", False):
                    kwargs["shard_ctx"] = {
                        "mesh": ex.mesh,
                        "axis_map": ex._op_axis_maps.get(op.name, {}),
                        "sp_mode": getattr(model.config, "sp_mode", "ring")}
                outs = op.forward(p, xs, training=False, rng=op_rng, **kwargs)
            return outs

        outs = run()  # warmup/compile
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for _ in range(iters):
            outs = run()
        jax.block_until_ready(outs)
        ms = (time.perf_counter() - t0) / iters * 1e3
        for i, t in enumerate(op.outputs):
            vals[t] = outs[i]
        rows.append({"op": op.name, "type": type(op).__name__, "ms": ms,
                     "output_shape": op.outputs[0].dims})
    rows.sort(key=lambda r: -r["ms"])
    return rows


def in_situ_op_summary(model, batch: Dict) -> List[dict]:
    """Per-op breakdown of the PRODUCTION train-step program: lowers and
    compiles the exact jitted step the training loop runs, then attributes
    every optimized-HLO instruction to its graph op via the named_scope
    metadata (`jvp(op)` = forward, `transpose(jvp(op))` = backward).
    Returns [{op, fwd_instructions, bwd_instructions}], heaviest first —
    the in-situ analog of the reference's --profiling per-op event timers
    (linear.cu:526-553), without de-fusing the program.

    Requires a compiled model with a train step (model.compile + loaders).
    """
    import re

    import jax as _jax

    step = model._train_step
    lowered = step.lower(model.params, model.opt_state, model.bn_state,
                         batch, _jax.random.PRNGKey(0))
    txt = lowered.compile().as_text()
    op_names = sorted((op.name for op in model.ops), key=len, reverse=True)
    fwd: Dict[str, int] = {}
    bwd: Dict[str, int] = {}
    for path in re.findall(r'op_name="([^"]+)"', txt):
        for name in op_names:
            if f"jvp({name})" in path or f"/{name}/" in path \
                    or path.endswith(f"/{name}"):
                side = bwd if "transpose(" in path else fwd
                side[name] = side.get(name, 0) + 1
                break
    rows = [{"op": n,
             "fwd_instructions": fwd.get(n, 0),
             "bwd_instructions": bwd.get(n, 0)}
            for n in {**fwd, **bwd}]
    rows.sort(key=lambda r: -(r["fwd_instructions"] + r["bwd_instructions"]))
    return rows


_COLLECTIVE_OPS = ("all-reduce", "reduce-scatter", "all-gather",
                   "collective-permute", "all-to-all")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                "u64": 8, "f64": 8, "c64": 8, "c128": 16}


def hlo_collective_stats(hlo_text: str) -> Dict[str, float]:
    """Count the collective instructions of an optimized-HLO dump and sum
    their output bytes — the static half of the compute/collective
    breakdown. Async pairs count once (the ``-start`` op; its ``-done``
    is the same transfer completing)."""
    import re

    count = 0
    nbytes = 0.0
    per_kind: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = re.search(r"=\s+(.*?)\s+(%?)("
                      + "|".join(_COLLECTIVE_OPS)
                      + r")(-start)?\(", line)
        if m is None or "-done(" in line:
            continue
        kind = m.group(3)
        count += 1
        per_kind[kind] = per_kind.get(kind, 0) + 1
        shapes = re.findall(r"([a-z]\d*\w*)\[([0-9,]*)\]", m.group(1))
        if m.group(4) and len(shapes) > 1:
            # async '-start' lowering: the tuple result carries the
            # operand alias buffers alongside the result — counting them
            # all would report ~2x the sync-lowered equivalent. The
            # RESULT is the last element.
            shapes = shapes[-1:]
        for dt, dims in shapes:
            b = _DTYPE_BYTES.get(dt)
            if b is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * b
    out: Dict[str, float] = {"collective_instructions": count,
                             "collective_bytes": nbytes}
    for kind, n in per_kind.items():
        out[f"collective_{kind.replace('-', '_')}"] = n
    return out


def step_phase_breakdown(model, batch: Optional[Dict] = None,
                         iters: int = 3) -> Dict[str, float]:
    """Per-step compute/collective/epilogue breakdown of the train step —
    the observability for the in-graph overlap work (ROADMAP item 4):

      * ``device_step_ms`` — measured wall of the full fused step, run
        through an UNDONATED re-jit of the production step body (the
        model's own params/opt-state are never consumed, so this is safe
        to call mid-training);
      * ``epilogue_ms`` / ``epilogue_fraction`` — measured wall of the
        optimizer update alone (zero gradients; elementwise update time
        is value-independent): the scan epilogue that bucketed grad sync
        + the ZeRO-1 sharded update shrink;
      * ``collective_instructions`` / ``collective_bytes`` (+ per-kind
        counts) — optimized-HLO collective ops of the PRODUCTION compiled
        program, so an overlap regression (all-reduce where a
        reduce-scatter should be) is visible without tracing;
      * ``grad_sync_overlapped`` — whether FFConfig.overlap_grad_sync was
        compiled in.

    Surfaced through ``FFModel.step_breakdown`` which merges the result
    into ``model.last_step_breakdown`` alongside fit()'s host-side
    numbers."""
    import jax.numpy as jnp

    ex = model.executor
    if getattr(ex, "jits_per_group", False):
        raise RuntimeError(
            "step_phase_breakdown needs the single-program executor "
            "(operator-placement strategies jit per sub-mesh group)")
    if model._train_step is None or model.optimizer is None:
        raise RuntimeError("compile() with an optimizer first")
    if batch is None:
        batch = model._current_batch or model._stage_batch()
    sharded = ex.shard_batch(batch)
    rng = jax.random.PRNGKey(0)

    # full step, re-jitted WITHOUT donation so the timing loop can feed
    # the same (still-live) arguments every iteration
    body = ex._train_step_body(model.optimizer, model.loss_type,
                               model.metric_types, model._loss_tensor)
    step = jax.jit(body)
    args = (model.params, model.opt_state, model.bn_state, sharded, rng)
    jax.block_until_ready(step(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = step(*args)
    jax.block_until_ready(out)
    device_step_ms = (time.perf_counter() - t0) / iters * 1e3

    # epilogue: the optimizer update alone (what the serial scan epilogue
    # pays after the last microbatch's backward)
    zeros_g = jax.tree_util.tree_map(jnp.zeros_like, model.params)
    upd = jax.jit(model.optimizer.update)
    jax.block_until_ready(upd(model.params, zeros_g, model.opt_state))
    t0 = time.perf_counter()
    for _ in range(iters):
        res = upd(model.params, zeros_g, model.opt_state)
    jax.block_until_ready(res)
    epilogue_ms = (time.perf_counter() - t0) / iters * 1e3

    rows: Dict[str, float] = {
        "device_step_ms": round(device_step_ms, 4),
        "epilogue_ms": round(epilogue_ms, 4),
        "compute_ms": round(max(device_step_ms - epilogue_ms, 0.0), 4),
        "epilogue_fraction": round(
            min(epilogue_ms / max(device_step_ms, 1e-9), 1.0), 4),
        "grad_sync_overlapped": bool(
            getattr(model.config, "overlap_grad_sync", False)),
    }
    try:
        txt = model._train_step.lower(*args).compile().as_text()
        rows.update(hlo_collective_stats(txt))
    except Exception:  # pragma: no cover — HLO text is best-effort
        rows.update({"collective_instructions": -1,
                     "collective_bytes": -1.0})
    return rows


def export_taskgraph(model, filename: str):
    """Op graph + strategies as Graphviz DOT (reference DotFile analog)."""
    from flexflow_tpu.ops.base import InputOp

    lines = ["digraph taskgraph {", "  rankdir=LR;"]
    for op in model.ops:
        am = {}
        if model.executor is not None:
            am = model.executor._op_axis_maps.get(op.name, {})
        label = f"{op.name}\\n{type(op).__name__}"
        used = {a: d for a, d in am.items() if d is not None}
        if used:
            label += f"\\n{used}"
        shape = "box" if isinstance(op, InputOp) else "ellipse"
        lines.append(f'  "{op.name}" [label="{label}", shape={shape}];')
    for op in model.ops:
        for t in op.inputs:
            if t.owner_op is not None:
                lines.append(f'  "{t.owner_op.name}" -> "{op.name}";')
    lines.append("}")
    with open(filename, "w") as f:
        f.write("\n".join(lines))
    return filename


def export_sim_taskgraph(model, filename: str, mesh_shape=None):
    """Simulated schedule as Graphviz DOT with per-task start/end times
    (reference: --taskgraph, the simulator's DotFile dump used at
    simulator.cc:496-545). Uses the model's resolved strategy (compile()
    first) and the C++ event-driven simulator's timeline."""
    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.csim import get_search_problem

    mesh_shape = mesh_shape or model.config.mesh_shape
    cost = CostModel(model, mesh_shape)
    prob = get_search_problem(model, cost, mesh_shape)
    strategy = {}
    if model.executor is not None:
        strategy = {name: am
                    for name, am in model.executor._op_axis_maps.items()}
    choices = prob.choices_for(strategy)
    # honor op placement: the strategy's device blocks shape the timeline
    places = {name: (min(pc.device_ids) if pc.device_ids else 0)
              for name, pc in model.config.strategies.items()}
    total, rows = prob.simulate_timeline(choices, places)

    lines = ["digraph sim_taskgraph {", "  rankdir=LR;",
             f'  label="simulated iteration: {total * 1e3:.3f} ms";']
    for r in rows:
        if r["kind"] == "compute":
            lines.append(
                f'  "{r["name"]}" [shape=ellipse, label="{r["name"]}\\n'
                f'[{r["start"] * 1e3:.3f}, {r["finish"] * 1e3:.3f}] ms"];')
        elif r["kind"] == "grad_sync":
            node = f'{r["name"]}_sync'
            lines.append(
                f'  "{node}" [shape=diamond, label="sync {r["name"]}\\n'
                f'[{r["start"] * 1e3:.3f}, {r["finish"] * 1e3:.3f}] ms"];')
            lines.append(f'  "{r["name"]}" -> "{node}" [style=dashed];')
    for r in rows:
        if r["kind"] == "comm":
            lines.append(
                f'  "{r["src"]}" -> "{r["dst"]}" [color=red, '
                f'label="[{r["start"] * 1e3:.3f}, '
                f'{r["finish"] * 1e3:.3f}] ms"];')
    comm_edges = {(r["src"], r["dst"]) for r in rows if r["kind"] == "comm"}
    for op in prob.ops:
        for t in op.inputs:
            if t.owner_op is not None and t.owner_op.name in prob.op_index:
                if (t.owner_op.name, op.name) not in comm_edges:
                    lines.append(f'  "{t.owner_op.name}" -> "{op.name}";')
    lines.append("}")
    with open(filename, "w") as f:
        f.write("\n".join(lines))
    return total, filename
