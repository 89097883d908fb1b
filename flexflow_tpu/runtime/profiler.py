"""Profiling / tracing.

Reference observability (SURVEY §5.1): per-op cudaEvent timing behind
--profiling (linear.cu:526-553), simulator DOT export (--taskgraph), Legion
-lg:prof logs. TPU equivalents:

  * IN-SITU attribution: the executors trace every op under
    jax.named_scope(op.name), so each instruction of the PRODUCTION jitted
    program carries the op name in its HLO metadata — a
    jax.profiler.start_trace() trace attributes device ops back to graph
    ops (the host's ff.* spans of runtime/telemetry.py lie in the same
    trace). scope_table reads a compiled program's text into
    {instruction: (graph op, phase)}; every program a serving engine or a
    model runs is registered (register_program) and program_scopes lowers
    the ones it is asked for, after the fact; in_situ_op_summary counts
    the train step's instructions by op
  * export_taskgraph: the op graph + strategy as Graphviz DOT (the
    simulator's DotFile analog, simulator.h:78-131)
"""

from __future__ import annotations

import itertools
import re
import weakref
from typing import Dict, List, Optional, Tuple

import jax
from jax._src import profiler as _jax_profiler  # the session of start_trace


# ---- the scope table ------------------------------------------------------
#
# Every executor traces a graph op under jax.named_scope(op.name), the ops
# trace their phases under one of PHASES, and the code outside every graph
# op lies under one of OUTSIDE_OPS. A scope is trace-time only: it reaches
# the metadata of the compiled program's instructions
# (op_name="jit(decode)/jit(main)/while/body/attn_3/gather/gather") and costs
# nothing when the program runs. A device trace names an event after its HLO
# instruction, so {instruction: (op, phase)} is what books device seconds to
# the graph (benchmark/scope_reduce.py).

PHASES = ("project", "index", "select", "gather", "core", "out",
          "route", "experts", "shared", "latent",
          "conv", "scan", "update", "gate_norm", "seat")
OUTSIDE_OPS = ("sampler", "loss", "optimizer", "grad_sync")

_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", re.M)
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_OPERAND = re.compile(r"(?<![=\w])%([\w.\-]+)")
_NO_CHAIN = re.compile(
    r"^(?:\(.*?\)|\S+) (?:while|conditional|call|parameter|constant|"
    r"get-tuple-element|tuple|iota|broadcast)\(")
_BLOCK = re.compile(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \(.*\{\s*\n)")
_TUPLE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* tuple\((.*?)\)"
                    r"(?:, |$)", re.M)
_ROOT_TUPLE = re.compile(r"^\s*ROOT %?([\w.\-]+) = .* tuple\(", re.M)
_WHILE = re.compile(r" while\(%?([\w.\-]+)\), condition=%?[\w.\-]+, "
                    r"body=%?([\w.\-]+)")
_ELEMENT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* get-tuple-element\("
                      r"%?[\w.\-]+\), index=(\d+)", re.M)
_INHERIT_PASSES = 16    # chains of scope-less instructions are short


def scope_of(op_name: str, graph_ops) -> Optional[Tuple[str, str]]:
    """(op, phase) of one HLO ``op_name``: ``op`` the outermost word that
    names a graph op or is one of OUTSIDE_OPS, ``phase`` the first scope
    under it that is one of PHASES (else ""); None outside all of them.
    ``transpose(jvp(attn_2))`` reads as ``attn_2``: forward and backward
    alike. The path's last part is the primitive (``gather`` is one), never
    a scope."""
    words = [w for part in op_name.split("/")[:-1]
             for w in _WORD.findall(part)]
    for i, w in enumerate(words):
        if w in graph_ops or w in OUTSIDE_OPS:
            return w, next((v for v in words[i + 1:] if v in PHASES), "")
    return None


def _instructions(hlo_text: str):
    """[(instruction name, op_name or None, the rest of its line)] of a
    compiled program's text. A fusion carries its root's metadata; one
    without any gets the ROOT instruction's of the computation it calls."""
    heads = [(m.start(), m.group(1)) for m in _COMPUTATION.finditer(hlo_text)]
    rows, roots, at = [], {}, 0
    for m in _INSTRUCTION.finditer(hlo_text):
        while at + 1 < len(heads) and heads[at + 1][0] < m.start():
            at += 1
        found = _OP_NAME.search(m.group(3))
        path = found.group(1) if found else None
        if m.group(1) and heads and heads[at][0] < m.start():
            roots[heads[at][1]] = path
        rows.append((m.group(2), path, m.group(3)))
    out = []
    for name, path, body in rows:
        if path is None:
            callee = _CALLS.search(body)
            path = roots.get(callee.group(1)) if callee else None
        out.append((name, path, body))
    return out


def _operands(body: str) -> List[str]:
    return _OPERAND.findall(body.split(", metadata=", 1)[0])


def _loop_links(hlo_text: str) -> Dict[str, List[str]]:
    """{instruction that feeds a ``while``'s state: the loop body's
    get-tuple-elements of that position}: a weight's layout copy hoisted
    in front of a decode loop reaches its consumer only through the
    loop's state, and so does what one turn of the body hands the next
    through its ROOT tuple (the slices that prefetch the next turn's
    first weight)."""
    blocks = {}
    for block in _BLOCK.split(hlo_text):
        head = _COMPUTATION.match(block)
        if head:
            blocks[head.group(1)] = block
    tuples = {m.group(1): _OPERAND.findall(m.group(2))
              for m in _TUPLE.finditer(hlo_text)}
    links: Dict[str, List[str]] = {}
    for state, body in _WHILE.findall(hlo_text):
        elements: Dict[int, List[str]] = {}
        for name, index in _ELEMENT.findall(blocks.get(body, "")):
            elements.setdefault(int(index), []).append(name)
        root = _ROOT_TUPLE.search(blocks.get(body, ""))
        for fed_by in (state, root.group(1) if root else None):
            for i, fed in enumerate(tuples.get(fed_by, ())):
                links.setdefault(fed, []).extend(elements.get(i, ()))
    return links


def scope_table(hlo_text: str, graph_ops) -> Dict[str, Tuple[str, str]]:
    """{instruction name: (op, phase)} for every instruction of a compiled
    program's text (``compile().as_text()``) that lies under a graph op or
    one of OUTSIDE_OPS; a fusion is booked to its root. ``graph_ops``: the
    graph's op names; a mapping {name: phase} also says where a Mosaic
    call directly under that op belongs (the device names such a call
    after its innermost scope, so the kernels the benchmark's readers
    select by ``attn_<i>`` / ``moe_<i>`` stay outside every phase
    scope).

    What XLA makes itself carries no jax scope (a layout copy of a weight,
    the asynchronous slices that prefetch one, the reduce-windows a cumsum
    is decomposed into): such an instruction is booked to what it is
    computed FROM (its first operand with a scope), else to what it is
    computed FOR (its first user with one), through chains of them. A
    source (a parameter, a constant, an element of the loop's state) is
    no link of such a chain: it would tie unrelated consumers together.
    Control flow (``while``, ``conditional``, ``call``) inherits nothing:
    its own time is the loop's."""
    kernel_phase = graph_ops if isinstance(graph_ops, dict) \
        else dict.fromkeys(graph_ops, "")
    rows = _instructions(hlo_text)
    out = {}
    for name, path, body in rows:
        scope = scope_of(path, kernel_phase) if path else None
        if scope is not None:
            op, phase = scope
            if not phase and _MOSAIC in body:
                phase = kernel_phase.get(op, "")
            out[name] = (op, phase)
    # the links of a chain: scope-less, and no source; a scoped instruction
    # stays reachable as an operand wherever it stands in the text
    names = {name for name, _, body in rows
             if name not in out and not _NO_CHAIN.search(body)}
    bare, users = [], {}
    for name, _, body in rows:
        if name in names:
            bare.append((name, [o for o in _operands(body)
                                if o in names or o in out]))
    for name, _, body in rows:          # who reads a scope-less instruction
        for o in _operands(body):
            if o not in out:
                users.setdefault(o, []).append(name)
    for fed, elements in _loop_links(hlo_text).items():
        if fed in names:    # what feeds a loop is computed FOR its body
            users.setdefault(fed, []).extend(
                u for g in elements for u in users.get(g, ()))
    for _ in range(_INHERIT_PASSES):
        settled = len(out)
        for find in (lambda name, operands: operands,
                     lambda name, operands: users.get(name, ())):
            for name, operands in bare:
                if name not in out:
                    scope = next((out[n] for n in find(name, operands)
                                  if n in out), None)
                    if scope is not None:
                        out[name] = scope
        if len(out) == settled:
            break
    return out


# ---- the registry of live programs -----------------------------------------

def executables(fn) -> int:
    """How many executables a jitted callable holds (its trace cache's
    size, as the retrace sentinel of runtime/locks.py reads it)."""
    return getattr(fn, "_cache_size", int)()


class Program:
    """One jitted program some owner runs: its short name, the jitted
    callable, the abstract arguments of the call it was noted at, its
    graph's op names and how many executables the callable held then
    (``compiles``: an owner whose arguments' shardings may drift notes the
    program again when that count has moved). The OWNER holds this record;
    the registry holds it weakly."""

    __slots__ = ("name", "fn", "args", "graph_ops", "compiles",
                 "__weakref__")

    def __init__(self, name, fn, args, graph_ops):
        self.name, self.fn, self.args = name, fn, args
        self.graph_ops = graph_ops
        self.compiles = executables(fn)

    @property
    def module(self) -> str:
        """What a device trace's per-program line calls it."""
        return "jit_" + getattr(self.fn, "__name__", "")

    def text(self) -> str:
        """The compiled program's text: lowered on the abstract arguments,
        the executable from the compile cache (nothing runs)."""
        return self.fn.lower(*self.args).compile().as_text()


_PROGRAMS: "weakref.WeakValueDictionary[int, Program]" = \
    weakref.WeakValueDictionary()
_next_program = itertools.count()


def _abstract(x):
    """Shape, dtype and sharding of an array argument (never its buffer:
    the call that follows may donate it); anything else as it is. Only a
    COMMITTED array's sharding is kept: a step counter on the default
    device beside parameters on a mesh lowers as it ran, wherever jit puts
    it."""
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    committed = isinstance(x, jax.Array) and x.committed
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if committed else None,
        weak_type=getattr(x, "weak_type", False))


def register_program(name: str, fn, args, graph_ops) -> Program:
    """Note that ``fn`` (a jitted callable) is about to run on ``args``
    under the name ``name``: one tree_map over the arguments and a dict
    insert, nothing lowered. The caller keeps the returned record for as
    long as the program lives."""
    prog = Program(name, fn, jax.tree_util.tree_map(_abstract, tuple(args)),
                   graph_ops)
    _PROGRAMS[next(_next_program)] = prog
    return prog


# The programs dispatched under the profiler trace that is running, or that
# ran last, held STRONGLY: a traced run asks for its tables after the window,
# when the engine or model that ran the slice may be gone (a benchmark's
# generator has returned). The next trace, or forget_traced(), lets them go.
_TRACED: Dict[int, Program] = {}
_traced_session = 0


def tracing() -> bool:
    """Whether a ``jax.profiler`` trace is being recorded right now."""
    return _jax_profiler._profile_state.profile_session is not None


def note_traced(prog: Program):
    """``prog`` is being dispatched while ``tracing()``: keep it (and so its
    owner) until the next trace begins."""
    global _traced_session
    session = id(_jax_profiler._profile_state.profile_session)
    if session != _traced_session:
        _TRACED.clear()
        _traced_session = session
    _TRACED[id(prog)] = prog


def forget_traced():
    """Let go of the last traced slice's programs."""
    _TRACED.clear()


def live_programs() -> List[Program]:
    """The registered programs whose owners are alive (or that the last
    traced slice ran), oldest first."""
    return [p for _, p in sorted(_PROGRAMS.items())]


def program_scopes(names=None) -> Dict[str, Dict[str, Tuple[str, str]]]:
    """{program name: scope_table of its compiled text} for the live
    programs called ``names`` (all of them with None). THIS is where a
    registered program is lowered, and only those asked for; of two live
    programs of one name the younger is read."""
    asked = {p.name: p for p in live_programs()
             if names is None or p.name in names}
    return {name: scope_table(p.text(), p.graph_ops)
            for name, p in asked.items()}


def graph_op_phases(model) -> Dict[str, str]:
    """``scope_table``'s ``graph_ops`` for a model's graph: each op's name
    and the phase its own Mosaic kernels belong to (``Op.kernel_phase``)."""
    return {op.name: getattr(op, "kernel_phase", "") for op in model.ops}


def in_situ_op_summary(model, batch: Dict) -> List[dict]:
    """Per-op instruction counts of the PRODUCTION train-step program:
    [{op, fwd_instructions, bwd_instructions}], heaviest first, from the
    scopes of the step lowered on ``batch`` (``transpose(jvp(op))`` is the
    backward side). Device SECONDS by op and phase come from a profiler
    trace and ``program_scopes`` (benchmark/scope_reduce.py)."""
    txt = model._train_step.lower(
        model.params, model.opt_state, model.bn_state, batch,
        jax.random.PRNGKey(0)).compile().as_text()
    ops = {op.name for op in model.ops}
    rows: Dict[str, dict] = {}
    for _, path, _ in _instructions(txt):
        scope = scope_of(path, ops) if path else None
        if scope and scope[0] in ops:
            row = rows.setdefault(scope[0], {
                "op": scope[0], "fwd_instructions": 0, "bwd_instructions": 0})
            row["bwd_instructions" if "transpose(" in path
                else "fwd_instructions"] += 1
    return sorted(rows.values(), key=lambda r: -(
        r["fwd_instructions"] + r["bwd_instructions"]))


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
