"""Reading a compiled program's text (`compile().as_text()`) by computation:
what the tests of the sampler's gate ask of it. Instructions are read by
`runtime/profiler.py`'s own expressions."""

import re

from flexflow_tpu.runtime import profiler

_CALLEE = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPCODE = re.compile(r"(?:\(.*?\)|\S+) ([\w\-]+)\(")
CONTROL_FLOW = ("while", "conditional", "call")


def opcode(body):
    m = _OPCODE.match(body)
    return m.group(1) if m else None


def branches_of(body):
    """The branch computations a `conditional`'s line names, in order."""
    found = _BRANCHES.search(body)
    return [c.strip().lstrip("%") for c in found.group(1).split(",")] \
        if found else []


def computations(text):
    """({computation: [(instruction, op_name or None, rest of its line)]},
    {computation: [(opcode of the caller, callee)]})."""
    rows, calls = {}, {}
    for block in profiler._BLOCK.split(text):
        head = profiler._COMPUTATION.match(block)
        if head is None:
            continue
        rows[head.group(1)] = ins = profiler._instructions(
            block.split("\n", 1)[-1])
        calls[head.group(1)] = [
            (opcode(body), callee) for _, _, body in ins
            for callee in _CALLEE.findall(body) + branches_of(body)]
    return rows, calls


def reach(calls, roots, through=None):
    """`roots` and every computation called from them, through callers of
    the opcodes `through` only (all of them by default)."""
    seen, todo = set(), list(roots)
    while todo:
        comp = todo.pop()
        if comp not in seen:
            seen.add(comp)
            todo += [callee for op, callee in calls.get(comp, ())
                     if through is None or op in through]
    return seen
