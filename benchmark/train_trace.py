"""A traced training run's device time by what the step spends it on: the
flash kernels by name, and every op of the step by the graph op it was traced
under.

Which device op is which (TPU v5e, jax 0.9.0): a `pallas_call` given `name=`
keeps it inside its HLO instruction's name, behind the transforms and scopes
it was traced under, so the three flash kernels read as
`..flash_attention_fwd..`, `..flash_attention_bwd_dq..` and
`..flash_attention_bwd_dkv..` whatever layer and pass they run in
(`kernel_of`). Every other op is a fusion, a convolution or a copy whose event
carries its HLO instruction's name (`fusion.412`) and no jax scope. The scope
is in the COMPILED step's text instead: `metadata={op_name="jit(step)/..
/transpose(jvp(attn_2))/.."}` on every instruction. `scopes_of` reads that
text into {instruction name: "attn" | "moe" | "lm_head" | ..} (the graph op's
name without its layer index; forward and transposes alike), and the generator of a traced
run hands it over in `ctx["step_scopes"]` (`step_text`: the step the program
ran, lowered again on its own arguments after the window and fetched from the
compile cache). A fusion that XLA formed across two graph ops is booked to the
op of its root instruction.

`reduce_train` works on `span_reduce.load`'s structure. A trace without a
flash kernel and a `ctx` without scopes give None for what they would have
fed: the readers then leave their metrics out.

By hand, after a traced run: python3 benchmark/train_trace.py .bench_trace/<cell>
"""

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import moe_trace as mt, span_reduce as sr  # noqa: E402
from benchmark import trace_reduce as tr  # noqa: E402

# kind -> what the instruction's name holds; the longer names first
FLASH = {"bwd_dq": "flash_attention_bwd_dq",
         "bwd_dkv": "flash_attention_bwd_dkv",
         "fwd": "flash_attention_fwd"}
WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                         r'metadata=\{[^}]*op_name="([^"]*)"', re.M)


def head_of(name):
    """`%fusion.3 = bf16[..] fusion(..)` -> `fusion.3`."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def kernel_of(name):
    """'fwd' | 'bwd_dq' | 'bwd_dkv' | None for a device op's name."""
    head = head_of(name)
    for kind, needle in FLASH.items():
        if needle in head:
            return kind
    return None


def scope_of(op_name, graph_ops):
    """The graph op an HLO `op_name` lies under, without its layer index:
    `jit(step)/jit(main)/transpose(jvp(attn_2))/dot_general` -> `attn`,
    `../jvp(lm_head)/..` -> `lm_head`. The outermost word that names a graph
    op (`graph_ops`) is the executor's scope (`named_scope(op.name)` around
    the whole op); None outside every op (the loss, the optimizer)."""
    for word in WORD.findall(op_name):
        if word in graph_ops:
            return re.sub(r"_\d+$", "", word)
    return None


def scopes_of(hlo_text, graph_ops):
    """{instruction name: scope} for every instruction of a compiled
    program's text that lies under one of the graph's ops."""
    graph_ops = frozenset(graph_ops)
    out = {}
    for head, op_name in INSTRUCTION.findall(hlo_text):
        scope = scope_of(op_name, graph_ops)
        if scope:
            out[head] = scope
    return out


def step_text(ff, batch):
    """The compiled text of the train step `ff` runs, lowered on its own
    arguments (nothing runs; the executable comes from the compile cache)."""
    import jax

    sharded = ff.executor.shard_batch(batch)
    key = jax.random.split(ff._rng)[1]
    return ff._train_step.lower(ff.params, ff.opt_state, ff.bn_state,
                                sharded, key).compile().as_text()


def reduce_train(planes, scopes=None):
    """  window_s, busy_s
      flash     {kind: {"calls", "seconds"}} over the kernel's events that
                began inside the window, each counted whole (None without
                such an event)
      scope_s   {scope: own seconds inside the window} (None without
                `scopes`); `known_s` is the own time of ops whose
                instruction the step's text holds at all"""
    ops, busy, _ = sr._device(planes)
    t0, t1 = sr._window(planes, ops)
    out = {"window_s": (t1 - t0) / 1e9,
           "busy_s": sum(min(e, t1) - max(s, t0) for s, e in busy
                         if e > t0 and s < t1) / 1e9,
           "flash": None, "scope_s": None, "known_s": None}
    flash = {k: {"calls": 0, "seconds": 0.0} for k in FLASH}
    for name, s, d in ops:
        kind = kernel_of(name)
        if kind and t0 <= s < t1:
            flash[kind]["calls"] += 1
            flash[kind]["seconds"] += d / 1e9
    if any(v["calls"] for v in flash.values()):
        out["flash"] = flash
    if scopes:
        own = mt._own_inside(ops, [(t0, t1)])
        by = {}
        for name, sec in own.items():
            scope = scopes.get(head_of(name))
            if scope:
                by[scope] = by.get(scope, 0.0) + sec
        out["scope_s"] = by
        out["known_s"] = sum(by.values())
    return out


def table(red):
    rows = [f"window {red['window_s']:.3f} s, busy {red['busy_s']:.3f} s"]
    if red["flash"]:
        rows.append("flash kernels (calls, seconds): " + ", ".join(
            f"{k} {v['calls']} {v['seconds']:.4f}"
            for k, v in sorted(red["flash"].items())))
    if red["scope_s"] is not None:
        rows.append("own seconds by graph op: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                red["scope_s"].items(), key=lambda kv: -kv[1])))
    return rows


def for_ctx(ctx):
    """The reduction of THIS run's trace, made once per run (kept in `ctx`)
    and printed; None where the run was not traced on a device or the newest
    trace on disk is not this run's."""
    trace = ctx.get("trace")
    if not trace:
        return None
    if "train_trace" not in ctx:
        path = sr.newest_xplane()
        red = reduce_train(sr.load(path), ctx.get("step_scopes")) \
            if path else None
        if red and abs(red["window_s"] - trace["window_s"]) > 1e-6:
            red = None
        for row in table(red) if red else ["no reduction of this run"]:
            print(f"[train_trace] {row}", flush=True)
        ctx["train_trace"] = red
    return ctx["train_trace"]


def scope_share(ctx, scope):
    """Percent of the traced slice's busy time in ops under the graph ops
    called `scope` (`attn`, `moe`, ..); None where there is nothing to
    read."""
    red = for_ctx(ctx)
    if not red or not red["busy_s"] or not (red["scope_s"] or {}).get(scope):
        return None
    return 100.0 * red["scope_s"][scope] / red["busy_s"]


if __name__ == "__main__":
    for row in table(reduce_train(sr.load(tr.find_xplane(sys.argv[1])))):
        print(row)
