"""Every device op has a name: the scope table of a compiled program
(runtime/profiler.py scope_table), the registry of live programs
(register_program / program_scopes), the phases the ops declare, the
`program` count on the dispatching spans and the warning of a held tick.

On the CPU at tiny sizes: the COMPILED text differs from the chip's (other
fusions, no Mosaic calls outside interpret mode), the scopes it carries do
not, and the scopes are what is held here.
"""

import collections
import gc
import logging
import re
import weakref

import jax
import numpy as np
import pytest

import flexflow_tpu as fft
import hlo_text
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.deepseek_v32 import deepseek_v32_lm
from flexflow_tpu.models.kanana2 import kanana2_lm
from flexflow_tpu.models.llama import llama_lm
from flexflow_tpu.runtime import faultinject, profiler, serving, telemetry

VOCAB = 89


def llama_engine(layers=2, **kw):
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    model = FFModel(cfg)
    _, logits = llama_lm(model, 2, seq_len=16, hidden=64, layers=layers,
                         heads=4, kv_heads=2, vocab_size=VOCAB)
    model.compile(final_tensor=logits)
    return model.make_serving_engine(serve_slots=3, kv_page_size=4,
                                     max_seq_len=64, **kw)


def prompts(seed, lengths, vocab=VOCAB):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, vocab, (n,)).astype(np.int32) for n in lengths]


NO_DEVICE_OP = ("constant", "broadcast", "bitcast", "get-tuple-element",
                "parameter", "tuple", "iota")


def traced(text):
    """(name, op_name) of the instructions that can be device ops: outside
    every fused or applied computation (a fusion is ONE op), traced by jax
    (a whole `jit(..)` path) and doing work (not a constant or a bitcast)."""
    inner = set(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", text))
    out = []
    for block in profiler._BLOCK.split(text):
        head = profiler._COMPUTATION.match(block)
        if head is None or head.group(1) in inner:
            continue
        for name, path, body in profiler._instructions(block):
            opcode = re.match(r"(?:\([^)]*\)|\S+) ([\w\-]+)\(", body)
            if path and path.startswith("jit(") and opcode \
                    and opcode.group(1) not in NO_DEVICE_OP:
                out.append((name, path))
    return out


# ---- the table -------------------------------------------------------------

HLO = '''HloModule jit_decode, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(decode)/jit(main)/while/body/attn_3/gather/jit(_take)/gather" stack_frame_id=4}
}

%body.2 (st: (s32[], f32[8])) -> (s32[], f32[8]) {
  %st = (s32[], /*index=1*/f32[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%st), index=0
  %gte.1 = f32[8]{0} get-tuple-element(%st), index=1
  %gte.2 = f32[8]{0} get-tuple-element(%st), index=2
  %mul.3 = f32[8]{0} multiply(%gte.1, %gte.1), metadata={op_name="jit(decode)/jit(main)/while/body/ffn_up_1/mul"}
  %add.4 = f32[8]{0} add(%gte.2, %gte.2), metadata={op_name="jit(decode)/jit(main)/while/body/attn_3/out/add"}
  %copy.7 = f32[8]{0} copy(%gte.1)
  ROOT %tuple.6 = (s32[], f32[8]{0}, f32[8]{0}) tuple(%gte.0, %mul.3, %copy.7)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %c0 = s32[] constant(0)
  %copy.9 = f32[8]{0} copy(%p)
  %tuple.5 = (s32[], /*index=1*/f32[8]{0}) tuple(%c0, %copy.9)
  %while.1 = (s32[], /*index=1*/f32[8]{0}) while(%tuple.5), condition=%cond.3, body=%body.2
  %rw.1 = f32[8]{0} reduce-window(%sort.12, %c0), window={size=8}, to_apply=%fused_computation.1
  %copy.5 = f32[8]{0} copy(%rw.1)
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.8 = f32[8]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(decode)/jit(main)/attn_3/select/while/body/add"}
  %attn_3.11 = f32[8]{0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/jit(main)/attn_3/pallas_call"}
  %dsa_index_scores.5 = f32[8]{0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/jit(main)/attn_3/index/dsa_index_scores/pallas_call"}
  %gather.2 = f32[8]{0} gather(%p), metadata={op_name="jit(decode)/jit(main)/attn_3/gather"}
  %dot.4 = f32[8]{0} dot(%p, %p), metadata={op_name="jit(step)/jit(main)/transpose(jvp(attn_3))/project/dot_general"}
  %sort.12 = f32[8]{0} sort(%p), metadata={op_name="jit(decode)/jit(main)/while/body/sampler/sort"}
  %sub.1 = f32[8]{0} subtract(%p, %p), metadata={op_name="jit(step)/jit(main)/optimizer/attn_3/sub"}
  %copy.3 = f32[8]{0} copy(%sub.1)
  %copy.1 = f32[8]{0} copy(%p), metadata={op_name="jit(decode)/jit(main)/while/body/dynamic_update_slice"}
  ROOT %copy.2 = f32[8]{0} copy(%p)
}
'''


def test_scope_table_books_each_instruction_to_its_op_and_phase():
    table = profiler.scope_table(HLO, {"attn_3": "core", "ffn_up_1": ""})
    assert table == {
        # a fusion without metadata is its ROOT instruction's
        "add.1": ("attn_3", "gather"), "fusion.7": ("attn_3", "gather"),
        "fusion.8": ("attn_3", "select"),
        # a Mosaic call directly under the op: the op's kernel_phase
        "attn_3.11": ("attn_3", "core"),
        "dsa_index_scores.5": ("attn_3", "index"),
        # the path's last part is the primitive, never a phase
        "gather.2": ("attn_3", ""),
        # forward and transpose(jvp(..)) alike
        "dot.4": ("attn_3", "project"),
        "sort.12": ("sampler", ""),
        # the OUTERMOST word decides: a parameter's name under the optimizer
        "sub.1": ("optimizer", ""),
        "mul.3": ("ffn_up_1", ""),
        # what XLA made without a scope: computed FROM the sort (a chain)...
        "rw.1": ("sampler", ""), "copy.5": ("sampler", ""),
        # ... also FROM an operand that stands EARLIER in the text
        "copy.3": ("optimizer", ""),
        # ... or FOR the loop body's reader of that position of its state,
        # be it fed in front of the loop or by the turn before (a prefetch
        # of the next turn's weight)
        "copy.9": ("ffn_up_1", ""),
        "add.4": ("attn_3", "out"), "copy.7": ("attn_3", "out")}
    # a source is no link (%p feeds every op here), nor is control flow
    assert not {"p", "c0", "gte.1", "tuple.5", "while.1"} & set(table)
    # names alone (no kernel phases) work too; nothing else is in the table
    assert profiler.scope_table(HLO, ["attn_3"])["attn_3.11"] \
        == ("attn_3", "")
    assert "copy.1" not in table and "copy.2" not in table


def test_phases_are_declared_once():
    assert set(profiler.PHASES) >= {"project", "index", "select", "gather",
                                    "core", "out", "route", "experts",
                                    "shared"}
    assert profiler.OUTSIDE_OPS == ("sampler", "loss", "optimizer",
                                    "grad_sync")


# ---- (a) the registry, on a tiny llama engine -------------------------------

def test_every_engine_program_is_registered_once_and_lowered_only_when_asked(
        monkeypatch):
    before = {id(p) for p in profiler.live_programs()}
    lowered = []
    real = profiler.Program.text
    monkeypatch.setattr(profiler.Program, "text",
                        lambda self: lowered.append(self.name) or real(self))
    eng = llama_engine()
    eng.run(prompts(0, (5, 9, 3)), max_new_tokens=6, temperature=0.7)
    mine = [p for p in profiler.live_programs() if id(p) not in before]
    assert sorted(p.name for p in mine) == sorted(
        serving.program_name(k) for k in eng._programs)
    assert {p.name for p in mine} == {"prefill_b8", "prefill_b16",
                                      "decode_k8"}
    # an untraced run pays a dict insert a compile: nothing was lowered
    assert lowered == []
    tables = profiler.program_scopes({"decode_k8"})
    assert lowered == ["decode_k8"] and set(tables) == {"decode_k8"}
    # abstract arguments only: no buffer of the donated pool is held
    leaves = jax.tree_util.tree_leaves(mine[0].args)
    assert leaves and not any(isinstance(x, jax.Array) for x in leaves)
    # the registry holds no engine alive
    ref = weakref.ref(eng)
    del eng, mine, tables
    gc.collect()
    assert ref() is None
    assert {id(p) for p in profiler.live_programs()} <= before


def test_decode_table_books_the_sort_to_the_sampler_and_leaves_little_out():
    eng = llama_engine()
    eng.run(prompts(1, (5, 9)), max_new_tokens=6, temperature=0.7)
    prog = eng._registered[("decode", eng.decode_chunk)]
    assert prog.name == "decode_k8" and prog.module == "jit_decode"
    text = prog.text()
    table = profiler.scope_table(text, prog.graph_ops)
    rows = traced(text)
    sorts = [n for n, p in rows if p.endswith("/sort")]
    assert sorts and all(table[n] == ("sampler", "") for n in sorts)
    dots = [n for n, p in rows if "/attn_0/" in p and "dot_general" in p]
    phases = collections.Counter(table[n][1] for n in dots)
    assert phases["project"] >= 3 and phases["out"] >= 1   # wq wk wv | wo
    assert set(table[n][1] for n in dots) <= {"project", "core", "out"}
    # what is in no table is the decode scan's own arithmetic (its counter,
    # each step's write and rotary positions, the stacking of its outputs),
    # under no named scope at all ...
    missing = [p for n, p in rows if n not in table]
    bare = {"decode", "main", "while", "body", "cond", "closed_call", "jit"}
    assert all(set(profiler._WORD.findall(p.rsplit("/", 1)[0])) <= bare
               for p in missing), missing
    # ... and as much of it whatever the depth: 8 of this 2-layer program's
    # 133 instructions, under 3 % of a program of 24 layers (chat-steady's)
    shallow = llama_engine(layers=1)
    shallow.run(prompts(1, (5, 9)), max_new_tokens=6, temperature=0.7)
    prog1 = shallow._registered[("decode", shallow.decode_chunk)]
    rows1 = traced(prog1.text())
    table1 = profiler.scope_table(prog1.text(), prog1.graph_ops)
    assert len([n for n, _ in rows1 if n not in table1]) == len(missing)
    a_layer = len(rows) - len(rows1)
    assert a_layer > 0
    assert len(missing) < 0.03 * (len(rows1) + 23 * a_layer)


def test_the_samplers_gate_and_both_its_branches_are_booked_to_the_sampler():
    """The conditional around the warp (ops/sampling.py) and every device
    op of its two branch computations, with the loops a draw runs on the
    CPU, read `sampler`: the gate moves seconds inside that row and none
    into the unscoped share."""
    eng = llama_engine()
    eng.run(prompts(1, (5, 9)), max_new_tokens=6)
    prog = eng._registered[("decode", eng.decode_chunk)]
    text = prog.text()
    table = profiler.scope_table(text, prog.graph_ops)
    rows, calls = hlo_text.computations(text)
    gates = [(name, body) for ins in rows.values() for name, _, body in ins
             if hlo_text.opcode(body) == "conditional"]
    assert len(gates) == 1
    assert table[gates[0][0]] == ("sampler", "")
    branches = hlo_text.branches_of(gates[0][1])
    assert len(branches) == 2
    # a loop's body and condition (and the call the CPU wraps a small loop
    # in) hold device ops of their own; what a fusion or a reduction
    # applies is part of that ONE op
    seen = hlo_text.reach(calls, branches, through=hlo_text.CONTROL_FLOW)
    ops, copies = collections.Counter(), 0
    bodies = {name: body for ins in rows.values() for name, _, body in ins}
    for comp in seen:
        for name, _, body in rows[comp]:
            opcode = hlo_text.opcode(body)
            # (but for the copies of a constant the CPU makes into the state
            # of a loop it wraps in a call, a counter's zero: a source is
            # no link. The copy of a FUSION into that state, which stands
            # after its operand in the text, is booked FROM it.)
            if opcode == "copy" and name not in table:
                source, = profiler._operands(body)
                assert hlo_text.opcode(bodies[source]) == "constant", name
                continue
            copies += opcode == "copy"
            if opcode not in NO_DEVICE_OP:
                assert table.get(name, ("", ""))[0] == "sampler", (comp, name)
                ops[opcode] += 1
    assert ops["sort"] == 1 and len(seen) > 2      # the draw's loops too
    assert copies       # a scope-less copy in the branches, and booked
    # outside the gate and still the sampler's: the finite check
    finite = [n for n, p in traced(text) if p.endswith("/is_finite")]
    assert finite and all(table[n] == ("sampler", "") for n in finite)


# ---- (b) the sparse latent attention's phases -------------------------------

def dsa_model():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=3)
    ff = FFModel(cfg)
    _, logits = deepseek_v32_lm(
        ff, 2, seq_len=96, hidden=64, layers=2, heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
        v_head_dim=32, index_n_heads=4, index_head_dim=32, index_topk=16,
        dense_layers=1, ffn_hidden=128, num_experts=16, experts_per_token=4,
        expert_hidden=32, n_group=4, topk_group=2, experts_held=(4, 4),
        score_bias_std=0.05, vocab_size=128)
    ff.compile(final_tensor=logits)
    return ff


def test_latent_decode_program_holds_every_phase_and_the_row_gather():
    eng = dsa_model().make_serving_engine(
        serve_slots=2, kv_page_size=8, max_seq_len=96, decode_chunk=4,
        prefix_cache=False, paged_attention_impl="pallas")
    eng.run(prompts(5, (40, 23), 128), max_new_tokens=4)
    prog = eng._registered[("decode", 4)]
    text = prog.text()
    table = profiler.scope_table(text, prog.graph_ops)
    attn = {v[1] for v in table.values() if v[0].startswith("attn_")}
    assert attn >= {"project", "index", "select", "gather", "core", "out"}
    moe = {v[1] for v in table.values() if v[0].startswith("moe_")}
    assert moe >= {"route", "experts", "shared"}
    # the take of (slots x index_topk, lat_width) rows of the pool
    op = eng.gen.attn_ops[0]
    shape = f"[{eng.slots},{op.index_topk},{op.lat_width}]"
    takes = [n for n, p, body in profiler._instructions(text)
             if p and p.endswith("/gather") and body.startswith("f32" + shape)]
    assert takes and all(table[n][1] == "gather" for n in takes)
    # the program's prefill programs were registered too, and none lowered
    assert {p.name for p in eng._registered.values()} \
        == {"prefill_b32", "prefill_b64", "decode_k4"}


# ---- (c) a train step --------------------------------------------------------

def test_train_step_table_names_loss_and_optimizer_and_both_directions():
    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1}, seed=3)
    ff = FFModel(cfg)
    tokens, logits = kanana2_lm(
        ff, 2, seq_len=32, hidden=64, layers=2, heads=4, kv_lora_rank=32,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        dense_layers=1, ffn_hidden=96, num_experts=16, experts_per_token=4,
        expert_hidden=24, shared_experts=2, experts_held=(4, 4),
        score_bias_std=0.1, vocab_size=128)
    ff.compile(fft.AdamOptimizer(alpha=1e-3),
               fft.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [fft.MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    toks = np.random.default_rng(0).integers(0, 128, (4, 33), dtype=np.int32)
    fft.SingleDataLoader(ff, tokens, toks[:, :-1])
    fft.SingleDataLoader(ff, ff.label_tensor, toks[:, 1:, None])
    assert ff._registered == {}         # registered at its first call
    ff.fit(epochs=1, verbose=False)
    prog = ff._registered["train_step"]
    assert prog in profiler.live_programs() and prog.module == "jit_step"
    text = prog.text()
    table = profiler.program_scopes({"train_step"})["train_step"]
    ops = {v[0] for v in table.values()}
    assert {"loss", "optimizer", "attn_1", "moe_1", "lm_head"} <= ops
    rows = dict(traced(text))
    fwd = [n for n, p in rows.items() if "/jvp(attn_1)/" in p]
    bwd = [n for n, p in rows.items() if "transpose(jvp(attn_1))" in p]
    assert fwd and bwd
    assert {table[n][0] for n in fwd + bwd} == {"attn_1"}
    assert {table[n][1] for n in bwd} >= {"project", "core", "out"}
    missing = [p for n, p in rows.items() if n not in table]
    assert len(missing) < 0.02 * len(rows), missing
    # once the step has stopped compiling (its second call may compile
    # again: the mesh test below) a fit() registers nothing again
    ff.fit(epochs=1, verbose=False)
    prog = ff._registered["train_step"]
    assert prog.compiles == profiler.executables(ff._train_step)
    ff.fit(epochs=1, verbose=False)
    assert ff._registered["train_step"] is prog


def test_a_step_on_a_mesh_lowers_again_beside_an_uncommitted_argument():
    """Parameters live on a data x model mesh, the optimizer's step count
    and the step's key on the default device, uncommitted: the registry
    keeps a sharding only where the array was committed to one, or the
    second lowering refuses the devices the first took."""
    cfg = FFConfig(batch_size=4, mesh_shape={"data": 2, "model": 2}, seed=3)
    ff = FFModel(cfg)
    tokens, logits = llama_lm(ff, 4, seq_len=16, hidden=32, layers=1,
                              heads=4, kv_heads=2, vocab_size=VOCAB)
    ff.compile(fft.AdamOptimizer(alpha=1e-3),
               fft.LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [fft.MetricsType.METRICS_SPARSE_CATEGORICAL_CROSSENTROPY],
               final_tensor=logits)
    toks = np.random.default_rng(0).integers(0, VOCAB, (4, 17),
                                             dtype=np.int32)
    fft.SingleDataLoader(ff, tokens, toks[:, :-1])
    fft.SingleDataLoader(ff, ff.label_tensor, toks[:, 1:, None])
    for _ in range(3):                  # one step an epoch
        ff.fit(epochs=1, verbose=False)
    prog = ff._registered["train_step"]
    # the second call's arguments carry the shardings the first call's
    # outputs took and compile again: the program noted is the one that
    # runs NOW, or a device trace's instruction names meet another table
    assert prog.compiles == profiler.executables(ff._train_step)
    state = (ff.params, ff.opt_state, ff.bn_state)
    for noted, live in zip(jax.tree_util.tree_leaves(prog.args[:3]),
                           jax.tree_util.tree_leaves(state)):
        assert noted.sharding is None or noted.sharding == live.sharding
    args = jax.tree_util.tree_leaves(prog.args)
    assert any(a.sharding is None for a in args)
    assert any(a.sharding is not None and len(a.sharding.device_set) == 4
               for a in args)
    table = profiler.program_scopes({"train_step"})["train_step"]
    assert {"loss", "optimizer", "attn_0"} <= {v[0] for v in table.values()}


# ---- (d) the spans say which program they ran --------------------------------

def test_dispatching_spans_carry_the_registrys_name():
    telemetry.reset()
    eng = llama_engine()
    eng.run(prompts(2, (5, 12)), max_new_tokens=5)
    names = {p.name for p in eng._registered.values()}
    tr = telemetry.tracer()
    for span in ("decode_dispatch", "prefill", "compile"):
        got = {e["args"]["program"] for e in tr.events(name=span)}
        assert got and got <= names, (span, got)
    assert {e["args"]["program"] for e in tr.events(name="decode_dispatch")} \
        == {"decode_k8"}
    assert serving.program_name(("prefill_hit", 128, 255)) \
        == "prefill_hit_b128_m255"
    assert serving.program_name(("prefill", 2048, 16, 0)) == "prefill_b2048"
    assert serving.program_name(("page_import",)) == "page_import"


def test_interleaved_prefill_chunks_say_their_program():
    telemetry.reset()
    eng = llama_engine(prefill_chunk=8, prefill_interleave_chunks=1)
    eng.run(prompts(4, (14,)), max_new_tokens=3)
    got = {e["args"]["program"]
           for e in telemetry.tracer().events(name="prefill_chunk")}
    assert got == {"prefill_ichunk_b16_s0", "prefill_ichunk_b16_s8"}
    assert got <= {p.name for p in eng._registered.values()}


# ---- a held tick explains itself ----------------------------------------------

def test_a_held_tick_logs_one_warning_built_from_its_spans(monkeypatch, caplog):
    telemetry.reset()
    eng = llama_engine()
    warm = prompts(3, (5, 9))
    eng.run(warm, max_new_tokens=4)             # every program compiled
    monkeypatch.setenv("FF_FAULT", "slow(1200)@serve:1")
    faultinject.reset()
    from flexflow_tpu.logger import fflogger

    fflogger.addHandler(caplog.handler)     # it does not propagate to root
    try:
        with caplog.at_level(logging.WARNING, logger="flexflow_tpu"):
            eng.run(prompts(7, (6,)), max_new_tokens=4)    # no prefix hit
    finally:
        fflogger.removeHandler(caplog.handler)
        monkeypatch.delenv("FF_FAULT")
        faultinject.reset()
    held = [r.getMessage() for r in caplog.records
            if "serving: tick" in r.getMessage()]
    # ONE warning: the quick ticks that followed said nothing (and a tick
    # that compiles has its compile line instead)
    assert len(held) == 1, held
    msg = held[0]
    # the tick's own spans with their seconds and counts, `program` included:
    # the admission that slept is host work, no fetch waited for the chip
    assert "admit 1.2" in msg and "engine_step 1.2" in msg
    assert "'program': 'prefill_b8'" in msg and "prefill_fetch 0.0" in msg


# ---- a traced slice keeps its programs until its tables are read ----------------

def test_programs_dispatched_under_a_trace_outlive_their_engine(tmp_path):
    """A benchmark's generator returns, its engine goes, and only then are
    the tables asked for: what ran under the profiler trace is kept until
    the next trace (or forget_traced)."""
    eng = llama_engine()
    eng.run(prompts(6, (5,)), max_new_tokens=3)        # compiled, untraced
    assert not profiler.tracing()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "t"), profiler_options=opts)
    try:
        assert profiler.tracing()
        eng.run(prompts(8, (6,)), max_new_tokens=3)    # prefill_b8, decode_k8
    finally:
        jax.profiler.stop_trace()
    ref = weakref.ref(eng)
    del eng
    gc.collect()
    assert ref() is not None
    kept = {p.name for p in profiler.live_programs() if p.fn is not None
            and any(p is q for q in profiler._TRACED.values())}
    assert kept == {"prefill_b8", "decode_k8"}
    assert set(profiler.program_scopes({"decode_k8"})) == {"decode_k8"}
    profiler.forget_traced()
    gc.collect()
    assert ref() is None
