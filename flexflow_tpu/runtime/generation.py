"""Autoregressive generation with a static-shape KV cache.

Reference parity + extension: the reference's inference story is
CompMode::COMP_MODE_INFERENCE (include/ffconst.h:1-130) — the training
graph run forward-only, re-attending the whole prefix at every step
(src/ops/attention.cu keeps full-sequence descriptors). This module is the
TPU-native modern path: ONE jitted program performs prefill + a
`lax.scan` decode loop over a fixed-shape KV cache, so every decode step
is the same compiled XLA program (no retracing, no dynamic shapes) and
the host dispatches once per generate() call, not once per token.

Design notes:
  * The graph is validated up front: only ops whose forward is
    per-position (dense/norm/elementwise/embedding/...) plus causal
    self-attention are allowed, so a graph that silently mixes positions
    (conv, pooling, LSTM, concat on seq, ...) is rejected with the op
    name instead of generating garbage.
  * The KV cache stores PRE-broadcast kv heads ((B, L, KVH, Dh)), so
    grouped-query attention shrinks cache HBM by heads/kv_heads — the
    reason GQA exists (models/llama.py).
  * Sampling: greedy (temperature=0), temperature, optional top-k.
    After `eos_id` is emitted a row keeps emitting `pad_id`.
  * Sharding: the decode program runs under the model's mesh via jit;
    params keep their training shardings (head-sharded TP decodes with
    per-shard caches by GSPMD propagation from the weight shardings).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.ops.base import InputOp
from flexflow_tpu.runtime.executor import resolve_tied_params
from flexflow_tpu.runtime.kv_pool import op_keeps

# ops whose forward treats every (batch, position) independently — safe to
# run on a (B, 1, ...) decode slice exactly as on the full sequence
_DECODE_SAFE = {
    OperatorType.OP_LINEAR,
    OperatorType.OP_EMBEDDING,
    OperatorType.OP_LAYERNORM,
    OperatorType.OP_RMSNORM,
    OperatorType.OP_DROPOUT,   # inference: identity
    OperatorType.OP_CAST,
    OperatorType.OP_SCALAR_MULTIPLY,
    OperatorType.OP_IDENTITY,
    OperatorType.OP_EXP,
    OperatorType.OP_SIN,
    OperatorType.OP_COS,
    OperatorType.OP_POW,
    OperatorType.OP_RSQRT,
    OperatorType.OP_RELU,
    OperatorType.OP_SIGMOID,
    OperatorType.OP_TANH,
    OperatorType.OP_ELU,
    OperatorType.OP_GELU,
    OperatorType.OP_EW_ADD,
    OperatorType.OP_EW_MUL,
    OperatorType.OP_EW_SUB,
    OperatorType.OP_EW_DIV,
    OperatorType.OP_EW_MAX,
    OperatorType.OP_EW_MIN,
    # MoE routes each token independently (router logits -> top-k expert
    # FFNs). A dropless op never drops; for a capacity-trained op the
    # walk sets the buffer to the slab's token count, which guarantees
    # ZERO drops (a token never picks the same expert twice): either
    # way the row independence that decode promises
    OperatorType.OP_MOE,
    OperatorType.OP_GATED_MLP,
}


class Generator:
    """Compiles generate() programs for a decoder-only LM built on FFModel.

    Build once per model (after compile()); each (prompt shape,
    max_new_tokens) pair jits its own program, cached on this object.
    """

    def __init__(self, model, temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 quantize: Optional[str] = None):
        if quantize not in (None, "int8", "fp8"):
            raise ValueError(f"quantize must be None, 'int8' or 'fp8', "
                             f"got {quantize!r}")
        if quantize == "fp8" and getattr(jnp, "float8_e4m3fn", None) is None:
            raise ValueError(
                "quantize='fp8' needs a jax build with jnp.float8_e4m3fn;"
                " this build lacks it — use 'int8'")
        self.model = model
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.quantize = quantize
        # int8 cache: see _quantized_params for the validity rule
        self._qparams = None
        self._qparams_key = None
        self._q_refs = None
        # per-generator weight override (rolling deploy hot-swap): when
        # set, decode programs read these params instead of model.params
        # — same tree structure/shapes/dtypes, so warm programs never
        # retrace. None = serve the shared model weights.
        self._params_override = None
        self._override_version = 0
        # compiled decode programs, LRU-bounded (FF_GEN_PROGRAM_CACHE,
        # default 8): a long-lived serving process sweeping
        # max_new_tokens/prompt shapes must not accumulate XLA programs
        # (and their device buffers) for the life of the model
        import collections

        self._jitted: Dict = collections.OrderedDict()

        if getattr(model.executor, "jits_per_group", False):
            raise NotImplementedError(
                "generate() is unsupported under an operator-placement "
                "strategy (params live on disjoint sub-meshes; one decode "
                "program cannot span them) — compile with a non-placement "
                "strategy for generation")
        input_ops = [op for op in model.ops if isinstance(op, InputOp)]
        tok_inputs = [op for op in input_ops
                      if op.outputs[0].dtype in (DataType.DT_INT32,
                                                 DataType.DT_INT64)]
        if len(input_ops) != 1 or not tok_inputs:
            kinds = ", ".join(
                f"{op.name}:{op.outputs[0].dtype.name}" for op in input_ops)
            raise ValueError(
                "generate() needs a decoder-only LM with exactly one "
                f"integer token input; this graph has [{kinds}]")
        self.token_input = tok_inputs[0]
        self.attn_ops = []
        # ops whose cache is ONE fixed-size recurrent state a sequence
        # (`state_cache_protocol`, ops/mamba.py), beside the per-token rows
        # of the attention ops
        self.state_ops = []
        for op in model.ops:
            if isinstance(op, InputOp):
                continue
            if getattr(op, "state_cache_protocol", False):
                self.state_ops.append(op)
            elif getattr(op, "kv_cache_protocol", False):
                if not op.causal:
                    raise ValueError(
                        f"{op.name}: generate() requires causal attention")
                if any(t is not op.inputs[0] for t in op.inputs):
                    raise ValueError(
                        f"{op.name}: generate() supports self-attention "
                        "only (q, k, v must be the same tensor)")
                self.attn_ops.append(op)
            elif op.op_type == OperatorType.OP_SOFTMAX:
                ax = op.axis % op.outputs[0].num_dims
                if ax != op.outputs[0].num_dims - 1:
                    raise ValueError(
                        f"{op.name}: softmax over a non-feature axis mixes "
                        "positions; not decodable")
            elif op.op_type not in _DECODE_SAFE:
                raise ValueError(
                    f"{op.name} ({op.op_type.name}) mixes sequence "
                    "positions or is unsupported in the KV-cache decode "
                    "path; generate() supports transformer decoder graphs")
        cached = self.attn_ops + self.state_ops
        if not cached:
            raise ValueError(
                "graph has neither an attention op (per-token K/V rows) nor "
                "a recurrent-state op: nothing to cache, nothing to decode "
                "from")
        # dropless MoE ops count their routing inside the serve programs
        self.dropless_moe_ops = [
            op for op in model.ops
            if op.op_type == OperatorType.OP_MOE and op.dropless]
        # the prefill TAIL: the ops that no attention (or recurrent-state)
        # op is downstream of. Each is per-position (validated above) and
        # feeds the logits alone, so a prefill runs it on the final position
        # only (lm_head included) and a non-final chunk not at all. By
        # dependency, not by place in `model.ops`: a branch that leaves the
        # residual stream before the last cached op and rejoins it after
        # (a shortcut-connected expert layer, models/longcat_flash.py) is
        # tail wherever the model file lists it. In a plain decoder these
        # are exactly the ops past the last cached one.
        feeds_cache = set()     # tensors some cached op reads, transitively
        for op in reversed(model.ops):
            if op in cached or any(t in feeds_cache for t in op.outputs):
                feeds_cache.update(op.inputs)
        self._tail_ops = {
            op for op in model.ops
            if not isinstance(op, InputOp) and op not in cached
            and not any(t in feeds_cache for t in op.outputs)}

    # ---- weight-only quantization (int8 / fp8) -----------------------------

    def _quantized_params(self):
        """Weight-only quantization, dtype-parameterized (``self.
        quantize`` = 'int8' or 'fp8'): every float weight with >= 2 dims
        stores as {"q": int8|float8_e4m3fn, "s": f32 per-OUTPUT-CHANNEL
        scale}; dequant happens per-use inside the jitted decode program
        (the narrow->compute convert fuses into the consuming matmul, so
        the weight read from HBM — the decode bottleneck — is the
        quantized bytes: half of bf16, a quarter of f32). Scales vary
        over every dim EXCEPT the leading (contraction-side) axis —
        finer than per-tensor on every weight and finer than the old
        per-last-dim scheme on 3-D attention weights (wq (in, H, Dh)
        gets an (H, Dh) scale grid instead of sharing one scale across
        heads); granularity is unconstrained for correctness because the
        weight is dequantized before the matmul consumes it. 1-D weights
        (norm scales, biases) stay exact. Lossy by design: logits shift
        slightly vs full precision — tests/test_quantized_serving.py
        pins per-channel strictly no worse than a per-tensor baseline on
        every zoo layer."""
        import weakref

        # validity = version (bumped by the params setter / set_weights)
        # AND leaf identity (catches raw in-place `ff.params[op][w] = x`
        # mutation) AND liveness of the recorded leaves (a dead weakref
        # means an id could have been recycled, so ids stop being
        # authoritative — rebuild)
        src = self._source_params()
        leaves = jax.tree_util.tree_leaves(src)
        try:
            refs = tuple(weakref.ref(w) for w in leaves)
        except TypeError:
            # non-weakref-able leaf: liveness is unverifiable, so ids are
            # never authoritative — disable caching rather than risk a
            # recycled-id stale hit
            refs = None
        key = (self.model._params_version, self._override_version,
               tuple(map(id, leaves)))
        if (self._qparams is not None and self._qparams_key == key
                and self._q_refs is not None
                and all(r() is not None for r in self._q_refs)):
            return self._qparams
        if self.quantize == "fp8":
            qdtype = jnp.float8_e4m3fn
            qmax = float(jnp.finfo(qdtype).max)
        else:
            qdtype, qmax = jnp.int8, 127.0
        out = {}
        for op_name, ws in src.items():
            q_ws = {}
            for w_name, w in ws.items():
                if w.ndim >= 2 and jnp.issubdtype(w.dtype, jnp.floating):
                    wf = jnp.asarray(w, jnp.float32)
                    scale = jnp.max(jnp.abs(wf), axis=0,
                                    keepdims=True) / qmax
                    scale = jnp.maximum(scale, 1e-12)
                    # clip BEFORE the cast: an fp8 overflow cast is nan,
                    # not saturation
                    q = jnp.clip(wf / scale, -qmax, qmax)
                    if qdtype == jnp.int8:
                        q = jnp.round(q)
                    q_ws[w_name] = {"q": q.astype(qdtype), "s": scale}
                else:
                    q_ws[w_name] = w
            out[op_name] = q_ws
        self._qparams = out
        self._qparams_key = key
        self._q_refs = refs
        return out

    @staticmethod
    def _deq(v, cdtype):
        if isinstance(v, dict) and "q" in v:
            return (v["q"].astype(jnp.float32) * v["s"]).astype(cdtype)
        return v

    # ---- graph walks -------------------------------------------------------

    def _compute_dtype(self):
        if self.model.config.compute_dtype == "bfloat16":
            return jnp.bfloat16
        return jnp.float32

    def _walk(self, params, state, tokens, caches, pos, last_only=False,
              rope_pos=None, row_lengths=None, prompt_len=None,
              chunk_start=None, skip_tail=False, gather_last=False,
              paged=None, lora=None, routing=None, lowerings=None,
              expert_rows=None):
        """Interpret the graph on a (B, S) token slab. pos=None means
        prefill (positions 0..S-1, fills cache); otherwise S == 1 and pos
        is the traced cache slot of the token. last_only=True narrows the
        prefill tail (`_tail_ops`: every op no cached op depends on, each
        per-position, validated in __init__), so only the final position
        flows through it and the lm_head — O(1/S) of its FLOPs and no (B, S, V) logits
        materialization; with `row_lengths` (ragged right-padded prompts)
        the tail gathers each row's own last valid position instead of
        column -1, and decode steps get per-row RoPE positions + a pad-
        slot cache mask (see MultiHeadAttention.decode_forward).
        `routing`, if a list, collects each dropless MoE op's (2,) routing
        counts of this walk, `lowerings` the lowering each of those calls
        took and `expert_rows` the rows its grouped products were given
        (ops/moe.py)."""
        bf16 = self._compute_dtype() == jnp.bfloat16

        def to_compute(a):
            if bf16 and a.dtype == jnp.float32:
                return a.astype(jnp.bfloat16)
            return a

        s_full = tokens.shape[1]
        vals = {self.token_input.outputs[0]: tokens}
        new_caches = {}
        for op in self.model.ops:
            if isinstance(op, InputOp):
                continue
            tail = op in self._tail_ops
            if skip_tail and tail:
                # non-final prefill chunk: only the caches matter; the
                # tail (final norm + lm_head, a last layer's shortcut
                # branch) is unused
                continue
            xs = [vals[t] for t in op.inputs]
            if last_only and pos is None and tail and s_full > 1:
                if row_lengths is None:
                    xs = [x[:, -1:] if (x.ndim >= 2 and x.shape[1] == s_full)
                          else x for x in xs]
                else:
                    last = (row_lengths - 1)[:, None]

                    def take_last(x):
                        if not (x.ndim >= 2 and x.shape[1] == s_full):
                            return x
                        ix = last.reshape((-1, 1) + (1,) * (x.ndim - 2))
                        ix = jnp.broadcast_to(
                            ix, (x.shape[0], 1) + x.shape[2:])
                        return jnp.take_along_axis(x, ix, axis=1)

                    xs = [take_last(x) for x in xs]
            # the op's scope holds the reading of its weights too
            # (dequantization, the cast): runtime/profiler.py scope_table
            with jax.named_scope(op.name):
                if self.quantize:
                    cdtype = self._compute_dtype()
                    deq = lambda v: self._deq(v, cdtype)
                    p = {k: deq(v)
                         for k, v in params.get(op.name, {}).items()}
                    p = resolve_tied_params(self.model, params, op.name, p,
                                            leaf=deq)
                else:
                    p = resolve_tied_params(self.model, params, op.name,
                                            params.get(op.name, {}))
                if bf16:
                    p = {k: to_compute(v) for k, v in p.items()}
            with jax.named_scope(op.name):
                if getattr(op, "state_cache_protocol", False):
                    out, nc = self._state_step(
                        op, p, xs, caches[op.name], pos, paged, row_lengths,
                        chunk_start, gather_last, rope_pos)
                    new_caches[op.name] = nc
                    outs = [out]
                elif getattr(op, "kv_cache_protocol", False):
                    cache = caches[op.name]
                    if paged is not None:
                        # continuous-batching slot decode over the paged
                        # pool (runtime/serving.py): per-slot positions,
                        # page-table gather instead of a contiguous cache.
                        # A (B, S>1) slab is the speculative-decode verify
                        # pass: write_pos is (B, S) per-position. "impl"
                        # routes the attention body (einsum page-gather
                        # oracle vs the Pallas paged kernel) per engine.
                        # an op that keeps a window has its group's ring
                        # of pages for a table (runtime/kv_pool.py)
                        keep = op_keeps(op)
                        table = paged["page_table"] if keep is None \
                            else paged["window_tables"][keep]
                        if tokens.shape[1] > 1:
                            out, nc = op.paged_verify_forward(
                                p, xs, cache, table,
                                paged["write_pos"], paged["rope_pos"],
                                paged["row_len"], paged["prompt_pad"],
                                impl=paged.get("impl"))
                        else:
                            # the groups of slots whose rows begin with
                            # the same pages, for an op that can stream
                            # such a page once a group (a ring is a
                            # slot's own)
                            share = {"shared": paged["shared"]} \
                                if "shared" in paged and keep is None \
                                and hasattr(op, "shared_members_cap") else {}
                            out, nc = op.paged_decode_forward(
                                p, xs, cache, table,
                                paged["write_pos"], paged["rope_pos"],
                                paged["row_len"], paged["prompt_pad"],
                                impl=paged.get("impl"), **share)
                    elif pos is None:
                        if gather_last:
                            # ragged chunked prefill: read-only query of
                            # each row's last prompt position against the
                            # chunk-filled cache
                            out, nc = op.query_forward(
                                p, xs, cache, rope_pos=row_lengths - 1,
                                row_lengths=row_lengths)
                        elif chunk_start is not None:
                            out, nc = op.chunk_forward(p, xs, cache,
                                                       chunk_start)
                        else:
                            out, nc = op.prefill_forward(p, xs, cache)
                    else:
                        out, nc = op.decode_forward(
                            p, xs, cache, pos, rope_pos=rope_pos,
                            row_lengths=row_lengths, prompt_len=prompt_len)
                    new_caches[op.name] = nc
                    outs = [out]
                else:
                    kwargs = {}
                    if getattr(op, "wants_shard_ctx", False):
                        kwargs["shard_ctx"] = None
                    if lora is not None \
                            and op.name in lora["pool"] \
                            and op.op_type == OperatorType.OP_LINEAR:
                        # multi-tenant serving (runtime/serving.py): the
                        # per-slot adapter-page gather + batched LoRA
                        # delta, inside the one fixed-shape program
                        from flexflow_tpu.ops.lora import gather_op_lora

                        kwargs["lora"] = gather_op_lora(
                            lora["pool"], op.name, lora["pages"])
                    if op.op_type == OperatorType.OP_MOE:
                        if op.dropless:
                            # rows of free slots and of a prompt's
                            # padding route nowhere: no expert is
                            # streamed for a row nobody reads
                            kwargs["row_mask"] = self._live_rows(
                                xs[0].shape[:-1], paged, row_lengths,
                                chunk_start, gather_last)
                            kwargs["routing"] = routing
                            kwargs["lowerings"] = lowerings
                            kwargs["expert_rows"] = expert_rows
                        else:
                            # a capacity op drops nothing at inference
                            # when its buffer holds the whole slab (see
                            # MoE.forward), hence row independence for
                            # ragged/batched decode
                            kwargs["capacity"] = int(
                                np.prod(xs[0].shape[:-1]))
                    if op.stateful:
                        outs, _ = op.forward_stateful(
                            p, state.get(op.name, {}), xs,
                            training=False, rng=None)
                    else:
                        outs = op.forward(p, xs, training=False, rng=None,
                                          **kwargs)
            for i, t in enumerate(op.outputs):
                vals[t] = outs[i]
        if skip_tail:
            return None, new_caches
        return vals[self.model._final_tensor], new_caches

    @staticmethod
    def _state_step(op, p, xs, state, pos, paged, row_lengths, chunk_start,
                    gather_last, rope_pos=None):
        """A recurrent-state op's part of a walk: the slab advances the
        sequence's one state (a prefill slab from `chunk_start`, padding
        rows past `row_lengths` leaving it alone; a decode token; the
        engine's slots, live ones only), except in the ragged prefill's
        gather pass, which re-reads the last live row's kept output."""
        if paged is not None:
            if xs[0].shape[1] > 1:
                raise NotImplementedError(
                    f"{op.name}: a recurrent state cannot be verified at "
                    "several positions in one pass (speculative decoding)")
            at = {"positions": paged["rope_pos"]} if getattr(
                op, "state_wants_positions", False) else {}
            return op.paged_step_forward(p, xs, state, paged["row_len"] > 0,
                                         impl=paged.get("impl"), **at)
        if pos is not None:
            if getattr(op, "state_wants_positions", False):
                # a rotary op: the token's own position (a ragged row's)
                return op.step_forward(
                    p, xs, state, pos if rope_pos is None else rope_pos)
            return op.step_forward(p, xs, state)
        if gather_last:
            return op.last_forward(p, xs, state)
        return op.scan_forward(p, xs, state,
                               0 if chunk_start is None else chunk_start,
                               row_lengths)

    def init_caches(self, batch: int, max_len: int, dtype):
        """The contiguous per-request caches of one prefill (and of
        generate()'s decode): per-token rows for the attention ops, one
        state for the recurrent ones."""
        caches = {op.name: op.init_cache(batch, max_len, dtype)
                  for op in self.attn_ops}
        caches.update({op.name: op.init_state(batch, dtype)
                       for op in self.state_ops})
        return caches

    @staticmethod
    def _live_rows(shape, paged, row_lengths, chunk_start, gather_last):
        """(B, S) bool: which rows of a slab some request reads. A paged
        decode slot is live while a request holds it (the engine zeroes
        `row_len` on retirement); a ragged prompt's rows are live up to
        its length; None where every row is."""
        if paged is not None:
            return jnp.broadcast_to((paged["row_len"] > 0)[:, None], shape)
        if row_lengths is None or gather_last:
            return None
        at = (0 if chunk_start is None else chunk_start) \
            + jnp.arange(shape[1])
        return at[None, :] < row_lengths[:, None]

    def _prefill(self, params, state, tokens, caches, row_lengths,
                 prefill_chunk, lora=None, routing=None, lowerings=None,
                 expert_rows=None, loop=False):
        """Whole-prompt prefill, or chunked (`prefill_chunk` > 0 and the
        prompt longer than it): each chunk writes its k/v and attends the
        static prefix slice under the same causal rule — score memory is
        O(chunk * S) not O(S^2). Logits are bitwise-equal to whole-prompt
        prefill on the einsum path; when whole-prompt prefill rides the
        flash kernel (TPU), accumulation order differs, so equality is
        within kernel tolerance there.

        Ragged + chunked (round 5): a ragged row's last position can fall
        in ANY chunk, so every chunk runs cache-only (skip_tail) and a
        final read-only GATHER pass queries each row's own last prompt
        token against the filled cache (MultiHeadAttention.query_forward)
        — right-padding keeps this sound: a real position's causal window
        holds only real positions, and pad slots' garbage k/v are masked
        by row_lengths in the gather and in every decode step."""
        # An attention op may ask (`prefill_chunk_barrier`) that each chunk
        # end in a barrier, which keeps XLA from hoisting the NEXT chunks'
        # projections above this chunk's work; models whose ops do not ask
        # keep the program they had.
        if any(getattr(op, "prefill_chunk_barrier", False)
               for op in self.attn_ops):
            def close(caches, tokens):
                return jax.lax.optimization_barrier((caches, tokens))
        else:
            def close(caches, tokens):
                return caches, tokens
        b, s0 = tokens.shape
        if not prefill_chunk or s0 <= prefill_chunk:
            return self._walk(params, state, tokens, caches, None,
                              last_only=True, row_lengths=row_lengths,
                              prompt_len=s0, lora=lora, routing=routing,
                              lowerings=lowerings, expert_rows=expert_rows)
        if loop:
            return self._prefill_loop(params, state, tokens, caches,
                                      row_lengths, prefill_chunk, lora)
        starts = list(range(0, s0, prefill_chunk))
        if row_lengths is not None:
            for st in starts:
                # row_lengths: a chunk's attention does not read it, a
                # dropless MoE masks the prompt's padding rows by it
                _, caches = self._walk(
                    params, state, tokens[:, st:st + prefill_chunk],
                    caches, None, chunk_start=st, skip_tail=True, lora=lora,
                    row_lengths=row_lengths, routing=routing,
                    lowerings=lowerings, expert_rows=expert_rows)
                caches, tokens = close(caches, tokens)
            tok_last = jnp.take_along_axis(
                tokens, (row_lengths - 1)[:, None], axis=1)      # (B, 1)
            return self._walk(params, state, tok_last, caches, None,
                              last_only=True, row_lengths=row_lengths,
                              gather_last=True, lora=lora, routing=routing,
                              lowerings=lowerings, expert_rows=expert_rows)
        for st in starts[:-1]:
            _, caches = self._walk(
                params, state, tokens[:, st:st + prefill_chunk], caches,
                None, chunk_start=st, skip_tail=True, lora=lora,
                routing=routing, lowerings=lowerings,
                expert_rows=expert_rows)
            caches, tokens = close(caches, tokens)
        st = starts[-1]
        return self._walk(params, state, tokens[:, st:], caches, None,
                          last_only=True, chunk_start=st, lora=lora,
                          routing=routing, lowerings=lowerings,
                          expert_rows=expert_rows)

    def chunk_loop_refusal(self) -> Optional[str]:
        """Why this graph's chunks cannot run as one loop body, or None."""
        for op in self.attn_ops:
            if not getattr(op, "traced_chunk_start", False) \
                    or getattr(op, "window", 0) \
                    or getattr(op, "flash_chunks", False):
                return (f"{op.name} attends a static slice of its prefix "
                        "(a window, a flash tile, a selection)")
        if self.dropless_moe_ops:
            return (f"{self.dropless_moe_ops[0].name} counts its routing "
                    "walk by walk")
        return None

    def _prefill_loop(self, params, state, tokens, caches, row_lengths,
                      chunk, lora=None):
        """The ragged chunked prefill as ONE loop: a `fori_loop` over the
        chunk starts whose body (one walk of `chunk` rows from a TRACED
        start) is compiled once, where `_prefill` unrolls a body a chunk
        (a 40-layer graph's 16 k bucket in 16 chunks is 640 layer bodies:
        minutes of compile). It runs only the chunks that hold live rows
        (`row_lengths`), so one bucket's program costs a short prompt
        little. The attention ops attend the whole contiguous cache under
        the causal rule (no static slice of the prefix exists); the state
        ops carry their state from chunk to chunk as ever. Ends with the
        gather pass of the ragged chunked prefill."""
        assert tokens.shape[1] % chunk == 0, (tokens.shape, chunk)

        def body(i, caches):
            st = i * chunk
            toks = jax.lax.dynamic_slice_in_dim(tokens, st, chunk, axis=1)
            _, new = self._walk(params, state, toks, caches, None,
                                chunk_start=st, skip_tail=True, lora=lora,
                                row_lengths=row_lengths)
            return new

        n = (jnp.max(row_lengths) + chunk - 1) // chunk
        caches = jax.lax.fori_loop(0, n, body, caches)
        tok_last = jnp.take_along_axis(
            tokens, (row_lengths - 1)[:, None], axis=1)          # (B, 1)
        return self._walk(params, state, tok_last, caches, None,
                          last_only=True, row_lengths=row_lengths,
                          gather_last=True, lora=lora)

    # ---- sampling ----------------------------------------------------------

    def _sample(self, logits, key, with_score=False):
        """logits (B, V) -> (token (B,) int32, logp (B,) f32 or None).
        The score is the MODEL's log-probability of the chosen token
        (raw softmax, independent of temperature/top-k warping of the
        sampling distribution); computed only when requested, so
        score-free decode programs never pay the full-vocab
        log_softmax."""
        logits = logits.astype(jnp.float32)
        if self.temperature <= 0.0:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            warped = logits / self.temperature
            vocab = logits.shape[-1]
            top_k = self.top_k
            if top_k >= vocab:
                # HF semantics: top_k >= vocab is a legal no-op
                # (full-distribution sampling) — a caller sweeping top_k or
                # serving a tiny-vocab model must not crash at trace time.
                if not getattr(self, "_warned_topk", False):
                    from flexflow_tpu.logger import fflogger
                    fflogger.warning(
                        "top_k=%d >= vocab %d; treating as top_k=0 "
                        "(full-distribution sampling)", top_k, vocab)
                    self._warned_topk = True
                top_k = 0
            if top_k > 0:
                # scatter from the top_k indices (not a >=kth threshold
                # compare, which keeps every logit TIED with the k-th
                # value — more than k candidates on ties)
                vals, idxs = jax.lax.top_k(warped, top_k)
                warped = jnp.full_like(warped, -jnp.inf).at[
                    jnp.arange(warped.shape[0])[:, None], idxs].set(vals)
            tok = jax.random.categorical(key, warped, axis=-1
                                         ).astype(jnp.int32)
        if not with_score:
            return tok, None
        logp = jax.nn.log_softmax(logits, axis=-1)
        score = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
        return tok, score

    # ---- the compiled program ---------------------------------------------

    def _build(self, max_new_tokens: int, ragged: bool = False,
               prefill_chunk: int = 0, with_scores: bool = False,
               early_exit: bool = False):
        cdtype = self._compute_dtype()

        def gen(params, state, tokens, key, lengths):
            b, s0 = tokens.shape
            max_len = s0 + max_new_tokens
            row_lengths = lengths if ragged else None
            caches = self.init_caches(b, max_len, cdtype)
            logits, caches = self._prefill(params, state, tokens, caches,
                                           row_lengths, prefill_chunk)
            key, sub = jax.random.split(key)
            tok, score = self._sample(logits[:, -1], sub,
                                      with_score=with_scores)
            done = jnp.zeros((b,), bool)
            if self.eos_id is not None:
                done = tok == self.eos_id

            def step(caches, tok, done, key, i):
                """Shared decode-step body for the scan and while paths —
                i is the 0-based index of the NEXT token to produce."""
                logits, caches = self._walk(
                    params, state, tok[:, None], caches, s0 + i,
                    rope_pos=(row_lengths + i) if ragged else None,
                    row_lengths=row_lengths, prompt_len=s0)
                key, sub = jax.random.split(key)
                nxt, sc = self._sample(logits[:, 0], sub,
                                       with_score=with_scores)
                if self.eos_id is not None:
                    nxt = jnp.where(done, self.pad_id, nxt)
                    if with_scores:
                        sc = jnp.where(done, 0.0, sc)  # pads score 0
                    done = done | (nxt == self.eos_id)
                return caches, nxt, sc, done, key

            def body(carry, i):
                caches, tok, done, key = carry
                caches, nxt, sc, done, key = step(caches, tok, done, key, i)
                ys = (nxt, sc) if with_scores else nxt
                return (caches, nxt, done, key), ys

            if max_new_tokens > 1 and early_exit:
                # while_loop wrapper: stop as soon as every live row has
                # emitted eos. Token-identical to the full-length scan —
                # the skipped iterations would only have appended pads
                # (which the output buffers are pre-filled with). Costs
                # one extra (i, buffers) carry vs the scan; wins whenever
                # rows finish early. No eos_id => done never flips and the
                # loop runs the full length, same as the scan.
                buf = jnp.full((b, max_new_tokens), self.pad_id, jnp.int32)
                buf = buf.at[:, 0].set(tok)
                sbuf = jnp.zeros((b, max_new_tokens), jnp.float32)
                if with_scores:
                    sbuf = sbuf.at[:, 0].set(score)

                def cond(carry):
                    i = carry[0]
                    done = carry[4]
                    return (i < max_new_tokens - 1) & ~jnp.all(done)

                def wbody(carry):
                    i, caches, tok, (buf, sbuf), done, key = carry
                    caches, nxt, sc, done, key = step(caches, tok, done,
                                                      key, i)
                    buf = buf.at[:, i + 1].set(nxt)
                    if with_scores:
                        sbuf = sbuf.at[:, i + 1].set(sc)
                    return (i + 1, caches, nxt, (buf, sbuf), done, key)

                carry = (jnp.asarray(0, jnp.int32), caches, tok,
                         (buf, sbuf), done, key)
                _, _, _, (buf, sbuf), _, _ = jax.lax.while_loop(
                    cond, wbody, carry)
                new, scores = buf, sbuf
            elif max_new_tokens > 1:
                _, ys = jax.lax.scan(
                    body, (caches, tok, done, key),
                    jnp.arange(max_new_tokens - 1, dtype=jnp.int32))
                rest = ys[0] if with_scores else ys
                new = jnp.concatenate([tok[:, None], rest.T], axis=1)
                if with_scores:
                    scores = jnp.concatenate([score[:, None], ys[1].T],
                                             axis=1)
            else:
                new = tok[:, None]
                if with_scores:
                    scores = score[:, None]
            out = jnp.concatenate([tokens, new], axis=1)
            return (out, scores) if with_scores else out

        return jax.jit(gen)

    # ---- beam search -------------------------------------------------------

    def _build_beam(self, max_new_tokens: int, num_beams: int,
                    length_penalty: float, prefill_chunk: int = 0,
                    ragged: bool = False):
        """Beam decode as one jitted scan. Beams live flattened on the
        batch dim (B*K rows); each step re-orders the KV caches by beam
        parent with a batched gather. Finished beams (emitted eos) are
        frozen: only pad continues them, at logp 0, so their score stops
        changing; the final pick normalizes by emitted length^penalty.
        With `ragged` (right-padded prompts + row lengths), prefill
        scores each row at its OWN last valid position — exactly as the
        greedy path does — and decode steps carry per-row RoPE positions
        and the pad-slot cache mask, repeated per beam."""
        cdtype = self._compute_dtype()
        K = num_beams

        def gen(params, state, tokens, lengths):
            b, s0 = tokens.shape
            max_len = s0 + max_new_tokens
            row_lengths = lengths if ragged else None
            caches = self.init_caches(b, max_len, cdtype)
            logits, caches = self._prefill(params, state, tokens, caches,
                                           row_lengths, prefill_chunk)
            logp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32),
                                      axis=-1)                  # (B, V)
            vocab = logp.shape[-1]
            scores, tok = jax.lax.top_k(logp, K)                # (B, K)
            tok = tok.astype(jnp.int32)
            done = (tok == self.eos_id) if self.eos_id is not None \
                else jnp.zeros((b, K), bool)
            # beam-flatten the caches: row b*K+k is beam k of batch row b
            caches = jax.tree.map(
                lambda c: jnp.repeat(c, K, axis=0), caches)
            # per-beam row lengths for the flattened (B*K) decode batch
            rep_lengths = (jnp.repeat(lengths, K) if ragged else None)
            buf = jnp.full((b, K, max_new_tokens), self.pad_id, jnp.int32)
            buf = buf.at[:, :, 0].set(tok)
            new_len = jnp.ones((b, K), jnp.int32)

            def body(carry, i):
                caches, buf, tok, scores, done, new_len = carry
                logits, caches = self._walk(
                    params, state, tok.reshape(b * K, 1), caches, s0 + i,
                    rope_pos=(rep_lengths + i) if ragged else None,
                    row_lengths=rep_lengths, prompt_len=s0)
                logp = jax.nn.log_softmax(
                    logits[:, 0].astype(jnp.float32), axis=-1)
                logp = logp.reshape(b, K, vocab)
                # frozen beams: pad continues at logp 0, everything else -inf
                frozen = jnp.full((vocab,), -jnp.inf
                                  ).at[self.pad_id].set(0.0)
                logp = jnp.where(done[..., None], frozen[None, None, :], logp)
                cand = (scores[..., None] + logp).reshape(b, K * vocab)
                scores, flat = jax.lax.top_k(cand, K)           # (B, K)
                parent = flat // vocab                          # (B, K)
                tok = (flat % vocab).astype(jnp.int32)
                gather = lambda a: jnp.take_along_axis(a, parent, axis=1)
                done = gather(done)
                new_len = gather(new_len)
                buf = jnp.take_along_axis(
                    buf, parent[:, :, None], axis=1)
                buf = buf.at[:, :, i + 1].set(tok)
                # reorder caches by beam parent (batched row gather)
                rows = (jnp.arange(b)[:, None] * K + parent).reshape(-1)
                caches = jax.tree.map(
                    lambda c: jnp.take(c, rows, axis=0), caches)
                if self.eos_id is not None:
                    new_len = jnp.where(done, new_len, new_len + 1)
                    done = done | (tok == self.eos_id)
                else:
                    new_len = new_len + 1
                return (caches, buf, tok, scores, done, new_len), None

            if max_new_tokens > 1:
                (caches, buf, tok, scores, done, new_len), _ = jax.lax.scan(
                    body, (caches, buf, tok, scores, done, new_len),
                    jnp.arange(max_new_tokens - 1, dtype=jnp.int32))
            norm = scores / jnp.maximum(new_len, 1).astype(
                jnp.float32) ** length_penalty
            best = jnp.argmax(norm, axis=1)                     # (B,)
            picked = jnp.take_along_axis(
                buf, best[:, None, None], axis=1)[:, 0]         # (B, T)
            best_score = jnp.take_along_axis(norm, best[:, None],
                                             axis=1)[:, 0]
            return jnp.concatenate([tokens, picked], axis=1), best_score

        return jax.jit(gen)

    def _source_params(self):
        """The weight tree decode programs read: the per-generator
        override when one is installed (rolling deploy), else the shared
        model params."""
        if self._params_override is not None:
            return self._params_override
        return self.model.params

    def set_params(self, tree):
        """Install (or, with ``tree=None``, clear) a per-generator weight
        override. The tree must match ``model.params`` in structure,
        shapes and dtypes — same geometry, so every warm decode program
        stays valid and nothing retraces. Invalidate the quantized-weight
        cache so the next program pull re-quantizes from the new source
        exactly once."""
        if tree is not None:
            ref_leaves, ref_def = jax.tree_util.tree_flatten(
                self.model.params)
            new_leaves, new_def = jax.tree_util.tree_flatten(tree)
            if new_def != ref_def:
                raise ValueError(
                    "set_params: tree structure differs from model.params "
                    "— a weight swap must be same-geometry")
            for ref, new in zip(ref_leaves, new_leaves):
                if (getattr(ref, "shape", None) != getattr(new, "shape",
                                                           None)
                        or getattr(ref, "dtype", None)
                        != getattr(new, "dtype", None)):
                    raise ValueError(
                        f"set_params: leaf geometry mismatch "
                        f"{getattr(ref, 'shape', None)}/"
                        f"{getattr(ref, 'dtype', None)} vs "
                        f"{getattr(new, 'shape', None)}/"
                        f"{getattr(new, 'dtype', None)}")
        self._params_override = tree
        self._override_version += 1
        self._qparams = None
        self._qparams_key = None
        self._q_refs = None

    def _params(self):
        return (self._quantized_params() if self.quantize
                else self._source_params())

    def _cached_program(self, key, build):
        """LRU lookup/insert for compiled decode programs."""
        import os

        fn = self._jitted.get(key)
        if fn is not None:
            self._jitted.move_to_end(key)
            return fn
        fn = self._jitted[key] = build()
        try:
            cap = int(os.environ.get("FF_GEN_PROGRAM_CACHE", "8") or 8)
        except ValueError:
            cap = 8
        while cap > 0 and len(self._jitted) > cap:
            self._jitted.popitem(last=False)
        return fn

    def beam_search(self, tokens: np.ndarray, max_new_tokens: int,
                    num_beams: int, length_penalty: float = 0.0,
                    prefill_chunk: int = 0, return_scores: bool = False,
                    prompt_lengths=None):
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}")
        tokens = jnp.asarray(tokens, jnp.int32)
        lengths, ragged = self._check_lengths(tokens, prompt_lengths)
        # prompt shape is part of the key: each LRU entry then holds ~one
        # XLA executable, so eviction genuinely bounds compiled programs
        # (a shape-generic jit wrapper would grow an unbounded internal
        # per-shape cache behind a single key)
        key = ("beam", max_new_tokens, num_beams, length_penalty,
               prefill_chunk, ragged, tuple(tokens.shape))
        fn = self._cached_program(key, lambda: self._build_beam(
            max_new_tokens, num_beams, length_penalty, prefill_chunk,
            ragged=ragged))
        out, score = fn(self._params(), self.model.bn_state, tokens,
                        lengths)
        if return_scores:
            # (B,) length-penalty-normalized total logp of the chosen beam
            return np.asarray(out), np.asarray(score)
        return np.asarray(out)

    @staticmethod
    def _check_lengths(tokens, prompt_lengths):
        """Validate (B,) prompt lengths against the prompt slab; returns
        (lengths_device_array, ragged_flag). Uniform prompts pass zeros —
        the compiled program ignores them."""
        ragged = prompt_lengths is not None
        if not ragged:
            return jnp.zeros((tokens.shape[0],), jnp.int32), False
        lengths = np.asarray(prompt_lengths, np.int32)
        if lengths.shape != (tokens.shape[0],):
            raise ValueError(
                f"prompt_lengths shape {lengths.shape} != "
                f"({tokens.shape[0]},)")
        if (lengths < 1).any() or (lengths > tokens.shape[1]).any():
            raise ValueError(
                f"prompt_lengths must be in [1, {tokens.shape[1]}], "
                f"got {lengths.tolist()}")
        return jnp.asarray(lengths), True

    def __call__(self, tokens: np.ndarray, max_new_tokens: int,
                 seed: int = 0, prompt_lengths=None,
                 prefill_chunk: int = 0, return_scores: bool = False,
                 early_exit: bool = False):
        """tokens (B, S0) int32 prompts -> (B, S0 + max_new_tokens) int32
        with the generated tokens in columns S0 onward. Uniform-length
        prompts by default; `prompt_lengths` (B,) enables ragged RIGHT-
        padded prompts — row b's prompt is tokens[b, :prompt_lengths[b]],
        pad slots are masked out of attention and RoPE continues from each
        row's true length. `prefill_chunk` > 0 prefills the prompt in
        chunks of that many positions (O(chunk * S) score memory).
        `early_exit` swaps the fixed-length decode scan for a while_loop
        that stops once every row has emitted eos — identical tokens,
        fewer steps whenever rows finish early."""
        tokens = jnp.asarray(tokens, jnp.int32)
        lengths, ragged = self._check_lengths(tokens, prompt_lengths)
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}")
        # prompt shape in the key: see beam_search — makes LRU eviction
        # actually bound compiled executables, not just jit wrappers
        cache_key = (max_new_tokens, ragged, prefill_chunk, return_scores,
                     early_exit, tuple(tokens.shape))
        fn = self._cached_program(cache_key, lambda: self._build(
            max_new_tokens, ragged, prefill_chunk,
            with_scores=return_scores, early_exit=early_exit))
        key = jax.random.PRNGKey(seed)
        res = fn(self._params(), self.model.bn_state, tokens, key, lengths)
        if return_scores:
            # (B, S0+new) tokens + (B, new) model logprobs per new token
            # (pads after eos carry 0.0)
            out, scores = res
            return np.asarray(out), np.asarray(scores)
        return np.asarray(res)
