"""Encoder-decoder (seq2seq) generation with KV-cached decoding.

Net-new capability for the NMT/seq2seq family (SURVEY S1): the reference
trains its LSTM NMT and twin-stream Transformer but has NO decode story
at all — inference is the training graph run forward. Here the
encoder runs ONCE, cross-attention k/v are projected ONCE from the
encoder states (MultiHeadAttention.encode_kv), and the decoder runs the
same one-program prefill + `lax.scan` token loop as the decoder-only
path (runtime/generation.py), with a KV cache on decoder
SELF-attention and the static k/v on cross-attention — per-token cost
is O(tgt_prefix + src) attention reads, never a re-encode.

Scope (v1): greedy and temperature/top-k sampling with eos/pad
handling; uniform-length source batches (pad-free); no beam, no int8 —
the decoder-only Generator documents both patterns for a later lift.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.ops.attention import MultiHeadAttention
from flexflow_tpu.ops.base import InputOp
from flexflow_tpu.runtime.executor import resolve_tied_params
from flexflow_tpu.runtime.generation import _DECODE_SAFE, Generator


class Seq2SeqGenerator:
    """Compiles generate programs for an encoder-decoder graph.

    Graph contract: exactly two inputs — a source and an int32/int64
    TARGET token input; the target stream's self-attention must be
    causal; cross-attention ops take q from the decoder stream and
    k = v = an encoder-side tensor, non-causal and rope-free (the
    seq2seq_lm builder's layout). Encoder ops may be anything the
    forward path supports; decoder non-attention ops must be
    per-position (_DECODE_SAFE), same rule as decoder-only decode.
    """

    def __init__(self, model, temperature: float = 0.0, top_k: int = 0,
                 eos_id: Optional[int] = None, pad_id: int = 0):
        self.model = model
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = eos_id
        self.pad_id = pad_id
        import collections

        self._jitted: Dict = collections.OrderedDict()

        if getattr(model.executor, "jits_per_group", False):
            raise NotImplementedError(
                "generate_seq2seq() is unsupported under an "
                "operator-placement strategy")
        inputs = [op for op in model.ops if isinstance(op, InputOp)]
        if len(inputs) != 2:
            raise ValueError(
                f"generate_seq2seq() needs exactly two graph inputs "
                f"(source, target tokens); this graph has {len(inputs)}")

        # the decoder stream is whatever transitively depends on the
        # target input; try each int input as the target and keep the
        # partition whose decoder self-attentions are all causal
        int_inputs = [op for op in inputs
                      if op.outputs[0].dtype in (DataType.DT_INT32,
                                                 DataType.DT_INT64)]
        if not int_inputs:
            raise ValueError(
                "generate_seq2seq() needs an integer target-token input")
        chosen = None
        for tgt in int_inputs:
            part = self._partition(model, tgt)
            if part is not None:
                chosen = (tgt, part)
                break
        if chosen is None:
            raise ValueError(
                "no input yields a decodable decoder stream (causal "
                "self-attention downstream of an int token input)")
        self.tgt_input, (self.enc_ops, self.dec_ops, self.self_ops,
                         self.cross_ops) = chosen
        self.src_input = next(op for op in inputs
                              if op is not self.tgt_input)
        # encoder tensors the decoder reads (cross k/v sources + any
        # other boundary values)
        dec_set = set(self.dec_ops)
        self.boundary = []
        for op in self.dec_ops:
            for t in op.inputs:
                if (t.owner_op is not None and t.owner_op not in dec_set
                        and not isinstance(t.owner_op, InputOp)
                        and t not in self.boundary):
                    self.boundary.append(t)

    @staticmethod
    def _partition(model, tgt_input):
        """Split ops into (encoder, decoder, self_attns, cross_attns)
        treating `tgt_input` as the decoder token stream; None when the
        split violates the decode contract (picks the wrong input)."""
        dec_tensors = {tgt_input.outputs[0]}
        enc_ops, dec_ops, self_ops, cross_ops = [], [], [], []
        for op in model.ops:
            if isinstance(op, InputOp):
                continue
            in_dec = any(t in dec_tensors for t in op.inputs)
            if not in_dec:
                enc_ops.append(op)
                continue
            dec_ops.append(op)
            dec_tensors.update(op.outputs)
            if isinstance(op, MultiHeadAttention):
                if op.inputs[0] is op.inputs[1] is op.inputs[2]:
                    if not op.causal:
                        return None  # bidirectional self-attn in decoder
                    self_ops.append(op)
                else:
                    # cross: q from decoder, k=v an encoder tensor
                    if op.inputs[1] is not op.inputs[2]:
                        return None
                    if op.inputs[1] in dec_tensors or op.causal or op.rope:
                        return None
                    cross_ops.append(op)
            elif op.op_type not in _DECODE_SAFE:
                return None
        if not self_ops:
            return None
        return enc_ops, dec_ops, self_ops, cross_ops

    # ---- walks --------------------------------------------------------------

    def _params_for(self, params, op):
        return self._cast_params(resolve_tied_params(
            self.model, params, op.name, params.get(op.name, {})))

    def _run_op(self, op, p, xs, state):
        with jax.named_scope(op.name):
            if op.stateful:
                outs, _ = op.forward_stateful(p, state.get(op.name, {}),
                                              xs, training=False, rng=None)
            else:
                kwargs = {}
                if getattr(op, "wants_shard_ctx", False):
                    kwargs["shard_ctx"] = None
                if op.op_type == OperatorType.OP_MOE and not op.dropless:
                    kwargs["capacity"] = int(np.prod(xs[0].shape[:-1]))
                outs = op.forward(p, xs, training=False, rng=None, **kwargs)
        return outs

    def _encode(self, params, state, src):
        """One forward over the encoder ops; returns {tensor: value} for
        the decoder-consumed boundary tensors."""
        vals = {self.src_input.outputs[0]: src}
        for op in self.enc_ops:
            xs = [vals[t] for t in op.inputs]
            outs = self._run_op(op, self._params_for(params, op), xs, state)
            for i, t in enumerate(op.outputs):
                vals[t] = outs[i]
        return {t: vals[t] for t in self.boundary}

    def _dec_walk(self, params, state, toks, enc_vals, self_caches,
                  cross_kvs, pos):
        """Walk the decoder ops on a (B, C) token slab. pos=None →
        prefill (fills self-attn caches causally); else C == 1 and pos
        is the cache slot. Cross-attention always reads the static
        kv."""
        vals = dict(enc_vals)
        vals[self.tgt_input.outputs[0]] = toks
        new_caches = {}
        for op in self.dec_ops:
            p = self._params_for(params, op)
            xs = [vals[t] for t in op.inputs]
            if op in self.self_ops:
                cache = self_caches[op.name]
                if pos is None:
                    out, nc = op.prefill_forward(p, xs, cache)
                else:
                    out, nc = op.decode_forward(p, xs, cache, pos)
                new_caches[op.name] = nc
                outs = [out]
            elif op in self.cross_ops:
                outs = [op.cross_forward_cached(p, xs, cross_kvs[op.name])]
            else:
                outs = self._run_op(op, p, xs, state)
            for i, t in enumerate(op.outputs):
                vals[t] = outs[i]
        return vals[self.model._final_tensor], new_caches

    # ---- sampling + dtype + program LRU: REUSED from the decoder-only
    # Generator, so the two paths cannot drift (top-k tie handling,
    # top_k>=vocab no-op warning, bf16 compute selection, cache bounds)
    _sample = Generator._sample
    _compute_dtype = Generator._compute_dtype
    _cached_program = Generator._cached_program

    def _cast_params(self, p):
        if self._compute_dtype() != jnp.bfloat16:
            return p
        return {k: v.astype(jnp.bfloat16) if v.dtype == jnp.float32 else v
                for k, v in p.items()}

    # ---- the compiled program -----------------------------------------------

    def _build(self, max_new_tokens: int):
        cdtype = self._compute_dtype()

        def gen(params, state, src, tgt, key):
            b, t0 = tgt.shape
            max_len = t0 + max_new_tokens
            enc_vals = self._encode(params, state, src)
            cross_kvs = {op.name: op.encode_kv(
                self._params_for(params, op), enc_vals[op.inputs[1]])
                for op in self.cross_ops}
            self_caches = {op.name: op.init_cache(b, max_len, cdtype)
                           for op in self.self_ops}
            logits, self_caches = self._dec_walk(
                params, state, tgt, enc_vals, self_caches, cross_kvs, None)
            key, sub = jax.random.split(key)
            tok, _ = self._sample(logits[:, -1], sub)
            done = (tok == self.eos_id) if self.eos_id is not None \
                else jnp.zeros((b,), bool)

            def body(carry, i):
                self_caches, tok, done, key = carry
                logits, self_caches = self._dec_walk(
                    params, state, tok[:, None], enc_vals, self_caches,
                    cross_kvs, t0 + i)
                key, sub = jax.random.split(key)
                nxt, _ = self._sample(logits[:, 0], sub)
                if self.eos_id is not None:
                    nxt = jnp.where(done, self.pad_id, nxt)
                    done = done | (nxt == self.eos_id)
                return (self_caches, nxt, done, key), nxt

            if max_new_tokens > 1:
                _, rest = jax.lax.scan(
                    body, (self_caches, tok, done, key),
                    jnp.arange(max_new_tokens - 1, dtype=jnp.int32))
                new = jnp.concatenate([tok[:, None], rest.T], axis=1)
            else:
                new = tok[:, None]
            return jnp.concatenate([tgt, new], axis=1)

        return jax.jit(gen)

    def __call__(self, src_tokens, tgt_prompt, max_new_tokens: int,
                 seed: int = 0):
        src = jnp.asarray(src_tokens)
        tgt = jnp.asarray(tgt_prompt, jnp.int32)
        key = ("s2s", max_new_tokens, tuple(src.shape), tuple(tgt.shape))
        fn = self._cached_program(key, lambda: self._build(max_new_tokens))
        return np.asarray(fn(self.model.params, self.model.bn_state, src,
                             tgt, jax.random.PRNGKey(seed)))
