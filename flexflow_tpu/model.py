"""FFModel: the user-facing graph builder + training driver.

API parity with the reference's FFModel (include/model.h:250-483; Python
surface python/flexflow/core/flexflow_cbinding.py): layer factory methods
append ops to a graph; `compile` resolves strategies (running the MCMC search
when budget > 0), builds the mesh, and initializes sharded params; the
training verbs (forward/zero_gradients/backward/update) and `fit` drive
jitted GSPMD steps.

Execution model difference from the reference: instead of per-op Legion index
launches scheduled by a mapper (§3.1 of SURVEY.md), the whole step is one XLA
program; strategies become sharding constraints inside it.
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.config import FFConfig
from flexflow_tpu.ffconst import (ActiMode, AggrMode, CompMode, DataType,
                                  LossType, MetricsType, OperatorType, PoolType)
from flexflow_tpu.ops.attention import MultiHeadAttention
from flexflow_tpu.ops.base import InputOp, Op
from flexflow_tpu.ops.conv import BatchNorm, Conv2D, Flat, Pool2D
from flexflow_tpu.ops.dense import BatchMatmul, Embedding, Linear
from flexflow_tpu.ops.elementwise import Cast, ElementBinary, ElementUnary, Mean
from flexflow_tpu.ops.norm import (AddLayerNorm, Dropout, LayerNorm, RMSNorm,
                                   Softmax)
from flexflow_tpu.ops.tensor_ops import (Concat, Gather, Pad, Reshape, Reverse,
                                         Split, TopK, Transpose)
from flexflow_tpu.parallel.mesh import make_mesh
from flexflow_tpu.parallel.strategy import (load_strategies_from_file,
                                            save_strategies_to_file)
from flexflow_tpu.runtime import profiler
from flexflow_tpu.runtime import telemetry as _telemetry
from flexflow_tpu.runtime.executor import GraphExecutor
from flexflow_tpu.runtime.loss import loss_type_from_name
from flexflow_tpu.runtime.metrics import (ROUTING_COUNTS, PerfMetrics,
                                          metrics_from_names)
from flexflow_tpu.tensor import Tensor

# process-wide model ids: the HBM ledger's per-instance source name
# (two FFModels in one process must not overwrite each other's rows)
_MODEL_IDS = iter(range(1 << 30))


def _tree_nbytes(tree) -> int:
    """Bytes of a tree's arrays, by shape and dtype (nothing is synced)."""
    return sum(int(getattr(a, "nbytes", 0))
               for a in jax.tree_util.tree_leaves(tree))


def _batch_shapes(batch: Dict) -> tuple:
    """What tells two executables of a forward program apart."""
    return tuple((name, tuple(v.shape)) for name, v in sorted(batch.items()))


class FFModel:
    def __init__(self, config: Optional[FFConfig] = None):
        self.config = config or FFConfig()
        self.ops: List[Op] = []
        self._op_counters: Dict[str, int] = {}
        self._dataloaders: List = []
        self.mesh = None
        self.executor: Optional[GraphExecutor] = None
        # bumped on every params replacement/mutation (property setter +
        # set_weights); consumers that derive from params (the int8 decode
        # cache) key their caches on it
        self._params_version = 0
        self.params = None
        self.opt_state = None
        self.bn_state = None
        self.optimizer = None
        self.loss_type: Optional[LossType] = None
        self.metric_types: List[MetricsType] = []
        self.label_tensor: Optional[Tensor] = None
        self.comp_mode = CompMode.COMP_MODE_TRAINING
        self._rng = jax.random.PRNGKey(self.config.seed)
        self._step_count = 0
        self._train_step = None
        self._train_scan = None
        # name -> profiler.Program of a train program this model has run:
        # the process-wide registry holds them weakly
        self._registered = {}
        # (program, batch shapes) whose first call has run (_first_call)
        self._called = set()
        # divergence-guarded step + its device-resident guard carry
        # (runtime/resilience.py; built in compile() when
        # config.on_nonfinite != "none")
        self._guarded_step = None
        self._guard_state = None
        self._eval_step = None
        self._predict_fn = None
        self._generators = {}
        # (dst_op, dst_weight) -> (src_op, src_weight, transform); see
        # tie_weights()
        self._tied = {}
        self._current_batch: Dict[str, np.ndarray] = {}
        self._aux_tensors: List[Tensor] = []  # scalar losses (MoE balance)
        self._cached_backward = None
        self._perf = PerfMetrics()
        # host-overlap step engine (runtime/pipeline_loader.py): the live
        # prefetch pipeline while fit() runs one (the supervisor reads
        # checkpoint cursors through it), and the last fit's per-step
        # host_wait/h2d/dispatch/device breakdown
        self._pipeline = None
        self.last_step_breakdown: Optional[Dict[str, float]] = None
        # fflint's per-chip HBM footprint estimate, stashed by compile's
        # lint pass for the flight recorder's accounting cross-check;
        # the ledger row name is per-instance so two models in one
        # process keep distinct rows
        self._lint_hbm_estimate: Optional[float] = None
        self._hbm_name = f"model-{next(_MODEL_IDS)}"
        # identity of the ledger this model registered on: a
        # flightrec.reset() swaps the singleton, so a plain once-flag
        # would permanently drop this model's row from later scrapes
        self._hbm_registered_on = None

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, value):
        self._params = value
        self._params_version += 1

    # ------------------------------------------------------------------ graph

    def _name(self, kind: str, name: Optional[str]) -> str:
        if name:
            return name
        n = self._op_counters.get(kind, 0)
        self._op_counters[kind] = n + 1
        return f"{kind}_{n}" if n else kind

    def _add(self, op: Op) -> Union[Tensor, List[Tensor]]:
        assert self.get_op_by_name(op.name) is None, \
            f"duplicate op name {op.name!r} (params/strategies key by name)"
        self.ops.append(op)
        return op.outputs[0] if len(op.outputs) == 1 else op.outputs

    def create_tensor(self, dims: Sequence[int],
                      dtype: DataType = DataType.DT_FLOAT,
                      name: Optional[str] = None,
                      create_grad: bool = True) -> Tensor:
        if not isinstance(dtype, DataType):
            # the classic misuse is passing the NAME positionally where dtype
            # goes — without this check the string rides the graph and
            # surfaces as a KeyError deep inside measurement/serialization
            raise TypeError(
                f"create_tensor dtype must be a DataType enum, got "
                f"{dtype!r} — did you mean name={dtype!r}?")
        op = InputOp(self, self._name("input", name), tuple(dims), dtype)
        op.finalize()
        assert self.get_op_by_name(op.name) is None, \
            f"duplicate input name {op.name!r} (batch dicts key by name)"
        self.ops.append(op)
        return op.outputs[0]

    # layer factories (reference: flexflow_c.h flexflow_model_add_*)

    def dense(self, input: Tensor, out_dim: int,
              activation: ActiMode = ActiMode.AC_MODE_NONE,
              use_bias: bool = True, name: Optional[str] = None, **kw) -> Tensor:
        return self._add(Linear(self, self._name("dense", name), [input],
                                out_dim, activation, use_bias))

    def conv2d(self, input: Tensor, out_channels: int, kernel_h: int,
               kernel_w: int, stride_h: int, stride_w: int, padding_h: int,
               padding_w: int, activation: ActiMode = ActiMode.AC_MODE_NONE,
               groups: int = 1, use_bias: bool = True,
               name: Optional[str] = None, **kw) -> Tensor:
        return self._add(Conv2D(self, self._name("conv2d", name), [input],
                                out_channels, kernel_h, kernel_w, stride_h,
                                stride_w, padding_h, padding_w, activation,
                                groups, use_bias))

    def pool2d(self, input: Tensor, kernel_h: int, kernel_w: int,
               stride_h: int, stride_w: int, padding_h: int, padding_w: int,
               pool_type: PoolType = PoolType.POOL_MAX,
               activation: ActiMode = ActiMode.AC_MODE_NONE,
               name: Optional[str] = None) -> Tensor:
        return self._add(Pool2D(self, self._name("pool2d", name), [input],
                                kernel_h, kernel_w, stride_h, stride_w,
                                padding_h, padding_w, pool_type, activation))

    def embedding(self, input: Tensor, num_entries: int, out_dim: int,
                  aggr: AggrMode = AggrMode.AGGR_MODE_NONE,
                  name: Optional[str] = None, **kw) -> Tensor:
        return self._add(Embedding(self, self._name("embedding", name), [input],
                                   num_entries, out_dim, aggr))

    def batch_norm(self, input: Tensor, relu: bool = True,
                   name: Optional[str] = None) -> Tensor:
        return self._add(BatchNorm(self, self._name("batch_norm", name),
                                   [input], relu))

    def layer_norm(self, input: Tensor, eps: float = 1e-5,
                   elementwise_affine: bool = True,
                   name: Optional[str] = None) -> Tensor:
        return self._add(LayerNorm(self, self._name("layer_norm", name),
                                   [input], eps, elementwise_affine))

    def add_layer_norm(self, input: Tensor, residual: Tensor,
                       eps: float = 1e-5,
                       name: Optional[str] = None) -> List[Tensor]:
        """Fused (input + residual, LN(input + residual)); returns
        [sum, normed]."""
        out = self._add(AddLayerNorm(self, self._name("add_ln", name),
                                     [input, residual], eps))
        return out if isinstance(out, list) else [out]

    def rms_norm(self, input: Tensor, eps: float = 1e-6,
                 name: Optional[str] = None) -> Tensor:
        return self._add(RMSNorm(self, self._name("rms_norm", name), [input], eps))

    def lstm(self, input: Tensor, hidden_size: int,
             return_sequences: bool = True, name: Optional[str] = None) -> Tensor:
        from flexflow_tpu.ops.recurrent import LSTM

        return self._add(LSTM(self, self._name("lstm", name), [input],
                              hidden_size, return_sequences))

    def gru(self, input: Tensor, hidden_size: int,
            return_sequences: bool = True, name: Optional[str] = None) -> Tensor:
        from flexflow_tpu.ops.recurrent import GRU

        return self._add(GRU(self, self._name("gru", name), [input],
                             hidden_size, return_sequences))

    def moe(self, input: Tensor, num_experts: int, hidden_dim: int,
            k: int = 2, capacity_factor: Optional[float] = 1.25,
            dispatch: str = "auto", expert: str = "gelu",
            renormalize: bool = True, scoring: str = "softmax",
            score_bias: Optional[float] = None, n_group: int = 1,
            topk_group: int = 1, routed_scaling: float = 1.0,
            shared_hidden_dim: int = 0, experts_held=None,
            aux_weight: float = 1e-2, latent_dim: int = 0,
            zero_experts: int = 0, router_f32: bool = False,
            name: Optional[str] = None) -> Tensor:
        """Mixture-of-experts FFN (net-new vs reference; expert-parallel over
        the 'expert' mesh axis). Returns the main output; the load-balancing
        aux loss, times `aux_weight` (0: the training loss is the task's
        alone), is folded into the training loss automatically.
        capacity_factor=None is the dropless op (no dropped token; lowered
        by the call's static shape, ops/moe.py; no `dispatch`). Otherwise
        dispatch: "auto" (dense einsums when experts are mesh-sharded, else
        sort-based) | "dense" | "sort". expert: "gelu" (w_in, w_out) |
        "swiglu" (w_gate, w_up, w_down) | "relu2" (w_up, w_down; dropless
        only); renormalize: kept gates rescaled to sum to 1 per token.
        latent_dim: the routed experts work in a space that narrow, between
        w_latent_in and w_latent_out (dropless only). The dropless op also
        takes the router's form (scoring, score_bias, n_group / topk_group,
        routed_scaling), a shared expert (shared_hidden_dim) and
        experts_held=(first, count), one chip's share of an
        expert-parallel layer, and zero_experts, router columns past
        num_experts whose pick returns the token itself times its gate
        (router_f32: the softmax router's matmul in float32): ops/moe.py."""
        from flexflow_tpu.ops.moe import MoE

        op = MoE(self, self._name("moe", name), [input], num_experts,
                 hidden_dim, k, capacity_factor, aux_weight=aux_weight,
                 dispatch=dispatch,
                 expert=expert, renormalize=renormalize, scoring=scoring,
                 score_bias=score_bias, n_group=n_group,
                 topk_group=topk_group, routed_scaling=routed_scaling,
                 shared_hidden_dim=shared_hidden_dim,
                 experts_held=experts_held, latent_dim=latent_dim,
                 zero_experts=zero_experts, router_f32=router_f32)
        outs = self._add(op)
        self._aux_tensors.append(outs[1])
        return outs[0]

    def batch_matmul(self, a: Tensor, b: Tensor,
                     name: Optional[str] = None) -> Tensor:
        return self._add(BatchMatmul(self, self._name("batch_matmul", name), [a, b]))

    def flat(self, input: Tensor, name: Optional[str] = None) -> Tensor:
        return self._add(Flat(self, self._name("flat", name), [input]))

    def softmax(self, input: Tensor, axis: int = -1,
                name: Optional[str] = None) -> Tensor:
        return self._add(Softmax(self, self._name("softmax", name), [input], axis))

    def dropout(self, input: Tensor, rate: float, seed: int = 0,
                name: Optional[str] = None) -> Tensor:
        return self._add(Dropout(self, self._name("dropout", name), [input],
                                 rate, seed))

    def multihead_attention(self, query: Tensor, key: Tensor, value: Tensor,
                            embed_dim: int, num_heads: int, kdim: int = 0,
                            vdim: int = 0, dropout: float = 0.0,
                            bias: bool = True, add_bias_kv: bool = False,
                            add_zero_attn: bool = False, causal: bool = False,
                            num_kv_heads: int = 0, rope: bool = False,
                            rope_theta: float = 10000.0,
                            qk_norm=False, eps: float = 1e-6,
                            window: int = 0, flash_chunks: bool = False,
                            softmax_scale: Optional[float] = None,
                            rope_dim: int = 0, sink: Optional[float] = None,
                            value_scale: float = 1.0,
                            name: Optional[str] = None, **kw) -> Tensor:
        """`window` > 0: a sliding-window layer (position i sees keys
        i - window < j <= i); `qk_norm`: True over all heads of a position,
        "head" over each head's entries; `softmax_scale`: what q k^T is
        multiplied by, None = 1 / sqrt(head size); `rope_dim` > 0: rotary
        over a head's first `rope_dim` entries only; `sink`: a learned logit
        a query head in the softmax's denominator (the value is the seeded
        draw's standard deviation); `value_scale`: v = value_scale * x Wv
        (ops/attention.py)."""
        return self._add(MultiHeadAttention(
            self, self._name("multihead_attention", name), [query, key, value],
            embed_dim, num_heads, kdim, vdim, dropout, bias, add_bias_kv,
            add_zero_attn, causal, num_kv_heads=num_kv_heads, rope=rope,
            rope_theta=rope_theta, qk_norm=qk_norm, eps=eps, window=window,
            flash_chunks=flash_chunks, softmax_scale=softmax_scale,
            rope_dim=rope_dim, sink=sink, value_scale=value_scale))

    def latent_attention(self, input: Tensor, embed_dim: int, num_heads: int,
                         q_lora_rank: Optional[int], kv_lora_rank: int,
                         qk_nope_head_dim: int, qk_rope_head_dim: int,
                         v_head_dim: int,
                         index_n_heads: Optional[int] = None,
                         index_head_dim: Optional[int] = None,
                         index_topk: Optional[int] = None,
                         rope_theta: float = 10000.0,
                         rope_scaling: Optional[dict] = None,
                         eps: float = 1e-6, uq_init_gain: float = 1.0,
                         q_lora_scale: float = 1.0,
                         kv_lora_scale: float = 1.0,
                         name: Optional[str] = None) -> Tensor:
        """Causal multi-head latent self-attention with a learned top-k
        selection of the cached tokens (DeepSeek MLA + lightning indexer,
        ops/mla.py): the cache holds one latent row and one index key a
        token instead of K and V per head. `q_lora_rank=None`: no query
        compression; `index_topk=None`: no indexer, plain causal MLA;
        `q_lora_scale` / `kv_lora_scale`: factors on the projected query
        and on the normalised latent (LongCat-Flash)."""
        from flexflow_tpu.ops.mla import LatentAttention

        return self._add(LatentAttention(
            self, self._name("latent_attention", name), [input], embed_dim,
            num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
            qk_rope_head_dim, v_head_dim, index_n_heads, index_head_dim,
            index_topk, rope_theta=rope_theta, rope_scaling=rope_scaling,
            eps=eps, uq_init_gain=uq_init_gain, q_lora_scale=q_lora_scale,
            kv_lora_scale=kv_lora_scale))

    def mamba2(self, input: Tensor, num_heads: int, head_dim: int,
               n_groups: int, state_size: int, conv_kernel: int = 4,
               chunk_size: int = 128, eps: float = 1e-5,
               name: Optional[str] = None) -> Tensor:
        """Mamba-2 mixer (ops/mamba.py): a selective state-space layer of
        `num_heads` heads of `head_dim`, B and C shared by `n_groups` groups
        of heads, `state_size` state columns a head; its cache is one
        fixed-size recurrent state a sequence."""
        from flexflow_tpu.ops.mamba import Mamba2Mixer

        return self._add(Mamba2Mixer(
            self, self._name("mamba2", name), [input], num_heads, head_dim,
            n_groups, state_size, conv_kernel=conv_kernel,
            chunk_size=chunk_size, eps=eps))

    def power_retention(self, input: Tensor, num_heads: int,
                        num_kv_heads: int, head_dim: int,
                        rope_theta: float = 1e6, chunk_size: int = 128,
                        eps: float = 1e-6, norm_eps: float = 1e-5,
                        decay_floor=(1e-4, 1e-2),
                        name: Optional[str] = None) -> Tensor:
        """Power-retention mixer (ops/retention.py): `num_heads` query heads
        of `head_dim` over `num_kv_heads` key / value heads, weights the
        square of the scaled scores under a gated decay; its cache is one
        fixed-size state a sequence (the keys' symmetric square times the
        values) and no per-token row."""
        from flexflow_tpu.ops.retention import PowerRetention

        return self._add(PowerRetention(
            self, self._name("power_retention", name), [input], num_heads,
            num_kv_heads, head_dim, rope_theta=rope_theta,
            chunk_size=chunk_size, eps=eps, norm_eps=norm_eps,
            decay_floor=decay_floor))

    def gated_mlp(self, input: Tensor, hidden_dim: int,
                  name: Optional[str] = None) -> Tensor:
        """SwiGLU feed-forward as one op (ops/dense.py `GatedMLP`): one
        in-projection to [gate | up] of 2 x `hidden_dim`, silu(gate) * up,
        one out-projection back."""
        from flexflow_tpu.ops.dense import GatedMLP

        return self._add(GatedMLP(self, self._name("gated_mlp", name),
                                  [input], hidden_dim))

    def transformer_pipeline_stack(self, input: Tensor, num_layers: int,
                                   num_heads: int, ffn_mult: int = 4,
                                   causal: bool = False,
                                   num_microbatches: Optional[int] = None,
                                   name: Optional[str] = None) -> Tensor:
        """L identical transformer blocks with stacked weights; under a
        'pipe' mesh axis the stack runs as a GPipe ring (graph-level pipeline
        parallelism — the reference's NMT chunked-timestep scheme, rnn.h:21-63,
        re-designed for TPU as layer stacking; see ops/pipelined.py)."""
        from flexflow_tpu.ops.pipelined import TransformerPipelineStack

        return self._add(TransformerPipelineStack(
            self, self._name("transformer_pipeline_stack", name), [input],
            num_layers, num_heads, ffn_mult, causal, num_microbatches))

    def reshape(self, input: Tensor, shape: Sequence[int],
                name: Optional[str] = None) -> Tensor:
        return self._add(Reshape(self, self._name("reshape", name), [input], shape))

    def transpose(self, input: Tensor, perm: Sequence[int],
                  name: Optional[str] = None) -> Tensor:
        return self._add(Transpose(self, self._name("transpose", name), [input], perm))

    def reverse(self, input: Tensor, axis: int,
                name: Optional[str] = None) -> Tensor:
        return self._add(Reverse(self, self._name("reverse", name), [input], axis))

    def concat(self, tensors: Sequence[Tensor], axis: int,
               name: Optional[str] = None) -> Tensor:
        return self._add(Concat(self, self._name("concat", name), list(tensors), axis))

    def split(self, input: Tensor, sizes: Union[int, Sequence[int]], axis: int,
              name: Optional[str] = None) -> List[Tensor]:
        if isinstance(sizes, int):
            n = sizes
            d = input.dims[axis]
            assert d % n == 0
            sizes = [d // n] * n
        out = self._add(Split(self, self._name("split", name), [input],
                              sizes, axis))
        return out if isinstance(out, list) else [out]

    def topk(self, input: Tensor, k: int, sorted: bool = True,
             name: Optional[str] = None) -> List[Tensor]:
        out = self._add(TopK(self, self._name("topk", name), [input], k, sorted))
        return out if isinstance(out, list) else [out]

    def gather(self, input: Tensor, index: Tensor, axis: int,
               name: Optional[str] = None) -> Tensor:
        return self._add(Gather(self, self._name("gather", name),
                                [input, index], axis))

    def cast(self, input: Tensor, dtype: DataType,
             name: Optional[str] = None) -> Tensor:
        return self._add(Cast(self, self._name("cast", name), [input], dtype))

    def pad(self, input: Tensor, pads, value: float = 0.0,
            name: Optional[str] = None) -> Tensor:
        return self._add(Pad(self, self._name("pad", name), [input], pads, value))

    def mean(self, input: Tensor, dims: Sequence[int], keepdims: bool = False,
             name: Optional[str] = None) -> Tensor:
        return self._add(Mean(self, self._name("mean", name), [input], dims, keepdims))

    # elementwise unary/binary

    def _unary(self, op_type: OperatorType, x: Tensor, name=None,
               scalar=None) -> Tensor:
        kind = op_type.name[3:].lower()
        return self._add(ElementUnary(self, self._name(kind, name), [x],
                                      op_type, scalar))

    def _binary(self, op_type: OperatorType, a: Tensor, b: Tensor, name=None) -> Tensor:
        kind = op_type.name[3:].lower()
        return self._add(ElementBinary(self, self._name(kind, name), [a, b], op_type))

    def exp(self, x, name=None):
        return self._unary(OperatorType.OP_EXP, x, name)

    def sin(self, x, name=None):
        return self._unary(OperatorType.OP_SIN, x, name)

    def cos(self, x, name=None):
        return self._unary(OperatorType.OP_COS, x, name)

    def relu(self, x, name=None):
        return self._unary(OperatorType.OP_RELU, x, name)

    def sigmoid(self, x, name=None):
        return self._unary(OperatorType.OP_SIGMOID, x, name)

    def tanh(self, x, name=None):
        return self._unary(OperatorType.OP_TANH, x, name)

    def elu(self, x, name=None):
        return self._unary(OperatorType.OP_ELU, x, name)

    def gelu(self, x, name=None):
        return self._unary(OperatorType.OP_GELU, x, name)

    def identity(self, x, name=None):
        return self._unary(OperatorType.OP_IDENTITY, x, name)

    def pow(self, x, exponent: float, name=None):
        return self._unary(OperatorType.OP_POW, x, name, scalar=exponent)

    def rsqrt(self, x, name=None):
        return self._unary(OperatorType.OP_RSQRT, x, name)

    def scalar_multiply(self, x, scalar: float, name=None):
        return self._unary(OperatorType.OP_SCALAR_MULTIPLY, x, name, scalar=scalar)

    def add(self, a, b, name=None):
        return self._binary(OperatorType.OP_EW_ADD, a, b, name)

    def subtract(self, a, b, name=None):
        return self._binary(OperatorType.OP_EW_SUB, a, b, name)

    def multiply(self, a, b, name=None):
        return self._binary(OperatorType.OP_EW_MUL, a, b, name)

    def divide(self, a, b, name=None):
        return self._binary(OperatorType.OP_EW_DIV, a, b, name)

    def max(self, a, b, name=None):
        return self._binary(OperatorType.OP_EW_MAX, a, b, name)

    def min(self, a, b, name=None):
        return self._binary(OperatorType.OP_EW_MIN, a, b, name)

    # -------------------------------------------------------------- compile

    def tie_weights(self, dst_op: str, dst_weight: str, src_op: str,
                    src_weight: str, transform: str = "same"):
        """Share one stored weight between two ops (reference parity: the
        NMT subsystem's SharedVariable, nmt/rnn.h:37-51, one logical
        weight behind many timestep ops; modern use: tied embedding /
        lm_head). The destination op stops owning storage — its weight is
        resolved from the source at trace time (transform: "same" |
        "transpose"), so gradients from both ops accumulate into the one
        array through autodiff. Call after building both ops, before
        compile()."""
        if transform not in ("same", "transpose"):
            raise ValueError(f"transform must be 'same' or 'transpose', "
                             f"got {transform!r}")
        if getattr(self, "executor", None) is not None:
            raise ValueError(
                "tie_weights must be called before compile(): params and "
                "the jitted step are already built, so a late tie would "
                "be silently ignored by traced programs")
        s, d = self.get_op_by_name(src_op), self.get_op_by_name(dst_op)
        for nm, op in ((src_op, s), (dst_op, d)):
            if op is None:
                raise ValueError(f"tie_weights: no op named {nm!r}")
        specs_s = {w.name: w for w in s.weight_specs()}
        specs_d = {w.name: w for w in d.weight_specs()}
        if src_weight not in specs_s:
            raise ValueError(f"tie_weights: {src_op!r} has no weight "
                             f"{src_weight!r} (has {list(specs_s)})")
        if dst_weight not in specs_d:
            raise ValueError(f"tie_weights: {dst_op!r} has no weight "
                             f"{dst_weight!r} (has {list(specs_d)})")
        shape_s = tuple(specs_s[src_weight].shape)
        if transform == "transpose":
            shape_s = shape_s[::-1]
        if tuple(specs_d[dst_weight].shape) != shape_s:
            raise ValueError(
                f"tie_weights: shape mismatch — {dst_op}.{dst_weight} is "
                f"{tuple(specs_d[dst_weight].shape)} but {src_op}."
                f"{src_weight} {transform} gives {shape_s}")
        if (src_op, src_weight) in self._tied:
            raise ValueError(
                f"tie_weights: source {src_op}.{src_weight} is itself tied "
                f"— chain ties to the original storage instead")
        if (dst_op, dst_weight) in self._tied:
            prev = self._tied[(dst_op, dst_weight)]
            raise ValueError(
                f"tie_weights: {dst_op}.{dst_weight} is already tied to "
                f"{prev[0]}.{prev[1]}")
        if any(src == (dst_op, dst_weight)
               for src in ((v[0], v[1]) for v in self._tied.values())):
            raise ValueError(
                f"tie_weights: {dst_op}.{dst_weight} is the SOURCE of an "
                f"existing tie; it must keep its storage — reverse the tie "
                f"or chain the other ops to the same source")
        self._tied[(dst_op, dst_weight)] = (src_op, src_weight, transform)

    def get_op_by_name(self, name: str) -> Optional[Op]:
        for op in self.ops:
            if op.name == name:
                return op
        return None

    def _setup_span(self, name: str, **counts):
        """A live lifecycle span on the ``setup`` track (ring event +
        ``ff.<name>`` annotation); the shared no-op under
        ``FFConfig.telemetry="off"``."""
        if getattr(self.config, "telemetry", "on") == "off":
            return _telemetry.NULL_SPAN
        return _telemetry.tracer().span(name, track="setup", **counts)

    def _first_call(self, name: str, fn, args, shapes=()):
        """``fn(*args)`` for one of this model's jitted programs. Its
        FIRST call (for eval and predict: the first of each batch shape)
        is where it is traced, lowered and compiled or loaded, so it runs
        under a ``compile`` span that ends when the outputs are ready;
        every later call is the bare call behind one set lookup. A second
        executable that jit builds on a mesh when the second call's
        shardings differ opens no span: jax's durations for it land in
        ``telemetry.setup_totals()``."""
        key = (name, shapes)
        if key in self._called:
            return fn(*args)
        self._called.add(key)
        span = self._setup_span("compile", program=name)
        if span is _telemetry.NULL_SPAN:    # off: neither span nor barrier
            return fn(*args)
        with span:
            out = fn(*args)
            jax.block_until_ready(out)
        return out

    def compile(self, optimizer=None,
                loss_type: Union[LossType, str] = LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
                metrics: Sequence = (MetricsType.METRICS_ACCURACY,),
                comp_mode: CompMode = CompMode.COMP_MODE_TRAINING,
                final_tensor: Optional[Tensor] = None):
        """Resolve strategies -> build mesh -> init sharded params.

        Reference: FFModel::compile (model.cc:1481-1646): optional strategy
        search, per-op create_output_and_partition/create_weights, fusion,
        label tensor, optimizer init.

        The whole of it is the ``model_compile`` span on the ``setup``
        track, over ``strategy_search``, ``init_params`` and
        ``init_optimizer`` (runtime/telemetry.py ``LIFECYCLE_SPANS``). The
        body stays in THIS frame: a wrapper frame between the caller and
        ``init_params`` made jax trace and lower the init programs a third
        slower on the chip's host (PERF.md section 6, PR 48).
        """
        with self._setup_span("model_compile") as whole:
            cfg = self.config
            self.optimizer = optimizer
            self.loss_type = loss_type_from_name(loss_type)
            self.metric_types = metrics_from_names(metrics)
            self.comp_mode = comp_mode
            # strategy import must land BEFORE the elastic hook: on a relaunch
            # with the original flags the imported file describes the OLD
            # topology, and the hook's mesh-refit re-derivation has to win over
            # it, not be clobbered by it
            if cfg.import_strategy_file:
                cfg.strategies.update(
                    load_strategies_from_file(cfg.import_strategy_file))
            # elastic recovery (runtime/elastic.py): with a checkpoint_dir set,
            # compare the newest intact checkpoint's recorded topology against
            # what this process actually has BEFORE the mesh is built — a
            # restart on fewer devices refits the mesh (csim-ranked), re-derives
            # the saved strategy, and preserves the global batch via grad-accum,
            # per cfg.on_topology_change; the later restore then re-shards the
            # saved params onto whatever mesh this compile produces
            self._elastic = None
            if cfg.checkpoint_dir:
                from flexflow_tpu.runtime.elastic import apply_elastic_policy

                self._elastic = apply_elastic_policy(self)
            self.mesh = make_mesh(cfg.mesh_shape)

            if cfg.search_budget > 0:
                with self._setup_span(
                        "strategy_search", budget=cfg.search_budget) as span:
                    from flexflow_tpu.search.driver import optimize_strategies_multi

                    # persistent cost DB: already-keyed op signatures load from
                    # disk instead of re-measuring/re-compiling (search/cost_db.py)
                    db_path = getattr(cfg, "cost_db_path", "") or None
                    measured = None
                    if cfg.measure_search_costs == "analyze":
                        from flexflow_tpu.search.measure import analyze_op_costs

                        measured = analyze_op_costs(
                            self, cfg.mesh_shape,
                            enable_parameter_parallel=cfg.enable_parameter_parallel,
                            enable_attribute_parallel=cfg.enable_attribute_parallel,
                            verbose=cfg.profiling, db_path=db_path)
                    elif cfg.measure_search_costs:
                        from flexflow_tpu.search.measure import measure_op_costs

                        measured = measure_op_costs(
                            self, cfg.mesh_shape,
                            cfg.enable_parameter_parallel,
                            cfg.enable_attribute_parallel,
                            verbose=cfg.profiling, db_path=db_path)
                    machine = None
                    if cfg.dcn_mesh_shape:
                        # two-tier topology: axes listed in dcn_mesh_shape span that
                        # many hosts, so their collectives are priced at the DCN tier
                        from flexflow_tpu.search.machine import MachineModel

                        machine = MachineModel(dcn_axes=dict(cfg.dcn_mesh_shape))
                    # multi-objective: time subject to the per-chip HBM cap — when
                    # the time-optimal strategy fits (the common case) the relief
                    # loop is a no-op and this is exactly the old time-only search
                    best = optimize_strategies_multi(self, budget=cfg.search_budget,
                                                     alpha=cfg.search_alpha,
                                                     machine=machine,
                                                     measured=measured)
                    cfg.strategies.update(best)
                    report = getattr(self, "_search_summary", None) or {}
                    seeds = len(report.get("seed_costs", ()))
                    span.annotate(simulator=report.get("simulator", ""),
                                  seeds=seeds,
                                  candidates=seeds + cfg.search_budget)
                    if cfg.export_strategy_file:
                        save_strategies_to_file(cfg.export_strategy_file, cfg.strategies)

            if cfg.strategy_lint != "off":
                # fflint (analysis/): static validation of the now-final
                # strategy table — pure graph+table checks, no tracing. A bad
                # strategy is named HERE (op + pass + rule) instead of
                # surfacing as a mesh-build/XLA error with no line back to
                # the offending axis. The schema pass (text-file round-trip,
                # a tempfile write per run) is file-facing and stays with the
                # CLI/scripts callers — compile validates the in-memory table.
                from flexflow_tpu.analysis import StrategyLintError, analyze
                from flexflow_tpu.logger import fflogger

                report = analyze(self, strategies=cfg.strategies,
                                 mesh_shape=cfg.mesh_shape,
                                 passes=("legality", "perf"))
                if cfg.strategy_lint == "strict" and report.errors():
                    raise StrategyLintError(report)
                report.log(fflogger)
                # stash the footprint pass's per-chip HBM estimate for the
                # accounting ledger's cross-check (runtime/flightrec.py:
                # ff_hbm_lint_estimated_bytes vs the tracked byte ledger) —
                # the lint already computed it, this costs nothing
                rows = report.by_code("hbm-footprint")
                if rows and rows[0].est_bytes:
                    self._lint_hbm_estimate = float(rows[0].est_bytes)

            self._final_tensor = final_tensor or self.ops[-1].outputs[0]
            # fused softmax + cross-entropy, the reference semantics: its CE
            # loss kernels consume the Softmax OUTPUT with an identity backward
            # through the softmax (loss_functions.cu grad = probs - one_hot),
            # which equals CE-from-logits. compute_loss applies log_softmax
            # itself, so a graph ending in Softmax must feed the loss its
            # logits INPUT — otherwise training runs on a double softmax with
            # flattened gradients. predict()/generate() still return the
            # softmax output.
            self._loss_tensor = self._final_tensor
            if self.loss_type in (LossType.LOSS_CATEGORICAL_CROSSENTROPY,
                                  LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY):
                fop = self._final_tensor.owner_op
                from flexflow_tpu.ops.norm import Softmax as _Softmax

                if isinstance(fop, _Softmax) \
                        and fop.axis in (-1, fop.outputs[0].num_dims - 1):
                    self._loss_tensor = fop.inputs[0]

            if cfg.perform_fusion:
                # reference: FFModel::apply_fusion after search (model.cc:1538-1593)
                from flexflow_tpu.ops.fused import apply_fusion

                protected = [self._final_tensor, self._loss_tensor] + list(
                    getattr(self, "_aux_tensors", ()))
                apply_fusion(self, protected=protected)

            # label tensor shaped like the final op's sample dims (model.cc:1615-1646)
            fdims = self._final_tensor.dims
            if self.loss_type == LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY:
                self.label_tensor = Tensor(dims=tuple(fdims[:-1]) + (1,),
                                           dtype=DataType.DT_INT32, name="label")
            else:
                self.label_tensor = Tensor(dims=fdims, dtype=DataType.DT_FLOAT,
                                           name="label")

            from flexflow_tpu.parallel.placement import (PlacementExecutor,
                                                         has_placement)

            if has_placement(cfg.strategies, self.mesh.size):
                # some op is placed on a proper device subset: lower via
                # per-group sub-mesh programs (the reference mapper's per-op
                # device_ids, mapper.cc:346-424)
                self.executor = PlacementExecutor(self)
            else:
                self.executor = GraphExecutor(self)
            self._rng, init_key = jax.random.split(self._rng)
            with self._setup_span("init_params") as span:
                self.params = self.executor.init_params(init_key)
                weights = jax.tree_util.tree_leaves(self.params)
                # one jitted program a weight (executor.init_params)
                span.annotate(weights=len(weights), programs=len(weights),
                              bytes=_tree_nbytes(weights))
            self.bn_state = self.executor.init_state()
            if self.optimizer is not None:
                if getattr(self.config, "fused_optimizer", False):
                    self.optimizer = self._maybe_fuse_optimizer(self.optimizer)
                if getattr(cfg, "overlap_grad_sync", False):
                    self.optimizer = self._maybe_shard_optimizer(self.optimizer)
                with self._setup_span("init_optimizer") as span:
                    self.opt_state = self.optimizer.init_state(self.params)
                    span.annotate(bytes=_tree_nbytes(self.opt_state))
                self._registered = {}   # programs of an earlier compile()
                self._train_step = self.executor.make_train_step(
                    self.optimizer, self.loss_type, self.metric_types,
                    self._loss_tensor)
                if cfg.on_nonfinite != "none":
                    from flexflow_tpu.logger import fflogger

                    if getattr(self.executor, "jits_per_group", False) \
                            or cfg.grad_accum_steps > 1:
                        fflogger.warning(
                            "on_nonfinite=%r: divergence guard unsupported "
                            "under operator placement / grad accumulation — "
                            "training runs unguarded", cfg.on_nonfinite)
                    else:
                        from flexflow_tpu.runtime.resilience import \
                            init_guard_state

                        self._guarded_step = \
                            self.executor.make_guarded_train_step(
                                self.optimizer, self.loss_type,
                                self.metric_types, self._loss_tensor,
                                guard_cfg={
                                    "on_nonfinite": cfg.on_nonfinite,
                                    "growth_interval":
                                        cfg.loss_scale_growth_interval,
                                })
                        self._guard_state = init_guard_state(cfg.loss_scale)
            self._eval_step = self.executor.make_eval_step(
                self.loss_type, self.metric_types, self._loss_tensor)
            self._called = set()        # every program compiles afresh

            if cfg.taskgraph_file:
                from flexflow_tpu.runtime.profiler import export_sim_taskgraph

                export_sim_taskgraph(self, cfg.taskgraph_file)

            if getattr(cfg, "telemetry", "on") != "off":
                # HBM accounting ledger (runtime/flightrec.py, ISSUE 15):
                # params/opt-state byte rows + the lint footprint
                # cross-check, published as ff_hbm_* gauges at scrape time
                # and embedded in every post-mortem bundle. Registered ONCE
                # per model (a recompile must not duplicate the source),
                # under a per-instance name (two models in one process must
                # not overwrite each other's rows).
                from flexflow_tpu.runtime import flightrec

                led = flightrec.hbm_ledger()
                if self._hbm_registered_on is not led:
                    self._hbm_registered_on = led
                    led.add_source(self._hbm_source)
                if self._lint_hbm_estimate is not None:
                    flightrec.hbm_ledger().set_lint_estimate(
                        self._lint_hbm_estimate)
            weights = jax.tree_util.tree_leaves(self.params)
            whole.annotate(
                ops=len(self.ops), weights=len(weights),
                weight_bytes=_tree_nbytes(weights),
                mesh=",".join(f"{a}={n}" for a, n
                              in self.config.mesh_shape.items()))

    def _maybe_fuse_optimizer(self, opt):
        """FFConfig.fused_optimizer: replicated-param strategies (single
        device / pure DP) get the global-flatten FusedUpdate; GSPMD-sharded
        strategies (TP/FSDP) get ShardedFusedUpdate, which flattens each
        device's LOCAL shards inside a shard_map — shard-local, zero
        collectives. Only operator-placement lowering still falls back to
        the per-leaf update (params live on disjoint sub-meshes, so no
        single program sees them all); a leaf whose shape doesn't divide
        its mesh extent also falls back, with the leaf named."""
        from flexflow_tpu.logger import fflogger
        from flexflow_tpu.runtime.optimizer import (FusedUpdate,
                                                    ShardedFusedUpdate)

        if getattr(self.executor, "jits_per_group", False):
            fflogger.warning(
                "fused_optimizer: unsupported under an operator-placement "
                "strategy (params live on disjoint sub-meshes) — using "
                "the per-leaf update")
            return opt
        shardings = (self.executor.param_shardings()
                     if self.mesh is not None and self.mesh.devices.size > 1
                     else {})
        sharded = any(any(e is not None for e in ns.spec)
                      for per_op in shardings.values()
                      for ns in per_op.values())
        if not sharded:
            return FusedUpdate(opt)

        from jax.sharding import PartitionSpec as P

        specs = {}
        for op, ws in self.params.items():
            specs[op] = {}
            for w, arr in ws.items():
                ns = shardings.get(op, {}).get(w)
                spec = ns.spec if ns is not None else P()
                try:
                    ShardedFusedUpdate.local_leaf_size(
                        arr.shape, spec, self.mesh)
                except ValueError as e:
                    fflogger.warning(
                        "fused_optimizer: weight %s/%s: %s — using the "
                        "per-leaf update", op, w, e)
                    return opt
                specs[op][w] = spec
        return ShardedFusedUpdate(opt, self.mesh, specs)

    def _maybe_shard_optimizer(self, opt):
        """FFConfig.overlap_grad_sync: pair the bucketed in-scan gradient
        reduce-scatter with the ZeRO-1 sharded optimizer update
        (runtime/optimizer.py Zero1Update) — each data shard updates its
        slice of params/opt-state from the already-scattered grads, then
        params all-gather once; opt-state HBM divides by the data degree.
        Falls back (reason logged) under operator placement (no single
        program sees every param), under a fused optimizer (its flat
        state layout is already sharded its own way — the in-scan grad
        buckets still apply), or on a mesh with no data axis > 1."""
        from flexflow_tpu.logger import fflogger
        from flexflow_tpu.runtime.optimizer import (FusedUpdate,
                                                    ShardedFusedUpdate,
                                                    Zero1Update)

        if getattr(self.executor, "jits_per_group", False):
            fflogger.warning(
                "overlap_grad_sync: ZeRO-1 update unsupported under an "
                "operator-placement strategy — using the unsharded update")
            return opt
        if isinstance(opt, (FusedUpdate, ShardedFusedUpdate)):
            fflogger.warning(
                "overlap_grad_sync: fused_optimizer already stores flat "
                "state in its own layout — skipping the ZeRO-1 wrap (the "
                "in-scan gradient buckets still apply)")
            return opt
        scatter = self.executor.grad_scatter_shardings()
        if not scatter:
            fflogger.info(
                "overlap_grad_sync: no data axis > 1 on mesh %s — nothing "
                "to scatter over", self.config.mesh_shape)
            return opt
        return Zero1Update(opt, scatter, self.executor.param_shardings())

    # ---------------------------------------------------------- train verbs

    def _stage_batch(self):
        batch = {}
        for dl in self._dataloaders:
            batch[dl.name] = dl.next_batch()
        return batch

    def _reset_dataloaders(self):
        for dl in self._dataloaders:
            dl.reset()

    def init_layers(self):
        """API parity (reference FFModel::init_layers model.cc:1342); params
        are initialized in compile(), so this is a barrier only."""
        jax.block_until_ready(self.params)

    def zero_gradients(self):
        pass  # functional autodiff: gradients are created fresh each step

    def next_batch_all(self):
        self._current_batch = self._stage_batch()

    def forward(self):
        pass  # fused into backward's value_and_grad (see class docstring)

    def backward(self):
        pass  # fused into update()

    def update(self):
        """Run one fused train step on the staged batch."""
        batch = self._current_batch or self._stage_batch()
        self._run_train_step(batch)

    def _run_train_step(self, batch: Dict[str, np.ndarray],
                        inject_nan: bool = False):
        sharded = self.executor.shard_batch(batch)
        self._rng, step_key = jax.random.split(self._rng)
        if self._guarded_step is not None:
            # guarded path (config.on_nonfinite != "none"): same RNG
            # split, bitwise-identical trajectory while finite; non-finite
            # steps leave params/opt state untouched in-graph. inject_nan
            # is the FF_FAULT nan_loss hook (a traced arg — no recompile).
            args = (self.params, self.opt_state, self.bn_state, sharded,
                    step_key, self._guard_state,
                    jnp.asarray(bool(inject_nan)))
            self._register_program("train_step", self._guarded_step, args)
            (self.params, self.opt_state, self.bn_state, loss, mets,
             self._guard_state) = self._first_call(
                 "train_step", self._guarded_step, args)
        else:
            if inject_nan:
                raise RuntimeError(
                    "nan_loss injection needs the in-graph divergence "
                    "guard: set FFConfig.on_nonfinite before compile()")
            args = (self.params, self.opt_state, self.bn_state, sharded,
                    step_key)
            self._register_program("train_step", self._train_step, args)
            (self.params, self.opt_state, self.bn_state, loss, mets) = \
                self._first_call("train_step", self._train_step, args)
        self._step_count += 1
        self._last_loss = loss
        self._last_metrics = mets
        return loss, mets

    def _register_program(self, name: str, fn, args):
        """A call of a train program notes its abstract arguments in the
        process-wide registry (runtime/profiler.py), where
        ``program_scopes()`` can lower it again when asked, if the jitted
        function has compiled since the last note: on a mesh the second
        call's arguments carry the shardings the first call's outputs took
        and compile a second executable, the one that runs from then on.
        Every later call pays one dict lookup, the count of the function's
        executables and the question whether a profiler trace is running.
        Nothing for an executor that jits per placement group (no one
        program to lower)."""
        prog = self._registered.get(name)
        if hasattr(fn, "lower") and (
                prog is None
                or prog.compiles != profiler.executables(fn)):
            prog = self._registered[name] = profiler.register_program(
                name, fn, args, profiler.graph_op_phases(self))
        if prog is not None and profiler.tracing():
            # a traced slice's tables are read after the window
            profiler.note_traced(prog)

    def _scan_eligible(self) -> bool:
        """Scanned multi-step training needs one program over one mesh
        (PlacementExecutor jits per sub-mesh group) and every dataset
        device-resident in the pre-batched (num_batches, batch, ...) layout."""
        return (self._train_step is not None
                # the scanned program has no divergence guard; with a
                # guard compiled in, fit must stay per-step or NaN steps
                # would commit silently
                and self._guarded_step is None
                and self._dataloaders
                # unequal loader lengths wrap per-loader on the per-step
                # path; the scanned program has one batch index, so the
                # two paths would diverge — fall back to per-step
                and len({dl.num_batches for dl in self._dataloaders}) == 1
                and not getattr(self.executor, "jits_per_group", False)
                and all(dl._try_stage_on_device() for dl in self._dataloaders))

    def train_scanned(self, n_steps: int):
        """Run `n_steps` training steps as ONE device program (lax.scan over
        the device-resident dataset — executor.make_train_scan). Per-step
        host dispatch disappears; losses/metrics come back stacked, shape
        (n_steps,). Batch order and wrap policy match the per-step path.
        """
        if not self._scan_eligible():  # NB: eligibility check also stages
            # the loaders on device — must run even under python -O
            raise RuntimeError(
                "train_scanned needs compile() with an optimizer, "
                "device-resident dataloaders, and a single-mesh executor")
        if self._train_scan is None:
            self._train_scan = self.executor.make_train_scan(
                self.optimizer, self.loss_type, self.metric_types,
                self._loss_tensor)
        staged = {dl.name: dl._dev_data for dl in self._dataloaders}
        nb = min(dl.num_batches for dl in self._dataloaders)
        start = (self._dataloaders[0].next_index
                 // self._dataloaders[0].batch_size) % nb
        self._rng, scan_key = jax.random.split(self._rng)
        args = (self.params, self.opt_state, self.bn_state, staged, scan_key,
                start, n_steps)
        self._register_program("train_scan", self._train_scan, args)
        (self.params, self.opt_state, self.bn_state, losses, mets) = \
            self._first_call("train_scan", self._train_scan, args)
        for dl in self._dataloaders:  # keep per-step verbs in sync
            dl.next_index = ((start + n_steps) % nb) * dl.batch_size
        self._step_count += n_steps
        self._last_loss = losses[-1]
        self._last_metrics = {k: v[-1] for k, v in mets.items()}
        return losses, mets

    # ---------------------------------------------------------------- fit

    def fit(self, epochs: Optional[int] = None, batch_size: Optional[int] = None,
            callbacks: Sequence = (), verbose: bool = True):
        """Training loop with throughput print (parity: base_model.py:374-436)."""
        assert self._train_step is not None, "compile() with an optimizer first"
        assert self._dataloaders, \
            "no dataloaders attached; create SingleDataLoader(ff, tensor, data)"
        epochs = epochs or self.config.epochs
        bs = batch_size or self.config.batch_size
        if batch_size is not None:
            for dl in self._dataloaders:
                dl.batch_size = batch_size
        num_batches = min(dl.num_batches for dl in self._dataloaders)
        assert num_batches > 0, (
            f"dataset smaller than batch_size "
            f"({min(dl.num_samples for dl in self._dataloaders)} samples < "
            f"{bs}); no full batch to train on")
        # loader preference: device-resident datasets (next_batch is an
        # on-device slice — the reference's ZC-resident design) > native
        # threaded host prefetch (csrc/dataloader.cc) > Python slicing.
        # Eligibility is decided for ALL loaders before any upload, and a
        # failed upload unstages the others, so a mixed or OOM-ing set never
        # strands half-staged copies in HBM.
        native_dl = None
        for dl in self._dataloaders:
            # a pin from a previous fit() (unstage/OOM) is not permanent:
            # re-attempt once per fit; genuine failures just re-fail
            dl._dev_failed = False
        staged = (all(dl.device_eligible() for dl in self._dataloaders)
                  and all(dl._try_stage_on_device()
                          for dl in self._dataloaders))
        if not staged:
            for dl in self._dataloaders:
                dl.unstage()
            from flexflow_tpu.runtime.native_loader import group_loader_for
            native_dl = group_loader_for(self)
            if native_dl is not None:
                num_batches = native_dl.num_batches
        # multi-step scanned epochs (config.scan_steps chunks per dispatch);
        # callbacks only observe epoch boundaries, so chunking inside an
        # epoch is observationally identical
        use_scan = (self.config.scan_steps > 0 and native_dl is None
                    and staged and self._scan_eligible())
        # fault tolerance (runtime/resilience.py): when checkpoint_dir is
        # set, auto-resume from the newest checkpoint (step counter, RNG,
        # dataloader cursors), checkpoint every checkpoint_every steps,
        # and turn SIGTERM (the preemption notice) into checkpoint-at-the-
        # next-step-boundary + graceful stop
        sup = None
        start_epoch = it0 = 0
        if self.config.checkpoint_dir:
            from flexflow_tpu.runtime.resilience import TrainSupervisor

            sup = TrainSupervisor(self)
            if sup.rewind_after and native_dl is not None:
                # the native threaded loader's shuffled cursor cannot seek
                # backwards, so a rewind would replay steps against the
                # WRONG batches — skip-step still protects; rewind needs
                # the deterministic loaders
                from flexflow_tpu.logger import fflogger

                fflogger.warning(
                    "nonfinite_rewind_after: rewind disabled under the "
                    "native dataloader (its cursor cannot rewind); "
                    "non-finite steps are still skipped in-graph")
                sup.rewind_after = 0
            # keep fit's dispatch async: only poll the guard's per-step
            # flag when prompt rewind is requested; otherwise the device-
            # side skip counter reconciles at finalize()
            sup.poll_nonfinite = bool(sup.rewind_after)
            sup.install()
            resumed = sup.resume()
            if resumed:
                start_epoch = min(resumed // num_batches, epochs)
                it0 = resumed % num_batches
            if use_scan and sup._fault_plan().has_step_events(
                    "nan_loss", "hang"):
                # per-step injection can't reach inside a scanned chunk —
                # silently ignoring a scheduled fault would make an
                # operator drill pass vacuously; run per-step instead
                from flexflow_tpu.logger import fflogger

                fflogger.warning(
                    "FF_FAULT schedules nan_loss/hang step events: "
                    "running per-step (scanned chunks bypass injection)")
                use_scan = False
        stopped = False
        warm = None
        # per-step wall breakdown (host_wait / h2d / dispatch / device),
        # reset at the warmup barrier so compile never pollutes it; logged
        # and stored on self.last_step_breakdown at the end of fit
        bd = {"host_wait": 0.0, "h2d": 0.0, "dispatch": 0.0,
              "device": 0.0, "steps": 0}
        # unified telemetry plane (runtime/telemetry.py): each step's
        # measured breakdown becomes a span tree on the "train" track
        # (trace id "step-<n>"), supervisor events (checkpoint publish,
        # rewind, watchdog) land on the same timeline from
        # resilience.py, and step wall time feeds an SLO histogram —
        # one exported trace shows the overlap schedule end to end
        from flexflow_tpu.runtime import flightrec as _flightrec

        tm_on = getattr(self.config, "telemetry", "on") != "off"
        # unconditional: configure() is how telemetry="off" reaches the
        # recorder's own gate (the train step-time and checkpoint-stall
        # SLOs window the histograms fit and the supervisor feed)
        _flightrec.configure(self.config)
        if tm_on and getattr(self.config, "metrics_port", 0):
            _telemetry.start_http_server(self.config.metrics_port)
        tm_step_hist = (_telemetry.registry().histogram(
            "ff_train_step_seconds",
            "fit() per-step wall time (host wait + h2d + dispatch)")
            if tm_on else None)
        # host-overlap step engine (runtime/pipeline_loader.py): a worker
        # thread prefetches + commits batches to device ahead of the loop,
        # and a dispatch-ahead ring below keeps up to
        # config.dispatch_ahead steps in flight. Host-resident data only
        # (device-resident loaders already slice on device, so there is
        # nothing to overlap); excluded under per-step guard polling
        # (prompt rewind syncs the loss every step anyway) and per-group
        # placement programs (their batches materialize inside the step).
        use_overlap = (not use_scan and not staged
                       and self.config.prefetch_depth > 0
                       and not getattr(self.executor, "jits_per_group", False)
                       and (sup is None or not sup.poll_nonfinite))
        pipe = None
        ring = collections.deque()  # in-flight step losses (device scalars)

        def _note_warm(first_loss):
            # ONE warmup barrier shared by all loop flavors: block on the
            # first step's own loss scalar — an output of the step
            # program, so it transitively waits on everything the step
            # produced; a second full-params sync was pure redundancy.
            # Excludes compile from the throughput window and resets the
            # breakdown counters.
            nonlocal warm, total
            jax.block_until_ready(first_loss)
            warm = time.time()
            total = 0
            for k in bd:
                bd[k] = 0 if k == "steps" else 0.0
            if pipe is not None:
                pipe.reset_stats()

        for cb in callbacks:
            cb.set_model(self)
            cb.on_train_begin()
        t0 = time.time()
        total = 0
        try:
            for epoch in range(start_epoch, epochs):
                # resuming mid-epoch: loader cursors were just restored —
                # the usual epoch-start reset would rewind them
                resuming = (sup is not None and epoch == start_epoch
                            and it0 > 0)
                for cb in callbacks:
                    cb.on_epoch_begin(epoch)
                self._perf = PerfMetrics()
                if native_dl is not None:
                    # reshuffle + restart prefetch each epoch; a resumed
                    # process's fresh loader sits on its construction-time
                    # permutation, so resuming into epoch >= 1 must also
                    # reset (one reshuffle — the uninterrupted run's exact
                    # permutations are unrecoverable for a shuffled
                    # loader, which is why bitwise resume is scoped to the
                    # deterministic loaders)
                    if epoch > start_epoch or (epoch == start_epoch
                                               and start_epoch > 0):
                        if pipe is not None:
                            # quiesce first: prefetched batches from the
                            # old epoch are discarded, the reset runs with
                            # the worker idle
                            pipe.epoch_break(native_dl.reset)
                        else:
                            native_dl.reset()
                    if resuming:
                        # the native loader's shuffled cursor cannot seek:
                        # discard the already-trained batches (pipe is
                        # still None here — it starts below, after the
                        # skip, so it never prefetches discarded batches)
                        for _ in range(it0):
                            native_dl.next_batch()
                elif not resuming:
                    if pipe is not None:
                        pipe.epoch_break(self._reset_dataloaders)
                    else:
                        self._reset_dataloaders()
                if use_overlap and pipe is None:
                    from flexflow_tpu.runtime.pipeline_loader import \
                        PipelineLoader

                    depth = self.config.prefetch_depth
                    pipe = (PipelineLoader.from_native(native_dl, self,
                                                       depth=depth)
                            if native_dl is not None else
                            PipelineLoader.from_loaders(self, depth=depth))
                    pipe.start()
                    self._pipeline = pipe
                epoch_mets = []  # device scalars; converted once per epoch so
                # the host never blocks mid-epoch (keeps XLA dispatch async)
                if use_scan:
                    it = it0
                    while it < num_batches:
                        if num_batches - it >= self.config.scan_steps:
                            chunk = self.config.scan_steps
                            t_c0 = time.perf_counter()
                            _, smets = self.train_scanned(chunk)
                            ev = None
                            if tm_on:
                                # dispatch time of one scanned chunk
                                # (device completion is async; the
                                # epoch_sync span carries the wait)
                                ev = _telemetry.tracer().complete(
                                    "train_scan_chunk", t_c0,
                                    time.perf_counter() - t_c0,
                                    track="train", steps=chunk)
                            epoch_mets.append((smets, bs, chunk, ev))
                        else:
                            # ragged epoch tail: n_steps is static to the
                            # scanned program, so a tail-sized scan would
                            # compile the whole model a second time — the
                            # per-step program is the cheaper spelling
                            chunk = 1
                            _, smets = self._run_train_step(
                                self._stage_batch())
                            epoch_mets.append((smets, bs, 1, None))
                        total += bs * chunk
                        it += chunk
                        if warm is None:
                            _note_warm(self._last_loss)
                        if sup is not None and sup.after_step():
                            stopped = True
                            break
                else:
                    it = it0
                    while it < num_batches:
                        t_b = time.perf_counter()
                        if pipe is not None:
                            # already sharded + committed by the worker:
                            # this wait is pure "input not ready yet"
                            batch = pipe.get()
                            t_h = t_s = time.perf_counter()
                        else:
                            batch = (native_dl.next_batch()
                                     if native_dl is not None
                                     else self._stage_batch())
                            t_h = time.perf_counter()
                            batch = self.executor.shard_batch(batch)
                            t_s = time.perf_counter()
                        loss, mets = self._run_train_step(
                            batch, inject_nan=(sup is not None
                                               and sup.nan_due()))
                        t_d = time.perf_counter()
                        bd["host_wait"] += t_h - t_b
                        bd["h2d"] += t_s - t_h
                        bd["dispatch"] += t_d - t_s
                        bd["steps"] += 1
                        ev = None
                        if tm_on:
                            sid = f"step-{self._step_count}"
                            tr = _telemetry.tracer()
                            ev = tr.complete("train_step", t_b, t_d - t_b,
                                             trace_id=sid, track="train",
                                             step=self._step_count)
                            tr.complete("host_wait", t_b, t_h - t_b,
                                        trace_id=sid, track="train")
                            if t_s > t_h:
                                tr.complete("h2d", t_h, t_s - t_h,
                                            trace_id=sid, track="train")
                            tr.complete("dispatch", t_s, t_d - t_s,
                                        trace_id=sid, track="train")
                            tm_step_hist.observe(t_d - t_b)
                            # train-side SLO tick for unsupervised fits
                            # (the supervisor's after_step ticks when
                            # one is installed): one predicate + one
                            # time compare until a window has elapsed
                            _flightrec.slo_monitor().maybe_evaluate()
                        epoch_mets.append((mets, bs, 1, ev))
                        total += bs
                        if warm is None:
                            _note_warm(loss)
                        elif pipe is not None:
                            # dispatch-ahead ring: block on the OLDEST
                            # in-flight step's loss once more than
                            # config.dispatch_ahead steps are outstanding.
                            # This waits on DEVICE progress (that step was
                            # dispatched dispatch_ahead steps ago), which
                            # is exactly what the supervisor's watchdog
                            # must time — not host dispatch
                            ring.append(loss)
                            if len(ring) > self.config.dispatch_ahead:
                                old = ring.popleft()
                                t_w = time.perf_counter()
                                with (sup.watchdog.arm(
                                        f"step {self._step_count} device "
                                        f"progress",
                                        scale=self.config.dispatch_ahead + 1)
                                      if sup is not None
                                      else contextlib.nullcontext()):
                                    jax.block_until_ready(old)
                                dt_w = time.perf_counter() - t_w
                                bd["device"] += dt_w
                                if tm_on:
                                    _telemetry.tracer().complete(
                                        "device_wait", t_w, dt_w,
                                        track="train")
                        if sup is not None:
                            step_before = self._step_count
                            if sup.after_step():
                                stopped = True
                                break
                            if self._step_count < step_before:
                                # divergence rewind: the supervisor rolled
                                # params/cursors/step back k steps — drop
                                # the discarded steps from this epoch's
                                # accounting and re-run them (a rewind
                                # past the epoch start clamps to it; those
                                # earlier steps re-run inside this epoch)
                                k = step_before - self._step_count
                                drop = min(k, len(epoch_mets))
                                if drop:
                                    del epoch_mets[-drop:]
                                total = max(total - bs * k, 0)
                                # restore the loop invariant
                                # _step_count == epoch_base + it: the
                                # step for index `it` already ran, so the
                                # next index is it + 1 - k
                                it = max(it + 1 - k, 0)
                                continue
                        it += 1
                it0 = 0
                # the epoch-end conversion is fit's big host sync point —
                # it blocks on every step dispatched since the last sync,
                # so the supervisor's watchdog (step_timeout_s) arms here,
                # scaled by the number of steps it waits on
                t_sync = time.perf_counter()
                with (sup.watchdog.arm(f"epoch {epoch} metrics sync",
                                       scale=max(len(epoch_mets), 1))
                      if sup is not None else contextlib.nullcontext()):
                    for mets, bs, n, ev in epoch_mets:
                        # per-step entries hold scalars (n=1); scanned
                        # chunks hold stacked (n,) arrays — np.asarray
                        # unifies both
                        arrs = {k: np.asarray(v) for k, v in mets.items()}
                        for j in range(n):
                            self._perf.update(
                                {k: float(a[j] if a.ndim else a)
                                 for k, a in arrs.items()}, bs)
                        # a routed model's step says where its (token,
                        # expert) assignments went: read here with the
                        # other metrics, summed into the breakdown and
                        # written onto the step's (or the chunk's) span
                        counts = {
                            k: int(arrs[k].max() if k.endswith("_max")
                                   else arrs[k].sum())
                            for k in ROUTING_COUNTS if k in arrs}
                        for k, v in counts.items():
                            bd[k] = (max(bd.get(k, 0), v)
                                     if k.endswith("_max")
                                     else bd.get(k, 0) + v)
                        if counts:
                            bd["moe_steps"] = bd.get("moe_steps", 0) + n
                            if ev is not None:
                                ev.setdefault("args", {}).update(counts)
                dt_sync = time.perf_counter() - t_sync
                bd["device"] += dt_sync
                if tm_on:
                    _telemetry.tracer().complete(
                        "epoch_sync", t_sync, dt_sync, track="train",
                        epoch=epoch, steps=len(epoch_mets))
                ring.clear()  # everything in flight just synced above
                if verbose:
                    print(f"epoch {epoch}: loss={float(self._last_loss):.4f} "
                          + self._perf.report(self.loss_type, self.metric_types))
                if stopped:  # preemption checkpoint written; partial epoch
                    break
                # a callback returning True from on_epoch_end stops training
                # (reference keras/callbacks.py early_stop semantics)
                if any(cb.on_epoch_end(epoch) for cb in callbacks):
                    break
        finally:
            if pipe is not None:
                # quiesce BEFORE the supervisor's final checkpoint: stop()
                # discards prefetched-but-untrained batches and rewinds
                # the loader cursors to the consumed position, so the
                # final save records exactly the synchronous loop's state
                pipe.stop()
                self._pipeline = None
            if native_dl is not None:
                native_dl.close()
            if sup is not None:
                sup.finalize()
        jax.block_until_ready(self.params)
        elapsed = time.time() - (warm or t0)
        if bd["steps"]:
            from flexflow_tpu.logger import fflogger

            wall = max(elapsed, 1e-9)
            if pipe is not None:
                # h2d ran on the worker thread — overlapped with device
                # compute, so it is reported but not part of loop wall
                bd["h2d"] = pipe.stats["h2d_s"]
            self.last_step_breakdown = dict(
                bd, wall_s=wall, overlap=pipe is not None,
                host_wait_fraction=min(bd["host_wait"] / wall, 1.0))
            fflogger.info(
                "fit step breakdown (%d steps, overlap=%s): host_wait "
                "%.1f%% | h2d %.1f%%%s | dispatch %.1f%% | device %.1f%% "
                "of %.3fs wall",
                bd["steps"], pipe is not None,
                100 * bd["host_wait"] / wall, 100 * bd["h2d"] / wall,
                " (worker, overlapped)" if pipe is not None else "",
                100 * bd["dispatch"] / wall, 100 * bd["device"] / wall,
                wall)
        if total and elapsed > 0 and verbose:
            print(f"epochs {epochs}, ELAPSED TIME = {elapsed:.4f}s, "
                  f"THROUGHPUT = {total / elapsed:.2f} samples/s")
        if tm_on:
            # close the simulator feedback loop (ISSUE 19b): compare the
            # search's predicted step time against the observed histogram,
            # publish the ff_csim_* drift gauges, and fold the observation
            # into the cost DB as a telemetry-tagged calib entry
            try:
                from flexflow_tpu.search import cost_db as _cost_db

                _cost_db.export_calibration(
                    self, path=getattr(self.config, "cost_db_path", "")
                    or None)
            except Exception:
                pass  # calibration must never fail a completed fit
        for cb in callbacks:
            cb.on_train_end()
        return self._perf

    def evaluate(self, batch: Dict[str, np.ndarray]):
        sharded = self.executor.shard_batch(batch)
        loss, mets, logits = self._first_call(
            "eval_step", self._eval_step,
            (self.params, self.bn_state, sharded), _batch_shapes(sharded))
        loss = float(loss)
        if not np.isfinite(loss):
            # eval already syncs the loss to host — a free divergence
            # signal (counter + log; resilience.py counters)
            from flexflow_tpu.logger import fflogger
            from flexflow_tpu.runtime.resilience import COUNTERS

            COUNTERS["eval_nonfinite"] += 1
            fflogger.warning("evaluate: non-finite loss %r at step %d",
                             loss, self._step_count)
        return loss, {k: float(v) for k, v in mets.items()}, logits

    def predict(self, batch: Dict[str, np.ndarray]):
        """Label-free inference through the forward-only program."""
        if self._predict_fn is None:
            fwd = self.executor.make_forward([self._final_tensor])
            # the placement executor jits per group (its arrays live on
            # different sub-meshes, which one outer jit cannot accept)
            self._predict_fn = fwd if getattr(
                self.executor, "jits_per_group", False) else jax.jit(fwd)
        sharded = self.executor.shard_batch(batch)
        return self._first_call(
            "predict", self._predict_fn,
            (self.params, self.bn_state, sharded), _batch_shapes(sharded))[0]

    def generate(self, tokens, max_new_tokens: int, temperature: float = 0.0,
                 top_k: int = 0, eos_token_id=None, pad_token_id: int = 0,
                 num_beams: int = 1, length_penalty: float = 0.0,
                 prompt_lengths=None, quantize=None,
                 prefill_chunk: int = 0, return_scores: bool = False,
                 seed: int = 0, early_exit: bool = False):
        """KV-cache autoregressive decoding for decoder-only LM graphs
        (runtime/generation.py). tokens: (B, S0) int32 prompts; returns
        (B, S0 + max_new_tokens) int32 with generated tokens in columns
        S0 onward — or, with return_scores=True, a (tokens, scores)
        tuple where scores is (B, max_new_tokens) per-token model
        logprobs for greedy/sampling (pads after eos carry 0.0) and (B,)
        length-penalty-normalized total logp of the chosen beam for beam
        search. prompt_lengths (B,) enables ragged right-padded prompts.
        num_beams > 1 switches to beam search (temperature/top_k ignored
        there; ragged prompts supported via prompt_lengths, same as
        greedy/sampling). length_penalty follows the
        norm score/len**penalty — the default 0.0 means RAW SUM of
        logprobs (length-biased toward short beams; HF-style length
        normalization is length_penalty=1.0). quantize="int8" decodes
        with weight-only int8 (lossy; halves weight HBM traffic vs
        bf16). prefill_chunk=N bounds prefill score memory.
        early_exit=True decodes through a while_loop that stops once
        every row has emitted eos — identical tokens to the full-length
        scan, fewer steps when rows finish early (greedy/sampling only).

        Compilation caching: each distinct (sampling config) keeps a
        Generator, and each distinct (max_new_tokens, ragged,
        prefill_chunk, scores | beam params) + prompt SHAPE traces its
        own XLA program. Programs are LRU-bounded per Generator
        (FF_GEN_PROGRAM_CACHE, default 8) so a long-lived serving
        process sweeping shapes doesn't accumulate compiled programs
        without bound; sweeping sampling configs still grows
        _generators — reuse temperatures/top_k where possible."""
        from flexflow_tpu.runtime.generation import Generator

        # beam search ignores temperature/top_k: key those out so a
        # sampling sweep reuses one Generator (and its compiled programs)
        key = ((0.0, 0, eos_token_id, pad_token_id, quantize)
               if num_beams > 1
               else (temperature, top_k, eos_token_id, pad_token_id,
                     quantize))
        gen = self._generators.get(key)
        if gen is None:
            # construct from the KEYED values (not the raw args): a beam
            # call keys temperature/top_k out, and its cached Generator
            # must behave greedy if a later num_beams=1 call reuses it
            gen = self._generators[key] = Generator(
                self, temperature=key[0], top_k=key[1],
                eos_id=eos_token_id, pad_id=pad_token_id,
                quantize=quantize)
        if num_beams > 1:
            return gen.beam_search(tokens, max_new_tokens, num_beams,
                                   length_penalty,
                                   prefill_chunk=prefill_chunk,
                                   return_scores=return_scores,
                                   prompt_lengths=prompt_lengths)
        return gen(tokens, max_new_tokens, seed=seed,
                   prompt_lengths=prompt_lengths,
                   prefill_chunk=prefill_chunk,
                   return_scores=return_scores, early_exit=early_exit)

    def _hbm_source(self):
        """HBM-ledger row (runtime/flightrec.py): what this model's
        training state holds on device, per subsystem."""
        subs = {"params": _tree_nbytes(self.params)}
        if self.opt_state is not None:
            subs["opt_state"] = _tree_nbytes(self.opt_state)
        if self.bn_state:
            subs["bn_state"] = _tree_nbytes(self.bn_state)
        return (self._hbm_name, subs)

    def dump_flight_record(self, directory: Optional[str] = None,
                           **note) -> Optional[str]:
        """Manual post-mortem bundle (runtime/flightrec.py, ISSUE 15):
        synchronously snapshot the recent trace window, metrics
        registry, log ring, HBM ledger, per-engine stats and the
        config/env fingerprint into an atomic, manifest-hashed bundle
        directory; returns its path. ``directory`` overrides
        ``FFConfig.flight_recorder_dir`` (one of the two must be set).
        Returns None when ``FFConfig.telemetry="off"`` — the off
        contract covers manual dumps too."""
        from flexflow_tpu.runtime import flightrec

        # recorder-only configure: re-arming the SLO monitor here would
        # reset live breach state on an operator's dump
        flightrec.recorder().configure(self.config)
        return flightrec.dump("manual", directory=directory,
                              source="model", **note)

    def make_serving_engine(self, **kwargs):
        """Continuous-batching serving engine (runtime/serving.py): one
        fixed-shape slot-decode program + a paged KV cache shared by all
        slots; the host scheduler admits queued prompts into freed slots
        and retires rows on eos/length. A radix prefix cache shares the
        KV pages of identical page-aligned prompt prefixes across
        requests (copy-on-write; on by default), and a draft model
        (``draft_model=`` + ``speculate_k=``) enables speculative
        decoding — token-identical greedy output, several tokens per
        verify dispatch. The quantized serving tier
        (``kv_cache_dtype="int8"|"fp8"|"bf16"`` and
        ``weight_dtype="int8"|"fp8"``) stores KV pages and/or weights
        narrow with in-kernel dequant: 2-4x the tokens per pool byte at
        a documented per-dtype divergence budget (docs/serving.md
        "Quantized tier"). ``host_kv_pages`` adds a pinned host-memory
        tier under the prefix cache (evicted ref-0 pages demote to host
        RAM and promote back on a hit — the shared-prefix corpus
        becomes host-RAM-sized), and ``warmup(prompts)`` drives every
        reachable prefill variant so timed windows never compile.
        Multi-tenant serving (ISSUE 14): per-request
        temperature/top-p/top-k/seed ride ``submit()`` as slot-resident
        state (greedy = temperature 0, bitwise; counter-based seeded
        streams reproduce across slots and failover), sampled requests
        speculate via the rejection-sampled accept rule
        (distribution-identical to the plain sampler), and
        ``adapter_pool_pages > 0`` + ``register_adapter()`` serve
        per-request LoRA adapters from a paged device pool with zero
        recompiles. Knobs default to this model's FFConfig
        (serve_slots, kv_page_size, kv_pages, decode_buckets,
        serve_prefix_cache, host_kv_pages, serve_speculate_k,
        draft_model, kv_cache_dtype, serve_weight_dtype,
        serve_temperature/top_p/top_k, serve_adapter_pool_pages,
        serve_lora_rank); kwargs override per engine (see
        ServingEngine)."""
        from flexflow_tpu.runtime.serving import ServingEngine

        # to a usable engine: the pool arrays, the weight casts, an
        # adapter pool's writer (its `compile` span nests here)
        with self._setup_span("engine_build") as span:
            eng = ServingEngine(self, **kwargs)
            span.annotate(slots=eng.slots, pages=eng.num_pages,
                          pool_bytes=int(eng.stats()["kv_pool_bytes"]))
        return eng

    def serve(self, prompts, max_new_tokens: int = 32, **kwargs):
        """One-shot continuous-batching serve: run `prompts` (list of 1-D
        int32 token arrays, any mix of lengths) to completion and return
        (outputs, stats) — outputs[i] is prompt + generated tokens for
        prompts[i] (None for a failed request), stats the engine's
        throughput/latency/occupancy summary. Greedy continuous batching
        is token-identical to per-request generate()."""
        eng = self.make_serving_engine(**kwargs)
        reqs = eng.run(prompts, max_new_tokens=max_new_tokens)
        outs = [r.output if r.state == "done" else None for r in reqs]
        return outs, eng.stats()

    def make_serving_router(self, replicas: int = 2, **kwargs):
        """Fleet serving router (runtime/router.py ServingRouter): N
        continuous-batching replicas of this model, each driven on its
        own thread, with failover (a crashed/hung replica is fenced and
        its work resubmitted to survivors exactly once), per-request
        deadlines, overload shedding (``max_queue`` /
        FFConfig.serve_max_queue) and least-loaded + prefix-affinity
        placement on the replicas' live health counters. ``roles=``
        (or FFConfig.serve_replica_roles) disaggregates the fleet:
        ``prefill`` replicas absorb long-prompt admission and hand the
        finished KV pages off to ``decode`` replicas as a serialized
        page slab — greedy streams stay token-identical, and a dead
        tier degrades to the mixed path. ``replicas`` is only the
        STARTING size: membership is live (``add_replica`` /
        ``remove_replica`` / ``request_preempt`` with exactly-once
        state evacuation), and runtime/autoscale.py's AutoscalePolicy
        can drive it from the SLO monitor's breach signal. Router
        kwargs (``max_queue``, ``health_timeout_s``,
        ``dispatch_backlog``, ``roles``, ``handoff_min_pages``,
        ``start``) are split out; everything else is forwarded to
        every replica's ServingEngine."""
        from flexflow_tpu.runtime.router import ServingRouter

        return ServingRouter(self, replicas=replicas, **kwargs)

    def serve_fleet(self, prompts, max_new_tokens: int = 32,
                    replicas: int = 2,
                    deadline_s: Optional[float] = None, **kwargs):
        """One-shot fleet serve: run `prompts` through a fresh N-replica
        ServingRouter and return (outputs, stats) — outputs[i] is prompt
        + generated tokens for prompts[i], or None for a request that
        failed, expired (``deadline_s``) or was shed; stats is the
        router's fleet ledger (per-replica engine rows included). Greedy
        fleet output is token-identical to single-replica serve() — the
        router moves work, never changes it."""
        router = self.make_serving_router(replicas=replicas, **kwargs)
        try:
            reqs = router.run(prompts, max_new_tokens=max_new_tokens,
                              deadline_s=deadline_s)
            outs = [r.output if r.state == "done" else None for r in reqs]
            stats = router.stats()
        finally:
            router.close()
        return outs, stats

    def generate_seq2seq(self, src_tokens, tgt_prompt=None,
                         max_new_tokens: int = 32, bos_token_id: int = 1,
                         temperature: float = 0.0, top_k: int = 0,
                         eos_token_id: Optional[int] = None,
                         pad_token_id: int = 0, seed: int = 0):
        """Encoder-decoder decoding (runtime/seq2seq_generation.py): the
        encoder runs once on `src_tokens` (B, S_src), cross-attention k/v
        are projected once, and the decoder runs the KV-cached one-program
        token loop starting from `tgt_prompt` (B, T0) — or a BOS column of
        `bos_token_id` when omitted. Returns (B, T0 + max_new_tokens)
        int32. Graph contract and v1 scope: see Seq2SeqGenerator."""
        from flexflow_tpu.runtime.seq2seq_generation import Seq2SeqGenerator

        key = ("s2s", temperature, top_k, eos_token_id, pad_token_id)
        gen = self._generators.get(key)
        if gen is None:
            gen = self._generators[key] = Seq2SeqGenerator(
                self, temperature=temperature, top_k=top_k,
                eos_id=eos_token_id, pad_id=pad_token_id)
        src = np.asarray(src_tokens)
        if tgt_prompt is None:
            tgt_prompt = np.full((src.shape[0], 1), bos_token_id, np.int32)
        return gen(src, tgt_prompt, max_new_tokens, seed=seed)

    # ------------------------------------------------------------ weights IO

    def get_weights(self, op_name: str, weight_name: str = "kernel") -> np.ndarray:
        tie = self._tied.get((op_name, weight_name))
        if tie is not None:
            from flexflow_tpu.runtime.executor import tie_transform

            src_op, src_w, tf = tie
            return np.asarray(tie_transform(
                np.asarray(self.params[src_op][src_w]), tf))
        return np.asarray(self.params[op_name][weight_name])

    def set_weights(self, op_name: str, weight_name: str, value: np.ndarray):
        tie = self._tied.get((op_name, weight_name))
        if tie is not None:
            raise ValueError(
                f"{op_name}.{weight_name} is tied to {tie[0]}.{tie[1]} — "
                f"set the source weight instead")
        shardings = self.executor.param_shardings()
        sh = shardings[op_name][weight_name]
        self.params[op_name][weight_name] = jax.device_put(
            jnp.asarray(value), sh)
        self._params_version += 1  # in-place mutation: bump by hand

    # ------------------------------------------------------------- strategy

    def export_strategies(self, filename: str):
        save_strategies_to_file(filename, self.config.strategies)

    def import_strategies(self, filename: str):
        self.config.strategies.update(load_strategies_from_file(filename))
