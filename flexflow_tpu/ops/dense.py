"""Linear (dense), Embedding, BatchMatmul.

Reference: src/ops/linear.cu (1115 LoC: cuBLAS GEMM + replica-tensor TP
machinery), src/ops/embedding.cu (custom gather/scatter-add kernels),
src/ops/batch_matmul.cu (cuBLAS strided batched GEMM).

TPU re-design: Linear is one jnp.einsum feeding the MXU; all outer dims are
batch (the reference does the same flattening, linear.cu:158). Parameter
parallelism = shard the kernel's out-feature dim over the 'model' mesh axis;
sharded autodiff inserts the psum that replaces the reference's replica tensor
+ backward2 reduction (linear.cu:774-835). Embedding's vocab-partitioned
lookup (DLRM's key strategy) shards the table on dim 0; XLA lowers the gather
to an all-gather-free one-hot matmul or dynamic-slice + psum under GSPMD.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flexflow_tpu.ffconst import ActiMode, AggrMode, DataType, OperatorType
from flexflow_tpu.ops.base import Op, WeightSpec


def apply_activation(x, acti: ActiMode):
    import jax

    if acti == ActiMode.AC_MODE_NONE:
        return x
    if acti == ActiMode.AC_MODE_RELU:
        return jax.nn.relu(x)
    if acti == ActiMode.AC_MODE_SIGMOID:
        return jax.nn.sigmoid(x)
    if acti == ActiMode.AC_MODE_TANH:
        return jnp.tanh(x)
    if acti == ActiMode.AC_MODE_GELU:
        return jax.nn.gelu(x)
    raise ValueError(f"unknown activation {acti}")


class Linear(Op):
    op_type = OperatorType.OP_LINEAR

    def __init__(self, model, name, inputs, out_dim: int,
                 activation: ActiMode = ActiMode.AC_MODE_NONE,
                 use_bias: bool = True):
        super().__init__(model, name, inputs)
        self.out_dim = out_dim
        self.activation = activation
        self.use_bias = use_bias
        self.in_dim = inputs[0].dims[-1]
        self.finalize()

    def output_shapes(self):
        ishape = self.inputs[0].dims
        return [tuple(ishape[:-1]) + (self.out_dim,)], [self.inputs[0].dtype]

    def weights(self) -> List[WeightSpec]:
        ws = [WeightSpec("kernel", (self.in_dim, self.out_dim), init="glorot",
                         fan=(self.in_dim, self.out_dim))]
        if self.use_bias:
            ws.append(WeightSpec("bias", (self.out_dim,), init="zero"))
        return ws

    def forward(self, params, xs, *, training=False, rng=None, lora=None):
        x = xs[0]
        y = jnp.einsum("...i,io->...o", x, params["kernel"],
                       preferred_element_type=x.dtype)
        if lora is not None:
            # gathered per-row LoRA delta (ops/lora.py): added BEFORE
            # bias/activation so it composes exactly like a merged
            # W + a@b*scale kernel would
            from flexflow_tpu.ops.lora import lora_delta

            a, b, scale = lora
            y = y + lora_delta(x, a, b, scale)
        if self.use_bias:
            y = y + params["bias"]
        return [apply_activation(y, self.activation)]

    @property
    def _contracted_output_dims(self):
        return (self.outputs[0].num_dims - 1,)

    def partitionable_output_dims(self):
        # sample dim(s) + out-channel (the reference's parameter-parallel dim,
        # linear.cu:144-269, gated by --enable-parameter-parallel)
        nd = self.outputs[0].num_dims
        return list(range(nd))

    def contract_size(self):
        # row-parallel: kernel sharded on in_dim, input sharded on its last
        # dim (a column-parallel producer's layout), output psum-replicated —
        # the Megatron pair that makes TP resharding-free. Reference analog:
        # replica-input Linear (linear.cu:171-192) + backward2 (:774-835).
        return self.in_dim

    def weight_partition(self, axis_map):
        from flexflow_tpu.parallel.pconfig import CONTRACT

        ax = self.axes_for_dim(axis_map, self.outputs[0].num_dims - 1)
        cax = self.axes_for_dim(axis_map, CONTRACT)
        out = {"kernel": P(cax, ax)}
        if self.use_bias:
            # bias adds after the psum; replicated over contract axes
            out["bias"] = P(ax)
        return out

    def contract_input_dim(self, input_idx):
        return self.inputs[input_idx].num_dims - 1

    def flops(self):
        batch = int(np.prod(self.outputs[0].dims[:-1]))
        return 2 * batch * self.in_dim * self.out_dim


class GatedMLP(Op):
    """A gated feed-forward block as ONE graph op (HF `GraniteMoeHybridMLP`:
    `input_linear`, chunk, `output_linear`):

        [g | u] = x W_in          # D -> 2 F, one matmul
        y = (silu(g) * u) W_out   # F -> D

    One op, so one scope: a trace books the whole block to its name
    (`mlp_<i>`), where `models/llama.py` `swiglu` spreads the same block
    over five ops. No bias. Every position is independent of the others
    (decode-safe, runtime/generation.py)."""

    op_type = OperatorType.OP_GATED_MLP

    def __init__(self, model, name, inputs, hidden_dim: int):
        super().__init__(model, name, inputs)
        self.dim = inputs[0].dims[-1]
        self.hidden_dim = int(hidden_dim)
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def weights(self) -> List[WeightSpec]:
        d, f = self.dim, self.hidden_dim
        # each half of w_in drawn as the (D, F) Linear it replaces
        return [WeightSpec("w_in", (d, 2 * f), init="glorot", fan=(d, f)),
                WeightSpec("w_out", (f, d), init="glorot")]

    def forward(self, params, xs, *, training=False, rng=None):
        import jax

        x = xs[0]
        gu = jnp.einsum("...i,io->...o", x, params["w_in"].astype(x.dtype),
                        preferred_element_type=x.dtype)
        h = jax.nn.silu(gu[..., :self.hidden_dim]) * gu[..., self.hidden_dim:]
        return [jnp.einsum("...i,io->...o", h,
                           params["w_out"].astype(x.dtype),
                           preferred_element_type=x.dtype)]

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims - 1))    # the rows

    def flops(self):
        rows = int(np.prod(self.outputs[0].dims[:-1]))
        return 6 * rows * self.dim * self.hidden_dim


class Embedding(Op):
    op_type = OperatorType.OP_EMBEDDING

    def __init__(self, model, name, inputs, num_entries: int, out_dim: int,
                 aggr: AggrMode = AggrMode.AGGR_MODE_NONE):
        super().__init__(model, name, inputs)
        self.num_entries = num_entries
        self.out_dim = out_dim
        self.aggr = aggr
        self.finalize()

    def output_shapes(self):
        ishape = self.inputs[0].dims
        if self.aggr == AggrMode.AGGR_MODE_NONE:
            shape = tuple(ishape) + (self.out_dim,)
        else:
            # bag aggregation over the last input dim (reference AGGR_MODE_SUM/AVG,
            # embedding.cu:165-226)
            shape = tuple(ishape[:-1]) + (self.out_dim,)
        return [shape], [DataType.DT_FLOAT]

    def weights(self):
        return [WeightSpec("kernel", (self.num_entries, self.out_dim),
                           init="glorot", fan=(self.num_entries, self.out_dim))]

    def forward(self, params, xs, *, training=False, rng=None):
        idx = xs[0].astype(jnp.int32)
        emb = jnp.take(params["kernel"], idx, axis=0)
        if self.aggr == AggrMode.AGGR_MODE_SUM:
            emb = jnp.sum(emb, axis=-2)
        elif self.aggr == AggrMode.AGGR_MODE_AVG:
            emb = jnp.mean(emb, axis=-2)
        return [emb]

    @property
    def _contracted_output_dims(self):
        return (self.outputs[0].num_dims - 1,)

    def partitionable_output_dims(self):
        nd = self.outputs[0].num_dims
        return [0, nd - 1]  # sample + embedding-channel (vocab-split table)

    def weight_partition(self, axis_map):
        ax = self.axes_for_dim(axis_map, self.outputs[0].num_dims - 1)
        return {"kernel": P(None, ax)}

    def flops(self):
        return 0  # memory-bound gather

    def input_axis_map(self, axis_map, input_idx):
        # index input has no channel dim; keep only sample-dim mappings
        ndims = self.inputs[input_idx].num_dims
        return {ax: (d if d is not None and d < ndims else None)
                for ax, d in (axis_map or {}).items()}


class BatchMatmul(Op):
    op_type = OperatorType.OP_BATCHMATMUL

    def __init__(self, model, name, inputs):
        super().__init__(model, name, inputs)
        self.finalize()

    def output_shapes(self):
        a, b = self.inputs[0].dims, self.inputs[1].dims
        assert a[:-2] == b[:-2], f"batch dims mismatch {a} @ {b}"
        assert a[-1] == b[-2], f"contraction mismatch {a} @ {b}"
        return [tuple(a[:-1]) + (b[-1],)], [self.inputs[0].dtype]

    def forward(self, params, xs, *, training=False, rng=None):
        return [jnp.matmul(xs[0], xs[1])]

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims - 2))

    def flops(self):
        a, b = self.inputs[0].dims, self.inputs[1].dims
        return 2 * int(np.prod(a)) * b[-1]
