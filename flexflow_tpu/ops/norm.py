"""Softmax, Dropout, LayerNorm, RMSNorm.

Reference: src/ops/softmax.cu (cuDNN softmax, sample-parallel only),
src/ops/dropout.cu (cuDNN dropout w/ reserve space). LayerNorm/RMSNorm are
net-new ops the reference lacks (its Transformer example builds LN from
primitives); first-class here because every modern transformer needs them.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import Op, WeightSpec


def fused_add_ln_refusal(rows: int, dim: int, dtype) -> Optional[str]:
    """Why the fused add+layernorm kernel cannot take a (rows, dim) input,
    or None when it can. The one eligibility rule AddLayerNorm and the
    chip_smoke.py kernel sweep both ask."""
    from flexflow_tpu.ops.pallas_kernels import add_ln_block_rows

    if dim % 128 != 0:
        return f"hidden {dim} is not a multiple of the 128-lane tile"
    if not add_ln_block_rows(rows, dim, dtype):
        return (f"no row block of a ({rows}, {dim}) "
                f"{jnp.dtype(dtype).name} input fits VMEM")
    return None


class Softmax(Op):
    op_type = OperatorType.OP_SOFTMAX

    def __init__(self, model, name, inputs, axis: int = -1):
        super().__init__(model, name, inputs)
        self.axis = axis
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def forward(self, params, xs, *, training=False, rng=None):
        return [jax.nn.softmax(xs[0], axis=self.axis)]

    def partitionable_output_dims(self):
        nd = self.outputs[0].num_dims
        ax = self.axis % nd
        return [i for i in range(nd) if i != ax]

    def flops(self):
        return 5 * self.outputs[0].volume()


class Dropout(Op):
    op_type = OperatorType.OP_DROPOUT
    needs_rng = True

    def __init__(self, model, name, inputs, rate: float, seed: int = 0):
        super().__init__(model, name, inputs)
        self.rate = rate
        self.seed = seed
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def forward(self, params, xs, *, training=False, rng=None):
        x = xs[0]
        if not training or self.rate <= 0.0:
            return [x]
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return [jnp.where(mask, x / keep, 0.0)]

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims))

    def flops(self):
        return self.outputs[0].volume()


class LayerNorm(Op):
    op_type = OperatorType.OP_LAYERNORM

    def __init__(self, model, name, inputs, eps: float = 1e-5,
                 elementwise_affine: bool = True):
        super().__init__(model, name, inputs)
        self.eps = eps
        self.affine = elementwise_affine
        self.dim = inputs[0].dims[-1]
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def weights(self):
        if not self.affine:
            return []
        return [WeightSpec("scale", (self.dim,), init="one"),
                WeightSpec("bias", (self.dim,), init="zero")]

    def forward(self, params, xs, *, training=False, rng=None):
        x = xs[0]
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) * jax.lax.rsqrt(var + self.eps)
        if self.affine:
            y = y * params["scale"] + params["bias"]
        return [y]

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims - 1))

    def flops(self):
        return 8 * self.outputs[0].volume()


class AddLayerNorm(Op):
    """Fused residual-add + LayerNorm: (s, y) = (x + r, LN(x + r)).

    The unfused graph writes the sum to HBM, re-reads it for the norm, and
    re-reads it again on the next residual hop; the fused op makes one pass
    (Pallas kernel on TPU, plain JAX elsewhere — XLA fuses the fallback
    too, so numerics are identical everywhere). Net-new op, same rationale
    as LayerNorm; enabled in the transformer blocks by
    FFConfig.use_fused_ln."""

    op_type = OperatorType.OP_LAYERNORM
    wants_shard_ctx = True  # per-shard kernel under sharding (see forward)

    def __init__(self, model, name, inputs, eps: float = 1e-5):
        super().__init__(model, name, inputs)
        self.eps = eps
        self.dim = inputs[0].dims[-1]
        assert inputs[0].dims == inputs[1].dims, \
            f"{name}: add_layer_norm inputs must agree, got " \
            f"{inputs[0].dims} vs {inputs[1].dims}"
        self.finalize()

    def output_shapes(self):
        d = self.inputs[0].dims
        t = self.inputs[0].dtype
        return [d, d], [t, t]

    def weights(self):
        return [WeightSpec("scale", (self.dim,), init="one"),
                WeightSpec("bias", (self.dim,), init="zero")]

    def _fused_ok(self, rows: int, dtype) -> bool:
        """Whether this call runs the Pallas kernel: on a TPU backend (or
        when tests force kernels with FF_FORCE_FLASH_ATTENTION=1) and the
        per-shard (rows, dim) input is one the kernel takes. A shape the
        kernel refuses is logged with its reason and runs the plain-JAX
        branch — decided here at trace time, never a Mosaic failure."""
        import os

        if not (jax.default_backend() == "tpu"
                or os.environ.get("FF_FORCE_FLASH_ATTENTION") == "1"):
            return False
        reason = fused_add_ln_refusal(rows, self.dim, dtype)
        if reason is not None:
            from flexflow_tpu.logger import fflogger

            fflogger.warning("%s: fused add+layernorm kernel refused (%s); "
                             "running the unfused ops", self.name, reason)
        return reason is None

    def forward(self, params, xs, *, training=False, rng=None,
                shard_ctx=None):
        x, r = xs[0], xs[1]
        scale, bias = params["scale"], params["bias"]
        # a pallas_call is a Mosaic custom call GSPMD cannot partition:
        # under a sharded strategy the kernel runs per-shard inside
        # shard_map over whichever sharded non-last dims divide evenly
        # (same pattern as attention._flash_dense); the op is row-wise,
        # so shards need no collectives
        mesh = (shard_ctx or {}).get("mesh")
        entries = [None] * (x.ndim - 1)
        if mesh is not None:
            from flexflow_tpu.parallel import shard_entries

            axis_map = (shard_ctx or {}).get("axis_map") or {}
            ent = shard_entries(mesh, axis_map, x.shape, range(x.ndim - 1))
            entries = [ent[d] for d in range(x.ndim - 1)]
        axes = [ax for e in entries if e
                for ax in (e if isinstance(e, tuple) else (e,))]
        rows = (math.prod(x.shape[:-1])
                // math.prod(mesh.shape[ax] for ax in axes))
        if self._fused_ok(rows, x.dtype):
            from flexflow_tpu.ops.pallas_kernels import fused_add_layernorm

            def run(x_, r_, scale_, bias_):
                shape = x_.shape
                s2, y2 = fused_add_layernorm(
                    x_.reshape(-1, self.dim), r_.reshape(-1, self.dim),
                    scale_, bias_, self.eps)
                return s2.reshape(shape), y2.reshape(shape)

            if any(e is not None for e in entries):
                from jax.sharding import PartitionSpec as P

                spec = P(*entries, None)
                w_spec = P(None)
                s2, y2 = jax.shard_map(
                    run, mesh=mesh, in_specs=(spec, spec, w_spec, w_spec),
                    out_specs=(spec, spec), check_vma=False)(
                        x, r, scale, bias)
                return [s2, y2]
            s2, y2 = run(x, r, scale, bias)
            return [s2, y2]
        s = x + r
        # f32 stats like the Pallas kernel, so bf16 numerics validated on
        # the fallback transfer to the TPU path
        sf = s.astype(jnp.float32)
        mean = jnp.mean(sf, axis=-1, keepdims=True)
        var = jnp.var(sf, axis=-1, keepdims=True)
        y = ((sf - mean) * jax.lax.rsqrt(var + self.eps)
             * scale.astype(jnp.float32) + bias.astype(jnp.float32))
        return [s, y.astype(s.dtype)]

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims - 1))

    def flops(self):
        return 9 * self.outputs[0].volume()


class RMSNorm(Op):
    op_type = OperatorType.OP_RMSNORM

    def __init__(self, model, name, inputs, eps: float = 1e-6):
        super().__init__(model, name, inputs)
        self.eps = eps
        self.dim = inputs[0].dims[-1]
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def weights(self):
        return [WeightSpec("scale", (self.dim,), init="one")]

    def forward(self, params, xs, *, training=False, rng=None):
        x = xs[0]
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return [x * jax.lax.rsqrt(ms + self.eps) * params["scale"]]

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims - 1))

    def flops(self):
        return 4 * self.outputs[0].volume()
