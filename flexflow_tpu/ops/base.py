"""Op base class and weight specs."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from flexflow_tpu.ffconst import DataType, OperatorType, ParameterSyncType
from flexflow_tpu.tensor import Parameter, Tensor


@dataclasses.dataclass
class WeightSpec:
    """Metadata for one trainable weight of an op (analog of the reference's
    create_weights + Initializer attachment, e.g. linear.cu:74-122)."""

    name: str
    shape: Tuple[int, ...]
    dtype: DataType = DataType.DT_FLOAT
    init: str = "glorot"  # glorot | zero | one | uniform | normal | constant
    init_args: Tuple = ()  # e.g. (low, high) for uniform
    # fan dims for glorot: (fan_in, fan_out) computed from shape by default
    fan: Optional[Tuple[int, int]] = None
    sync_type: ParameterSyncType = ParameterSyncType.NCCL


class Op:
    """Graph-node base.

    Subclasses set `op_type`, implement `output_shapes`, `forward`, and
    optionally `weights`, `weight_partition`, `partitionable_output_dims`,
    `flops`.
    """

    op_type: OperatorType = OperatorType.OP_NOOP
    stateful: bool = False  # True => implements forward_stateful (BatchNorm)
    needs_rng: bool = False  # True => forward uses rng (Dropout, MHA dropout)

    def __init__(self, model, name: str, inputs: Sequence[Tensor], **attrs):
        self.model = model
        self.name = name
        self.inputs: List[Tensor] = list(inputs)
        self.attrs: Dict[str, Any] = attrs
        self.outputs: List[Tensor] = []
        self._weight_specs: Optional[List[WeightSpec]] = None

    # -- graph construction --------------------------------------------------

    def finalize(self) -> None:
        """Infer outputs and register with the model graph."""
        shapes, dtypes = self.output_shapes()
        self.outputs = [
            Tensor(dims=tuple(s), dtype=dt, owner_op=self, owner_idx=i,
                   name=f"{self.name}:out{i}")
            for i, (s, dt) in enumerate(zip(shapes, dtypes))
        ]

    def output_shapes(self) -> Tuple[List[Tuple[int, ...]], List[DataType]]:
        raise NotImplementedError

    def weights(self) -> List[WeightSpec]:
        return []

    def weight_specs(self) -> List[WeightSpec]:
        if self._weight_specs is None:
            self._weight_specs = self.weights()
        return self._weight_specs

    # -- execution -----------------------------------------------------------

    def forward(self, params: Dict[str, Any], xs: List[Any], *,
                training: bool = False, rng=None) -> List[Any]:
        raise NotImplementedError

    def forward_stateful(self, params, state, xs, *, training=False, rng=None):
        raise NotImplementedError

    def init_state(self) -> Dict[str, Any]:
        return {}

    def init_state_for_shapes(self, in_shapes) -> Dict[str, Any]:
        """State sized for PER-SHARD input shapes (the measurement harness
        runs one shard standalone; channel-sharded BatchNorm needs its
        running stats sliced to the shard's channel count). Default: the
        full-size state."""
        return self.init_state()

    # -- parallelization metadata ---------------------------------------------

    def partitionable_output_dims(self) -> List[int]:
        """Logical output dims the search may partition. Default: sample dim
        only (the reference's conservative default for most ops)."""
        return [0]

    def single_axis_dims(self) -> List[int]:
        """Output dims the executor can shard over at most ONE mesh axis
        (the search must not propose multi-axis products for them). Default
        none; MultiHeadAttention's seq dim is the known case — the
        ring/Ulysses lowering needs a single named 'seq' axis."""
        return []

    def contract_size(self) -> Optional[int]:
        """Size of the op's weight-contraction dim, if the op supports
        CONTRACT (row-parallel) sharding: weight sharded on its input-feature
        dim, input sharded on its last dim, output psum-replicated. None =
        not contractable. Analog of the reference Linear's replica-dim
        machinery (linear.cu:171-192,774-835)."""
        return None

    def expert_parallel_size(self) -> Optional[int]:
        """Number of independently-shardable experts, if the op supports
        EXPERT (MoE expert-parallel) sharding: expert-indexed weights shard
        on their expert dim, tokens all-to-all to their experts and back,
        output replicated over the axis. None = not expert-parallelizable.
        The search proposes {axis: EXPERT} when the axis size divides it."""
        return None

    def pipeline_stages(self) -> int:
        """Number of identical stacked layers this op can split into pipeline
        stages (STAGE axis_map proposals): 0 = not pipelineable. Ops with a
        stacked-layer weight layout (TransformerPipelineStack) return their
        layer count; the search proposes {axis: STAGE} when the axis size
        divides it."""
        return 0

    def output_axis_map(self, axis_map: Dict[str, Optional[int]]
                        ) -> Dict[str, Optional[int]]:
        """The sharding the op's OUTPUT actually has under `axis_map`:
        CONTRACT and STAGE axes produce a psum-replicated output, so
        consumers see them as replicated."""
        return {ax: (d if d is not None and d >= 0 else None)
                for ax, d in (axis_map or {}).items()}

    def partial_sum_axes(self, axis_map: Dict[str, Optional[int]]
                         ) -> List[str]:
        """Mesh axes over which the op's output is a PARTIAL SUM before it
        is reduced: a CONTRACT axis (the row-parallel matmul's shards each
        hold one term). The cost model prices that reduction on the edge,
        by what the consumer keeps (`CostModel.edge_held_time`)."""
        from flexflow_tpu.parallel.pconfig import CONTRACT

        return [ax for ax, d in (axis_map or {}).items() if d == CONTRACT]

    def weight_partition(self, axis_map: Dict[str, Optional[int]]):
        """Given the op's output axis_map (mesh axis -> output dim), return
        {weight_name: PartitionSpec}. Default: fully replicated weights
        (reference: weights replicated under data parallelism,
        model.cc:948-1074 PS/NCCL layouts)."""
        from jax.sharding import PartitionSpec as P

        return {w.name: P(*([None] * len(w.shape))) for w in self.weight_specs()}

    @staticmethod
    def axes_for_dim(axis_map: Dict[str, Optional[int]], dim: int):
        """Mesh axes mapped to output dim `dim`, as a PartitionSpec entry:
        None, a single axis name, or a tuple."""
        axes = [ax for ax, d in (axis_map or {}).items() if d == dim]
        if not axes:
            return None
        return axes[0] if len(axes) == 1 else tuple(axes)

    # output dims whose sharding does NOT propagate to inputs (e.g. an
    # out-channel dim produced by a weight contraction: the input must stay
    # replicated over axes sharding it). Subclasses with weight-produced dims
    # override this.
    _contracted_output_dims: Tuple[int, ...] = ()

    def contract_input_dim(self, input_idx: int) -> Optional[int]:
        """The input dim a CONTRACT axis shards for `input_idx` (e.g. the
        last dim for Linear, the channel dim for Conv2D). None = CONTRACT
        axes leave this input replicated. Only meaningful for ops whose
        contract_size() is not None."""
        return None

    def input_axis_map(self, axis_map: Dict[str, Optional[int]], input_idx: int
                       ) -> Dict[str, Optional[int]]:
        """Propagate the op's output axis_map to the sharding it implies for
        input `input_idx` (analog of get_input_sub_tensor shard-shape rules,
        reference model.cc:128-205). Default: same map truncated to input
        rank, with weight-contracted dims dropped (their axes need the input
        replicated — e.g. a column-parallel Linear all-gathers its input over
        the 'model' axis; the cost model must see that) and CONTRACT axes
        mapped to contract_input_dim()."""
        from flexflow_tpu.parallel.pconfig import CONTRACT

        ndims = self.inputs[input_idx].num_dims
        nd_out = self.outputs[0].num_dims
        contracted = {(d % nd_out) for d in self._contracted_output_dims}
        cdim = self.contract_input_dim(input_idx)
        out = {}
        for ax, d in axis_map.items():
            if d == CONTRACT and cdim is not None:
                out[ax] = cdim
            else:
                out[ax] = (d if d is not None and 0 <= d < ndims
                           and d not in contracted else None)
        return out

    # -- cost model ------------------------------------------------------------

    def flops(self) -> int:
        """Per-sample-batch forward FLOPs estimate for the analytic cost model
        (fallback when real measurement is unavailable)."""
        return 2 * sum(t.volume() for t in self.outputs)

    def output_bytes(self) -> int:
        import numpy as np

        return sum(t.volume() * 4 for t in self.outputs)

    def weight_bytes(self) -> int:
        total = 0
        for w in self.weight_specs():
            n = 1
            for d in w.shape:
                n *= d
            total += n * 4
        return total

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r})"


class InputOp(Op):
    """Placeholder op owning a graph input tensor (reference: tensors created
    by FFModel::create_tensor, model.cc:762, have owner_op == NULL)."""

    op_type = OperatorType.OP_INPUT

    def __init__(self, model, name: str, dims: Tuple[int, ...], dtype: DataType):
        super().__init__(model, name, [])
        self._dims = tuple(dims)
        self._dtype = dtype

    def output_shapes(self):
        return [self._dims], [self._dtype]

    def forward(self, params, xs, *, training=False, rng=None):
        raise RuntimeError("InputOp is fed by the executor, never executed")
