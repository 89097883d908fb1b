"""Framework-wide enums.

Mirrors the reference's constant vocabulary (reference: include/ffconst.h:1-130,
TASO-aligned OperatorType) so strategy files, importers, and user code can use
the same names. Values are Python enums, not ABI-pinned ints, except where the
reference's numeric values leak into file formats (none do — strategy files key
by op *name*, reference: src/runtime/strategy.cc:95-148).
"""

import enum


class ActiMode(enum.Enum):
    AC_MODE_NONE = 10
    AC_MODE_RELU = 11
    AC_MODE_SIGMOID = 12
    AC_MODE_TANH = 13
    AC_MODE_GELU = 14


class AggrMode(enum.Enum):
    AGGR_MODE_NONE = 20
    AGGR_MODE_SUM = 21
    AGGR_MODE_AVG = 22


class PoolType(enum.Enum):
    POOL_MAX = 30
    POOL_AVG = 31


class DataType(enum.Enum):
    DT_FLOAT = 40
    DT_DOUBLE = 41
    DT_INT32 = 42
    DT_INT64 = 43
    DT_BOOLEAN = 44
    DT_HALF = 45
    DT_BFLOAT16 = 46
    DT_NONE = 49


class LossType(enum.Enum):
    LOSS_CATEGORICAL_CROSSENTROPY = 50
    LOSS_SPARSE_CATEGORICAL_CROSSENTROPY = 51
    LOSS_MEAN_SQUARED_ERROR_AVG_REDUCE = 52
    LOSS_MEAN_SQUARED_ERROR_SUM_REDUCE = 53
    LOSS_IDENTITY = 54


class CompMode(enum.Enum):
    COMP_MODE_TRAINING = 70
    COMP_MODE_INFERENCE = 71


class ParameterSyncType(enum.Enum):
    NONE = 80
    PS = 81
    NCCL = 82  # kept for API parity; lowers to XLA all-reduce (psum) on TPU


class MetricsType(enum.Enum):
    METRICS_ACCURACY = 1001
    METRICS_CATEGORICAL_CROSSENTROPY = 1002
    METRICS_SPARSE_CATEGORICAL_CROSSENTROPY = 1004
    METRICS_MEAN_SQUARED_ERROR = 1008
    METRICS_ROOT_MEAN_SQUARED_ERROR = 1016
    METRICS_MEAN_ABSOLUTE_ERROR = 1032


class OperatorType(enum.Enum):
    """Op vocabulary (reference: include/ffconst.h OperatorType, TASO-aligned)."""

    OP_INPUT = enum.auto()
    OP_WEIGHT = enum.auto()
    OP_NOOP = enum.auto()
    OP_CONV2D = enum.auto()
    OP_DROPOUT = enum.auto()
    OP_LINEAR = enum.auto()
    OP_BATCHMATMUL = enum.auto()
    OP_POOL2D = enum.auto()
    OP_RELU = enum.auto()
    OP_SIGMOID = enum.auto()
    OP_TANH = enum.auto()
    OP_ELU = enum.auto()
    OP_GELU = enum.auto()
    OP_FLAT = enum.auto()
    OP_SOFTMAX = enum.auto()
    OP_BATCHNORM = enum.auto()
    OP_LAYERNORM = enum.auto()
    OP_RMSNORM = enum.auto()
    OP_CONCAT = enum.auto()
    OP_SPLIT = enum.auto()
    OP_EMBEDDING = enum.auto()
    OP_EW_ADD = enum.auto()
    OP_EW_MUL = enum.auto()
    OP_EW_SUB = enum.auto()
    OP_EW_DIV = enum.auto()
    OP_EW_MAX = enum.auto()
    OP_EW_MIN = enum.auto()
    OP_SCALAR_MULTIPLY = enum.auto()
    OP_EXP = enum.auto()
    OP_SIN = enum.auto()
    OP_COS = enum.auto()
    OP_POW = enum.auto()
    OP_RSQRT = enum.auto()
    OP_IDENTITY = enum.auto()
    OP_RESHAPE = enum.auto()
    OP_REVERSE = enum.auto()
    OP_TRANSPOSE = enum.auto()
    OP_TOPK = enum.auto()
    OP_MULTIHEAD_ATTENTION = enum.auto()
    OP_ATTENTION = enum.auto()  # modern fused (flash/ring) attention
    OP_CAST = enum.auto()
    OP_PAD = enum.auto()
    OP_MEAN = enum.auto()
    OP_REDUCE_SUM = enum.auto()
    OP_FUSED = enum.auto()
    OP_LSTM = enum.auto()
    OP_GRU = enum.auto()
    OP_RNN = enum.auto()
    OP_MOE = enum.auto()  # mixture-of-experts (net-new vs reference)
    OP_GATHER = enum.auto()
    OP_AGG_SPEC = enum.auto()
    OP_GROUP_BY = enum.auto()
    OP_SLICE = enum.auto()
    OP_SQUEEZE = enum.auto()
    OP_UNSQUEEZE = enum.auto()
    OP_MAXIMUM = enum.auto()
    OP_MINIMUM = enum.auto()
    OP_SIGMOID_SILU_MULTI = enum.auto()
    OP_ROTARY_EMBEDDING = enum.auto()
    OP_MAMBA2 = enum.auto()  # selective state-space mixer (ops/mamba.py)
    OP_GATED_MLP = enum.auto()  # SwiGLU feed-forward as one op (ops/dense.py)
    OP_POWER_RETENTION = enum.auto()  # power-retention mixer (ops/retention.py)


# --- dtype lowering ---------------------------------------------------------

import numpy as _np  # noqa: E402


_DTYPE_TO_NP = {
    DataType.DT_FLOAT: _np.float32,
    DataType.DT_DOUBLE: _np.float64,
    DataType.DT_INT32: _np.int32,
    DataType.DT_INT64: _np.int64,
    DataType.DT_BOOLEAN: _np.bool_,
    DataType.DT_HALF: _np.float16,
}


def dtype_to_np(dt: DataType):
    if dt == DataType.DT_BFLOAT16:
        import jax.numpy as jnp

        return jnp.bfloat16
    return _DTYPE_TO_NP[dt]


def np_to_dtype(np_dtype) -> DataType:
    import jax.numpy as jnp

    d = _np.dtype(np_dtype) if np_dtype != jnp.bfloat16 else np_dtype
    if d == jnp.bfloat16:
        return DataType.DT_BFLOAT16
    for k, v in _DTYPE_TO_NP.items():
        if _np.dtype(v) == d:
            return k
    raise ValueError(f"unsupported numpy dtype {np_dtype}")
