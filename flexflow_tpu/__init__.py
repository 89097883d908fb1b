"""flexflow_tpu — a TPU-native auto-parallelizing deep-learning framework.

A ground-up rebuild of the capabilities of FlexFlow (the Legion/CUDA
auto-parallelizing DNN framework, see /root/reference) designed for TPU:
the operator graph lowers to a single GSPMD-sharded XLA program over a
`jax.sharding.Mesh`; parallelization strategies are per-op `ParallelConfig`s
(SOAP dimensions) lowered to `PartitionSpec`s; an MCMC search over a C++
event-driven simulator with a TPU machine model (ICI/DCN/HBM) discovers
hybrid strategies; hot kernels (ring attention, embedding bag, top-k) are
Pallas.

Public API mirrors the reference's FFModel surface
(reference: include/model.h:250-483, python/flexflow/core/flexflow_cbinding.py).
"""

import os as _os

if _os.environ.get("FLEXFLOW_FORCE_CPU_DEVICES"):
    # FLEXFLOW_FORCE_CPU_DEVICES=N provisions an N-device virtual CPU
    # platform, provided flexflow_tpu is imported before any jax use (the
    # test/example sweep scripts rely on this). No-op if the embedding
    # application already initialized a backend.
    from flexflow_tpu._env import force_cpu_devices_from_env as _force_cpu

    _force_cpu(_os.environ["FLEXFLOW_FORCE_CPU_DEVICES"])

from flexflow_tpu.ffconst import (  # noqa: F401
    ActiMode,
    AggrMode,
    CompMode,
    DataType,
    LossType,
    MetricsType,
    OperatorType,
    ParameterSyncType,
    PoolType,
)
from flexflow_tpu.config import FFConfig  # noqa: F401
from flexflow_tpu.tensor import Tensor, Parameter  # noqa: F401
from flexflow_tpu.model import FFModel  # noqa: F401
from flexflow_tpu.runtime.optimizer import SGDOptimizer, AdamOptimizer  # noqa: F401
from flexflow_tpu.runtime.schedule import (  # noqa: F401
    ConstantSchedule, ExponentialDecay, StepDecay, WarmupCosine,
    WarmupLinear)
from flexflow_tpu.runtime.initializer import (  # noqa: F401
    GlorotUniformInitializer,
    ZeroInitializer,
    UniformInitializer,
    NormInitializer,
    ConstantInitializer,
)
from flexflow_tpu.runtime.dataloader import SingleDataLoader  # noqa: F401
from flexflow_tpu.runtime.resilience import TrainSupervisor  # noqa: F401
from flexflow_tpu.runtime.elastic import TopologyChangedError  # noqa: F401
from flexflow_tpu.runtime.checkpoint import (  # noqa: F401
    CheckpointCorruptError,
)
from flexflow_tpu.parallel.pconfig import ParallelConfig  # noqa: F401

__version__ = "0.1.0"
