"""Test configuration: run everything on an 8-device virtual CPU mesh so
multi-chip sharding is exercised without TPU hardware (SURVEY.md §4:
"JAX offers CPU simulation of meshes, so distributed tests can run
single-host"). Pallas kernels run in interpret mode here because the suite
asks for it (FF_PALLAS_INTERPRET=1, inherited by worker subprocesses);
nothing in the package falls into it on its own.
"""

import os
import sys

os.environ.setdefault("FF_PALLAS_INTERPRET", "1")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# The suite runs WITHOUT jax's persistent compilation cache: identical tiny
# models recompile across files constantly and a warm cache once returned a
# wrong executable for test_grad_accum. Only the entry scripts place a cache
# (flexflow_tpu._env.resolve_compilation_cache).

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
