"""Hardware proof for the round-3 streaming flash kernels (VERDICT r2 #2):
compile + run forward and backward at seq 8192 — 2x the old FLASH_MAX_SEQ
cap — on the real chip, and report ms/iter.

The kernels stream opposing-side K/V tiles through the innermost grid
axis with O(block^2) VMEM scratch (ops/pallas_kernels.py), so sequence
length no longer bounds VMEM; this script is the on-chip leg of the
interpret-mode grad-exactness tests in tests/test_longcontext_dense.py.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from flexflow_tpu.ops.pallas_kernels import flash_attention  # noqa: E402

rs = np.random.RandomState(0)
b, s, h, d = 1, 8192, 4, 128
q = jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)
k = jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)
v = jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)

f = jax.jit(lambda q, k, v: flash_attention(q, k, v, True, 0.088))
# scalar fetch = the sync; warmed OUTSIDE
# the timed window so its compile doesn't pollute the ms/iter
sync = jax.jit(lambda a: a.astype(jnp.float32).sum())
o = f(q, k, v)
float(sync(o))
t0 = time.perf_counter()
for _ in range(10):
    o = f(q, k, v)
float(sync(o))
print("seq8192 fwd ok", (time.perf_counter() - t0) / 10 * 1e3, "ms/iter")

g = jax.jit(jax.grad(
    lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True, 0.088).astype(jnp.float32)),
    argnums=(0, 1, 2)))
gq, gk, gv = g(q, k, v)
float(sync(gq))  # warm the bwd program AND the sync fetch
t0 = time.perf_counter()
for _ in range(5):
    gq, gk, gv = g(q, k, v)
float(sync(gq))  # scalar fetch = real sync (see above)
print("seq8192 bwd ok", (time.perf_counter() - t0) / 5 * 1e3, "ms/iter")
