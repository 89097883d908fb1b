#!/usr/bin/env python3
"""Chip probe: the Mamba-2 decode state update alone, XLA's loop over the live
rows (`ops/mamba.py` `mamba_state_update`) against the Pallas kernel
(`mamba_state_update_pallas`), at `ssm-latentmoe-chat-saturated`'s shapes (32
slots x 128 heads x 64 x 128 float32, ten layers a step as the decode program
runs them, pools donated) with 32, 24 and 8 slots live. Prints ms a step, GB/s
and the share of 819 GB/s, and writes chiprun_out/upd_bench.json. This is
where PERF.md section 6's "loop 41.7 %, kernel 68.1 %" comes from, and why
the engine takes the kernel on the chip.

    chiprun -- python3 scripts/mamba_update_probe.py
"""
import sys, time, json
sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np
from flexflow_tpu.ops.mamba import mamba_state_update
from flexflow_tpu.ops.pallas_kernels import mamba_state_update_pallas
S, H, P, N, G, L = 32, 128, 64, 128, 8, 10
key = jax.random.PRNGKey(0)
def mk(shape, k): return jax.random.normal(jax.random.fold_in(key, k), shape, jnp.float32)
small = dict(d=jax.nn.sigmoid(mk((S, H), 1)), x=mk((S, H, P), 2), b=mk((S, G, N), 3), c=mk((S, G, N), 4))
out = {}
for name, fn in (("loop", mamba_state_update), ("pallas", mamba_state_update_pallas)):
    for nlive in (32, 24, 8):
        live = jnp.arange(S) % 4 < (nlive // 8)
        assert int(live.sum()) == nlive
        def step(pools, live):
            ys = []
            new = []
            for h in pools:
                y, h = fn(h, small["d"], small["x"], small["b"], small["c"], live)
                ys.append(y.sum()); new.append(h)
            return new, sum(ys)
        f = jax.jit(step, donate_argnums=(0,))
        pools = [mk((S, H, P, N), 10 + i) * 0.01 for i in range(L)]
        pools, y = f(pools, live); jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(20):
            pools, y = f(pools, live)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / 20
        gb = 2 * nlive * L * H * P * N * 4 / 1e9
        out[f"{name}_{nlive}"] = {"ms": dt * 1e3, "GBps": gb / dt, "share": gb / dt / 819}
        print(name, nlive, out[f"{name}_{nlive}"], flush=True)
        del pools
import os
os.makedirs("chiprun_out", exist_ok=True)
json.dump(out, open("chiprun_out/upd_bench.json", "w"), indent=1)
