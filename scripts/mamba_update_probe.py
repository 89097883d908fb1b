#!/usr/bin/env python3
"""Chip probe: the Mamba-2 decode state update alone, XLA's loop over the live
rows (`ops/mamba.py` `mamba_state_update`) against the Pallas kernel
(`mamba_state_update_pallas`), at the two cells' shapes as their decode
programs run them (`ssm-latentmoe-chat-saturated`: 32 slots x 8 groups x 128 x
1024 float32, ten layers a step; `hybrid-ssm-docqa-saturated`: 48 slots x 1
group x 128 x 4096, 36 layers a step; pools donated) with all, three quarters
and a quarter of the slots live. Prints ms a step, GB/s and the share of 819
GB/s, and writes chiprun_out/upd_bench.json. This is where PERF.md section
6's kernel-alone numbers come from (PR 37: loop 41.7 %, kernel 68.1 %; PR 44:
the kernel in the held layout), and why the engine takes the kernel on the
chip.

    chiprun -- python3 scripts/mamba_update_probe.py
"""
import json
import os
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp

from flexflow_tpu.ops.mamba import mamba_state_update
from flexflow_tpu.ops.pallas_kernels import mamba_state_update_pallas

# (slots, heads, P, N, groups, layers)
SHAPES = {"nemotron": (32, 128, 64, 128, 8, 10),
          "granite": (48, 64, 64, 128, 1, 36)}
STEPS = 20


def main():
    key = jax.random.PRNGKey(0)

    def mk(shape, k):
        return jax.random.normal(jax.random.fold_in(key, k), shape,
                                 jnp.float32)

    out = {}
    for cell, (S, H, P, N, G, L) in SHAPES.items():
        small = (jax.nn.sigmoid(mk((S, H), 1)), mk((S, H, P), 2),
                 mk((S, G, N), 3), mk((S, G, N), 4))
        for name, fn in (("loop", mamba_state_update),
                         ("pallas", mamba_state_update_pallas)):
            for quarters in (4, 3, 1):
                live = jnp.arange(S) % 4 < quarters
                nlive = int(live.sum())

                def step(pools, live):
                    ys, new = [], []
                    for h in pools:
                        y, h = fn(h, *small, live)
                        ys.append(y.sum())
                        new.append(h)
                    return new, sum(ys)

                f = jax.jit(step, donate_argnums=(0,))
                pools = [mk((S, G, N, H // G * P), 10 + i) * 0.01
                         for i in range(L)]
                pools, y = f(pools, live)
                jax.block_until_ready(y)
                t0 = time.perf_counter()
                for _ in range(STEPS):
                    pools, y = f(pools, live)
                jax.block_until_ready(y)
                dt = (time.perf_counter() - t0) / STEPS
                gb = 2 * nlive * L * H * P * N * 4 / 1e9
                out[f"{cell}_{name}_{nlive}"] = {
                    "ms": dt * 1e3, "us_slot_layer": dt * 1e6 / nlive / L,
                    "GBps": gb / dt, "share": gb / dt / 819}
                print(cell, name, nlive, out[f"{cell}_{name}_{nlive}"],
                      flush=True)
                del pools
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/upd_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
