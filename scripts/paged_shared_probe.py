#!/usr/bin/env python3
"""Chip probe: the paged kernel's shared-page form alone
(`paged_attention_fwd_pallas(shared=...)`, ops/pallas_kernels.py) beside the
per-slot kernel, at the pools of the two document cells: 48 slots over
documents of `--pages` pages, `m` slots a document (m = 1: nobody shares, the
per-slot kernel alone), two pages of its own a slot. Every row is one form at
one m: microseconds a call, and for the shared form the microseconds a
GROUP-page (the call less the per-slot kernel's time over the slots' own
pages, over groups x pages), which is what PERF.md section 6 (PR 49) fits
against the members. `--rows` sets the query rows a sub-block takes
(`_SHARED_BLOCK_ROWS`).

    chiprun -- python3 scripts/paged_shared_probe.py [--rows 64,128,256]
    JAX_PLATFORMS=cpu FF_PALLAS_INTERPRET=1 python3 scripts/paged_shared_probe.py --tiny
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ops import pallas_kernels as pk

PS = 128
STEPS = 16
OWN = 2         # pages of its own a slot streams after the shared ones
# slots, query heads, KV heads, key width, value width, sink
SHAPES = {"mimo": (48, 64, 4, 192, 128, False),
          "granite": (48, 32, 8, 64, 64, False)}
PEAK = 819e9


def pools(cell, n_pool, key):
    _, _, kvh, dqk, dv, _ = SHAPES[cell]
    if dqk % 128:       # MiMo: a flat row a token
        shapes = [(n_pool, PS, kvh * dqk), (n_pool, PS, kvh * dv)]
    else:               # granite: two heads of 64 a row of 128 lanes
        shapes = [(n_pool, PS, kvh * dqk // 128, 128)] * 2
    return [jax.random.normal(jax.random.fold_in(key, i), s, jnp.bfloat16)
            for i, s in enumerate(shapes)]


def main():
    global STEPS
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="mimo,granite")
    ap.add_argument("--pages", type=int, default=127)
    ap.add_argument("--members", default="1,2,3,4,6,8")
    ap.add_argument("--rows", default="128")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    print("device", jax.devices()[0].device_kind, flush=True)
    out = []
    for cell in args.cells.split(","):
        slots, h, kvh, dqk, dv, _ = SHAPES[cell]
        doc = args.pages
        if args.tiny:
            slots, doc, STEPS = 8, 3, 2
        width = doc + OWN + 1
        n_pool = 1 + slots * width
        key = jax.random.PRNGKey(7)
        kp, vp = pools(cell, n_pool, key)
        q = jax.random.normal(jax.random.fold_in(key, 9), (slots, 1, h, dqk),
                              jnp.bfloat16)
        wp = jnp.full((slots, 1), (doc + OWN) * PS - 5, jnp.int32)
        rl = jnp.full((slots,), doc * PS + 17, jnp.int32)
        pp = jnp.full((slots,), doc * PS + 128, jnp.int32)
        page_bytes = PS * kvh * (dqk + dv) * 2
        per_slot_us = None
        for rows in [int(r) for r in args.rows.split(",")]:
            pk._SHARED_BLOCK_ROWS = rows
            cap = min(pk.shared_members_cap(h), slots)
            for m in [int(x) for x in args.members.split(",")]:
                if m > cap or slots % m or (m == 1 and per_slot_us):
                    continue
                table = np.arange(1, 1 + slots * width, dtype=np.int32) \
                    .reshape(slots, width)
                groups = []
                if m > 1:
                    for g0 in range(0, slots, m):
                        table[g0:g0 + m, :doc] = table[g0, :doc]
                        groups.append((list(range(g0, g0 + m)), doc))
                shared = pk.pack_shared_groups(groups, slots, cap) \
                    if m > 1 else None

                def run(q, kp, vp, table, shared):
                    def step(i, q):
                        o = pk.paged_attention_fwd_pallas(
                            q, kp, vp, table, wp, rl, pp, 0.1, kv_heads=kvh,
                            shared=shared)
                        return q + jnp.pad(
                            o, ((0, 0),) * 3 + ((0, dqk - o.shape[-1]),)
                        ).astype(q.dtype) * 1e-3
                    return jax.lax.fori_loop(0, STEPS, step, q)

                f = jax.jit(run)
                a = (q, kp, vp, jnp.asarray(table),
                     None if shared is None
                     else tuple(jnp.asarray(x) for x in shared))
                try:
                    jax.block_until_ready(f(*a))
                    best = 1e9
                    for _ in range(3):
                        t0 = time.perf_counter()
                        o = f(*a)
                        jax.block_until_ready(o)
                        best = min(best, time.perf_counter() - t0)
                except Exception as e:
                    print(json.dumps({"cell": cell, "rows": rows, "m": m,
                                      "error": str(e)[:600]}), flush=True)
                    continue
                us = best / STEPS * 1e6
                row = {"cell": cell, "block_rows": rows, "members": m,
                       "us_a_call": us, "finite": bool(jnp.isfinite(
                           o.astype(jnp.float32)).all())}
                if m == 1:
                    per_slot_us = us
                    row["us_a_slot_page"] = us / (slots * (doc + OWN))
                    row["hbm_share"] = slots * (doc + OWN) * page_bytes \
                        / (us * 1e-6) / PEAK * 100
                else:
                    own = per_slot_us * OWN / (doc + OWN)
                    row["us_a_group_page"] = (us - own) / (slots // m * doc)
                    row["speedup"] = per_slot_us / us
                    row["distinct_hbm_share"] = (
                        (slots // m * doc + slots * OWN) * page_bytes
                        / (us * 1e-6) / PEAK * 100)
                out.append(row)
                print(json.dumps(row), flush=True)
    if not args.tiny:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/paged_shared_probe.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
