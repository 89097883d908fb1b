#!/usr/bin/env python3
"""Chip probe: the paged-attention kernel alone, in PR 26's form (`seconds a
call = a + b x pages`), at the pools of the serving cells, for each number of
pages a TURN of the kernel may take and each way its pages may land in VMEM:

  engine  `paged_attention_fwd_pallas` as the engine calls it: g from
          `paged_turn_pages`, a block of g > 1 pages landing dense through
          the wrapper's reshape of the pool (with `--parent DIR` also the
          kernel of the tree unpacked at DIR, as `parent`)
  padded  the pool as the engine holds it, (pages, ps, rows, 128), copied a
          page at a time into a (g, ps, rows, 128) buffer: rows padded to the
          dtype's sublane tile in VMEM, re-packed by the body's reshape
  dense   the same bytes at rest as (pages, ps x rows, 128): a page lands
          dense and the body's reshape merges leading dims only. Against
          `padded` it says whether a turn's cost is a TURN's (falls as 1 / g
          in both) or a TOKEN's of a padded page (falls only here); against
          `engine` it says whether XLA makes the wrapper's reshape a bitcast
          (the same time) or a copy of the pool

`padded` and `dense` call `_paged_attn_kernel` through a pallas_call of the
probe's own, at any g. Every step appends one token a slot to the pool (a
scatter, in place, as a decode step does) and then calls the kernel, 32 steps
a dispatch, the context `pages` pages in every slot. Prints one JSON line a
row and the fit, and writes chiprun_out/paged_turn_probe.json. PERF.md
section 6 (PR 45) quotes it (its rows `4d-g*` are `padded`, `bitcast-g*`
`engine`: the probe ran before the wrapper learned the reshape).

    chiprun -- python3 scripts/paged_turn_probe.py [--parent .chip_scratch/parent]
    JAX_PLATFORMS=cpu python3 scripts/paged_turn_probe.py --aot    # compile only
"""
import argparse
import functools
import importlib.util
import json
import math
import os
import sys
import time

sys.path.insert(0, ".")
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flexflow_tpu.ops import pallas_kernels as pk

PS = 128
STEPS = 32
# slots, heads, KV heads, head size, pool pages, table width, pages a slot
SHAPES = {
    "granite": (48, 32, 8, 64, 1664, 134, (16, 48, 79, 134)),
    "nemotron": (32, 32, 2, 128, 1056, 32, (2, 6, 16, 32)),
    "internlm2": (16, 16, 8, 128, 676, 66, (1, 4, 16, 66)),
    "olmoe": (32, 16, 16, 128, 480, 32, (1, 4, 8, 15)),
}
PEAK = 819e9


def direct_call(q, k, v, table, wp, rl, pp, scale, kvh, g):
    """`paged_attention_fwd_pallas` at g pages a turn with the pool as it is
    handed over: (pages, ps, rows, 128), a page landing padded, or (pages,
    ps x rows, 128), dense. The same kernel body and stream."""
    b, s, h, d = q.shape
    lanes = k.shape[-1]
    pack = lanes // d
    assert math.prod(k.shape[1:-1]) == PS * kvh // pack
    if pack > 1:
        lane_of = (jnp.arange(h) // (h // kvh)) % pack
        q = (q[:, :, :, None, :]
             * (lane_of[:, None] == jnp.arange(pack))[None, None, :, :, None]
             .astype(q.dtype)).reshape(b, s, h, lanes)
    nbuf = pk._paged_ring(g, k.dtype, k.shape[1:], v.shape[1:])
    last = (jnp.maximum(jnp.max(wp, axis=1), rl - 1) // PS).astype(jnp.int32)
    prefetch = [table, last, wp, rl, pp]

    def slot_map(bi, *_):
        return (bi, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(b,),
        in_specs=[pl.BlockSpec((1, s * h, lanes), slot_map),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, s * h, lanes), slot_map),
        scratch_shapes=[pltpu.VMEM((nbuf, g, *k.shape[1:]), k.dtype),
                        pltpu.VMEM((nbuf, g, *v.shape[1:]), v.dtype),
                        pltpu.SemaphoreType.DMA((2, nbuf)),
                        pltpu.SMEM((3,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(pk._paged_attn_kernel, s=s, h=h, kvh=kvh, ps=PS,
                          nbuf=nbuf, g=g, scale=scale, pack=pack),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s * h, lanes), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pk._interpret(),
    )(*prefetch, q.reshape(b, s * h, lanes), k, v)
    if pack > 1:
        out = jnp.take_along_axis(out.reshape(b, s, h, pack, d),
                                  lane_of[None, None, :, None, None], axis=3)
    return out.reshape(b, s, h, d)


def load_parent(path):
    spec = importlib.util.spec_from_file_location(
        "parent_pallas_kernels",
        os.path.join(path, "flexflow_tpu/ops/pallas_kernels.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stepper(form, kernels, g, kvh, rows):
    """A jitted dispatch of STEPS decode steps: append, then attend."""
    scale = 0.125

    def attend(q, k, v, table, wp, rl, pp):
        if form == "engine":
            return kernels.paged_attention_fwd_pallas(q, k, v, table, wp, rl,
                                                      pp, scale)
        return direct_call(q, k, v, table, wp, rl, pp, scale, kvh, g)

    def run(q, k, v, new, table, wp, rl, pp):
        page = jnp.take_along_axis(table, wp // PS, axis=1)[:, 0]
        off = wp[:, 0] % PS
        # one token a slot, in place (of a dense pool: its first row, which
        # times the same)
        at, row = ((page, off * rows), new[:, 0]) if form == "dense" \
            else ((page, off), new)

        def step(i, c):
            q, k, v = c
            k, v = k.at[at].set(row), v.at[at].set(row)
            o = attend(q, k, v, table, wp, rl, pp)
            return (q + o.astype(q.dtype) * 1e-3, k, v)

        return jax.lax.fori_loop(0, STEPS, step, (q, k, v))

    return jax.jit(run, donate_argnums=(1, 2))


def variants_of(rows, g_rule, parent):
    """(form, kernel module, g) of each variant a pool of `rows` rows a token
    is timed in."""
    out = [("engine", pk, g_rule)]
    if rows >= 8:
        # the rule gives 1: a block of two (padded) pages beside it
        out.append(("padded", pk, 2))
    else:
        out += [(form, pk, g) for form in ("padded", "dense")
                for g in (1, 2, 4) + ((8,) if rows == 2 else ())]
    if parent is not None:
        out.append(("engine", parent, 0))
    return out


def main():
    global STEPS
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--cells", default="granite,nemotron,internlm2,olmoe")
    ap.add_argument("--tiny", action="store_true",
                    help="a rehearsal: two slots, a few pages")
    ap.add_argument("--aot", action="store_true",
                    help="compile each variant for a described v5e; run none")
    args = ap.parse_args()
    parent = load_parent(args.parent) if args.parent else None
    on_chip = None
    if args.aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        os.environ.pop("FF_PALLAS_INTERPRET", None)
        on_chip = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    else:
        print("device", jax.devices()[0].device_kind, flush=True)
    rs = np.random.RandomState(0)
    out = []
    for cell in args.cells.split(","):
        slots, h, kvh, d, n_pool, width, page_counts = SHAPES[cell]
        if args.tiny:
            slots, n_pool, width, page_counts, STEPS = 2, 24, 9, (1, 5, 9), 2
        pack = 128 // d
        rows = kvh // pack
        g_rule = pk.paged_turn_pages(PS, rows, width)
        table = jnp.asarray(rs.randint(1, n_pool, (slots, width)), jnp.int32)
        for form, kernels, g in variants_of(rows, g_rule, parent):
            name = f"{form}-g{g}" if g else "parent"
            f = stepper(form, kernels, g, kvh, rows)
            shape = ((n_pool, PS * rows, 128) if form == "dense"
                     else (n_pool, PS, rows, 128))
            shapes = [(slots, 1, h, d), shape, shape, (slots, rows, 128)]
            if args.aot:
                specs = [jax.ShapeDtypeStruct(x, jnp.bfloat16,
                                              sharding=on_chip)
                         for x in shapes] \
                    + [jax.ShapeDtypeStruct(x, jnp.int32, sharding=on_chip)
                       for x in ((slots, width), (slots, 1), (slots,),
                                 (slots,))]
                try:
                    text = f.lower(*specs).compile().as_text()
                except Exception as e:
                    print(json.dumps({"cell": cell, "variant": name,
                                      "aot": "FAILED", "error": str(e)[:600]}),
                          flush=True)
                    continue
                pool = f"bf16[{','.join(str(x) for x in shape)}]"
                copies = [ln.strip()[:160] for ln in text.splitlines()
                          if " copy(" in ln and str(n_pool) in ln]
                print(json.dumps({"cell": cell, "variant": name, "aot": "ok",
                                  "pool": pool, "pool_copies": copies}),
                      flush=True)
                continue
            times = {}
            for pages in page_counts:
                key = jax.random.PRNGKey(pages)
                q, k, v, new = (
                    jax.random.normal(jax.random.fold_in(key, i), x,
                                      jnp.bfloat16)
                    for i, x in enumerate(shapes))
                wp = jnp.full((slots, 1), pages * PS - 1, jnp.int32)
                rl = jnp.zeros((slots,), jnp.int32)
                try:
                    q2, k, v = f(q, k, v, new, table, wp, rl, rl)
                    jax.block_until_ready(q2)
                    best = 1e9
                    for _ in range(3):
                        t0 = time.perf_counter()
                        q2, k, v = f(q, k, v, new, table, wp, rl, rl)
                        jax.block_until_ready(q2)
                        best = min(best, time.perf_counter() - t0)
                except Exception as e:  # Mosaic refused the form: say so
                    print(json.dumps({"cell": cell, "variant": name,
                                      "error": str(e)[:400]}), flush=True)
                    break
                del k, v
                times[pages] = best / STEPS
                gb = slots * pages * PS * rows * 128 * 2 * 2
                row = {"cell": cell, "variant": name, "pages_a_slot": pages,
                       "us_a_call": best / STEPS * 1e6,
                       "us_a_page": best / STEPS * 1e6 / (slots * pages),
                       "hbm_share": gb / (best / STEPS) / PEAK * 100,
                       "finite": bool(jnp.isfinite(
                           q2.astype(jnp.float32)).all())}
                out.append(row)
                print(json.dumps(row), flush=True)
            if len(times) >= 2:
                x = np.asarray([slots * p for p in times], np.float64)
                y = np.asarray(list(times.values()), np.float64) * 1e6
                b_, a_ = np.polyfit(x, y, 1)
                page_us = PS * rows * 128 * 2 * 2 / PEAK * 1e6
                fit = {"cell": cell, "variant": name, "fit_a_us": a_,
                       "fit_b_us_a_page": b_, "page_dma_us": page_us,
                       "residual_us": float(np.abs(a_ + b_ * x - y).max())}
                out.append(fit)
                print(json.dumps(fit), flush=True)
    if not (args.aot or args.tiny):
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/paged_turn_probe.json", "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
