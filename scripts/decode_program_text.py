#!/usr/bin/env python3
"""Say whether two trees dispatch the same decode programs, without a chip:
the text of what a tree LOWERS for a TPU v5e, hashed, to be compared with the
same from another tree (run it from each tree's root).

  --workload <cell> ...   the cell's own CPU rehearsal (`benchmark/run.py
        --rehearsal`: tiny size, the cell's builder, engine and traffic),
        and every decode program the engine builds in it is also lowered
        for the described chip on the shapes the engine passes, kernels
        compiled through Mosaic as on the chip (not interpreted). One line a
        program: its key, the sha256 of the lowered text and its length.
  --kernels               `paged_attention_fwd_pallas` at the REAL shapes of
        the serving cells' pools (and `dsa_index_scores_pallas` at its
        cell's), lowered the same way: the kernel's whole Mosaic module is
        in the text, so equal hashes are equal kernels.

    cd <tree> && JAX_PLATFORMS=cpu python3 <repo>/scripts/decode_program_text.py \\
        --kernels --workload chat-steady --workload moe-chat-steady

PERF.md section 6 (PR 49) holds the comparison this was written for.
"""
import argparse
import hashlib
import json
import os
import sys

ROOT = os.getcwd()
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# slots, query heads, KV heads, key width, value width, pool pages, table
# width, window layer's ring or None
POOLS = {
    "chat-steady/longprompt-steady": (16, 16, 8, 128, 128, 676, 66, None),
    "moe-chat-steady": (32, 16, 16, 128, 128, 480, 32, None),
    "ssm-latentmoe-chat-saturated": (32, 32, 2, 128, 128, 1056, 32, None),
    "swa-mixed-lengths-saturated global": (32, 64, 8, 128, 128, 1500, 70,
                                           None),
    "swa-mixed-lengths-saturated window": (32, 64, 8, 128, 128, 70, 2, 128),
    "hybrid-ssm-docqa-saturated": (48, 32, 8, 64, 64, 1664, 134, None),
}


def without_locations(text):
    """The lowered text with every Mosaic kernel's serialized module (the
    `body` of its custom call: MLIR bytecode, which carries the source LINE
    of every operation) replaced by the hash of the module printed without
    debug locations: two trees whose kernels differ only in where their
    lines stand in the file then lower to the same text."""
    import base64
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def printed(m):
        ctx = ir.Context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True      # `stable_mosaic`
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)
        return "mosaic:" + hashlib.sha256(asm.encode()).hexdigest()

    return re.sub(r'(?<=\\22body\\22: \\22)([A-Za-z0-9+/=]+)(?=\\22)',
                  printed, text)


def digest(text):
    text = without_locations(text)
    return hashlib.sha256(text.encode()).hexdigest()[:16], len(text)


def described_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def kernels(chip):
    import jax
    import jax.numpy as jnp

    from flexflow_tpu.ops import pallas_kernels as pk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    bf, i32 = jnp.bfloat16, jnp.int32
    for name, (b, h, kvh, dk, dv, pages, width, window) in POOLS.items():
        pack = 128 // dk if dk < 128 else 1
        pool = [sds((pages, 128, kvh // pack, d * pack), bf)
                for d in (dk, dv)]
        args = [sds((b, 1, h, dk), bf), *pool, sds((b, width), i32),
                sds((b, 1), i32), sds((b,), i32), sds((b,), i32)]
        text = jax.jit(lambda *a: pk.paged_attention_fwd_pallas(
            *a, 0.09, window=window)).lower(*args).as_text()
        print(json.dumps({"kernel": "paged_attention", "pool": name,
                          "sha": digest(text)}), flush=True)
    args = [sds((32, 64, 128), bf), sds((32, 64), jnp.float32),
            sds((2048, 128, 128), bf), sds((32, 260), i32),
            sds((32,), i32), sds((32,), i32), sds((32,), i32)]
    text = jax.jit(pk.dsa_index_scores_pallas).lower(*args).as_text()
    print(json.dumps({"kernel": "dsa_index_scores",
                      "pool": "dsa-docqa-saturated",
                      "sha": digest(text)}), flush=True)


def rehearse(cell, chip, keep):
    import jax

    from benchmark import run as bench_run
    from flexflow_tpu.runtime.serving import ServingEngine, program_name

    real = ServingEngine._compiled_call
    seen = {}

    def also_lowered(self, key, build, *args):
        if key[0] == "decode" and key not in seen:
            sds = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    jax.numpy.shape(a), jax.numpy.result_type(a),
                    sharding=chip), args)
            interpret = os.environ.pop("FF_PALLAS_INTERPRET", None)
            try:
                text = build().lower(*sds).as_text()
            finally:
                if interpret is not None:
                    os.environ["FF_PALLAS_INTERPRET"] = interpret
            seen[key] = digest(text)
            if keep:
                os.makedirs(keep, exist_ok=True)
                with open(os.path.join(
                        keep, f"{cell}.{program_name(key)}.txt"), "w") as f:
                    f.write(text)
        return real(self, key, build, *args)

    ServingEngine._compiled_call = also_lowered
    try:
        rc = bench_run.main(["--workload", cell, "--seed", "1", "--seconds",
                             "3", "--rehearsal"])
    finally:
        ServingEngine._compiled_call = real
    for key, sha in seen.items():
        print(json.dumps({"cell": cell, "program": program_name(key),
                          "key": list(key), "sha": sha, "rehearsal_rc": rc}),
              flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", action="append", default=[])
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--keep", default=None,
                    help="a directory to write the lowered texts to")
    args = ap.parse_args()
    chip = described_chip()
    if args.kernels:
        kernels(chip)
    for cell in args.workload:
        rehearse(cell, chip, args.keep)


if __name__ == "__main__":
    main()
