"""On-chip MFU probe: time the encoder classifier (hidden 1024, 8 layers,
seq 512 by default) under one configuration knob per run, via the scanned multi-step trainer (so the
numbers are free of per-dispatch latency).

Usage (one jax process per chip):
    python scripts/mfu_probe.py --no-flash          # XLA einsum attention
    python scripts/mfu_probe.py --heads 8           # head_dim 128
    python scripts/mfu_probe.py --master bfloat16
    python scripts/mfu_probe.py --seq 1024 --layers 4

Prints one JSON line. A probe of levers, not a benchmark: the cells of
benchmark/run.py are what PERF.md counts.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--hidden", type=int, default=1024)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--master", default="float32")
    p.add_argument("--no-flash", action="store_true")
    p.add_argument("--fused-ln", action="store_true")
    p.add_argument("--dtype", default="bfloat16")
    args = p.parse_args()

    import jax
    import numpy as np

    from benchmark.peaks import peaks_for
    from flexflow_tpu._env import resolve_compilation_cache

    resolve_compilation_cache()

    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer, SingleDataLoader)
    from flexflow_tpu.models.transformer import build_encoder_classifier
    from flexflow_tpu.ops.base import InputOp

    dev = jax.devices()[0]
    # the benchmark's roofline denominator; raises here, before any work,
    # on a device outside the table
    peak = peaks_for(dev.device_kind)["bf16_flops"]
    cfg = FFConfig(batch_size=args.batch, mesh_shape={"data": 1},
                   compute_dtype=args.dtype, master_dtype=args.master,
                   use_fused_ln=args.fused_ln,
                   use_flash_attention=not args.no_flash)
    ff = FFModel(cfg)
    x, out = build_encoder_classifier(ff, args.batch, args.seq, args.hidden,
                                      args.layers, args.heads)
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=out)
    rs = np.random.RandomState(0)
    n = args.batch * 4
    SingleDataLoader(ff, x, rs.randn(n, args.seq, args.hidden)
                     .astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 16, (n, 1)).astype(np.int32))

    losses, _ = ff.train_scanned(args.iters)  # compile + warm
    float(losses[-1])
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        losses, _ = ff.train_scanned(args.iters)
        float(losses[-1])
        dts.append((time.perf_counter() - t0) / args.iters)
    dt = min(dts)

    fwd = sum(op.flops() for op in ff.ops if not isinstance(op, InputOp))
    print(json.dumps({
        "knobs": {"flash": not args.no_flash, "heads": args.heads,
                  "master": args.master, "fused_ln": args.fused_ln,
                  "seq": args.seq, "layers": args.layers,
                  "hidden": args.hidden, "batch": args.batch},
        "backend": dev.platform,
        "samples_per_s": round(args.batch / dt, 2),
        "step_time_ms": round(dt * 1e3, 3),
        "mfu": round(3 * fwd / dt / peak, 4),
    }), flush=True)


if __name__ == "__main__":
    main()
