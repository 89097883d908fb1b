"""Worker script of the changed-topology drill (tests/test_multihost.py
`test_two_process_run_resumes_on_one_survivor_resharded`), launched through
flexflow_tpu.launcher.

Phase 1 runs it on TWO controller processes (4 virtual CPU devices each,
8-device global data mesh) with FF_FAULT=sigterm@step:<k>: both
controllers checkpoint collectively at the step boundary and stop —
the "pool preempted mid-epoch" half. Phase 2 re-runs the SAME script
single-process on 4 devices: FFModel.compile's elastic hook sees the
checkpoint's 8-device mesh against the surviving 4, refits the mesh, and
doubles grad_accum_steps so the global batch is preserved; the supervisor
resumes from the multihost checkpoint (host-numpy re-shard) and training
keeps decreasing — the "resumed on a changed topology" half.

Prints one machine-checkable line:
  ELASTIC pid=<i> status=<s> resumed=<r> step=<n> mesh=<axes> accum=<k>
          procs=<p> loss_ok=<0|1>
"""

import sys

import numpy as np

import jax


def main():
    ckpt = sys.argv[1]
    total = int(sys.argv[2]) if len(sys.argv) > 2 else 10

    from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType,
                              MetricsType, SGDOptimizer, SingleDataLoader,
                              TrainSupervisor)

    cfg = FFConfig(batch_size=32, epochs=1, seed=11, checkpoint_dir=ckpt,
                   checkpoint_every=2,
                   on_topology_change="resume_resharded")
    ff = FFModel(cfg)
    x = ff.create_tensor([32, 16], name="x")
    t = ff.dense(x, 64, ActiMode.AC_MODE_RELU, name="fc1")
    ff.dense(t, 4, name="out")
    ff.compile(SGDOptimizer(lr=0.1),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY])

    # identical data on every controller (SPMD: same program, same inputs)
    rs = np.random.RandomState(0)
    SingleDataLoader(ff, x, rs.randn(128, 16).astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 4, (128, 1)).astype(np.int32))

    sup = TrainSupervisor(ff, ckpt)
    status = sup.run(total)
    losses = sup.losses
    # the resumed leg must keep making optimization progress on the new
    # topology (bitwise identity is impossible across a mesh change;
    # trajectory-level progress is the contract)
    loss_ok = 1
    if losses and len(losses) >= 4:
        half = len(losses) // 2
        loss_ok = int(np.mean(losses[half:]) < np.mean(losses[:half]))
    print(f"ELASTIC pid={jax.process_index()} status={status} "
          f"resumed={sup._resumed} step={ff._step_count} "
          f"mesh={','.join(f'{a}={s}' for a, s in ff.config.mesh_shape.items())} "
          f"accum={ff.config.grad_accum_steps} "
          f"procs={jax.process_count()} loss_ok={loss_ok}", flush=True)


if __name__ == "__main__":
    main()
