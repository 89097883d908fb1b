"""Worker of the overlapped-sync preempt/resume drill
(tests/test_multihost.py
`test_two_process_overlapped_sync_preempt_resumes_bitwise`), launched
through flexflow_tpu.launcher on one OR two controller processes.

Trains with FFConfig.overlap_grad_sync on (bucketed in-scan grad
reduce-scatter + ZeRO-1 sharded optimizer update) and
async_checkpointing on — single-process that publishes checkpoints from
the background thread; on two controllers the collective multihost save
falls back to synchronous with a warning (the documented contract) —
under a TrainSupervisor. FF_FAULT=sigterm@step:<k> preempts phase 1; a
relaunch resumes and must continue BITWISE (the test compares the
resumed loss tail against an uninterrupted reference run).

Prints one machine-checkable line per process:
  OVERLAPSYNC pid=<i> status=<s> resumed=<r> step=<n> procs=<p>
              zero1=<0|1> losses=<l1,l2,...>   (losses at %.9f)
"""

import sys

import numpy as np

import jax


def main():
    ckpt = sys.argv[1]
    total = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    from flexflow_tpu import (ActiMode, FFConfig, FFModel, LossType,
                              MetricsType, SGDOptimizer, SingleDataLoader,
                              TrainSupervisor)
    from flexflow_tpu.runtime.optimizer import Zero1Update

    cfg = FFConfig(batch_size=32, epochs=1, seed=7, grad_accum_steps=2,
                   overlap_grad_sync=True, async_checkpointing=True,
                   checkpoint_dir=ckpt, checkpoint_every=2)
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
    losses = ",".join(f"{l:.9f}" for l in sup.losses)
    print(f"OVERLAPSYNC pid={jax.process_index()} status={status} "
          f"resumed={sup._resumed} step={ff._step_count} "
          f"procs={jax.process_count()} "
          f"zero1={int(isinstance(ff.optimizer, Zero1Update))} "
          f"losses={losses}", flush=True)


if __name__ == "__main__":
    main()
