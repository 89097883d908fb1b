#!/usr/bin/env python
"""Tune-then-consume smoke for the kernel autotuner (ci/run_ci.sh
`kernels` tier).

Proves the full loop against a REAL table file on disk, across the same
module-reload boundary a fresh process would cross:

  1. sweep (block_q, block_k) for the flash forward at one shape through
     the dispatch-floor timing harness and persist the winner;
  2. drop the in-process cache (simulating a new session), re-read the
     table from disk, and assert the lookup serves the tuned blocks;
  3. run flash_attention with the table live — the consuming trace must
     resolve to the tuned pick (counted as a table HIT) and produce the
     same numbers as the static-pick baseline (block size is a schedule
     choice, not semantics).

Run under JAX_PLATFORMS=cpu FF_PALLAS_INTERPRET=1 (what ci/run_ci.sh
exports) the kernels execute in interpret mode: the smoke exercises
exactly the code path a TPU re-tune takes.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    tmp = tempfile.mkdtemp(prefix="ff_kernel_tune_smoke_")
    table = os.path.join(tmp, "kernel_tune.json")
    os.environ["FF_KERNEL_TUNE_TABLE"] = table

    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.ops.pallas_kernels import (_resolve_blocks,
                                                 flash_attention_fwd_pallas)
    from flexflow_tpu.search import kernel_tune

    # 1. tune: a real measured sweep, persisted
    rec = kernel_tune.tune_flash_attention(
        256, head_dim=16, heads=2, batch=1,
        candidates=((64, 64), (128, 128), (256, 256)), iters=2,
        verbose=True)
    assert os.path.exists(table), "tuner did not write the table file"
    best = tuple(rec["blocks"])
    print(f"[smoke] tuned {best} (static {tuple(rec['static'])}, "
          f"changed={rec['changed']}) -> {table}")

    # 2. consume across a cache drop: a fresh read of the REAL file
    kernel_tune._TABLES.clear()
    kernel_tune.reset_stats()
    got = kernel_tune.lookup_blocks("flash_fwd", seq_q=256, seq_k=256,
                                    head_dim=16, dtype=jnp.float32,
                                    batch=1, heads=2, causal=True)
    assert got == best, f"disk round-trip served {got}, tuned {best}"
    assert _resolve_blocks("flash_fwd", 256, 256, 16, jnp.float32,
                           None, None, batch=1, heads=2,
                           causal=True) == best
    assert kernel_tune.stats()["hits"] >= 1, "lookup not counted as HIT"

    # 3. the consuming kernel: tuned pick == static pick numerically
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 256, 2, 16), jnp.float32)
    k = jnp.asarray(rs.randn(1, 256, 2, 16), jnp.float32)
    v = jnp.asarray(rs.randn(1, 256, 2, 16), jnp.float32)
    tuned, _ = flash_attention_fwd_pallas(q, k, v, True, 0.25,
                                          need_lse=False)
    static, _ = flash_attention_fwd_pallas(q, k, v, True, 0.25,
                                           block_q=256, block_k=256,
                                           need_lse=False)
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(static),
                               rtol=2e-5, atol=2e-5)
    # dtype keying: the f32-tuned entry must MISS for a bf16 query
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=256, seq_k=256, head_dim=16,
        dtype=jnp.bfloat16, batch=1, heads=2, causal=True) is None
    print("[smoke] kernel_tune tune->persist->consume: PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
