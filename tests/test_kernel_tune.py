"""Block-size autotuner (search/kernel_tune.py) + the measure.py timing
primitive and cost-signature bugfix it rides on.

Anchors:
  * table round-trip: a tuned winner persists to disk and a fresh
    lookup serves it; flash_attention's block resolution consults it;
  * cold fallback: no table -> the static _pick_block heuristic,
    byte-identical to the pre-tuner behavior, and a MISS is counted;
  * keying: dtype is part of the shape signature and the device key
    carries the jax version — a bf16-measured entry can never serve an
    fp32 query, and a version bump invalidates by key mismatch;
  * an illegal persisted entry (blocks not dividing the shape) falls
    back loudly instead of crashing the trace;
  * measure._op_signature records input dtypes + the environment
    signature (the ISSUE-7 cost-table bugfix).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.pallas_kernels import _pick_block, _resolve_blocks
from flexflow_tpu.search import kernel_tune, measure


@pytest.fixture
def table(tmp_path, monkeypatch):
    """A fresh table file path wired through the env knob, with the
    in-process cache and counters reset around the test."""
    path = str(tmp_path / "kernel_tune.json")
    monkeypatch.setenv("FF_KERNEL_TUNE_TABLE", path)
    kernel_tune.reload(path)
    kernel_tune.reset_stats()
    yield path
    kernel_tune.reload(path)
    kernel_tune.reset_stats()


def test_cold_fallback_is_static_heuristic(table):
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=640, seq_k=640, head_dim=64,
        dtype=jnp.float32, batch=1, heads=1, causal=True) is None
    assert kernel_tune.stats()["misses"] == 1
    bq, bk = _resolve_blocks("flash_fwd", 640, 640, 64, jnp.float32,
                             None, None)
    assert (bq, bk) == (_pick_block(640, 512), _pick_block(640, 512)) \
        == (128, 128)


def test_static_rule_is_legal_for_every_admitted_sequence(table):
    """With no table (the driver's machine has none) the static rule alone
    picks the tiles: for every sequence `flash_eligible` admits to the TPU
    (128 to 8192 in steps of 128) the outer tile divides the sequence and is
    whole lane tiles (what a (1, 1, block_q) row of lse needs), and the rows
    a step takes at a time divide the tile and are whole lane tiles too."""
    from flexflow_tpu.ops.pallas_kernels import (_OUTER_BLOCK, _chunk_rows,
                                                 flash_tile_counts)

    for seq in range(128, 8192 + 1, 128):
        for kernel in ("flash_fwd", "flash_bwd"):
            bq, bk = _resolve_blocks(kernel, seq, seq, 128, jnp.bfloat16,
                                     None, None)
            assert bq == bk == _pick_block(seq, _OUTER_BLOCK)
            assert seq % bq == 0 and bq % 128 == 0 and bq <= _OUTER_BLOCK
            chunk = _chunk_rows(bq)
            assert bq % chunk == 0 and chunk % 128 == 0
        counts = flash_tile_counts(seq, seq, bq, bk, 0, True)
        assert counts["masked"] == seq // bq    # the diagonal's own tiles
    assert kernel_tune.stats()["hits"] == 0
    assert _pick_block(4096, _OUTER_BLOCK) == 1024
    assert _pick_block(1536, _OUTER_BLOCK) == 512
    assert _pick_block(640, _OUTER_BLOCK) == 128


def test_record_roundtrip_and_resolve(table):
    sig = kernel_tune.shape_sig(seq_q=640, seq_k=640, head_dim=64,
                                dtype=jnp.float32, batch=1, heads=1,
                                causal=True)
    kernel_tune.record("flash_fwd", sig, (320, 640), 1.5e-3,
                       candidates={(128, 128): 2e-3, (320, 640): 1.5e-3})
    # in-memory cache refreshed by record(); a cold re-read also works
    kernel_tune.reload(table)
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=640, seq_k=640, head_dim=64,
        dtype=jnp.float32, batch=1, heads=1, causal=True) == (320, 640)
    # the kernel entry point consults the table (tuned != static 128)
    assert _resolve_blocks("flash_fwd", 640, 640, 64, jnp.float32,
                           None, None) == (320, 640)
    # batch/heads/causal are IN the key: any mismatch misses to static
    assert _resolve_blocks("flash_fwd", 640, 640, 64, jnp.float32,
                           None, None, batch=32, heads=1,
                           causal=True) == (128, 128)
    assert _resolve_blocks("flash_fwd", 640, 640, 64, jnp.float32,
                           None, None, causal=False) == (128, 128)
    # explicit blocks BYPASS the table (the tuner's own sweep must)
    assert _resolve_blocks("flash_fwd", 640, 640, 64, jnp.float32,
                           640, 128) == (640, 128)
    # and the file on disk is a valid atomic-written JSON table
    with open(table) as f:
        data = json.load(f)
    assert data["version"] == 1
    (key, entry), = data["entries"].items()
    assert key.startswith("flash_fwd|") and sig in key
    assert kernel_tune.device_key() in key
    assert entry["blocks"] == [320, 640]


def test_dtype_and_version_are_in_the_key(table):
    f32sig = kernel_tune.shape_sig(seq_q=256, seq_k=256, head_dim=64,
                                   dtype=jnp.float32, batch=1, heads=4,
                                   causal=True)
    kernel_tune.record("flash_fwd", f32sig, (128, 128), 1e-3)
    # same shape, bf16 query: MISS (a bf16 tile has half the bytes — an
    # f32-measured winner is noise for it)
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=256, seq_k=256, head_dim=64,
        dtype=jnp.bfloat16, batch=1, heads=4, causal=True) is None
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=256, seq_k=256, head_dim=64,
        dtype=jnp.float32, batch=1, heads=4, causal=True) == (128, 128)
    # a jax-version bump (simulated: rewrite the key with another
    # version) invalidates by mismatch, never serves stale blocks
    with open(table) as f:
        data = json.load(f)
    (key, entry), = data["entries"].items()
    stale = key.replace(f"jax-{jax.__version__}", "jax-0.0.1")
    assert stale != key
    with open(table, "w") as f:
        json.dump({"version": 1, "entries": {stale: entry}}, f)
    kernel_tune.reload(table)
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=256, seq_k=256, head_dim=64,
        dtype=jnp.float32, batch=1, heads=4, causal=True) is None


def test_table_written_after_first_lookup_is_picked_up(table):
    """A long-lived consumer must see a table another process writes
    AFTER its first (empty) lookup — the cache is keyed by the file's
    (mtime, size), not cached-forever (the documented out-of-process
    re-tune flow)."""
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=640, seq_k=640, head_dim=64,
        dtype=jnp.float32, batch=1, heads=1, causal=True) is None
    sig = kernel_tune.shape_sig(seq_q=640, seq_k=640, head_dim=64,
                                dtype=jnp.float32, batch=1, heads=1,
                                causal=True)
    key = f"flash_fwd|{kernel_tune.device_key()}|{sig}"
    # out-of-band write (no record(), no reload — a foreign process)
    with open(table, "w") as f:
        json.dump({"version": 1,
                   "entries": {key: {"blocks": [320, 640],
                                     "seconds": 1e-3}}}, f)
    os.utime(table, (0, 0))  # force a stat change even on coarse clocks
    assert kernel_tune.lookup_blocks(
        "flash_fwd", seq_q=640, seq_k=640, head_dim=64,
        dtype=jnp.float32, batch=1, heads=1, causal=True) == (320, 640)


def test_illegal_entry_falls_back(table):
    sig = kernel_tune.shape_sig(seq_q=256, seq_k=256, head_dim=64,
                                dtype=jnp.float32, batch=1, heads=1,
                                causal=True)
    kernel_tune.record("flash_fwd", sig, (96, 96), 1e-3)  # !| 256
    assert _resolve_blocks("flash_fwd", 256, 256, 64, jnp.float32,
                           None, None) == (256, 256)     # static pick
    st = kernel_tune.stats()
    # an illegal entry is a MISS (the static pick governed this trace),
    # never a hit — the hit counter means "a tuned pick actually ran"
    assert st["illegal"] == 1 and st["hits"] == 0 and st["misses"] == 1


def test_tune_then_consume_end_to_end(table):
    """The real sweep on a small shape: times every legal candidate
    through the dispatch-floor harness, persists the winner, and the
    flash forward then runs with the tuned blocks (interpret mode on
    CPU — the same code path a TPU re-tune takes)."""
    rec = kernel_tune.tune_flash_attention(
        128, head_dim=8, heads=2, batch=1,
        candidates=((64, 64), (128, 128), (512, 512)), iters=1)
    assert rec["kernel"] == "flash_fwd"
    assert tuple(rec["blocks"]) in ((64, 64), (128, 128))  # 512 illegal
    assert set(rec["candidates"]) == {"64x64", "128x128"}
    assert rec["static"] == [128, 128]
    got = kernel_tune.lookup_blocks("flash_fwd", seq_q=128, seq_k=128,
                                    head_dim=8, dtype=jnp.float32,
                                    batch=1, heads=2, causal=True)
    assert got == tuple(rec["blocks"])
    assert _resolve_blocks("flash_fwd", 128, 128, 8, jnp.float32,
                           None, None, batch=1, heads=2,
                           causal=True) == got
    # the consuming kernel actually executes with the tuned table live
    from flexflow_tpu.ops.pallas_kernels import flash_attention_fwd_pallas

    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(1, 128, 2, 8), jnp.float32)
    out, _ = flash_attention_fwd_pallas(q, q, q, True, 0.35,
                                        need_lse=False)
    assert out.shape == (2, 128, 8)  # (B*H, S, D) internal layout
    assert bool(jnp.isfinite(out).all())


def test_time_scalar_program_primitive():
    fn = jax.jit(lambda x: jnp.sum(x * 2.0))
    dt = measure.time_scalar_program(fn, jnp.ones((64, 64)), warmup=1,
                                     iters=2)
    assert dt > 0.0


def test_measure_signature_records_dtype_and_env():
    """ISSUE-7 bugfix: the cost-table signature must carry input dtypes
    and the (backend, device kind, jax version) environment — shapes
    alone let a bf16 timing serve an fp32 query across version bumps."""
    from flexflow_tpu import ActiMode, FFConfig, FFModel

    cfg = FFConfig(batch_size=2, mesh_shape={"data": 1})
    ff = FFModel(cfg)
    x = ff.create_tensor([2, 8], name="x")
    ff.dense(x, 4, ActiMode.AC_MODE_RELU, name="d0")
    op = next(o for o in ff.ops if o.name == "d0")
    sig = measure._op_signature(op, [(2, 8)], [(8, 4)])
    env = measure._env_signature()
    assert env == (jax.default_backend(),) + env[1:]
    assert env[2] == jax.__version__
    assert sig[-1] == env, "environment signature missing from cost key"
    dtypes = sig[-2]
    assert len(dtypes) == len(op.inputs) and "FLOAT" in dtypes[0].upper()
