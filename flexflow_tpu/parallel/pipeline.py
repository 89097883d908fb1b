"""Pipeline parallelism over a 'pipe' (or search-assigned STAGE) mesh axis.

The reference has pipelining only in its hand-rolled NMT subsystem (sequence
chunked LSTM_PER_NODE_LENGTH=10 per device, per-(layer,timestep)
ParallelConfig tables — nmt/rnn.h:21-63). TPU re-design: circulating
(collective-permute) schedules inside shard_map — every device holds ONE
stage's params (stacked params sharded on dim 0 over the axis); microbatches
ripple through the ring via `lax.ppermute`; the whole schedule is a
`lax.scan`, so it jits into one XLA program.

Two schedules:
  * `pipeline` — GPipe forward; under outer autodiff the reverse scan gives
    a pipelined backward, stashing per-(tick) residuals: O(num_micro)
    boundary activations per device.
  * `pipeline_train_1f1b` — a hand-scheduled one-forward-one-backward
    training step: each scan tick runs (at most) one microbatch forward AND
    one backward, with the backward recomputing its stage from a stashed
    input (activation recompute). The stash is a ring of
    min(num_micro, 2*stages - 1) microbatch INPUTS — per-device activation
    memory is O(stages), independent of num_micro, which is the 1F1B memory
    property GPipe lacks.

Constraint (classic for both): all stages share one activation shape.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax



def gpipe_loop(stage_fn: Callable, stage_params, x_mb, axis_name: str):
    """Run inside shard_map. stage_params: this device's stage params (pytree,
    leading stage dim already stripped). x_mb: (num_micro, mb, ...) — the full
    microbatched input (replicated; only stage 0 reads it). Returns
    (num_micro, mb, ...) outputs (valid on the LAST stage; use
    `pipeline()` below for the replicated gather)."""
    n_stage = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    num_micro = x_mb.shape[0]
    steps = num_micro + n_stage - 1
    mb_shape = x_mb.shape[1:]

    from flexflow_tpu.parallel.ring_attention import pvary

    buf0 = jnp.zeros(mb_shape, x_mb.dtype)  # activation arriving at this stage
    out0 = jnp.zeros_like(x_mb)
    buf0, out0 = pvary(buf0, axis_name), pvary(out0, axis_name)
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    def step(carry, t):
        buf, outs = carry
        # stage 0 ingests microbatch t (clamped; bubbles compute garbage that
        # is never written out)
        mb_idx = jnp.clip(t, 0, num_micro - 1)
        inp = jnp.where(idx == 0, x_mb[mb_idx], buf)
        y = stage_fn(stage_params, inp)
        # last stage completed microbatch t-(n_stage-1) this step
        done_idx = t - (n_stage - 1)
        write = jnp.logical_and(idx == n_stage - 1, done_idx >= 0)
        outs = lax.cond(
            write,
            lambda o: lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(done_idx, 0, num_micro - 1), 0),
            lambda o: o, outs)
        buf_next = lax.ppermute(y, axis_name, perm)
        return (buf_next, outs), None

    (_, outs), _ = lax.scan(step, (buf0, out0), jnp.arange(steps))
    return outs


def pipeline(stage_fn: Callable, stacked_params, x, mesh, axis_name: str = "pipe",
             num_microbatches: int = None, data_axis: str = None):
    """User-facing pipelined apply.

    stage_fn(params_i, x) -> y with y.shape == x.shape
    stacked_params: pytree with leading dim = num_stages
    x: (batch, ...) global input. Returns (batch, ...) output.
    data_axis: optional mesh axis the microbatch dim is ALSO sharded over
    (composes dp x pp: each pipe ring runs on its data slice).
    """
    from jax.sharding import PartitionSpec as P

    n_stage = mesh.shape[axis_name]
    num_micro = num_microbatches or n_stage
    b = x.shape[0]
    assert b % num_micro == 0, f"batch {b} % microbatches {num_micro}"
    x_mb = x.reshape(num_micro, b // num_micro, *x.shape[1:])

    def inner(params, xm):
        params = jax.tree_util.tree_map(lambda a: a[0], params)  # strip stage dim
        outs = gpipe_loop(stage_fn, params, xm, axis_name)
        # broadcast final outputs from the last stage to all stages so the
        # result is replicated over 'pipe' (psum of one-hot contribution)
        idx = lax.axis_index(axis_name)
        contrib = jnp.where(idx == n_stage - 1, outs, jnp.zeros_like(outs))
        return lax.psum(contrib, axis_name)

    dp = (data_axis if data_axis and mesh.shape.get(data_axis, 1) > 1
          else None)
    pspec = jax.tree_util.tree_map(
        lambda a: P(axis_name, *([None] * (a.ndim - 1))), stacked_params)
    xspec = P(None, dp) if dp else P()
    out = jax.shard_map(inner, mesh=mesh, in_specs=(pspec, xspec),
                        out_specs=xspec, check_vma=False)(
        stacked_params, x_mb)
    return out.reshape(b, *out.shape[2:])


def _1f1b_loop(stage_fn, loss_fn, params, x_mb, lab_mb, head_params,
               axis_name: str):
    """Per-device 1F1B body (inside shard_map). Schedule, for n stages and
    m microbatches over ticks t = 0 .. 2(n-1)+m-1:
        forward  of microbatch j at stage i: tick t = i + j
        backward of microbatch j at stage i: tick t = 2(n-1) - i + j
    Both are injective in j for fixed (i, t), so each device does at most
    one F and one B per tick; the last stage runs B(j) in the same tick as
    F(j) (the loss cotangent seeds immediately — no wait). The backward
    recomputes its stage via jax.vjp from a stashed INPUT; live in-flight
    microbatches per device never exceed 2(n-1-i), so a ring stash of
    S = min(m, 2n-1) slots is aliasing-safe: a live F(j) and live B(j')
    share a slot only if j - j' is a positive multiple of S, impossible
    with both live (j - j' < m <= S or masked)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = x_mb.shape[0]
    S = min(m, 2 * n - 1)
    ticks = 2 * (n - 1) + m

    from flexflow_tpu.parallel.ring_attention import pvary

    mb_shape = x_mb.shape[1:]
    buf_f0 = pvary(jnp.zeros(mb_shape, x_mb.dtype), axis_name)
    buf_b0 = pvary(jnp.zeros(mb_shape, x_mb.dtype), axis_name)
    stash0 = pvary(jnp.zeros((S,) + mb_shape, x_mb.dtype), axis_name)
    g0 = jax.tree_util.tree_map(
        lambda a: pvary(jnp.zeros_like(a), axis_name), params)
    gh0 = jax.tree_util.tree_map(
        lambda a: pvary(jnp.zeros_like(a), axis_name), head_params)
    dx0 = pvary(jnp.zeros_like(x_mb), axis_name)
    loss0 = pvary(jnp.zeros((), jnp.float32), axis_name)

    perm_f = [(i, (i + 1) % n) for i in range(n)]
    perm_b = [(i, (i - 1) % n) for i in range(n)]
    is_last = idx == n - 1

    def tick(carry, t):
        buf_f, buf_b, stash, g, gh, dx, loss = carry

        # ---- forward slot: F(idx, jf) ----
        jf = t - idx
        do_f = jnp.logical_and(jf >= 0, jf < m)
        mb_f = jnp.clip(jf, 0, m - 1)
        inp = jnp.where(idx == 0, x_mb[mb_f], buf_f)
        slot_f = mb_f % S
        stash = lax.cond(
            do_f,
            lambda s: lax.dynamic_update_index_in_dim(s, inp, slot_f, 0),
            lambda s: s, stash)
        y = stage_fn(params, inp)

        # last stage: this microbatch's loss + cotangent seed, same tick
        lab = lab_mb[mb_f]
        loss_j, (dy_j, dh_j) = jax.value_and_grad(
            lambda yy, hp: loss_fn(yy, lab, hp), argnums=(0, 1))(
                y, head_params)
        fin = jnp.logical_and(is_last, do_f)
        loss = loss + jnp.where(fin, loss_j.astype(jnp.float32), 0.0)
        # select, not multiply-by-mask: dead warm-up ticks run stage_fn on
        # zero-initialized garbage, and a loss with log/div yields NaN there;
        # 0*NaN = NaN would poison the accumulator even though the tick is
        # masked. where() drops the dead value entirely.
        gh = jax.tree_util.tree_map(
            lambda a, b: a + jnp.where(fin, b, jnp.zeros_like(b)), gh, dh_j)

        # ---- backward slot: B(idx, jb) ----
        jb = t - (2 * (n - 1) - idx)
        do_b = jnp.logical_and(jb >= 0, jb < m)
        mb_b = jnp.clip(jb, 0, m - 1)
        inp_b = stash[mb_b % S]
        cot = jnp.where(is_last, dy_j, buf_b).astype(inp_b.dtype)
        _, pull = jax.vjp(stage_fn, params, inp_b)
        dparams, dinp = pull(cot)
        g = jax.tree_util.tree_map(
            lambda a, b: a + jnp.where(do_b, b, jnp.zeros_like(b)), g, dparams)
        dx = lax.cond(
            jnp.logical_and(idx == 0, do_b),
            lambda d: lax.dynamic_update_index_in_dim(d, dinp, mb_b, 0),
            lambda d: d, dx)

        buf_f = lax.ppermute(y, axis_name, perm_f)
        buf_b = lax.ppermute(dinp, axis_name, perm_b)
        return (buf_f, buf_b, stash, g, gh, dx, loss), None

    carry0 = (buf_f0, buf_b0, stash0, g0, gh0, dx0, loss0)
    (buf_f, buf_b, stash, g, gh, dx, loss), _ = lax.scan(
        tick, carry0, jnp.arange(ticks))
    return g, gh, dx, loss


def pipeline_train_1f1b(stage_fn: Callable, loss_fn: Callable,
                        stacked_params, x, labels, mesh,
                        axis_name: str = "pipe",
                        num_microbatches: int = None,
                        head_params=None, data_axis: str = None):
    """One 1F1B-scheduled pipelined training step (fwd + bwd + grads).

    stage_fn(params_i, h) -> h' with h'.shape == h.shape
    loss_fn(y_mb, labels_mb, head_params) -> scalar mean loss for one
        microbatch (the trainable head — e.g. the LM output projection —
        lives in `head_params`, replicated over the pipe axis)
    stacked_params: pytree with leading dim = num_stages
    x: (batch, ...); labels: (batch, ...)

    Returns (loss, grads, head_grads, dx): microbatch-mean loss
    (replicated), grads with the same stage-stacked structure as
    stacked_params (sharded over `axis_name` on dim 0 — exactly the layout
    an optimizer update wants), head grads (replicated, already summed over
    microbatches — divide by num_microbatches upstream if loss_fn returns a
    per-microbatch mean), and d(loss_sum)/dx.

    Memory: O(min(m, 2n-1)) stashed microbatch inputs per device (true
    1F1B in-flight bound) — vs O(m) boundary residuals for autodiff through
    `pipeline` — at the cost of one forward recompute per backward, the
    standard TPU rematerialization trade.
    """
    from jax.sharding import PartitionSpec as P

    n_stage = mesh.shape[axis_name]
    num_micro = num_microbatches or n_stage
    b = x.shape[0]
    assert b % num_micro == 0, f"batch {b} % microbatches {num_micro}"
    x_mb = x.reshape(num_micro, b // num_micro, *x.shape[1:])
    lab_mb = labels.reshape(num_micro, b // num_micro, *labels.shape[1:])
    if head_params is None:
        head_params = {}

    dp = (data_axis if data_axis and mesh.shape.get(data_axis, 1) > 1
          else None)

    def inner(params, xm, lm, hp):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        g, gh, dx, loss = _1f1b_loop(stage_fn, loss_fn, params, xm, lm, hp,
                                     axis_name)
        # stage grads stay sharded (leading stage dim restored); loss /
        # head grads / dx live on one stage only — psum replicates them
        g = jax.tree_util.tree_map(lambda a: a[None], g)
        gh = jax.tree_util.tree_map(
            lambda a: lax.psum(a, axis_name), gh)
        dx = lax.psum(dx, axis_name)
        loss = lax.psum(loss, axis_name) / num_micro
        if dp is not None:
            # dp x pp: each slice's loss_fn already means over ITS sub-
            # microbatch, so the full-batch per-microbatch mean (and its
            # grad) is the MEAN over slices; dx stays sharded (out_spec
            # xspec) — it is d(slice loss)/d(slice inputs), scaled below
            # by the same 1/dp so the full-batch semantics match
            nd = mesh.shape[dp]
            g = jax.tree_util.tree_map(lambda a: lax.psum(a, dp) / nd, g)
            gh = jax.tree_util.tree_map(lambda a: lax.psum(a, dp) / nd, gh)
            loss = lax.psum(loss, dp) / nd
            dx = dx / nd
        return g, gh, dx, loss

    pspec = jax.tree_util.tree_map(
        lambda a: P(axis_name, *([None] * (a.ndim - 1))), stacked_params)
    hspec = jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                   head_params)
    xspec = P(None, dp) if dp else P()
    g, gh, dx, loss = jax.shard_map(
        inner, mesh=mesh, in_specs=(pspec, xspec, xspec, hspec),
        out_specs=(pspec, hspec, xspec, P()), check_vma=False)(
            stacked_params, x_mb, lab_mb, head_params)
    return (loss, g, gh,
            dx.reshape(b, *dx.shape[2:]))
