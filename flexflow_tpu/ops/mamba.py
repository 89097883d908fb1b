"""Mamba-2 mixer (Dao & Gu, arXiv:2405.21060; HF `modeling_nemotron_h.py`
`NemotronHMamba2Mixer`): a selective state-space layer whose cache is a
FIXED-SIZE recurrent state per sequence, not per-token rows.

    [z | xBC | dt] = u W_in                      # d_inner + (d_inner + 2 G N) + H
    xBC = silu(causal_depthwise_conv1d(xBC; w_conv (C, K), b_conv))
    x (H heads, P), B, C (G groups, N); head j reads group j // (H / G)
    dt = softplus(dt + dt_bias),  A = -exp(A_log)          # per head, f32
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T             # (P, N) per head, f32
    y_t = H_t C_t + D x_t
    y = RMSNorm_groups(y * silu(z); w) W_out               # mean of squares per group of d_inner / G

`forward` (fit, predict and every prefill) runs the chunked form (SSD): inside
a chunk of `chunk_size` rows the recurrence is two matmuls against the
lower-triangular decay matrix, between chunks a short scan carries the state.
It is `jax.numpy` throughout, so autodiff gives the backward. A decode step
(`step_forward`, `paged_step_forward`) is the recurrence's one-step form.

The state protocol (`state_cache_protocol`, beside attention's
`kv_cache_protocol`; runtime/generation.py asks the op):

  * a sequence's state is `{"conv": (B, K - 1, C) compute dtype, "h":
    (B, G, N, H / G x P) float32}`: the last K - 1 pre-convolution rows and
    the recurrent state. float32 because a recurrence accumulates its
    rounding over every token of the sequence.
  * THE LAYOUT OF "h" is this module's own (and the kernel's,
    `pallas_kernels.mamba_state_update_pallas`): a group's heads lie side by
    side, the state dimension N along the sublanes and (head, p) dense along
    the lanes, `h[b, g, n, i * P + p]` = the equations' `H[b, g * H / G + i,
    p, n]`. Why: a decode step's per-(head, p) operands (the decay, dt x)
    are then ROWS, which is how `_split` hands x over; B and C are columns
    of N that a whole group shares; and `y = H C` sums along the sublanes,
    vector adds with no reduction across lanes. Prefill, the pool, the
    snapshot rows, the kernel and XLA's loop all hold it so, and
    `ssd_chunked`'s einsums emit and read it as an index order. Nothing
    outside takes "h" apart; a check that wants the equations' (.., H, P, N)
    asks `logical_state` (a view for the host, off every timed path).
  * `init_state(batch, dtype)` is the zero state; `scan_forward(params, xs,
    state, start, row_lengths)` advances it over a slab of rows that begins at
    position `start` and returns the slab's outputs: rows at or past a
    sequence's `row_lengths` (a prompt bucket's padding) leave the state
    alone (their dt is forced to 0, and the conv tail is taken from the live
    rows), so chunk after chunk of a chunked prefill carries exactly the state
    after the last live row. The slab's output at the last live row is kept in
    the state (`"out_last"`) for the ragged prefill's final gather pass
    (`last_forward`), which re-reads that row and must not advance anything.
  * `init_state_pool(slots, dtype)` is the serving engine's pool, one state a
    slot; `seat_state(pool, state, slot)` writes a prefilled state into its
    slot; `paged_step_forward(params, xs, pool, live, impl)` advances the
    LIVE slots by one token in place (a dead slot's state is neither read
    nor written: the Pallas kernel `mamba_state_update_pallas` under the
    engine's impl "pallas", `mamba_state_update` otherwise). A released
    slot keeps its last state until the next `seat_state` overwrites all of
    it: nothing reads a slot that is not live.
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import Op, WeightSpec


def ssd_chunked(x, dt, a, bm, cm, h0, chunk: int):
    """The recurrence over a slab, chunked. x (B, S, H, P); dt (B, S, H) f32,
    0 on rows that must not move the state; a (H,) f32 (negative); bm, cm
    (B, S, G, N); h0 (B, G, N, H / G x P) f32, the held layout -> (y (B, S,
    H, P) f32 without the D term, h like h0 after the slab's last row). S is
    padded to a multiple of `chunk` with dt = 0 rows."""
    b, s, nh, p = x.shape
    g, n = bm.shape[2:]
    q = nh // g * p                 # a group's (head, p) lanes
    pad = -s % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
                         for v in (x, dt, bm, cm))
    nc = (s + pad) // chunk
    cd = x.dtype
    x = x.reshape(b, nc, chunk, nh, p)
    dt = dt.reshape(b, nc, chunk, nh)
    bm = bm.reshape(b, nc, chunk, g, n)
    cm = cm.reshape(b, nc, chunk, g, n)
    # cumulative log-decay inside each chunk: (B, H, nc, Q)
    acum = jnp.cumsum((dt * a).transpose(0, 3, 1, 2), axis=-1)
    # 1. inside a chunk: y_l += sum_{s <= l} (C_l . B_s) exp(A_l - A_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cm, bm,
                    preferred_element_type=jnp.float32)
    diff = acum[..., :, None] - acum[..., None, :]          # (B, H, nc, Q, Q)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(tri, diff, -jnp.inf)).transpose(0, 2, 1, 3, 4)
    m = (jnp.repeat(cb, nh // g, axis=2) * decay
         * dt.transpose(0, 1, 3, 2)[..., None, :])          # (B, nc, H, Q, Q)
    y = jnp.einsum("bchls,bcshp->bclhp", m.astype(cd), x,
                   preferred_element_type=jnp.float32)
    # 2. what each chunk adds to the state: sum_s exp(A_last - A_s) dt_s x_s B_s^T
    to_end = jnp.exp(acum[..., -1:] - acum).transpose(0, 2, 3, 1)  # (B,nc,Q,H)
    xw = (x * (dt * to_end)[..., None]).astype(cd)
    add = jnp.einsum("bcsgn,bcsgq->bcgnq", bm,
                     xw.reshape(b, nc, chunk, g, q),
                     preferred_element_type=jnp.float32)
    # 3. between chunks: h_{c+1} = exp(A_last of c) h_c + add_c
    across = jnp.repeat(jnp.exp(acum[..., -1]).transpose(2, 0, 1), p,
                        axis=-1).reshape(nc, b, g, 1, q)

    def carry(h, ca):
        d, add_c = ca
        return d * h + add_c, h

    h, h_in = jax.lax.scan(carry, h0.astype(jnp.float32),
                           (across, add.transpose(1, 0, 2, 3, 4)))
    # 4. the state entering a chunk, read by its rows: C_l . h_in * exp(A_l)
    off = jnp.einsum("bclgn,cbgnq->bclgq", cm, h_in.astype(cd),
                     preferred_element_type=jnp.float32
                     ).reshape(b, nc, chunk, nh, p)
    y = y + off * jnp.exp(acum).transpose(0, 2, 3, 1)[..., None]
    return y.reshape(b, nc * chunk, nh, p)[:, :s], h


def lane_rows(decay, dtx, groups: int):
    """A step's per-head decay (S, H) and dt x (S, H, P) as rows over a
    group's (head, p) lanes, (S, G, 1, H / G x P) each: what multiplies a
    state in the held layout."""
    s, nh, p = dtx.shape
    return (jnp.repeat(decay, p, axis=-1).reshape(s, groups, 1, -1),
            dtx.reshape(s, groups, 1, -1))


def mamba_state_update(h, decay, dtx, bm, cm, live):
    """One token of the recurrence on a pool of states, LIVE rows only, in
    place: h (S, G, N, H / G x P) f32 (the held layout), decay (S, H) f32 =
    exp(dt A), dtx (S, H, P) f32 = dt x, bm, cm (S, G, N) f32, live (S,) bool
    -> (y (S, H, P) f32 = H_t C_t, h). A loop over the live rows whose carry
    is the pool: each turn reads one row's state, writes it back through a
    dynamic-update-slice that XLA does in place, and a dead row's state is
    never touched (a `where` over the whole pool would stream every row, live
    or not, there and back)."""
    s, g = bm.shape[:2]
    dq, xq = lane_rows(decay, dtx, g)
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)   # live first

    def one(i, carry):
        h, y = carry
        r = order[i]
        row = jax.lax.dynamic_index_in_dim(h, r, 0, keepdims=False)
        pick = functools.partial(jax.lax.dynamic_index_in_dim, index=r,
                                 axis=0, keepdims=False)
        new = pick(dq) * row + pick(bm)[:, :, None] * pick(xq)  # (G, N, Q)
        yr = jnp.sum(new * pick(cm)[:, :, None], axis=1)        # (G, Q)
        return (jax.lax.dynamic_update_index_in_dim(h, new, r, 0),
                jax.lax.dynamic_update_index_in_dim(y, yr, r, 0))

    h, y = jax.lax.fori_loop(
        0, jnp.sum(live, dtype=jnp.int32), one,
        (h, jnp.zeros((s, g, h.shape[-1]), jnp.float32)))
    return y.reshape(dtx.shape), h


class Mamba2Mixer(Op):
    op_type = OperatorType.OP_MAMBA2
    state_cache_protocol = True

    def __init__(self, model, name, inputs, num_heads: int, head_dim: int,
                 n_groups: int, state_size: int, conv_kernel: int = 4,
                 chunk_size: int = 128, eps: float = 1e-5):
        super().__init__(model, name, inputs)
        self.dim = inputs[0].dims[-1]
        self.num_heads, self.head_dim = int(num_heads), int(head_dim)
        self.n_groups, self.state_size = int(n_groups), int(state_size)
        self.conv_kernel, self.chunk_size = int(conv_kernel), int(chunk_size)
        self.eps = float(eps)
        if self.num_heads % self.n_groups:
            raise ValueError(f"{name}: n_groups {n_groups} must divide "
                             f"num_heads {num_heads}")
        self.d_inner = self.num_heads * self.head_dim
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.state_size
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def weights(self) -> List[WeightSpec]:
        d, di, c, nh = self.dim, self.d_inner, self.conv_dim, self.num_heads
        bound = 1.0 / math.sqrt(self.conv_kernel)   # torch's Conv1d default
        return [
            WeightSpec("w_in", (d, di + c + nh), init="glorot"),
            WeightSpec("conv_w", (c, self.conv_kernel), init="uniform",
                       init_args=(-bound, bound)),
            WeightSpec("conv_b", (c,), init="uniform",
                       init_args=(-bound, bound)),
            # dt = softplus(dt_bias) log-uniform over [1e-3, 1e-1] (the
            # published time_step_min / time_step_max); A in [1, 16]
            WeightSpec("dt_bias", (nh,), init="uniform",
                       init_args=(math.log(math.expm1(1e-3)),
                                  math.log(math.expm1(1e-1)))),
            WeightSpec("A_log", (nh,), init="uniform",
                       init_args=(0.0, math.log(16.0))),
            WeightSpec("D", (nh,), init="one"),
            WeightSpec("norm_w", (di,), init="one"),
            WeightSpec("w_out", (di, d), init="glorot"),
        ]

    # ---- the layer's pieces -------------------------------------------------

    def _project(self, params, u):
        """(z (.., d_inner), xBC (.., conv_dim) pre-convolution, dt (.., H)
        f32 after the softplus)."""
        with jax.named_scope("project"):
            zxd = u @ params["w_in"].astype(u.dtype)
            z = zxd[..., :self.d_inner]
            xbc = zxd[..., self.d_inner:self.d_inner + self.conv_dim]
            dt = jax.nn.softplus(
                zxd[..., self.d_inner + self.conv_dim:].astype(jnp.float32)
                + params["dt_bias"].astype(jnp.float32))
        return z, xbc, dt

    def _split(self, xbc):
        """x (.., H, P), B, C (.., G, N) of convolved rows."""
        lead = xbc.shape[:-1]
        g, n = self.n_groups, self.state_size
        x = xbc[..., :self.d_inner].reshape(
            *lead, self.num_heads, self.head_dim)
        bm = xbc[..., self.d_inner:self.d_inner + g * n].reshape(*lead, g, n)
        cm = xbc[..., self.d_inner + g * n:].reshape(*lead, g, n)
        return x, bm, cm

    def _gate_out(self, params, y, z):
        """Gated group RMSNorm and the out projection: y, z (.., d_inner)."""
        with jax.named_scope("gate_norm"):
            v = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
            vg = v.reshape(*v.shape[:-1], self.n_groups, -1)
            vg = vg * jax.lax.rsqrt(
                jnp.mean(vg * vg, axis=-1, keepdims=True) + self.eps)
            v = (vg.reshape(v.shape)
                 * params["norm_w"].astype(jnp.float32)).astype(z.dtype)
        with jax.named_scope("out"):
            return v @ params["w_out"].astype(v.dtype)

    def _scan(self, params, xs, state, start, row_lengths):
        u = xs[0]
        b, s, _ = u.shape
        k1 = self.conv_kernel - 1
        z, xbc, dt = self._project(params, u)
        # the sequence's rows left from this slab's first on (all of the
        # slab without `row_lengths`), and those of them that lie in it
        rel = (jnp.full((b,), s, jnp.int32) if row_lengths is None
               else row_lengths.astype(jnp.int32) - start)
        n_live = jnp.clip(rel, 0, s)
        live = jnp.arange(s)[None, :] < n_live[:, None]          # (B, S)
        with jax.named_scope("conv"):
            xp = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc],
                                 axis=1)                          # (B, S+K-1, C)
            w = params["conv_w"].astype(xbc.dtype)
            conv = params["conv_b"].astype(xbc.dtype) + sum(
                xp[:, k:k + s] * w[:, k] for k in range(self.conv_kernel))
            xbc_c = jax.nn.silu(conv)
            # the K - 1 rows before the first dead one: rows n_live - K + 1
            # .. n_live - 1 of the slab, which sit K - 1 later in `xp`
            tail = jax.vmap(lambda rows, n0: jax.lax.dynamic_slice_in_dim(
                rows, n0, k1, axis=0))(xp, n_live)
        with jax.named_scope("scan"):
            x, bm, cm = self._split(xbc_c)
            a = -jnp.exp(params["A_log"].astype(jnp.float32))
            y, h = ssd_chunked(x, jnp.where(live[..., None], dt, 0.0), a, bm,
                               cm, state["h"], self.chunk_size)
            y = y + params["D"].astype(jnp.float32)[:, None] * x
        out = self._gate_out(params, y.reshape(b, s, self.d_inner), z)
        new = {"conv": tail.astype(state["conv"].dtype), "h": h}
        if "out_last" in state:
            # the slab's output at the sequence's last live row, where it
            # lies in this slab (the ragged prefill's gather pass reads it)
            here = (rel > 0) & (rel <= s)
            last = jnp.take_along_axis(
                out, jnp.maximum(n_live - 1, 0)[:, None, None], axis=1)
            new["out_last"] = jnp.where(here[:, None, None], last,
                                        state["out_last"])
        return out, new

    def _step(self, params, u, conv, update):
        """One token: u (B, 1, D), conv (B, K-1, C) -> (out (B, 1, D), the
        new conv rows, whatever `update` returned beside y). `update(decay,
        dtx, bm, cm)` advances the recurrent state and gives (y (B, H, P)
        f32, its result)."""
        b = u.shape[0]
        z, xbc, dt = self._project(params, u)
        with jax.named_scope("conv"):
            xp = jnp.concatenate([conv.astype(xbc.dtype), xbc], axis=1)
            w = params["conv_w"].astype(xbc.dtype)
            cv = params["conv_b"].astype(xbc.dtype) + sum(
                xp[:, k] * w[:, k] for k in range(self.conv_kernel))
            x, bm, cm = self._split(jax.nn.silu(cv))                  # (B, H, P) ..
        with jax.named_scope("update"):
            a = -jnp.exp(params["A_log"].astype(jnp.float32))
            dt1 = dt[:, 0]                                       # (B, H)
            xf = x.astype(jnp.float32)
            y, res = update(jnp.exp(dt1 * a), dt1[..., None] * xf,
                            bm.astype(jnp.float32), cm.astype(jnp.float32))
            y = y + params["D"].astype(jnp.float32)[:, None] * xf
        out = self._gate_out(params, y.reshape(b, 1, self.d_inner), z)
        return out, xp[:, 1:], res

    # ---- graph forward -----------------------------------------------------

    def forward(self, params, xs, *, training=False, rng=None):
        return [self._scan(params, xs, self.init_state(
            xs[0].shape[0], xs[0].dtype, out_last=False), 0, None)[0]]

    # ---- the state protocol --------------------------------------------------

    def init_state(self, batch: int = 1, dtype=jnp.float32, out_last=True):
        """The zero state of `batch` sequences (a prefill's contiguous
        per-request state; `out_last` only where a gather pass may follow)."""
        st = {"conv": jnp.zeros((batch, self.conv_kernel - 1, self.conv_dim),
                                dtype),
              "h": jnp.zeros((batch, self.n_groups, self.state_size,
                              self.d_inner // self.n_groups), jnp.float32)}
        if out_last:
            st["out_last"] = jnp.zeros((batch, 1, self.dim), dtype)
        return st

    def scan_forward(self, params, xs, state, start=0, row_lengths=None):
        return self._scan(params, xs, state, start, row_lengths)

    def last_forward(self, params, xs, state):
        """The ragged prefill's gather pass: the output at each sequence's
        last live row, which the scan kept; the state does not move."""
        return state["out_last"].astype(xs[0].dtype), state

    def step_forward(self, params, xs, state):
        """One decode token for every sequence of a contiguous state."""
        def update(decay, dtx, bm, cm):
            dq, xq = lane_rows(decay, dtx, self.n_groups)
            h = dq * state["h"] + bm[..., None] * xq
            return jnp.einsum("bgnq,bgn->bgq", h, cm).reshape(dtx.shape), h

        out, conv, h = self._step(params, xs[0], state["conv"], update)
        return out, {**state, "conv": conv.astype(state["conv"].dtype),
                     "h": h}

    def logical_state(self, state):
        """A state's arrays with "h" as the equations write it, (.., H, P,
        N), out of the held layout (.., G, N, H / G x P): for a host-side
        check against a reference (`ServingEngine.slot_state`), never on a
        timed path."""
        h = np.asarray(state["h"])
        lead, n = h.shape[:-3], self.state_size
        h = np.moveaxis(h.reshape(*lead, self.n_groups, n, -1,
                                  self.head_dim), -3, -1)
        return {**state, "h": h.reshape(*lead, self.num_heads,
                                        self.head_dim, n)}

    def state_bytes_per_slot(self, dtype) -> int:
        return (4 * self.num_heads * self.head_dim * self.state_size
                + jnp.dtype(dtype).itemsize * (self.conv_kernel - 1)
                * self.conv_dim)

    def init_state_pool(self, slots: int, dtype):
        return self.init_state(slots, dtype, out_last=False)

    def seat_state(self, pool, state, slot):
        """Write one prefilled sequence's state (batch 1) into `slot`: the
        WHOLE slot, so nothing of the request that held it before is left."""
        return {k: jax.lax.dynamic_update_index_in_dim(
            pool[k], state[k][0].astype(pool[k].dtype), slot, 0)
            for k in pool}

    def _kernel_takes_layout(self) -> bool:
        from flexflow_tpu.ops.pallas_kernels import LANES, MAMBA_STATE_ROWS
        return (self.d_inner // self.n_groups % LANES == 0
                and self.state_size % MAMBA_STATE_ROWS == 0)

    def paged_step_forward(self, params, xs, pool, live, impl="einsum"):
        """One decode token for the serving engine's slots: xs[0] (slots, 1,
        D), live (slots,) bool. Only live slots' states are read and written,
        each once and in place (`impl` "pallas": the kernel that streams a
        live slot's state through VMEM, where the held layout fills whole
        vector registers: a group's lanes a multiple of 128, N of the
        kernel's `MAMBA_STATE_ROWS`; otherwise XLA's loop over the live
        rows, the parity oracle); the conv rows (1.5 % of the state's bytes)
        move under a select."""
        update = mamba_state_update
        if impl == "pallas" and self._kernel_takes_layout():
            from flexflow_tpu.ops.pallas_kernels import (
                mamba_state_update_pallas as update)
        out, conv, h = self._step(
            params, xs[0], pool["conv"],
            lambda *a: update(pool["h"], *a, live))
        conv = jnp.where(live[:, None, None], conv.astype(pool["conv"].dtype),
                         pool["conv"])
        return out, {"conv": conv, "h": h}

    # ---- parallelization / cost ---------------------------------------------

    def partitionable_output_dims(self):
        return [0]      # the batch: the scan runs along the sequence

    def flops(self):
        ntokens = self.inputs[0].volume() // self.dim
        nh, p, n, q = (self.num_heads, self.head_dim, self.state_size,
                       self.chunk_size)
        proj = 2 * self.dim * (2 * self.d_inner + 2 * self.n_groups * n + nh) \
            + 2 * self.d_inner * self.dim
        # per token: C.B over a chunk (G groups), the masked product against
        # x, the chunk's state and its read-out
        scan = 2 * q * n * self.n_groups + 2 * q * nh * p + 4 * nh * p * n
        return int(ntokens * (proj + scan + 2 * self.conv_kernel
                              * self.conv_dim))
