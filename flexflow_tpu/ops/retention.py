"""Power retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; `model_type` `brumby`): a mixer whose cache is
ONE fixed-size state a sequence, the keys' symmetric square times the values,
and no per-token row at all.

    q = RoPE(RMSNorm_head(a W_q))   (H heads x hd)     k = RoPE(RMSNorm_head(a W_k))   (KV x hd)
    v = a W_v   (KV x hd)           l = logsigmoid(a W_g + b_g)   (KV, float32: the log-decay)
    G_t = sum_{u <= t} l_u
    w_tj = (q_t,i . k_j,g / hd)^2 exp(G_t,g - G_j,g)    (query head i reads KV head g = i // (H / KV), j <= t)
    y_t,i = sum_j w_tj v_j,g / (sum_j w_tj + eps_n);    out = concat_i(y) W_o

No softmax and no running maximum: the weights are a polynomial of the scores,
so the same function is a recurrence over a state,

    S_t = exp(l_t) S_{t-1} + phi(k_t) v_t^T,   z_t = exp(l_t) z_{t-1} + phi(k_t),
    y_t,i = phi(q_t,i)^T S_t / (phi(q_t,i) . z_t + eps_n),    phi(x) . phi(y) = (x . y / hd)^2

`forward` (predict, every prefill, every prefix hit's tail) runs the chunked
form: inside a chunk of `chunk_size` rows the masked quadratic form with the
decay matrix, between chunks `phi(Q) S` and the carried `(S, z)`. It is
`jax.numpy` throughout. A decode step (`step_forward`, `paged_step_forward`)
is the recurrence's one-step form.

THE LAYOUT OF THE STATE is this module's own (and the kernel's,
`pallas_kernels.retention_state_update_pallas`). phi is held by DIAGONALS:

    phi(x)[d, a] = c_d x_a x_{(a - d) mod hd} / hd,   d = 0 .. hd / 2,
    c_0 = 1,  c_d = sqrt 2 (0 < d < hd / 2),  c_{hd/2} = 1

Row d is `x * roll(x, d)`: one rotation of a 128-lane register, so the kernel
forms phi from the 128-wide q and k and it never lies in memory. Diagonal 0 is
the squares; every unordered pair {a, b} with 0 < (a - b) mod hd < hd / 2
appears once (hence sqrt 2); the half-way diagonal holds each of its pairs
twice (hence 1). That is (hd / 2 + 1) x hd = 8320 rows at hd 128 where the
upper triangle has 8256 (the published kernels pad 16 x 16 tiles to 9216).
`"s"` is (B, KV, hd / 2 + 1, hd [value], hd [a]) float32, the value along the
sublanes and a along the lanes, so that a step's per-diagonal operands
(phi(k)[d], phi(q_i)[d]) are ROWS; `"z"` is (B, KV, hd / 2 + 1, hd) float32.
Nothing outside takes them apart; `logical_state` hands a check the same
arrays (the reference holds its state in this order too and says so).

The state protocol is ops/mamba.py's (`state_cache_protocol`): `init_state`,
`scan_forward(params, xs, state, start, row_lengths)` (rows at or past
`row_lengths` leave the state alone: their log-decay is forced to 0 and
their key's contribution to 0; `start` places the rotary), `last_forward`,
`step_forward`, `init_state_pool` / `seat_state` / `state_bytes_per_slot`,
`paged_step_forward` (live slots only, in place). Unlike a state-space layer
this one is rotary: `state_wants_positions` asks the walk for each row's
position at a decode step.
"""

from __future__ import annotations

import functools
import math
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.attention import _apply_rope
from flexflow_tpu.ops.base import Op, WeightSpec

_HIGHEST = jax.lax.Precision.HIGHEST


def phi_coefficients(head_dim: int):
    """(hd / 2 + 1,) float32: c_d / hd of the diagonals."""
    c = np.full((head_dim // 2 + 1,), math.sqrt(2.0), np.float32)
    c[0] = c[-1] = 1.0
    return c / np.float32(head_dim)


@functools.lru_cache(maxsize=None)
def _rotations(head_dim: int):
    """(hd, (hd / 2 + 1) x hd) one-hot: column (d, a) picks entry (a - d)
    mod hd, so `x @ _rotations` is every `roll(x, d)` side by side."""
    nd = head_dim // 2 + 1
    src = (np.arange(head_dim)[None, :] - np.arange(nd)[:, None]) % head_dim
    onehot = np.zeros((head_dim, nd * head_dim), np.float32)
    onehot[src.reshape(-1), np.arange(nd * head_dim)] = 1.0
    return onehot


def phi(x):
    """The symmetric square of the last axis by diagonals, in float32:
    (.., hd) -> (.., hd / 2 + 1, hd). The rotations are ONE product with a
    one-hot matrix (a stack of hd / 2 + 1 `jnp.roll`s is as many slices and
    concatenations a call: 7 s of compile a chunked layer where this takes
    2.5, ISSUE 50), exact: a bfloat16 entry times one in one pass, a float32
    entry under HIGHEST."""
    hd = x.shape[-1]
    exact = x.dtype == jnp.bfloat16
    rolled = jnp.dot(
        x, jnp.asarray(_rotations(hd), x.dtype),
        precision=None if exact else _HIGHEST,
        preferred_element_type=jnp.float32).reshape(*x.shape[:-1], -1, hd)
    return (rolled * x.astype(jnp.float32)[..., None, :]
            * phi_coefficients(hd)[:, None])


def retention_chunked(q, k, v, l, live, s0, z0, chunk: int, eps_n: float):
    """The recurrence over a slab, chunked. q (B, S, KV, R, hd), k, v (B, S,
    KV, hd) in the compute dtype; l (B, S, KV) f32 log-decay, 0 on rows that
    must not move the state; live (B, S) bool, False on those rows; s0 (B,
    KV, ND, hd, hd) f32 and z0 (B, KV, ND, hd) f32 (the held layout) -> (y
    (B, S, KV, R, hd) f32, s, z after the slab's last live row). S is padded
    to a multiple of `chunk` with dead rows."""
    b, s, kv, r, hd = q.shape
    pad = -s % chunk
    if pad:
        q, k, v, l, live = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, l, live))
    nc = (s + pad) // chunk
    cd = q.dtype
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def per_chunk(a):
        return jnp.moveaxis(a.reshape(b, nc, chunk, *a.shape[2:]), 1, 0)

    def one(carry, xs):
        st, z = carry
        qc, kc, vc, lc, alive = xs
        g = jnp.cumsum(lc, axis=1)                       # (B, C, KV)
        gt = g.transpose(0, 2, 1)                        # (B, KV, C)
        # 1. inside the chunk: w_tj = (q_t . k_j / hd)^2 exp(G_t - G_j)
        sc = jnp.einsum("btgrk,bjgk->bgrtj", qc, kc,
                        preferred_element_type=jnp.float32) / hd
        diff = gt[..., :, None] - gt[..., None, :]       # (B, KV, C, C)
        seen = tri & alive[:, None, None, :]
        w = sc * sc * jnp.exp(jnp.where(seen, diff, -jnp.inf))[:, :, None]
        num = jnp.einsum("bgrtj,bjgv->btgrv", w.astype(cd), vc,
                         preferred_element_type=jnp.float32)
        den = w.sum(-1).transpose(0, 3, 1, 2)            # (B, C, KV, R)
        # 2. what the state entering the chunk gives its rows
        pq = phi(qc)                                     # (B, C, KV, R, ND, hd)
        into = jnp.exp(g)[..., None]                     # (B, C, KV, 1)
        num = num + into[..., None] * jnp.einsum(
            "btgrda,bgdva->btgrv", pq.astype(cd), st.astype(cd),
            preferred_element_type=jnp.float32)
        den = den + into * jnp.einsum("btgrda,bgda->btgr", pq, z,
                                      precision=_HIGHEST)
        # 3. what the chunk leaves: exp(G_end) S + sum_j exp(G_end - G_j)
        #    phi(k_j) v_j^T, in float32 (a state sums over the sequence)
        to_end = jnp.where(alive[..., None], jnp.exp(g[:, -1:] - g), 0.0)
        pk = phi(kc) * to_end[..., None, None]           # (B, C, KV, ND, hd)
        across = jnp.exp(gt[..., -1])                    # (B, KV)
        st = across[..., None, None, None] * st + jnp.einsum(
            "bjgda,bjgv->bgdva", pk, vc.astype(jnp.float32),
            precision=_HIGHEST)
        z = across[..., None, None] * z + pk.sum(axis=1)
        return (st, z), num / (den + eps_n)[..., None]

    (st, z), y = jax.lax.scan(
        one, (s0.astype(jnp.float32), z0.astype(jnp.float32)),
        tuple(map(per_chunk, (q, k, v, l, live))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, kv, r, hd)
    return y[:, :s], st, z


def retention_step(st, z, decay, q, k, v):
    """One token on states held side by side: st (.., KV, ND, hd, hd), z (..,
    KV, ND, hd) f32, decay (.., KV) f32 = exp(l), q (.., KV, R, hd), k, v (..,
    KV, hd) -> (num (.., KV, R, hd), den (.., KV, R), st, z)."""
    pk = phi(k)
    st = decay[..., None, None, None] * st \
        + pk[..., None, :] * v.astype(jnp.float32)[..., None, :, None]
    z = decay[..., None, None] * z + pk
    pq = phi(q)
    num = jnp.einsum("...grda,...gdva->...grv", pq, st, precision=_HIGHEST)
    den = jnp.einsum("...grda,...gda->...gr", pq, z, precision=_HIGHEST)
    return num, den, st, z


def retention_state_update(st, z, decay, q, k, v, live):
    """One token of the recurrence on a pool of states, LIVE rows only, in
    place: st (S, KV, ND, hd, hd), z (S, KV, ND, hd) f32 (the held layout),
    decay (S, KV) f32, q (S, KV, R, hd), k, v (S, KV, hd) f32, live (S,) bool
    -> (num (S, KV, R, hd) f32, den (S, KV, R) f32, st, z). A loop over the
    live rows whose carry is the pool, as `mamba_state_update`: a dead row's
    state is never touched."""
    n = st.shape[0]
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)   # live first

    def one(i, carry):
        st, z, num, den = carry
        row = order[i]
        pick = functools.partial(jax.lax.dynamic_index_in_dim, index=row,
                                 axis=0, keepdims=False)
        nr, dr, sr, zr = retention_step(pick(st), pick(z), pick(decay),
                                        pick(q), pick(k), pick(v))
        put = jax.lax.dynamic_update_index_in_dim
        return (put(st, sr, row, 0), put(z, zr, row, 0),
                put(num, nr, row, 0), put(den, dr, row, 0))

    st, z, num, den = jax.lax.fori_loop(
        0, jnp.sum(live, dtype=jnp.int32), one,
        (st, z, jnp.zeros(q.shape, jnp.float32),
         jnp.zeros(q.shape[:-1], jnp.float32)))
    return num, den, st, z


class PowerRetention(Op):
    op_type = OperatorType.OP_POWER_RETENTION
    state_cache_protocol = True
    # rotary: a decode step needs each row's position (runtime/generation.py
    # `_state_step` hands it over as `positions`)
    state_wants_positions = True

    def __init__(self, model, name, inputs, num_heads: int,
                 num_kv_heads: int, head_dim: int, rope_theta: float = 1e6,
                 chunk_size: int = 128, eps: float = 1e-6,
                 norm_eps: float = 1e-5, decay_floor=(1e-4, 1e-2)):
        super().__init__(model, name, inputs)
        self.dim = inputs[0].dims[-1]
        self.num_heads, self.num_kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim, self.chunk_size = int(head_dim), int(chunk_size)
        self.rope_theta = float(rope_theta)
        self.eps, self.norm_eps = float(eps), float(norm_eps)
        self.decay_floor = tuple(map(float, decay_floor))
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{name}: num_kv_heads {num_kv_heads} must "
                             f"divide num_heads {num_heads}")
        if self.head_dim % 2:
            raise ValueError(f"{name}: head_dim {head_dim} must be even "
                             "(the rotary's halves, phi's diagonals)")
        self.group = self.num_heads // self.num_kv_heads
        self.diagonals = self.head_dim // 2 + 1
        self.finalize()

    def output_shapes(self):
        return [self.inputs[0].dims], [self.inputs[0].dtype]

    def weights(self) -> List[WeightSpec]:
        d, nh, kv, hd = (self.dim, self.num_heads, self.num_kv_heads,
                         self.head_dim)
        lo, hi = self.decay_floor
        return [
            WeightSpec("wq", (d, nh, hd), init="glorot", fan=(d, nh * hd)),
            WeightSpec("wk", (d, kv, hd), init="glorot", fan=(d, kv * hd)),
            WeightSpec("wv", (d, kv, hd), init="glorot", fan=(d, kv * hd)),
            WeightSpec("wg", (d, kv), init="glorot"),
            # 1 - exp(l) = sigmoid(-b) log-uniform over `decay_floor` at a
            # zero projection: a state neither kept for ever nor forgotten
            # inside a document (as the Mamba draws fix dt)
            WeightSpec("gate_bias", (kv,), init="uniform",
                       init_args=(math.log((1.0 - hi) / hi),
                                  math.log((1.0 - lo) / lo))),
            WeightSpec("q_norm", (hd,), init="one"),
            WeightSpec("k_norm", (hd,), init="one"),
            WeightSpec("wo", (nh, hd, d), init="glorot", fan=(nh * hd, d)),
        ]

    # ---- the layer's pieces -------------------------------------------------

    def _head_norm(self, xh, scale):
        x = xh.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                              + self.eps) * scale.astype(jnp.float32)
        return x.astype(xh.dtype)

    def _project(self, params, u, offset):
        """q (B, S, KV, R, hd), k, v (B, S, KV, hd) in u's dtype, normed and
        turned to positions `offset` + 0 .. S - 1 (`offset` a scalar or one
        a row); l (B, S, KV) float32."""
        with jax.named_scope("project"):
            q = jnp.einsum("bsd,dhk->bshk", u, params["wq"].astype(u.dtype))
            k = jnp.einsum("bsd,dhk->bshk", u, params["wk"].astype(u.dtype))
            v = jnp.einsum("bsd,dhk->bshk", u, params["wv"].astype(u.dtype))
            q = _apply_rope(self._head_norm(q, params["q_norm"]),
                            self.rope_theta, offset)
            k = _apply_rope(self._head_norm(k, params["k_norm"]),
                            self.rope_theta, offset)
            l = jax.nn.log_sigmoid(
                jnp.einsum("bsd,dg->bsg", u, params["wg"].astype(u.dtype),
                           preferred_element_type=jnp.float32)
                + params["gate_bias"].astype(jnp.float32))
            q = q.reshape(*q.shape[:2], self.num_kv_heads, self.group,
                          self.head_dim)
        return q, k, v, l

    def _out(self, params, y, dtype):
        """y (B, S, KV, R, hd) float32 -> (B, S, D)."""
        with jax.named_scope("out"):
            y = y.reshape(*y.shape[:2], self.num_heads,
                          self.head_dim).astype(dtype)
            return jnp.einsum("bshk,hkd->bsd", y,
                              params["wo"].astype(dtype))

    def _scan(self, params, xs, state, start, row_lengths):
        u = xs[0]
        b, s, _ = u.shape
        q, k, v, l = self._project(params, u, start)
        rel = (jnp.full((b,), s, jnp.int32) if row_lengths is None
               else row_lengths.astype(jnp.int32) - start)
        n_live = jnp.clip(rel, 0, s)
        live = jnp.arange(s)[None, :] < n_live[:, None]          # (B, S)
        with jax.named_scope("scan"):
            y, st, z = retention_chunked(
                q, k, v, jnp.where(live[..., None], l, 0.0), live,
                state["s"], state["z"], self.chunk_size, self.norm_eps)
        out = self._out(params, y, u.dtype)
        new = {"s": st, "z": z}
        if "out_last" in state:
            # the slab's output at the sequence's last live row, where it
            # lies in this slab (the ragged prefill's gather pass reads it)
            here = (rel > 0) & (rel <= s)
            last = jnp.take_along_axis(
                out, jnp.maximum(n_live - 1, 0)[:, None, None], axis=1)
            new["out_last"] = jnp.where(here[:, None, None], last,
                                        state["out_last"])
        return out, new

    def _step(self, params, u, positions, update):
        """One token: u (B, 1, D) at `positions` (B,) -> (out (B, 1, D),
        whatever `update` returned beside num and den). `update(decay, q, k,
        v)` advances the state and gives (num (B, KV, R, hd), den (B, KV,
        R), its result), all float32."""
        q, k, v, l = self._project(params, u, positions)
        with jax.named_scope("update"):
            f32 = jnp.float32
            num, den, res = update(jnp.exp(l[:, 0]), q[:, 0].astype(f32),
                                   k[:, 0].astype(f32), v[:, 0].astype(f32))
            y = num / (den + self.norm_eps)[..., None]
        return self._out(params, y[:, None], u.dtype), res

    # ---- graph forward -----------------------------------------------------

    def forward(self, params, xs, *, training=False, rng=None):
        return [self._scan(params, xs, self.init_state(
            xs[0].shape[0], xs[0].dtype, out_last=False), 0, None)[0]]

    # ---- the state protocol --------------------------------------------------

    def init_state(self, batch: int = 1, dtype=jnp.float32, out_last=True):
        """The zero state of `batch` sequences (a prefill's contiguous
        per-request state; `out_last` only where a gather pass may follow)."""
        kv, nd, hd = self.num_kv_heads, self.diagonals, self.head_dim
        st = {"s": jnp.zeros((batch, kv, nd, hd, hd), jnp.float32),
              "z": jnp.zeros((batch, kv, nd, hd), jnp.float32)}
        if out_last:
            st["out_last"] = jnp.zeros((batch, 1, self.dim), dtype)
        return st

    def scan_forward(self, params, xs, state, start=0, row_lengths=None):
        return self._scan(params, xs, state, start, row_lengths)

    def last_forward(self, params, xs, state):
        """The ragged prefill's gather pass: the output at each sequence's
        last live row, which the scan kept; the state does not move."""
        return state["out_last"].astype(xs[0].dtype), state

    def step_forward(self, params, xs, state, positions):
        """One decode token for every sequence of a contiguous state, each
        at its own position (a scalar places them all)."""
        def update(decay, q, k, v):
            num, den, st, z = retention_step(state["s"], state["z"], decay,
                                             q, k, v)
            return num, den, (st, z)

        b = xs[0].shape[0]
        out, (st, z) = self._step(
            params, xs[0], jnp.broadcast_to(jnp.asarray(positions), (b,)),
            update)
        return out, {**state, "s": st, "z": z}

    def logical_state(self, state):
        """A state's arrays for a host-side check against a reference
        (`ServingEngine.slot_state`): the held layout IS the one the
        equations' phi is written in (by diagonals; the module's docstring),
        so they go out as they are."""
        return {k: np.asarray(state[k]) for k in ("s", "z")}

    def state_bytes_per_slot(self, dtype=None) -> int:
        return 4 * self.num_kv_heads * self.diagonals * self.head_dim \
            * (self.head_dim + 1)

    def init_state_pool(self, slots: int, dtype):
        return self.init_state(slots, dtype, out_last=False)

    def seat_state(self, pool, state, slot):
        """Write one prefilled sequence's state (batch 1) into `slot`: the
        WHOLE slot, so nothing of the request that held it before is left."""
        return {k: jax.lax.dynamic_update_index_in_dim(
            pool[k], state[k][0].astype(pool[k].dtype), slot, 0)
            for k in pool}

    def _kernel_takes_layout(self) -> bool:
        from flexflow_tpu.ops.pallas_kernels import (LANES,
                                                     RETENTION_TILE_ROWS)
        return (self.head_dim == LANES
                and self.group + 3 <= RETENTION_TILE_ROWS)

    def paged_step_forward(self, params, xs, pool, live, impl="einsum",
                           positions=None):
        """One decode token for the serving engine's slots: xs[0] (slots, 1,
        D), live (slots,) bool, positions (slots,). Only live slots' states
        are read and written, each once and in place (`impl` "pallas": the
        kernel that streams a live slot's S and z through VMEM and forms phi
        there, where a head is one register's 128 lanes and a KV group's
        rows fit one tile; otherwise XLA's loop over the live rows, the
        parity oracle)."""
        update = retention_state_update
        if impl == "pallas" and self._kernel_takes_layout():
            from flexflow_tpu.ops.pallas_kernels import (
                retention_state_update_pallas as update)

        def advance(decay, q, k, v):
            num, den, st, z = update(pool["s"], pool["z"], decay, q, k, v,
                                     live)
            return num, den, {"s": st, "z": z}

        return self._step(params, xs[0], positions, advance)

    # ---- parallelization / cost ---------------------------------------------

    def partitionable_output_dims(self):
        return [0]      # the batch: the scan runs along the sequence

    def flops(self):
        ntokens = self.inputs[0].volume() // self.dim
        nh, kv, hd, c = (self.num_heads, self.num_kv_heads, self.head_dim,
                         self.chunk_size)
        proj = 2 * self.dim * (2 * nh * hd + 2 * kv * hd + kv)
        rows = self.diagonals * hd
        # per token: the chunk's scores and weighted values, phi(q) S, and
        # the chunk's addition to the state
        scan = 4 * c * nh * hd + 2 * nh * rows * hd + 2 * kv * rows * hd
        return int(ntokens * (proj + scan))
