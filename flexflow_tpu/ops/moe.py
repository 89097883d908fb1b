"""Mixture-of-Experts op (expert parallelism over the 'expert' mesh axis).

Net-new vs the reference (SURVEY §2.5: "EP — absent, no MoE ops"). GShard-
style capacity-based top-k routing with two dispatch lowerings:

  * dense: (N, E, C) one-hot dispatch/combine einsums — under GSPMD,
    sharding the expert dim over the 'expert' axis turns these into
    all-to-alls over ICI; chosen whenever the mesh actually shards experts.
  * sort: tokens sorted by expert id, gathered into the (E*C, D) expert
    buffer and scatter-added back — O(N*k) routing state instead of the
    dense path's O(N*E*C), the practical choice at real token counts when
    experts are not mesh-sharded (single chip / pure dp).

FFModel.moe(dispatch="auto"|"dense"|"sort") selects; both share the router
and produce identical outputs when capacity does not bind (tested).
Includes the standard load-balancing auxiliary loss (Shazeer et al.),
surfaced through the op-aux mechanism so the executor folds it into the
training loss.

`capacity_factor=None` is the DROPLESS op (OLMoE, Mixtral and every served
expert model: no token is ever dropped). It reads no `dispatch` and no
`capacity`. One routing (top-k of the f32 softmax, the same counts and aux
value) feeds two lowerings of the experts, chosen by `dropless_lowering`
from static facts of the call alone, never by an option:

  * grouped: the N*k (token, expert) assignments are stable-sorted by
    expert and the experts run as grouped matmuls (`jax.lax.ragged_dot`,
    on the TPU XLA's own Mosaic grouped-matmul kernel) over exactly N*k
    rows, so work and expert-weight traffic follow the routing instead of
    a capacity buffer. fit() (it has the gradient), predict and every call
    of more than MOE_STREAM_MAX_ROWS tokens run it, on any backend.
  * streamed: a call of few tokens (a decode step, a short prefill bucket)
    of SwiGLU experts on one TPU chip, outside training. There XLA's kernel
    walks each group in 256-row tiles of which about four rows are live and
    is MXU-bound at 61 % of the bandwidth the weights need; the Pallas
    kernel `moe_expert_stream_pallas` keeps the N rows in VMEM, multiplies
    all of them by each HIT expert's three matrices as they stream past
    once, and picks each row's own experts by select on an (N, E) gate
    matrix: no sort, no gather into expert order, no scatter back.

The architecture is described by constructor arguments, none of them a
performance selector: `expert` ("gelu": two matrices w_in/w_out as above;
"swiglu": w_gate, w_up (E, D, F), w_down (E, F, D)) and `renormalize`
(kept gates rescaled to sum to 1 per token; OLMoE's `norm_topk_prob` is
false). The dropless op also takes the router's form (`MoE._route`, the one
routing function both lowerings are fed by): `scoring` ("softmax" |
"sigmoid"), `score_bias` (a learned (E,) bias added to the scores for the
SELECTION only, never to the gates: DeepSeek-V3's `e_score_correction_bias`),
`n_group` / `topk_group` (group-limited top-k: a group's score is the sum of
its two best biased scores, only the `topk_group` best groups' experts can be
chosen), `routed_scaling` (a factor on the kept gates), `shared_hidden_dim`
(a SwiGLU expert every token passes through, beside the routed ones) and
`experts_held=(first, count)`: the layer routes over all `num_experts` but
holds, and computes, only experts first .. first + count - 1, which is one
chip's share of an expert-parallel layer (its output is the shared expert
plus ITS experts' part of the routed sum; the exchange that adds the other
chips' parts is not in this op).

`zero_experts=Z` (LongCat-Flash's zero-computation experts) widens the
router, the selection bias and the top-k to `num_experts + Z` columns, of which
the last Z name NO weight: a pick of one returns the token itself times its
gate. Their gates sum into one factor a token on the op's input (the identity
term, scope `zero`), computed where the token lives, once, and by no held
share's experts; everything that sizes or counts expert work (both lowerings,
`_held_assignments`, `held_rows_cap`, whose even share is N k held /
(num_experts + Z), the aux term, `flops`) sees the real experts alone. A
token's expert work so varies from 0 to k real picks. `router_f32` computes
the softmax router's matmul in float32 at the highest precision, as such a
model's published gate does (the sigmoid router always did; OLMoE's softmax
router stays a compute-dtype matmul under the default). With zero experts the
routing counts grow from [assignments, experts hit] by [identity picks, real
picks] of live rows.

`expert="relu2"` is the two-matrix expert `relu(x w_up)^2 w_down` (Nemotron:
no gate matrix), on the same dropless op and both of its lowerings; the
shared expert then has that form too. `latent_dim` (LatentMoE) puts the
routed experts into a narrower space: `l = x w_latent_in` (D, L), the experts
are (L, F) / (F, L), and the gate-weighted sum goes back through
`w_latent_out` (L, D). The router and the shared expert read the D-wide
input. A held share projects ITS experts' sum, so the shares of a layer add
up to the layer.

A held share (fewer experts held than routed over) in the grouped lowering,
with a gradient or without (fit(), predict, a prefill chunk of more than
MOE_STREAM_MAX_ROWS rows): of the N*k assignments only about held /
num_experts land here, and they sort to the front. The grouped lowering then
gathers and multiplies `held_rows_cap` sorted rows a pass (HELD_ROWS_SLACK x
the even share) instead of all N*k, and scatter-adds their gate-weighted
results into the tokens, in as many passes as the held rows need
(`MoE._held_passes`: a `while_loop` on the count, one pass under any routing
within the slack of even). No token is dropped whatever the routing, a call
pays for the passes its rows fill, and no array of N*k rows is in such a
program. The backward runs the same passes with each pass's forward
recomputed (a `custom_vjp`), so nothing of N*k rows, and nothing of a pass, is
kept between the forward and the backward. A layer that holds every expert
keeps the all-rows form (there the compact form's rows ARE all N*k), and the
streamed lowering (a decode step, a bucket of few rows) sorts nothing.
`expert_rows` counts what the grouped products were given: passes x
`held_rows_cap` for a held share, N*k for the all-rows form.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flexflow_tpu.ffconst import DataType, OperatorType
from flexflow_tpu.ops.base import Op, WeightSpec


def _buffer_mm(x, w):
    """(E, C, in) capacity buffer x (E, in, out) expert matrices."""
    return jnp.einsum("eci,eio->eco", x, w)


def dropless_lowering(backend: str, expert: str, training: bool,
                      devices: int, n_tokens: int, dim: int,
                      hidden_dim: int, dtype) -> str:
    """'streamed' or 'grouped': which lowering a dropless call takes, from
    static facts of the call alone. Streamed needs the Pallas kernel's
    preconditions: a TPU (Mosaic; interpret mode is for tests), SwiGLU
    experts (the kernel's one arithmetic), no gradient (it has no VJP:
    fit() keeps `ragged_dot`), a program on ONE device (over a mesh GSPMD
    owns the op: an 'expert' axis shards the matrices, a 'data' axis the
    rows), at most MOE_STREAM_MAX_ROWS tokens (up to the MXU tile's height
    every row rides along with every hit expert for free; beyond it that
    trade loses to sorted groups), and sizes Mosaic can tile."""
    from flexflow_tpu.ops.pallas_kernels import (MOE_STREAM_MAX_ROWS,
                                                 moe_stream_chunk)

    if (backend == "tpu" and expert in _DROPLESS_EXPERTS and not training
            and devices == 1 and n_tokens <= MOE_STREAM_MAX_ROWS
            and moe_stream_chunk(dim, hidden_dim, dtype,
                                 len(_DROPLESS_EXPERTS[expert])) is not None):
        return "streamed"
    return "grouped"


# the dropless expert forms (both of which the expert-stream kernel
# computes): their (E, in, F) matrices, then the (E, F, in) one
_DROPLESS_EXPERTS = {
    "swiglu": ("w_gate", "w_up", "w_down"),
    "relu2": ("w_up", "w_down"),
}


def _backend() -> str:
    return jax.default_backend()


# a held share's sorted rows a pass: this many times the rows an even routing
# sends here, rounded up to whole tiles of rows
HELD_ROWS_SLACK = 2.0
HELD_ROWS_TILE = 256


def held_rows_cap(n_tokens: int, k: int, held: int, num_experts: int) -> int:
    """Rows of the compact form: at most all N*k of them. `num_experts` is
    the router's width (zero-computation columns included: they take their
    share of the N*k picks and send no row anywhere)."""
    even = n_tokens * k * held / num_experts
    tiles = -(-int(HELD_ROWS_SLACK * even) // HELD_ROWS_TILE)
    return min(n_tokens * k, max(1, tiles) * HELD_ROWS_TILE)


class MoE(Op):
    op_type = OperatorType.OP_MOE
    has_aux = True  # second output = scalar load-balancing loss

    kernel_phase = "experts"  # profiler.scope_table: an unnamed Mosaic call

    def __init__(self, model, name, inputs, num_experts: int, hidden_dim: int,
                 k: int = 2, capacity_factor: Optional[float] = 1.25,
                 aux_weight: float = 1e-2, dispatch: str = "auto",
                 expert: str = "gelu", renormalize: bool = True,
                 scoring: str = "softmax",
                 score_bias: Optional[float] = None, n_group: int = 1,
                 topk_group: int = 1, routed_scaling: float = 1.0,
                 shared_hidden_dim: int = 0, experts_held=None,
                 latent_dim: int = 0, zero_experts: int = 0,
                 router_f32: bool = False):
        super().__init__(model, name, inputs)
        self.num_experts = num_experts
        self.hidden_dim = hidden_dim
        # router columns past `num_experts` that name no weight: a pick of
        # one is the token itself times its gate
        self.zero_experts = int(zero_experts)
        self.router_width = num_experts + self.zero_experts
        self.router_f32 = bool(router_f32)
        self.k = min(k, self.router_width)
        self.capacity_factor = capacity_factor
        self.aux_weight = aux_weight
        if dispatch not in ("auto", "dense", "sort"):
            raise ValueError(f"dispatch must be auto|dense|sort, got {dispatch!r}")
        if expert not in ("gelu", "swiglu", "relu2"):
            raise ValueError(
                f"expert must be gelu|swiglu|relu2, got {expert!r}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"scoring must be softmax|sigmoid, got {scoring!r}")
        self.dispatch = dispatch
        self.expert = expert
        self.renormalize = renormalize
        self.scoring = scoring
        # std of the seeded draw of the selection-only bias; None = no bias
        self.score_bias = score_bias
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.routed_scaling = float(routed_scaling)
        self.shared_hidden_dim = int(shared_hidden_dim)
        self.latent_dim = int(latent_dim)
        self.held_first, self.held_count = (
            (0, num_experts) if experts_held is None
            else (int(experts_held[0]), int(experts_held[1])))
        plain = (scoring == "softmax" and score_bias is None
                 and self.n_group == 1 and self.routed_scaling == 1.0
                 and not self.shared_hidden_dim and experts_held is None
                 and not self.latent_dim and expert != "relu2"
                 and not self.zero_experts and not self.router_f32)
        if not plain and (capacity_factor is not None
                          or expert not in _DROPLESS_EXPERTS):
            raise ValueError(
                "scoring, score_bias, n_group, routed_scaling, "
                "shared_hidden_dim, experts_held, latent_dim, zero_experts, "
                "router_f32 and expert='relu2' belong to the dropless op "
                "(capacity_factor=None, expert='swiglu' or 'relu2')")
        if self.zero_experts < 0 or (self.zero_experts and (
                self.n_group > 1 or self.latent_dim)):
            raise ValueError(
                f"zero_experts {zero_experts}: >= 0, and neither a "
                f"group-limited router nor latent experts beside them")
        if num_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"n_group {n_group} must divide num_experts {num_experts} "
                f"and topk_group {topk_group} lie in 1..n_group")
        if not (0 <= self.held_first and self.held_count >= 1
                and self.held_first + self.held_count <= num_experts):
            raise ValueError(
                f"experts_held {experts_held}: (first, count) inside "
                f"0..{num_experts}")
        self.dim = inputs[0].dims[-1]
        # the width the routed experts read and write
        self.expert_dim = self.latent_dim or self.dim
        self.capacity = None            # dropless: no buffer to size
        if capacity_factor is not None:
            ntokens = 1
            for s in inputs[0].dims[:-1]:
                ntokens *= s
            self.capacity = max(
                1, int(capacity_factor * ntokens * self.k / num_experts))
        self.finalize()

    @property
    def dropless(self) -> bool:
        return self.capacity_factor is None

    def output_shapes(self):
        return ([self.inputs[0].dims, ()],
                [self.inputs[0].dtype, DataType.DT_FLOAT])

    def weights(self) -> List[WeightSpec]:
        E, D, F = self.router_width, self.dim, self.hidden_dim
        L = self.expert_dim
        H = self.held_count         # the expert matrices this layer holds
        *up, down = _DROPLESS_EXPERTS.get(self.expert, ("w_in", "w_out"))
        ws = [WeightSpec("router", (D, E), init="glorot", fan=(D, E))] + [
            WeightSpec(w, (H, L, F), init="glorot", fan=(L, F)) for w in up
        ] + [WeightSpec(down, (H, F, L), init="glorot", fan=(F, L))]
        if self.latent_dim:
            ws += [WeightSpec("w_latent_in", (D, L), init="glorot"),
                   WeightSpec("w_latent_out", (L, D), init="glorot")]
        if self.score_bias is not None:
            ws.append(WeightSpec("score_bias", (E,), init="normal",
                                 init_args=(0.0, float(self.score_bias))))
        if self.shared_hidden_dim:
            Fs = self.shared_hidden_dim
            ws += [WeightSpec(f"shared_{w[2:]}", (D, Fs), init="glorot")
                   for w in up]
            ws += [WeightSpec("shared_down", (Fs, D), init="glorot")]
        return ws

    _EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down", "w_in", "w_out")

    def _expert_ffn(self, params, x, mm):
        """Every expert's FFN on its own rows; `mm(rows, w)` multiplies
        each row by its expert's matrix (a batched einsum over the
        capacity buffer, a grouped matmul over the sorted rows)."""
        w = {k: v.astype(x.dtype) for k, v in params.items()
             if k in self._EXPERT_WEIGHTS}
        if self.expert == "swiglu":
            return mm(jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]),
                      w["w_down"])
        if self.expert == "relu2":
            return mm(jnp.square(jax.nn.relu(mm(x, w["w_up"]))), w["w_down"])
        return mm(jax.nn.gelu(mm(x, w["w_in"])), w["w_out"])

    def _use_sort_dispatch(self) -> bool:
        if self.dispatch != "auto":
            return self.dispatch == "sort"
        mesh = getattr(self.model, "mesh", None)
        # same condition as weight_partition: dense pays off only when the
        # experts actually shard over the 'expert' axis (all-to-all lowering)
        ep = (mesh is not None and "expert" in getattr(mesh, "axis_names", ())
              and mesh.shape["expert"] > 1
              and self.held_count % mesh.shape["expert"] == 0)
        return not ep

    def forward(self, params, xs, *, training=False, rng=None,
                capacity=None, row_mask=None, routing=None,
                lowerings=None, group_sizes=None, expert_rows=None):
        """Dropless op: `row_mask` (bool, the shape of x without its last
        dim; None = every row live) gives masked rows group size 0 and
        output 0, so the free slots of a decode batch stream no expert;
        `routing`, if a list, receives this call's int32 (2,) counts
        [assignments, experts hit] (traced values: the serving engine
        sums them inside its programs); `lowerings`, if a list, receives
        'streamed' or 'grouped', the lowering this call took (a static
        fact, known while tracing); `group_sizes`, if a list, receives the
        int32 (held experts,) rows each held expert got (a traced value:
        the train step sums, counts and maxes it into its metrics);
        `expert_rows`, if a list, receives the rows this call's grouped
        products were given: the static N*k of the all-rows form, a held
        share's passes x `held_rows_cap` (a traced int32), 0 where the
        call streamed.

        Capacity op: `capacity` overrides the build-time training capacity. The
        inference path (runtime/generation.py) passes N (the slab's token
        count): a token never picks the same expert twice, so per-expert
        assignments are <= N and C=N guarantees ZERO drops — standard
        inference semantics, and the row-independence the decode path
        promises (a row's output can never depend on other rows through
        capacity competition)."""
        x = xs[0]
        orig_shape = x.shape
        D, E = self.dim, self.num_experts
        t = x.reshape(-1, D)  # (N, D)
        N = t.shape[0]
        C = capacity if capacity is not None else self.capacity

        if self.dropless:
            return self._forward_dropless(params, t, orig_shape, row_mask,
                                          routing, training, lowerings,
                                          group_sizes, expert_rows)
        with jax.named_scope("route"):
            logits = t @ params["router"].astype(t.dtype)   # (N, E)
            gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        if self._use_sort_dispatch():
            return self._forward_sort(params, t, gates, orig_shape,
                                      capacity=C)

        # top-k routing with capacity (GShard): iteratively take the best
        # expert per token, mask, repeat k times
        combine = jnp.zeros((N, E, C), jnp.float32)
        remaining = gates
        aux_me = jnp.mean(gates, axis=0)                    # (E,)
        ce = jnp.zeros((E,), jnp.float32)
        slots_used = jnp.zeros((E,), jnp.float32)  # carried across k rounds so
        # round r's assignments start after round r-1's (distinct slots, total
        # capacity C per expert — not C per round)
        for _ in range(self.k):
            choice = jnp.argmax(remaining, axis=-1)          # (N,)
            onehot = jax.nn.one_hot(choice, E, dtype=jnp.float32)
            ce = ce + jnp.mean(onehot, axis=0)
            pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # rank in round
            pos_in_e = (jnp.sum(pos, axis=-1)
                        + jnp.sum(onehot * slots_used, axis=-1)).astype(jnp.int32)
            fits = (pos_in_e < C).astype(jnp.float32)
            keep = fits * jnp.max(onehot * remaining, axis=-1)  # gate value
            slot = jax.nn.one_hot(jnp.clip(pos_in_e, 0, C - 1), C,
                                  dtype=jnp.float32)
            combine = combine + keep[:, None, None] * onehot[:, :, None] \
                * slot[:, None, :]
            slots_used = slots_used + jnp.sum(onehot * fits[:, None], axis=0)
            remaining = remaining * (1.0 - onehot)

        if self.renormalize:    # kept gates over the selected experts
            denom = jnp.sum(combine, axis=(1, 2), keepdims=True)
            combine = jnp.where(denom > 0,
                                combine / jnp.maximum(denom, 1e-9), combine)
        dispatch = (combine > 0).astype(t.dtype)             # (N, E, C)

        with jax.named_scope("experts"):
            expert_in = jnp.einsum("nec,nd->ecd", dispatch, t)  # (E, C, D)
            expert_out = self._expert_ffn(params, expert_in, _buffer_mm)
            y = jnp.einsum("nec,ecd->nd", combine.astype(t.dtype),
                           expert_out)

        # load-balancing aux loss: E * sum(mean_gate * mean_assignment)
        aux = self.aux_weight * E * jnp.sum(aux_me * (ce / self.k))
        return [y.reshape(orig_shape), aux.astype(jnp.float32)]

    def _forward_sort(self, params, t, gates, orig_shape, capacity):
        """Sort-based dispatch: O(N*k) routing state. Token assignments are
        ordered round-major (all round-0 picks first, in token order) so
        capacity drops match the dense path's position rule exactly.
        `capacity` is resolved by forward() — the single resolution site."""
        D, E, k = self.dim, self.num_experts, self.k
        C = capacity
        N = t.shape[0]

        topk_gates, topk_idx = jax.lax.top_k(gates, k)      # (N, k)
        flat_e = topk_idx.T.reshape(-1)                     # (k*N,) round-major
        flat_g = topk_gates.T.reshape(-1)

        order = jnp.argsort(flat_e)                         # stable
        sorted_e = flat_e[order]
        counts = jnp.bincount(flat_e, length=E)             # (E,)
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(k * N) - starts[sorted_e]         # pos within expert
        keep = (rank < C).astype(jnp.float32)
        dest = sorted_e * C + jnp.clip(rank, 0, C - 1)      # (k*N,)
        token = order % N                                   # round-major flatten
        gate = flat_g[order] * keep

        if self.renormalize:    # over each token's surviving experts
            denom = jnp.zeros((N,), jnp.float32).at[token].add(gate)
            gate = gate / jnp.maximum(denom[token], 1e-9)

        # gather tokens into the expert buffer (each kept assignment owns a
        # distinct slot; dropped ones contribute zero to a clipped slot)
        with jax.named_scope("experts"):
            buf = jnp.zeros((E * C, D), t.dtype)
            buf = buf.at[dest].add(t[token] * keep[:, None].astype(t.dtype))
            expert_out = self._expert_ffn(params, buf.reshape(E, C, D),
                                          _buffer_mm)
            flat_out = expert_out.reshape(E * C, D)
            y = jnp.zeros((N, D), t.dtype).at[token].add(
                flat_out[dest] * gate[:, None].astype(t.dtype))

        me = jnp.mean(gates, axis=0)
        ce = counts.astype(jnp.float32) / N
        aux = self.aux_weight * E * jnp.sum(me * (ce / k))
        return [y.reshape(orig_shape), aux.astype(jnp.float32)]

    def lowering(self, n_tokens: int, training: bool, dtype) -> str:
        """The dropless lowering a call of `n_tokens` rows takes here
        (`dropless_lowering` on this op, this process and this mesh)."""
        mesh = getattr(self.model, "mesh", None)
        return dropless_lowering(
            _backend(), self.expert, training,
            1 if mesh is None else mesh.size, n_tokens, self.expert_dim,
            self.hidden_dim, dtype)

    def _route(self, params, t):
        """The dropless op's one routing function: (scores (N, E) f32,
        top_g (N, k) f32 gates, top_e (N, k) int32 experts) over ALL
        `router_width` columns (the experts, whichever of them this layer
        holds, then the zero-computation ones).

        softmax: top-k of the f32 softmax of a compute-dtype matmul (OLMoE),
        of a float32 matmul with `router_f32` (LongCat-Flash); with a
        `score_bias` the selection runs on p + b and the gates are p's.
        sigmoid: s = sigmoid(t W_r), the matmul in f32; the selection runs on
        s' = s + score_bias and, with n_group > 1, only inside the
        `topk_group` groups whose two best s' sum highest; the gates are
        s (never s') of the chosen experts, renormalised over them and
        times `routed_scaling` (DeepSeek-V3)."""
        E, k = self.router_width, self.k
        if self.scoring == "softmax" and not self.router_f32:
            router = params["router"].astype(t.dtype)
            scores = jax.nn.softmax((t @ router).astype(jnp.float32),
                                    axis=-1)
        else:
            # float32 inputs, as the published gate computes it: a bf16
            # product's rounding would flip choices at near-ties that the
            # model itself does not have
            logits = jnp.dot(
                t.astype(jnp.float32), params["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            scores = jax.nn.softmax(logits, axis=-1) \
                if self.scoring == "softmax" else jax.nn.sigmoid(logits)
        sel = scores
        if self.score_bias is not None:
            sel = sel + params["score_bias"].astype(jnp.float32)
        if self.n_group > 1:
            G = self.n_group
            grouped = sel.reshape(-1, G, E // G)
            gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            kept = jax.lax.top_k(gscore, self.topk_group)[1]    # (N, tg)
            in_kept = jnp.any(kept[:, :, None] == jnp.arange(G), axis=1)
            sel = jnp.where(jnp.repeat(in_kept, E // G, axis=1), sel,
                            -jnp.inf)
        if sel is scores:
            top_g, top_e = jax.lax.top_k(scores, k)         # (N, k)
        else:
            top_e = jax.lax.top_k(sel, k)[1]
            top_g = jnp.take_along_axis(scores, top_e, axis=-1)
        if self.renormalize:
            top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)
        if self.routed_scaling != 1.0:
            top_g = top_g * self.routed_scaling
        return scores, top_g, top_e

    def _shared_expert(self, params, t):
        """The expert every row passes through, of the op's own form."""
        if self.expert == "relu2":
            u, d = (params[n].astype(t.dtype)
                    for n in ("shared_up", "shared_down"))
            return jnp.square(jax.nn.relu(t @ u)) @ d
        g, u, d = (params[n].astype(t.dtype)
                   for n in ("shared_gate", "shared_up", "shared_down"))
        return (jax.nn.silu(t @ g) * (t @ u)) @ d

    def _forward_dropless(self, params, t, orig_shape, row_mask, routing,
                          training, lowerings, group_sizes=None,
                          expert_rows=None):
        """No capacity, no dropped token: `_route`'s top-k, the held
        experts over exactly the (token, expert) pairs that chose them
        (grouped or streamed, see the module docstring), each token's
        results weighted by its gates and summed, plus the shared expert
        where the layer has one. A row's output depends on no other
        row."""
        k = self.k
        N = t.shape[0]
        with jax.named_scope("route"):
            gates, top_g, top_e = self._route(params, t)
        lo, E = self.held_first, self.held_count
        live = None if row_mask is None else row_mask.reshape(N)
        took = self.lowering(N, training, t.dtype)
        if lowerings is not None:
            lowerings.append(took)
        x = t
        if self.latent_dim:
            with jax.named_scope("latent"):
                x = t @ params["w_latent_in"].astype(t.dtype)
        if took == "streamed":
            y, sizes = self._experts_streamed(params, x, top_g, top_e, live)
        else:
            y, sizes = self._experts_grouped(params, x, top_g, top_e, live)
        if expert_rows is not None:
            cap = 0 if took == "streamed" else self._rows_a_pass(N)
            # whole passes (`_held_passes`), counted where the sizes are
            expert_rows.append(cap if cap in (0, N * k)
                               else cap * -(-jnp.sum(sizes) // cap))
        if self.latent_dim:
            with jax.named_scope("latent"):
                y = y.astype(t.dtype) @ params["w_latent_out"].astype(t.dtype)
        if group_sizes is not None:
            group_sizes.append(sizes)
        counts = [jnp.sum(sizes), jnp.sum(sizes > 0)]
        if self.zero_experts:
            with jax.named_scope("zero"):
                # the identity term: the gates of a row's zero-computation
                # picks, summed, times the row itself (0 for a dead row)
                zero = top_e >= self.num_experts                # (N, k)
                if live is not None:
                    zero &= live[:, None]
                y = y.astype(jnp.float32) + jnp.sum(
                    jnp.where(zero, top_g, 0.0), axis=-1,
                    keepdims=True) * t.astype(jnp.float32)
                picks = jnp.sum(zero)
                alive = N if live is None else jnp.sum(live)
                counts += [picks, alive * k - picks]
        if routing is not None:
            routing.append(jnp.stack(counts).astype(jnp.int32))
        with jax.named_scope("route"):
            me = jnp.mean(gates, axis=0)
            if E != self.router_width:
                me = me[lo:lo + E]
            ce = sizes.astype(jnp.float32) / N
            aux = self.aux_weight * E * jnp.sum(me * (ce / k))
        y = y.astype(t.dtype)
        if self.shared_hidden_dim:
            with jax.named_scope("shared"):
                y = y + self._shared_expert(params, t)
        return [y.reshape(orig_shape), aux.astype(jnp.float32)]

    def _experts_grouped(self, params, t, top_g, top_e, live):
        """(y (N, D) f32, sizes (E,) int32): the N*k assignments
        stable-sorted by expert, grouped matmuls over exactly those rows,
        unsorted, gate-weighted and summed per token. A held share works on
        `held_rows_cap` sorted rows a pass (module docstring)."""
        with jax.named_scope("route"):
            flat_e, top_g, masked = self._held_assignments(top_g, top_e,
                                                           live)
            order = jnp.argsort(flat_e, stable=True)
            sizes = jnp.bincount(
                flat_e, length=self.held_count).astype(jnp.int32)
        with jax.named_scope("experts"):
            return self._grouped_rows(params, t, top_g, order, sizes,
                                      masked), sizes

    def _held_assignments(self, top_g, top_e, live):
        """(flat_e (N*k,), top_g, masked): each assignment's expert as this
        layer numbers its held ones, `held_count` (no expert here) for a
        dead row's and for one held elsewhere, whose gates become 0."""
        lo, E, k = self.held_first, self.held_count, self.k
        N = top_e.shape[0]
        flat_e = top_e.reshape(-1)                          # token-major
        masked = live is not None
        if E != self.router_width:
            # assignments to experts held elsewhere (and to the zero-
            # computation columns, which hold nothing), like a dead row's,
            # go to no expert here
            masked = True
            flat_e = flat_e - lo
            here = (flat_e >= 0) & (flat_e < E)
            if live is not None:
                here &= jnp.repeat(live, k)
            flat_e = jnp.where(here, flat_e, E)
            top_g = top_g * here.reshape(N, k)
        elif live is not None:
            # a dead row's k assignments go to no expert: the id E sorts
            # behind every group and bincount drops it
            flat_e = jnp.where(jnp.repeat(live, k), flat_e, E)
            top_g = top_g * live[:, None]
        return flat_e, top_g, masked

    def _rows_a_pass(self, n_tokens: int) -> int:
        """Sorted rows the grouped lowering works on at a time: all N*k
        where the layer holds every expert, a held share's
        `held_rows_cap`."""
        if self.held_count == self.router_width:
            return n_tokens * self.k
        return held_rows_cap(n_tokens, self.k, self.held_count,
                             self.router_width)

    def _grouped_rows(self, params, t, top_g, order, sizes, masked):
        """y (N, D) f32 of `_experts_grouped` from the sorted order."""
        k = self.k
        N, D = t.shape

        def all_rows():
            # unsort: where each (token, choice) landed in the sorted rows
            back = jnp.zeros((N * k,), jnp.int32).at[order].set(
                jnp.arange(N * k, dtype=jnp.int32))
            rows = t[order // k]                            # (N*k, D)
            out = self._expert_ffn(
                params, rows, lambda x, w: jax.lax.ragged_dot(x, w, sizes))
            if masked:
                # rows past the last group belong to no expert; what a
                # grouped matmul leaves there is unspecified
                out = jnp.where(
                    (jnp.arange(N * k) < jnp.sum(sizes))[:, None], out, 0)
            return jnp.einsum("nk,nkd->nd", top_g,
                              out[back].reshape(N, k, D).astype(jnp.float32))

        cap = self._rows_a_pass(N)
        if cap == N * k:
            return all_rows()
        # whole passes of `cap` sorted rows; a padding row is past every
        # group, like a row held elsewhere
        pad = -(N * k) % cap
        order = jnp.concatenate([order, jnp.zeros((pad,), order.dtype)])
        names = _DROPLESS_EXPERTS[self.expert]
        weights = tuple(params[n].astype(t.dtype) for n in names)
        # `top_g` is already 0 where the assignment is not held here
        return self._held_passes(cap)(weights, t, top_g.reshape(-1), order,
                                      sizes)

    def _held_passes(self, cap: int):
        """f(weights, t, gates (N*k,), order, sizes) -> (N, D) f32: the held
        assignments' gate-weighted expert outputs summed into their tokens,
        `cap` sorted rows a pass, as many passes as the held rows need (one
        under an even routing; a `while_loop`, so a step that routes
        everything here still drops nothing and a usual step pays for one
        pass). Its gradient runs the same passes again, each pass's forward
        recomputed, so nothing of N*k rows is kept between the two."""
        k = self.k

        def one_pass(weights, t, gates, order, sizes, p):
            start = p * cap
            front = jax.lax.dynamic_slice_in_dim(order, start, cap)
            tok = front // k
            ends = jnp.cumsum(sizes)
            # the rows of each group that lie inside this pass's window
            mine = jnp.clip(jnp.minimum(ends, start + cap)
                            - jnp.maximum(ends - sizes, start), 0, None)
            # rows past the pass's last group belong to no expert, and what
            # a grouped matmul (or its transpose) leaves there is
            # unspecified: on the chip, whatever the buffer held, NaN
            # included. Every grouped product is masked where it leaves the
            # matmul, and the rows where they enter, so that neither pass
            # direction carries such a value on.
            valid = (jnp.arange(cap) < jnp.sum(mine))[:, None]
            out = self._expert_ffn(
                dict(zip(_DROPLESS_EXPERTS[self.expert], weights)),
                jnp.where(valid, t[tok], 0),
                lambda x, w: jnp.where(
                    valid, jax.lax.ragged_dot(x, w, mine), 0))
            return jnp.zeros(t.shape, jnp.float32).at[tok].add(
                out.astype(jnp.float32) * gates[front][:, None])

        def passes(sizes):
            return -(-jnp.sum(sizes) // cap)

        @jax.custom_vjp
        def held(weights, t, gates, order, sizes):
            def body(c):
                p, y = c
                return p + 1, y + one_pass(weights, t, gates, order, sizes,
                                           p)

            return jax.lax.while_loop(
                lambda c: c[0] < passes(sizes), body,
                (jnp.int32(0), jnp.zeros(t.shape, jnp.float32)))[1]

        def fwd(weights, t, gates, order, sizes):
            return held(weights, t, gates, order, sizes), (
                weights, t, gates, order, sizes)

        def bwd(res, dy):
            weights, t, gates, order, sizes = res
            f32 = functools.partial(jnp.zeros_like, dtype=jnp.float32)

            def body(c):
                p, acc = c
                _, pull = jax.vjp(
                    lambda w, x, g: one_pass(w, x, g, order, sizes, p),
                    weights, t, gates)
                return p + 1, jax.tree.map(
                    lambda a, d: a + d.astype(jnp.float32), acc, pull(dy))

            _, (dw, dt, dg) = jax.lax.while_loop(
                lambda c: c[0] < passes(sizes), body,
                (jnp.int32(0), (jax.tree.map(f32, weights), f32(t),
                                f32(gates))))
            none = functools.partial(np.zeros, dtype=jax.dtypes.float0)
            return (jax.tree.map(lambda d, w: d.astype(w.dtype), dw, weights),
                    dt.astype(t.dtype), dg.astype(gates.dtype),
                    none(order.shape), none(sizes.shape))

        held.defvjp(fwd, bwd)
        return held

    def _experts_streamed(self, params, t, top_g, top_e, live):
        """(y (N, D), sizes (E,) int32) through the expert-stream kernel:
        the (N, E) gate matrix (a row's gate where it chose the expert,
        MOE_NOT_CHOSEN elsewhere and in every dead row) and the rows per
        expert are all the routing state there is. `sizes` counts what
        `bincount` counts in the grouped lowering, and a token never picks
        an expert twice, so the max over its k picks is the one gate."""
        from flexflow_tpu.ops.pallas_kernels import (
            MOE_NOT_CHOSEN, moe_expert_stream_pallas)

        with jax.named_scope("route"):
            held = jnp.arange(self.held_count)
            if self.held_count != self.num_experts:
                held = held + self.held_first
            picked = top_e[:, :, None] == held
            if live is not None:                            # (N, k, E)
                picked &= live[:, None, None]
            gmat = jnp.max(jnp.where(picked, top_g[:, :, None],
                                     MOE_NOT_CHOSEN), axis=1)
            sizes = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
        # directly under the op's scope: the device names this unnamed
        # Mosaic call `moe_<i>`, which the benchmark's readers select by
        # (scope_table books it to `kernel_phase`)
        y = moe_expert_stream_pallas(
            t, gmat, sizes, *(params[n].astype(t.dtype)
                              for n in _DROPLESS_EXPERTS[self.expert]))
        return y, sizes

    def partitionable_output_dims(self):
        return list(range(self.outputs[0].num_dims - 1))

    def expert_parallel_size(self):
        return self.held_count

    def weight_partition(self, axis_map):
        from flexflow_tpu.parallel.pconfig import EXPERT

        # searched expert parallelism: any axis the strategy mapped to the
        # EXPERT sentinel shards the expert dim of w_in/w_out
        eaxes = [ax for ax, d in (axis_map or {}).items() if d == EXPERT]
        if not eaxes:
            # legacy convention: shard over a literal 'expert' mesh axis if
            # present, regardless of activation sharding
            mesh_axes = getattr(self.model, "mesh", None)
            if (mesh_axes is not None
                    and "expert" in getattr(mesh_axes, "axis_names", ())
                    and mesh_axes.shape["expert"] > 1
                    and self.held_count % mesh_axes.shape["expert"] == 0):
                eaxes = ["expert"]
        e = None if not eaxes else (eaxes[0] if len(eaxes) == 1
                                    else tuple(eaxes))
        # the router, the selection bias and the shared expert stay whole
        return {w.name: P(e, None, None) if w.name in self._EXPERT_WEIGHTS
                else P(*([None] * len(w.shape)))
                for w in self.weight_specs()}

    def flops(self):
        ntokens = self.inputs[0].volume() // self.dim
        matmuls = 3 if self.expert == "swiglu" else 2
        # the share of a token's k picks that lands on the experts held here
        # (a pick of a zero-computation column costs nothing)
        routed = ntokens * self.k * self.held_count / self.router_width
        if self.latent_dim:
            return int(2 * matmuls * (routed * self.latent_dim
                                      * self.hidden_dim + ntokens * self.dim
                                      * self.shared_hidden_dim)
                       + 4 * ntokens * self.dim * self.latent_dim)
        return int(2 * matmuls * self.dim
                   * (routed * self.hidden_dim
                      + ntokens * self.shared_hidden_dim))

    def input_axis_map(self, axis_map, input_idx):
        # negative sentinels (CONTRACT/STAGE/EXPERT) must not leak into the
        # input map: the input arrives replicated over those axes
        ndims = self.inputs[input_idx].num_dims
        return {ax: (d if d is not None and 0 <= d < ndims - 1 else None)
                for ax, d in (axis_map or {}).items()}
