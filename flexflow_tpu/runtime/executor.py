"""Graph executor: lowers the op graph + strategy table into jitted,
GSPMD-sharded XLA programs.

This replaces the reference's entire launch machinery — per-op IndexLaunchers,
the FFMapper's tag->ParallelConfig->device resolution (src/mapper/mapper.cc:
346-424), and Legion's implicit region copies — with ONE traced program per
(train step | inference step): each op's output gets a
`with_sharding_constraint` from its ParallelConfig (the "mapper tag"), and XLA
GSPMD inserts all resharding/halo/collective traffic over ICI. The jit cache
plays the role of Legion tracing (flexflow_cbinding.py:394-397).
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flexflow_tpu.ffconst import CompMode, LossType, MetricsType, dtype_to_np
from flexflow_tpu.ops.base import InputOp, Op
from flexflow_tpu.parallel.mesh import mesh_shape_dict
from flexflow_tpu.parallel.pconfig import ParallelConfig
from flexflow_tpu.runtime.initializer import init_weight
from flexflow_tpu.runtime.loss import compute_loss
from flexflow_tpu.runtime.metrics import batch_metrics, routing_counts


def resolve_axis_map(pc: ParallelConfig, mesh_shape: Dict[str, int],
                     ndims: int) -> Dict[str, Optional[int]]:
    """Fill in pc.axis_map from degrees when a strategy came from a file
    (degrees only). Greedy: each partitioned dim takes unused mesh axes whose
    sizes multiply to its degree; sample dim prefers 'data'."""
    from flexflow_tpu.parallel.pconfig import CONTRACT, EXPERT, STAGE

    if pc.axis_map is not None:
        # explicit axis_map (search output, or a file's @axismap record):
        # validate against THIS mesh — a file written on a differently-
        # named mesh must fail here with the axis named, not deep inside
        # JAX; a same-name different-SIZE mesh silently changes degrees,
        # so check the recorded dims still match
        missing = [ax for ax, d in pc.axis_map.items()
                   if d is not None and ax not in mesh_shape]
        if missing:
            raise ValueError(
                f"strategy axis_map references mesh axes {missing} absent "
                f"from this mesh {mesh_shape} — the strategy was "
                f"produced for a different mesh; regenerate it or rename "
                f"the mesh axes")
        # dim indices must be valid for THIS op's rank: a hand-edited /
        # corrupt @axismap record would otherwise surface as a bare
        # IndexError inside from_axis_map rather than a diagnosis
        bad = {ax: d for ax, d in pc.axis_map.items()
               if d is not None and d not in (CONTRACT, STAGE, EXPERT)
               and not (0 <= d < ndims)}
        if bad:
            raise ValueError(
                f"strategy axis_map entries {bad} map mesh axes to tensor "
                f"dims outside this op's rank {ndims} (valid: 0..{ndims - 1} "
                f"or the CONTRACT/STAGE/EXPERT sentinels) — the @axismap "
                f"record is corrupt or was written for a different operator")
        if pc.dims:
            # re-derive degrees exactly the way the serializer did
            # (from_axis_map: CONTRACT appends a trailing degree, STAGE
            # contributes none) so a correct unchanged-mesh strategy
            # never trips the drift warning
            from flexflow_tpu.parallel.pconfig import ParallelConfig as _PC

            expect = _PC.from_axis_map(ndims, mesh_shape, pc.axis_map).dims
            if tuple(expect) != tuple(pc.dims):
                from flexflow_tpu.logger import fflogger

                fflogger.warning(
                    "strategy axis_map on this mesh gives degrees %s but "
                    "the strategy recorded %s — the mesh axis sizes "
                    "changed since it was written; executing at the NEW "
                    "degrees", tuple(expect), tuple(pc.dims))
        return pc.axis_map
    remaining = dict(mesh_shape)
    axis_map: Dict[str, Optional[int]] = {}
    # a degree list one longer than the output rank carries a trailing
    # CONTRACT (row-parallel) degree — the reference's replica-dim
    # convention (linear.cu:171-192); resolved like any other dim but
    # mapped to the CONTRACT sentinel
    targets = list(range(min(ndims, len(pc.dims))))
    if len(pc.dims) == ndims + 1 and pc.dims[ndims] > 1:
        targets.append(ndims)
    order = sorted(targets, key=lambda d: (d != 0,))  # sample dim first
    for d in order:
        deg = pc.dims[d]
        logical = CONTRACT if d == ndims else d
        if deg == 1:
            continue
        # prefer canonical axis for the dim role
        prefs = (["data"] if d == 0 else []) + list(remaining.keys())
        single = [ax for ax in prefs if remaining.get(ax) == deg]
        if single:
            axis_map[single[0]] = logical
            del remaining[single[0]]
            continue
        # general case: smallest subset of remaining axes whose sizes
        # multiply to the degree (covers 3+-axis factorizations)
        found = None
        axes = list(remaining.keys())
        for r in range(2, len(axes) + 1):
            for combo in itertools.combinations(axes, r):
                prod = 1
                for ax in combo:
                    prod *= remaining[ax]
                if prod == deg:
                    found = combo
                    break
            if found:
                break
        if not found:
            raise ValueError(
                f"strategy degree {deg} on dim {d} not expressible as a "
                f"product of unused mesh axes (mesh {mesh_shape}, "
                f"remaining {remaining})")
        for ax in found:
            axis_map[ax] = logical
            del remaining[ax]
    return axis_map


class GraphExecutor:
    def __init__(self, model):
        self.model = model
        self.mesh: Mesh = model.mesh
        self.mesh_shape = mesh_shape_dict(self.mesh)
        self._op_axis_maps: Dict[str, Dict[str, Optional[int]]] = {}
        self._batch_sharding_cache: Dict[Tuple[str, int], NamedSharding] = {}
        self._resolve_strategies()

    # ---- strategy resolution ------------------------------------------------

    def _resolve_strategies(self):
        strategies = self.model.config.strategies
        for op in self.model.ops:
            if isinstance(op, InputOp):
                continue
            pc = strategies.get(op.name)
            nd = op.outputs[0].num_dims
            if pc is None:
                pc = ParallelConfig.data_parallel(
                    nd, self.mesh_shape.get("data", 1))
                if "data" not in self.mesh_shape:
                    pc = ParallelConfig.replicated(nd)
            am = resolve_axis_map(pc, self.mesh_shape, nd)
            self._op_axis_maps[op.name] = am

    def op_output_sharding(self, op: Op) -> NamedSharding:
        am = self._op_axis_maps.get(op.name, {})
        pspec = ParallelConfig(axis_map=am).to_partition_spec(
            op.outputs[0].num_dims, list(self.mesh.axis_names))
        return NamedSharding(self.mesh, pspec)

    def input_sharding(self, tensor) -> NamedSharding:
        # batch-shard graph inputs on 'data' if present
        entries = [None] * tensor.num_dims
        if "data" in self.mesh_shape and self.mesh_shape["data"] > 1:
            entries[0] = "data"
        return NamedSharding(self.mesh, P(*entries))

    def param_shardings(self) -> Dict[str, Dict[str, NamedSharding]]:
        fsdp = getattr(self.model.config, "fsdp_axis", "")
        if fsdp and fsdp not in self.mesh_shape:
            raise ValueError(
                f"fsdp_axis={fsdp!r} is not a mesh axis "
                f"(mesh {self.mesh_shape})")
        out: Dict[str, Dict[str, NamedSharding]] = {}
        for op in self.model.ops:
            specs = op.weight_specs()
            if not specs:
                continue
            am = self._op_axis_maps.get(op.name, {})
            wp = op.weight_partition(am)
            shapes = {w.name: w.shape for w in specs}
            out[op.name] = {
                name: NamedSharding(
                    self.mesh,
                    _with_fsdp(ps, shapes.get(name), fsdp,
                               self.mesh_shape.get(fsdp, 1)) if fsdp else ps)
                for name, ps in wp.items()}
        return out

    def grad_scatter_shardings(self) -> Dict[str, Dict[str, NamedSharding]]:
        """ZeRO-1 / bucketed-grad-sync layout (FFConfig.overlap_grad_sync):
        each weight's strategy(+FSDP) sharding with its largest
        still-unsharded divisible dim ADDITIONALLY split over the data
        axis — the per-op "bucket" the accumulation scan reduce-scatters
        gradients into, and the layout the ZeRO-1 optimizer update runs
        in. A weight the data axis cannot divide (or that FSDP already
        shards over it, the ZeRO-3 case) keeps its param sharding and
        rides the plain all-reduce path. Returns {} when the mesh has no
        data axis > 1 — nothing to scatter over."""
        n = self.mesh_shape.get("data", 0)
        if n <= 1:
            return {}
        base = self.param_shardings()
        out: Dict[str, Dict[str, NamedSharding]] = {}
        for op in self.model.ops:
            specs = op.weight_specs()
            if not specs:
                continue
            per = {}
            for spec in specs:
                ns = base.get(op.name, {}).get(spec.name)
                if ns is None:
                    continue
                per[spec.name] = NamedSharding(
                    self.mesh, _with_fsdp(ns.spec, spec.shape, "data", n))
            if per:
                out[op.name] = per
        return out

    # ---- parameter / state initialization -----------------------------------

    def init_params(self, rng_key) -> Dict[str, Dict[str, jnp.ndarray]]:
        """Sharded param init: each weight's init runs jitted with its target
        sharding as out_sharding, so a vocab-sharded embedding table never
        materializes replicated. Deliberately one tiny jit per weight (NOT
        one batched program per model): the key is a traced argument, so
        same-shape inits share a jaxpr and jax's lowering/compilation
        caches dedupe them across ops, models, and tests in a process — a
        per-model batched program bakes the per-op key constants into a
        unique HLO and recompiles for every model built."""
        shardings = self.param_shardings()
        params: Dict[str, Dict[str, jnp.ndarray]] = {}
        for op in self.model.ops:
            specs = op.weight_specs()
            if not specs:
                continue
            op_params = {}
            master_bf16 = self.model.config.master_dtype == "bfloat16"
            tied = getattr(self.model, "_tied", {})
            for i, spec in enumerate(specs):
                if (op.name, spec.name) in tied:
                    continue  # storage lives with the tie source
                key = jax.random.fold_in(
                    jax.random.fold_in(rng_key, _stable_hash(op.name)), i)
                sharding = shardings[op.name].get(spec.name)
                init_fn = functools.partial(init_weight, spec)
                dtype = dtype_to_np(spec.dtype)

                def _init(k, f=init_fn, d=dtype):
                    w = f(k, dtype=d)
                    # bf16 master weights: storage halves, init stays f32
                    if master_bf16 and w.dtype == jnp.float32:
                        w = w.astype(jnp.bfloat16)
                    return w

                op_params[spec.name] = jax.jit(
                    _init, out_shardings=sharding)(key)
            params[op.name] = op_params
        return params

    def init_state(self) -> Dict[str, Dict[str, jnp.ndarray]]:
        state = {}
        for op in self.model.ops:
            if op.stateful:
                s = op.init_state()
                state[op.name] = {k: jnp.asarray(v) for k, v in s.items()}
        return state

    # ---- forward interpretation ---------------------------------------------

    def apply_graph(self, params, state, input_values: Dict[Any, jnp.ndarray],
                    *, training: bool, rng,
                    group_sizes=None) -> Tuple[Dict[Any, jnp.ndarray], Dict]:
        """Interpret the graph in topo order. Returns (tensor->value map,
        new_state). `group_sizes`, if a list, receives from each dropless
        MoE op the rows each of its held experts got (ops/moe.py)."""
        vals: Dict[Any, jnp.ndarray] = dict(input_values)
        new_state: Dict[str, Dict] = {}
        # mixed precision: master params stay f32; compute runs in bf16 on the
        # MXU when config.compute_dtype == "bfloat16" (autodiff through the
        # casts yields f32 grads)
        bf16 = self.model.config.compute_dtype == "bfloat16"

        def to_compute(a):
            return a.astype(jnp.bfloat16) if (bf16 and a.dtype == jnp.float32) else a

        vals = {k: to_compute(v) for k, v in vals.items()}
        for idx, op in enumerate(self.model.ops):
            if isinstance(op, InputOp):
                t = op.outputs[0]
                if t not in vals:
                    raise ValueError(f"missing input value for {op.name}")
                continue
            xs = [vals[t] for t in op.inputs]
            op_rng = None
            if op.needs_rng and rng is not None:
                op_rng = jax.random.fold_in(rng, idx)
                seed = getattr(op, "seed", 0)
                if seed:
                    op_rng = jax.random.fold_in(op_rng, seed)
            # named_scope stamps the op name into the HLO metadata of every
            # instruction it traces (the cast of its weights included), so
            # a jax.profiler trace's device ops of the PRODUCTION jitted
            # program attribute back to graph ops (runtime/profiler.py
            # scope_table) — the in-situ analog of the reference's
            # --profiling per-op events (linear.cu:526-553)
            with jax.named_scope(op.name):
                p = resolve_tied_params(self.model, params, op.name,
                                        params.get(op.name, {}))
                if bf16:
                    p = {k: to_compute(v) for k, v in p.items()}
            kwargs = {}
            if getattr(op, "wants_shard_ctx", False):
                kwargs["shard_ctx"] = {
                    "mesh": self.mesh,
                    "axis_map": self._op_axis_maps.get(op.name, {}),
                    "sp_mode": getattr(self.model.config, "sp_mode", "ring"),
                }
            if group_sizes is not None and getattr(op, "dropless", False):
                kwargs["group_sizes"] = group_sizes
            with jax.named_scope(op.name):
                if op.stateful:
                    outs, ns = op.forward_stateful(
                        p, state.get(op.name, {}), xs,
                        training=training, rng=op_rng)
                    new_state[op.name] = ns
                else:
                    outs = op.forward(p, xs, training=training, rng=op_rng,
                                      **kwargs)
            sharding = self.op_output_sharding(op)
            for i, t in enumerate(op.outputs):
                v = outs[i]
                if v.ndim == t.num_dims and _spec_rank_ok(sharding.spec, v.ndim):
                    v = jax.lax.with_sharding_constraint(v, sharding)
                elif i == 0 and v.ndim == t.num_dims:
                    # the strategy's axis map targets the primary output; a
                    # rank mismatch there is a bad strategy entry, not a
                    # condition to silently skip (secondary outputs of other
                    # ranks — e.g. MoE's scalar aux loss — stay unconstrained)
                    raise ValueError(
                        f"sharding constraint for {op.name!r} has rank "
                        f"{len(sharding.spec)} but its output is rank "
                        f"{v.ndim} — the strategy entry does not match this "
                        f"op's output; fix or regenerate the strategy file")
                vals[t] = v
        for k, v in state.items():
            if k not in new_state:
                new_state[k] = v
        return vals, new_state

    # ---- compiled steps -----------------------------------------------------

    def _make_loss_fn(self, loss_type: LossType,
                      metric_types: List[MetricsType], final_tensor,
                      label_key="label"):
        """loss_fn(p, state, batch, rng) -> (loss, (new_state, mets)) —
        shared by the plain, scanned, and divergence-guarded step
        builders."""
        input_ops = [op for op in self.model.ops if isinstance(op, InputOp)]
        aux_tensors = list(getattr(self.model, "_aux_tensors", ()))
        routed = any(getattr(op, "dropless", False) for op in self.model.ops)

        def loss_fn(p, st, batch, rng):
            input_values = {op.outputs[0]: batch[op.name] for op in input_ops}
            sizes = [] if routed else None
            vals, new_state = self.apply_graph(
                p, st, input_values, training=True, rng=rng,
                group_sizes=sizes)
            logits = vals[final_tensor]
            with jax.named_scope("loss"):
                loss = compute_loss(loss_type, logits, batch[label_key])
                for t in aux_tensors:  # e.g. MoE load-balancing losses
                    loss = loss + vals[t]
                mets = batch_metrics(
                    loss_type, metric_types, logits, batch[label_key],
                    ignore_index=getattr(self.model.config,
                                         "metrics_ignore_index", None))
            if sizes:
                mets.update(routing_counts(sizes))
            return loss, (new_state, mets)

        return loss_fn

    def _train_step_body(self, optimizer, loss_type: LossType,
                         metric_types: List[MetricsType], final_tensor,
                         label_key="label"):
        """The un-jitted fused fwd+bwd+update body shared by the per-step
        program and the scanned multi-step program."""
        accum = max(1, int(getattr(self.model.config, "grad_accum_steps", 1)))
        loss_fn = self._make_loss_fn(loss_type, metric_types, final_tensor,
                                     label_key)

        def step(params, opt_state, state, batch, rng):
            (loss, (new_state, mets)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, state, batch, rng)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = optimizer.update(
                    params, grads, opt_state)
            return new_params, new_opt_state, new_state, loss, mets

        # in-graph grad-sync overlap (FFConfig.overlap_grad_sync): carry
        # the accumulated grads through the scan in the data-scattered
        # ZeRO-1 bucket layout instead of the full (replicated /
        # all-reduced) tree — GSPMD then lowers each microbatch's
        # cross-data-shard grad reduction to a reduce-scatter whose
        # collective overlaps the NEXT microbatch's backward, and the
        # scan epilogue shrinks to the final bucket + the sharded update
        overlap = (bool(getattr(self.model.config, "overlap_grad_sync",
                                False))
                   and self.mesh_shape.get("data", 1) > 1)
        scatter = self.grad_scatter_shardings() if overlap else {}

        def accum_step(params, opt_state, state, batch, rng):
            # gradient accumulation: the global batch splits into `accum`
            # equal microbatches scanned through fwd+bwd with summed grads
            # and ONE optimizer update — numerically the full-batch step
            # (all losses are batch means, so mean-of-means is exact),
            # with activation memory of a microbatch. Net-new vs the
            # reference (its global batch is always one wave of shards).
            for k, v in batch.items():
                if v.shape[0] % accum:
                    raise ValueError(
                        f"batch dim {v.shape[0]} of {k!r} not divisible by "
                        f"grad_accum_steps={accum}")
            micro = {k: v.reshape(accum, v.shape[0] // accum, *v.shape[1:])
                     for k, v in batch.items()}

            def constrain(tree):
                if not scatter:
                    return tree
                from flexflow_tpu.runtime.optimizer import \
                    apply_tree_shardings

                with jax.named_scope("grad_sync"):
                    return apply_tree_shardings(
                        tree, scatter, jax.lax.with_sharding_constraint)

            def accum_zero(p):
                # low-precision grads accumulate in f32: summing `accum`
                # bf16 microbatch grads in bf16 drops low bits on every
                # add (the scan used to sum in the grad dtype); the f32
                # carry only lives for the scan's duration
                dt = jnp.float32 if p.dtype in (jnp.bfloat16,
                                                jnp.float16) else p.dtype
                return jnp.zeros(p.shape, dt)

            def body(carry, mb_i):
                g_acc, st = carry
                mb, i = mb_i
                (loss, (st, mets)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(
                        params, st, mb, jax.random.fold_in(rng, i))
                with jax.named_scope("grad_sync"):
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype), g_acc, grads)
                return (constrain(g_acc), st), (loss, mets)

            zeros = constrain(jax.tree.map(accum_zero, params))
            (g_sum, new_state), (losses, mets) = jax.lax.scan(
                body, (zeros, state),
                (micro, jnp.arange(accum, dtype=jnp.int32)))
            with jax.named_scope("grad_sync"):
                grads = jax.tree.map(lambda g: g / accum, g_sum)
            loss = jnp.mean(losses)
            # counts and totals (accuracy_count/_total) sum across
            # microbatches; mean metrics average (equal sizes -> exact)
            mets = {k: (jnp.sum(v) if k.endswith(("_count", "_total"))
                        else jnp.max(v) if k.endswith("_max")
                        else jnp.mean(v))
                    for k, v in mets.items()}
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = optimizer.update(params, grads,
                                                             opt_state)
            return new_params, new_opt_state, new_state, loss, mets

        return accum_step if accum > 1 else step

    def make_train_step(self, optimizer, loss_type: LossType,
                        metric_types: List[MetricsType], final_tensor,
                        label_key="label"):
        step = self._train_step_body(optimizer, loss_type, metric_types,
                                     final_tensor, label_key)
        return jax.jit(step, donate_argnums=(0, 1, 2))

    def make_guarded_train_step(self, optimizer, loss_type: LossType,
                                metric_types: List[MetricsType], final_tensor,
                                guard_cfg: Dict, label_key="label"):
        """Divergence-guarded train step (runtime/resilience.py): the
        finite-loss/grad-norm check and the skip/keep selection are
        compiled INTO the step — one jnp.isfinite reduction over the loss
        plus the global grad-norm (f32), a jnp.where per state leaf — so
        the happy path makes NO device→host round trip the plain step
        doesn't. With loss_scale == 1.0 and every step finite, the
        trajectory is bitwise identical to make_train_step's.

        Signature:
            fn(params, opt_state, state, batch, rng, guard_state,
               inject_nan)
              -> (params, opt_state, state, loss, mets, guard_state)
        guard_state: resilience.init_guard_state() pytree (device-resident
        streaks / loss scale / skip counter). inject_nan: traced bool —
        the FF_FAULT nan_loss hook adds NaN to the loss in-graph, so
        injection reuses the one compiled program.

        Returned mets add: nonfinite (0/1 this step), grad_norm,
        loss_scale, skipped_total."""
        mode = guard_cfg.get("on_nonfinite", "skip")
        backoff = float(guard_cfg.get("backoff", 2.0))
        growth_interval = int(guard_cfg.get("growth_interval", 200))
        min_scale = float(guard_cfg.get("min_loss_scale", 2.0 ** -14))
        max_scale = float(guard_cfg.get("max_loss_scale", 2.0 ** 15))
        loss_fn = self._make_loss_fn(loss_type, metric_types, final_tensor,
                                     label_key)

        def gstep(params, opt_state, state, batch, rng, gstate, inject_nan):
            scale = gstate["loss_scale"]

            def scaled(p, st, b, r):
                loss, aux = loss_fn(p, st, b, r)
                loss = loss + jnp.where(inject_nan, jnp.nan, 0.0
                                        ).astype(loss.dtype)
                return loss * scale.astype(loss.dtype), (loss, aux)

            (_, (raw_loss, (new_state, mets))), grads = jax.value_and_grad(
                scaled, has_aux=True)(params, state, batch, rng)
            inv = (1.0 / scale)
            grads = jax.tree_util.tree_map(
                lambda g: (g * inv.astype(g.dtype)), grads)
            leaves = jax.tree_util.tree_leaves(grads)
            gnorm_sq = jnp.float32(0.0)
            for g in leaves:
                gnorm_sq = gnorm_sq + jnp.sum(
                    jnp.square(g.astype(jnp.float32)))
            finite = jnp.isfinite(raw_loss) & jnp.isfinite(gnorm_sq)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = optimizer.update(params, grads,
                                                             opt_state)

            def sel(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new, old)

            params_out = sel(new_params, params)
            opt_out = sel(new_opt_state, opt_state)
            state_out = sel(new_state, state)
            bad = ~finite
            streak = jnp.where(bad, gstate["bad_streak"] + 1, 0)
            good = jnp.where(bad, 0, gstate["good_streak"] + 1)
            if mode == "backoff":
                down = jnp.maximum(scale / backoff, min_scale)
                grow = good >= growth_interval
                up = jnp.where(grow, jnp.minimum(scale * backoff, max_scale),
                               scale)
                new_scale = jnp.where(bad, down, up)
                good = jnp.where(grow & ~bad, 0, good)
            else:
                new_scale = scale
            new_gstate = {"bad_streak": streak, "good_streak": good,
                          "loss_scale": new_scale,
                          "skipped": gstate["skipped"]
                          + bad.astype(jnp.int32)}
            mets = dict(mets)
            mets["nonfinite"] = bad.astype(jnp.int32)
            mets["grad_norm"] = jnp.sqrt(gnorm_sq)
            mets["loss_scale"] = new_scale
            mets["skipped_total"] = new_gstate["skipped"]
            return (params_out, opt_out, state_out, raw_loss, mets,
                    new_gstate)

        return jax.jit(gstep, donate_argnums=(0, 1, 2, 5))

    def make_train_scan(self, optimizer, loss_type: LossType,
                        metric_types: List[MetricsType], final_tensor,
                        label_key="label"):
        """Multi-step trainer: ONE device program runs `n_steps` training
        steps via lax.scan over the pre-batched device-resident dataset
        (dataloader staging shape (num_batches, batch, ...)).

        This is the TPU-native analog of the reference's Legion tracing
        around each training iteration (flexflow_cbinding.py:394-397,
        base_model.py:408-418): where Legion records the task launch
        pattern once and replays it without re-analysis, here the whole
        step sequence is a single compiled XLA program, so per-step host
        dispatch (batch slice + rng split + step launch) disappears
        entirely — which matters whenever host->device latency is
        non-trivial relative to step time.

        Returned fn signature:
            fn(params, opt_state, state, staged, rng, start, n_steps)
        with `staged` a dict name -> (num_batches, batch, ...) device
        array, `start` the starting batch index (wraps mod num_batches),
        and `n_steps` STATIC. Returns (params, opt_state, state, losses,
        mets) with per-step losses stacked shape (n_steps,) and each
        metric stacked likewise.
        """
        step = self._train_step_body(optimizer, loss_type, metric_types,
                                     final_tensor, label_key)

        def scan_fn(params, opt_state, state, staged, rng, start, n_steps):
            # min across datasets: loaders may stage unequal sample counts
            # (model.py's cursor math uses the same modulus)
            nb = min(v.shape[0] for v in staged.values())

            def body(carry, i):
                params, opt_state, state = carry
                bi = jax.lax.rem(start + i, nb)
                batch = {k: jax.lax.dynamic_index_in_dim(v, bi, 0,
                                                         keepdims=False)
                         for k, v in staged.items()}
                step_rng = jax.random.fold_in(rng, i)
                params, opt_state, state, loss, mets = step(
                    params, opt_state, state, batch, step_rng)
                return (params, opt_state, state), (loss, mets)

            (params, opt_state, state), (losses, mets) = jax.lax.scan(
                body, (params, opt_state, state),
                jnp.arange(n_steps, dtype=jnp.int32))
            return params, opt_state, state, losses, mets

        return jax.jit(scan_fn, static_argnums=(6,), donate_argnums=(0, 1, 2))

    def make_eval_step(self, loss_type: LossType,
                       metric_types: List[MetricsType], final_tensor,
                       label_key="label"):
        input_ops = [op for op in self.model.ops if isinstance(op, InputOp)]

        def step(params, state, batch):
            input_values = {op.outputs[0]: batch[op.name] for op in input_ops}
            vals, _ = self.apply_graph(params, state, input_values,
                                       training=False, rng=None)
            logits = vals[final_tensor]
            loss = compute_loss(loss_type, logits, batch[label_key])
            mets = batch_metrics(
                loss_type, metric_types, logits, batch[label_key],
                ignore_index=getattr(self.model.config,
                                     "metrics_ignore_index", None))
            return loss, mets, logits

        return jax.jit(step)

    def make_forward(self, final_tensors=None, training: bool = False):
        """Plain forward fn over graph inputs (used by __graft_entry__ and
        inference)."""
        input_ops = [op for op in self.model.ops if isinstance(op, InputOp)]
        finals = final_tensors or [self.model.ops[-1].outputs[0]]

        def fwd(params, state, batch, rng=None):
            input_values = {op.outputs[0]: batch[op.name] for op in input_ops}
            vals, _ = self.apply_graph(params, state, input_values,
                                       training=training, rng=rng)
            return [vals[t] for t in finals]

        return fwd

    def batch_sharding(self, name: str, ndim: int) -> NamedSharding:
        """The committed placement for one batch entry, CACHED per
        (name, ndim) — building a fresh NamedSharding (and walking the op
        list) every step was pure hot-path overhead, and the prefetch
        pipeline (runtime/pipeline_loader.py) needs the same object so
        ahead-of-time puts and in-step puts agree exactly."""
        key = (name, ndim)
        sh = self._batch_sharding_cache.get(key)
        if sh is None:
            input_by_name = {op.name: op.outputs[0]
                             for op in self.model.ops
                             if isinstance(op, InputOp)}
            if name in input_by_name:
                sh = self.input_sharding(input_by_name[name])
            else:
                entries = [None] * ndim
                if "data" in self.mesh_shape and self.mesh_shape["data"] > 1:
                    entries[0] = "data"
                sh = NamedSharding(self.mesh, P(*entries))
            self._batch_sharding_cache[key] = sh
        return sh

    def reshard_params(self, host_tree):
        """Place a host (numpy) params tree onto THIS executor's mesh —
        the restore half of topology-free checkpoints: the saved arrays
        are placement-less bytes, so whatever mesh the restoring process
        compiled with (same, differently shaped, or a different device
        count entirely — the elastic path) determines the layout here,
        not the mesh that saved them."""
        return reshard_tree(host_tree, self.param_shardings())

    def shard_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        """Commit every batch entry to its cached NamedSharding. Entries
        that are ALREADY committed to the right sharding (a prefetched
        batch, or the device-resident loader's jitted slice) pass through
        untouched — the put is skipped, so calling this on a pre-sharded
        batch is a dict walk, not a transfer. Committed (not just
        correctly-placed) matters: an uncommitted array changes the warm
        step program's pjit signature and silently retraces it."""
        out = {}
        for k, v in batch.items():
            if not hasattr(v, "ndim"):  # plain list/scalar callers
                v = np.asarray(v)
            sh = self.batch_sharding(k, v.ndim)
            if (isinstance(v, jax.Array)
                    and getattr(v, "committed", False)
                    and v.sharding.is_equivalent_to(sh, v.ndim)):
                out[k] = v
            else:
                out[k] = jax.device_put(v, sh)
        return out


def reshard_tree(host_tree, shardings):
    """device_put a {op: {weight: array}} host tree leaf-by-leaf onto the
    given ``param_shardings()``-style placement map (leaves without an
    entry get default placement). Shared by GraphExecutor and
    PlacementExecutor so every restore path re-shards identically."""
    out = {}
    for op_name, ws in host_tree.items():
        per_op = shardings.get(op_name, {})
        out[op_name] = {
            name: jax.device_put(np.asarray(v), per_op.get(name))
            if per_op.get(name) is not None
            # ffsan: allow(uncommitted-device-put) — ops without a
            # recorded sharding deliberately take default placement
            # (restore-time, before any program is warm)
            else jax.device_put(np.asarray(v))
            for name, v in ws.items()}
    return out


def _with_fsdp(ps, shape, axis: str, axis_size: int):
    """FSDP post-process of a weight's PartitionSpec (FFConfig.fsdp_axis):
    shard its LARGEST still-unsharded, divisible dim over `axis` (on top
    of any strategy sharding, e.g. TP — 2D weight sharding). The training
    strategy stays activation-side; GSPMD inserts the all-gather at use
    and the gradient reduce-scatter, so param + optimizer-state HBM
    divide by the axis size — the ZeRO-3 design, spelled as shardings."""
    if shape is None or axis_size <= 1:
        return ps
    entries = list(ps) + [None] * (len(shape) - len(ps))
    used = set()
    for e in entries:
        for a in (e if isinstance(e, tuple) else (e,)):
            if a is not None:
                used.add(a)
    if axis in used:
        return ps  # strategy already spent this axis on the weight
    best = None
    for d, e in enumerate(entries):
        if e is None and shape[d] % axis_size == 0:
            if best is None or shape[d] > shape[best]:
                best = d
    if best is None:
        return ps  # nothing divisible: weight stays as the strategy left it
    entries[best] = axis
    return P(*entries)


def tie_transform(w, tf: str):
    """The single definition of tie transforms (FFModel.tie_weights);
    every params consumer (full-precision and quantized walks) resolves
    through here so a new transform cannot silently diverge."""
    return w.T if tf == "transpose" else w


def resolve_tied_params(model, params, op_name, p, leaf=None):
    """Materialize tied weights (FFModel.tie_weights) for `op_name` from
    their source op's storage. Runs inside the traced step, so autodiff
    accumulates both ops' gradients into the single source array. `leaf`
    optionally maps the raw stored leaf before the transform (the int8
    decode path dequantizes here)."""
    tied = getattr(model, "_tied", None)
    if not tied:
        return p
    out = None
    for (dst_op, dst_w), (src_op, src_w, tf) in tied.items():
        if dst_op != op_name:
            continue
        if out is None:
            out = dict(p)
        w = params[src_op][src_w]
        if leaf is not None:
            w = leaf(w)
        out[dst_w] = tie_transform(w, tf)
    return p if out is None else out


def _spec_rank_ok(spec, ndim) -> bool:
    return len(spec) <= ndim


def _stable_hash(s: str) -> int:
    h = 0
    for ch in s:
        h = (h * 31 + ord(ch)) % (2 ** 31)
    return h
