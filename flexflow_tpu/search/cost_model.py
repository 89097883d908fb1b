"""Analytic strategy cost model.

The Python-side cost oracle: given the op graph and a candidate strategy
(op name -> axis_map over the mesh, plus an optional device-block placement
per op), estimate one training-iteration time. Plays the role of the
reference's Simulator::simulate_runtime (simulator.cc:325-621): per-op
roofline compute cost, resharding cost where producer/consumer shardings
disagree (the reference's region-intersection comm tasks,
simulator.cc:252-285), gradient all-reduce per weight (the reference's
post-hoc NCCL cost, simulator.cc:548-594), an HBM over-capacity penalty
(simulator.cc:595-620), and per-device timelines so op placement is rankable
(simulator.cc:325-621 per-device busy lists).

`iteration_time` is an exact Python mirror of the C++ scheduler in
csrc/sim.cc — the native annealer and this objective must agree (tested in
tests/test_csim.py), so neither can drift silently.

What the prices follow (PERF.md, PR 36: fitted against hand-written
strategies run on the four-chip host): the job's own `compute_dtype`
(element size of activations, edges and gradient syncs, the MXU's peak),
`master_dtype` and optimizer (a weight with its gradient and moments, WHOLE
on every mesh axis that does not shard it); every edge is charged its
reshard forward and the transpose of it backward, and the reductions it
causes by what both of its ends hold (`edge_held_time`, PR 47: a psum is
no part of an op's own time); the optimizer's pass
over the state a chip holds, FSDP's passes over the gathered weights and a
sequence-sharded attention's key/value rotation are part of an op's
compute time; the share of a gradient all-reduce that the backend cannot
hide (`MachineModel.all_reduce_exposed`) holds the compute stream.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from flexflow_tpu.ops.base import InputOp, Op
from flexflow_tpu.search.machine import MachineModel

AxisMap = Dict[str, Optional[int]]

MEM_PENALTY_PER_BYTE = 1e-3 / 1e6  # 1 ms per MB over HBM (simulator.cc:612-617)

# Per-op memory-relief modes the multi-objective search chooses among
# (ISSUE 19): each trades step time for per-chip HBM, priced by
# op_mem_bytes (bytes side) + mem_mode_time (time side). "zero1" and
# "zero3" map onto REAL execution modes (FFConfig.overlap_grad_sync's
# ZeRO-1 sharded optimizer / fsdp_axis ZeRO-3); "remat" re-runs the
# forward in backward instead of stashing activations; "offload" parks
# grads + optimizer state host-side at host_bw streaming cost.
MEM_MODES = ("none", "remat", "zero1", "zero3", "offload")


_DTYPE_BYTES = {"bfloat16": 2, "float32": 4}   # what FFConfig admits


def _optimizer_slots(optimizer) -> int:
    """Moment arrays an optimizer keeps beside each weight: Adam's two, one
    for momentum, none for plain SGD; one where nothing is known yet."""
    optimizer = getattr(optimizer, "inner", optimizer)   # a fused wrapper
    if optimizer is None:
        return 1
    if hasattr(optimizer, "beta2"):
        return 2
    return 1 if getattr(optimizer, "momentum", 1.0) > 0.0 else 0


def _parts(axis_map: AxisMap, mesh_shape: Dict[str, int]) -> int:
    n = 1
    for ax, d in (axis_map or {}).items():
        if d is not None:
            n *= mesh_shape[ax]
    return n


def _shard_degree_on_dim(axis_map: AxisMap, mesh_shape: Dict[str, int],
                         dim: int) -> int:
    n = 1
    for ax, d in (axis_map or {}).items():
        if d == dim:
            n *= mesh_shape[ax]
    return n


def _parts_out(axis_map: AxisMap, mesh_shape: Dict[str, int]) -> int:
    """Partition count of the op's OUTPUT: CONTRACT and STAGE axes shard
    inputs/weights but deliver a replicated output, so they are excluded."""
    n = 1
    for ax, d in (axis_map or {}).items():
        if d is not None and d >= 0:
            n *= mesh_shape[ax]
    return n


def align_place(place: int, ndev: int, num_devices: int) -> int:
    """Mirror of sim.cc align_place: device blocks are GSPMD-expressible
    sub-meshes — ndev must divide the device count and the start must be a
    multiple of ndev, else the block collapses to 0."""
    if ndev <= 0 or ndev >= num_devices or num_devices % ndev != 0:
        return 0
    place = max(0, min(place, num_devices - ndev))
    return place - place % ndev


class CostModel:
    def __init__(self, model, mesh_shape: Dict[str, int],
                 machine: Optional[MachineModel] = None,
                 measured: Optional[Dict] = None,
                 dtype_bytes: Optional[int] = None,
                 fsdp_axis: str = ""):
        self.model = model
        self.mesh_shape = dict(mesh_shape)
        # what the job itself says of its arithmetic: activations, edges and
        # matmuls at `compute_dtype` (bf16: 2 bytes and the MXU's bf16
        # peak), weights, gradients and moments at `master_dtype`, as many
        # moments as the optimizer keeps. An f32 job with no optimizer yet
        # (a cost model built before compile()) reads 4, 4 and one moment:
        # the "weights + grads + opt state (x3)" this model always counted.
        cfg = getattr(model, "config", None)
        if dtype_bytes is None:
            dtype_bytes = _DTYPE_BYTES.get(
                str(getattr(cfg, "compute_dtype", "float32")), 4)
        self.master_bytes = _DTYPE_BYTES.get(
            str(getattr(cfg, "master_dtype", "float32")), 4)
        self.opt_slots = _optimizer_slots(getattr(model, "optimizer", None))
        if machine is None:
            # two-tier topology by default: when the model's config names
            # DCN-spanning axes (FFConfig.dcn_mesh_shape), EVERY cost
            # consumer — the search, csim's tables, the fflint perf pass —
            # prices collectives over those axes at the DCN tier without
            # each caller having to remember to build the machine itself
            dcn = getattr(getattr(model, "config", None),
                          "dcn_mesh_shape", None)
            machine = MachineModel(dcn_axes=dict(dcn)) if dcn \
                else MachineModel()
        self.machine = machine
        self._readers = None    # (len(model.ops), ids of every input tensor)
        self.measured = measured or {}  # (op_name, parts) -> seconds (fwd+bwd)
        self.dtype_bytes = dtype_bytes
        # FSDP (FFConfig.fsdp_axis): weights + opt state further shard over
        # this axis, paying a per-use all-gather — the simulator must see
        # both sides or it will veto memory-feasible FSDP configs (and
        # overrate infeasible non-FSDP ones). Defaulted from the model's
        # config when not given explicitly.
        if fsdp_axis:
            if fsdp_axis not in self.mesh_shape:
                raise ValueError(
                    f"fsdp_axis={fsdp_axis!r} is not a mesh axis "
                    f"(mesh {self.mesh_shape})")
            self.fsdp_axis = fsdp_axis
        else:
            # defaulted from the model config: the config axis may
            # legitimately be absent from a caller-supplied mesh — drop
            cfg_axis = getattr(getattr(model, "config", None),
                               "fsdp_axis", "") or ""
            self.fsdp_axis = cfg_axis if cfg_axis in self.mesh_shape else ""

    def _consumed(self, op: Op) -> bool:
        """Whether an op of the graph reads one of ``op``'s outputs."""
        ops = self.model.ops
        if self._readers is None or self._readers[0] != len(ops):
            self._readers = (len(ops), {id(t) for o in ops for t in o.inputs})
        return any(id(t) in self._readers[1] for t in op.outputs)

    @property
    def num_devices(self) -> int:
        n = 1
        for v in self.mesh_shape.values():
            n *= v
        return n

    # ---- per-op --------------------------------------------------------------

    def op_compute_time(self, op: Op, axis_map: AxisMap) -> float:
        from flexflow_tpu.parallel.pconfig import CONTRACT, EXPERT, STAGE

        parts = _parts(axis_map, self.mesh_shape)
        contract_axes = [ax for ax, d in (axis_map or {}).items()
                         if d == CONTRACT]
        stage_axes = [ax for ax, d in (axis_map or {}).items()
                      if d == STAGE]
        expert_axes = [ax for ax, d in (axis_map or {}).items()
                       if d == EXPERT]
        t = None
        if self.measured:
            # real-device measurement keyed by choice_key — per-shard output
            # shape PLUS the contract degree, which the output shape alone
            # cannot encode (search/measure.py; reference cache
            # simulator.cc:298-303); legacy fallback key: partition count
            from flexflow_tpu.search.measure import choice_key

            key = choice_key(op.name, op.outputs[0].dims, axis_map,
                             self.mesh_shape)
            if key in self.measured:
                t = self.measured[key]
            elif (not contract_axes and not stage_axes
                    and (op.name, parts) in self.measured):
                # the legacy parts-keyed fallback cannot distinguish
                # weight-sharding markers from output sharding — a STAGE
                # choice must not read a data-parallel shard's timing
                t = self.measured[(op.name, parts)]
        if t is None:
            flops = op.flops() / max(parts, 1)
            # inputs/weights are sharded over all axes incl. CONTRACT; the
            # output is psum-replicated over CONTRACT axes, so its bytes
            # divide only by the output partition count
            io_bytes = (sum(t_.volume() for t_ in op.inputs)
                        * self.dtype_bytes / max(parts, 1)
                        + sum(t_.volume() for t_ in op.outputs)
                        * self.dtype_bytes
                        / max(_parts_out(axis_map, self.mesh_shape), 1))
            fwd = self.machine.compute_time(flops, io_bytes, self.dtype_bytes)
            t = 3.0 * fwd  # fwd + ~2x bwd (reference measures both)
        # CONTRACT's psum is priced on the edge that knows what the consumer
        # keeps of it (`edge_held_time`), on top of either cost tier (the
        # measured shard time excludes comm). An output no op of the graph
        # consumes (the head's: the loss reads it) has no such edge and is
        # reduced whole, here.
        if contract_axes and not self._consumed(op):
            out_bytes = (sum(t_.volume() for t_ in op.outputs)
                         * self.dtype_bytes
                         / max(_parts_out(axis_map, self.mesh_shape), 1))
            for ax in contract_axes:
                t += self.machine.all_reduce_time(
                    out_bytes, self.mesh_shape[ax], ax)
        # STAGE (pipeline-parallel) axes: the op's layers shard n ways (the
        # 1/n compute is already in `parts`), but the schedule pays (a) the
        # pipeline bubble — (m + n - 1)/m with m microbatches — and (b) the
        # boundary-activation p2p: one ppermute of a microbatch activation
        # per tick, forward and backward (2x; the 1F1B recompute re-reads
        # stashed inputs locally, no extra hop). Priced on top of either
        # cost tier, like CONTRACT's psum.
        if stage_axes:
            n = 1
            for ax in stage_axes:
                n *= self.mesh_shape[ax]
            # the runtime honors num_microbatches verbatim (pipeline()
            # defaults to n when unset) — so must the bubble price: a
            # clamp would underprice m < n configurations
            m = int(getattr(op, "num_microbatches", 0) or 0) or n
            ticks = m + n - 1
            t *= ticks / m  # bubble stretches the compute timeline
            out_bytes = (sum(t_.volume() for t_ in op.outputs)
                         * self.dtype_bytes
                         / max(_parts_out(axis_map, self.mesh_shape), 1))
            mb_bytes = out_bytes / m
            t += 2.0 * ticks * (mb_bytes / self.machine.ici_bw
                                + self.machine.ici_latency)
        # EXPERT (expert-parallel) axes: experts shard over the axis (the
        # 1/n compute is in `parts`, the weight shards via
        # weight_partition) and tokens move to their experts and back —
        # a dispatch + combine all-to-all in forward, mirrored in
        # backward (4 all-to-alls of the activation volume per axis).
        if expert_axes:
            out_bytes = (sum(t_.volume() for t_ in op.outputs)
                         * self.dtype_bytes
                         / max(_parts_out(axis_map, self.mesh_shape), 1))
            for ax in expert_axes:
                t += 4.0 * self.machine.all_to_all_time(
                    out_bytes, self.mesh_shape[ax], ax)
        return t + self._state_pass_time(op, axis_map) \
            + self._ring_rotation_time(op, axis_map)

    def _state_pass_time(self, op: Op, axis_map: AxisMap) -> float:
        """HBM passes over the op's weights that no matmul's roofline holds
        (on top of either cost tier: a measured shard time has neither).
        The optimizer's update reads weight, moments and gradient and
        writes weight and moments, over what THIS chip holds: priced once
        the model has an optimizer (a cost model built before compile()
        prices none, as before). FSDP writes and reads the gathered weight
        at the compute dtype for the forward pass, again for the backward,
        and the whole gradient once before its reduce-scatter."""
        layout = self._weight_layout(op, axis_map)
        if not layout:
            return 0.0
        nbytes = 0.0
        if getattr(self.model, "optimizer", None) is not None:
            held, _ = self._weight_held(op, axis_map, layout)
            nbytes += held * self.master_bytes * (2 * (1 + self.opt_slots) + 1)
        for elems, sharded_axes, fsdp in layout:
            if fsdp:
                deg = 1
                for ax in sharded_axes:
                    deg *= self.mesh_shape.get(ax, 1)
                nbytes += 3 * 2 * elems / deg * self.dtype_bytes
        return nbytes / self.machine.hbm_bw

    def _ring_rotation_time(self, op: Op, axis_map: AxisMap) -> float:
        """An op that shards a `single_axis_dims` dim (attention's
        sequence) rotates its other inputs (keys, values) around that axis:
        n - 1 hops forward, twice that backward (the blocks and their
        gradients), each of one chip's share of those inputs."""
        t = 0.0
        for dim in op.single_axis_dims():
            for ax, d in (axis_map or {}).items():
                n = self.mesh_shape.get(ax, 1)
                if d != dim or n <= 1:
                    continue
                share = (sum(t_.volume() for t_ in op.inputs[1:])
                         * self.dtype_bytes
                         / max(_parts_out(axis_map, self.mesh_shape), 1))
                t += 3.0 * (n - 1) * self.machine.p2p_time(share)
        return t

    def _weight_layout(self, op: Op, axis_map: AxisMap):
        """[(elements, mesh axes that shard it, fsdp?)] for each weight of
        the op under `axis_map`: `weight_partition` says which axes shard a
        weight, and FSDP adds its axis only where the executor would
        (runtime._with_fsdp degrades an indivisible weight to unsharded,
        which then pays the plain all-reduce and holds its whole state)."""
        specs = op.weight_specs()
        if not specs:
            return []
        try:
            wp = op.weight_partition(axis_map or {})
        except Exception:
            wp = {}
        out = []
        for spec in specs:
            pspec = wp.get(spec.name)
            sharded_axes = set()
            if pspec is not None:
                for entry in pspec:
                    if entry is None:
                        continue
                    for ax in (entry if isinstance(entry, tuple) else (entry,)):
                        sharded_axes.add(ax)
            fsdp = False
            if (self.fsdp_axis and self.fsdp_axis not in sharded_axes
                    and self.mesh_shape[self.fsdp_axis] > 1):
                from flexflow_tpu.runtime.executor import _with_fsdp

                base = pspec or ()
                fsdp = _with_fsdp(base, spec.shape, self.fsdp_axis,
                                  self.mesh_shape[self.fsdp_axis]) is not base
            out.append((int(np.prod(spec.shape)), sharded_axes, fsdp))
        return out

    def op_grad_sync_time(self, op: Op, axis_map: AxisMap) -> float:
        """All-reduce of weight grads over mesh axes that parallelize the op
        but do not shard the weight itself (pure replication axes). Priced
        per axis so DCN-crossing axes get the two-tier cost."""
        return sum(self._grad_sync_parts(op, axis_map))

    def _grad_sync_parts(self, op: Op, axis_map: AxisMap):
        """(reduced, beside) seconds of `op_grad_sync_time`: the
        gradient's all-reduces over ICI axes, and everything else (FSDP's
        reduce-scatter and weight all-gathers, an all-reduce over a DCN
        axis). The schedule treats them differently: this backend runs an
        ICI all-reduce synchronously (`MachineModel.all_reduce_exposed`,
        measured on one host), the others beside the compute; the DCN
        tier was not measured and is priced as it was."""
        reduced = beside = 0.0
        for elems, sharded_axes, fsdp in self._weight_layout(op, axis_map):
            wbytes = elems * self.dtype_bytes
            shard_deg = 1
            for ax in sharded_axes:
                shard_deg *= self.mesh_shape.get(ax, 1)
            for ax, d in (axis_map or {}).items():
                if d is not None and ax not in sharded_axes:
                    if fsdp and ax == self.fsdp_axis:
                        # FSDP: the gradient over this axis reduce-scatters
                        # instead of all-reducing
                        beside += self.machine.reduce_scatter_time(
                            wbytes / shard_deg, self.mesh_shape[ax], ax)
                    else:
                        t = self.machine.all_reduce_time(
                            wbytes / shard_deg, self.mesh_shape[ax], ax)
                        if ax in self.machine.dcn_axes:
                            beside += t
                        else:
                            reduced += t
            if fsdp:
                # per-step weight re-materialization: all-gather the
                # fsdp-sharded weight at use in forward and again for
                # backward (2x); per-chip resident bytes are
                # wbytes / (shard_deg * fsdp_size)
                n = self.mesh_shape[self.fsdp_axis]
                beside += 2.0 * self.machine.all_gather_time(
                    wbytes / shard_deg / n, n, self.fsdp_axis)
        return reduced, beside

    def op_exposed_sync_time(self, op: Op, axis_map: AxisMap) -> float:
        """The part of `op_grad_sync_time` that also holds the chips'
        compute stream after the op: the share of the gradient's ICI
        all-reduces that this backend cannot hide behind other work."""
        return (self.machine.all_reduce_exposed
                * self._grad_sync_parts(op, axis_map)[0])

    def _weight_held(self, op: Op, axis_map: AxisMap, layout=None):
        """(held, scattered): the op's weight ELEMENTS one chip holds under
        `axis_map`, and what it would hold were each weight also split over
        every axis that replicates it (the axes ZeRO shards state over). A
        weight is whole on every axis that does not shard it: a batch or
        sequence axis parallelizes the op and leaves each chip its own copy
        of the weight, its gradient and its moments."""
        held = scattered = 0.0
        if layout is None:
            layout = self._weight_layout(op, axis_map)
        for elems, sharded_axes, fsdp in layout:
            deg = 1
            for ax in sharded_axes:
                deg *= self.mesh_shape.get(ax, 1)
            if fsdp:
                deg *= self.mesh_shape[self.fsdp_axis]
            held += elems / deg
            scattered += elems / self.num_devices
        return held, scattered

    def op_mem_bytes(self, op: Op, axis_map: AxisMap,
                     mem_mode: str = "none") -> float:
        """Per-device HBM bytes under this choice: each weight with its
        gradient and the optimizer's moments at `master_dtype`, WHOLE on
        every mesh axis that does not shard the weight (`_weight_held`;
        `fsdp_axis` counts where the executor applies it), plus the output
        activations at `compute_dtype` over the output's partition.

        ``mem_mode`` (one of MEM_MODES) applies the search-chosen relief:
          remat    — stash ~1/4 of activations, recompute the rest in bwd;
          zero1    — the moments shard over the axes that replicate the
                     weight (overlap_grad_sync's ZeRO-1 update);
          zero3    — weight, gradient and moments all shard over them
                     (fsdp_axis / ZeRO-3);
          offload  — gradient and moments live host-side, streamed per
                     step.
        The relief modes are the search's optimistic pricing, paid for on
        the time side by mem_mode_time."""
        held, scattered = self._weight_held(op, axis_map)
        slots = self.opt_slots
        if mem_mode == "zero1":
            elems = 2.0 * held + slots * scattered
        elif mem_mode == "zero3":
            elems = (2.0 + slots) * scattered
        elif mem_mode == "offload":
            elems = held
        else:
            elems = (2.0 + slots) * held
        act_term = (sum(t.volume() for t in op.outputs) * self.dtype_bytes
                    / max(_parts_out(axis_map, self.mesh_shape), 1))
        if mem_mode == "remat":
            act_term *= 0.25
        return elems * self.master_bytes + act_term

    def mem_mode_time(self, op: Op, axis_map: AxisMap,
                      mem_mode: str = "none") -> float:
        """Step-time overhead the relief mode costs — what the
        multi-objective search trades HBM bytes against.
          remat    — one extra forward: ~1/3 of the fwd+bwd compute time;
          zero1    — params all-gather once per step over the relief axes;
          zero3    — weight all-gather at fwd use + again for bwd, plus
                     the grad reduce-scatter (3 collectives);
          offload  — grads out + updated params back over host_bw."""
        if mem_mode in ("none", "") or mem_mode is None:
            return 0.0
        if mem_mode == "remat":
            return self.op_compute_time(op, axis_map) / 3.0
        held, scattered = self._weight_held(op, axis_map)
        w = held * self.master_bytes
        r = int(round(held / scattered)) if scattered else 1
        if mem_mode == "zero1":
            return self.machine.all_gather_time(w / r, r) if r > 1 else 0.0
        if mem_mode == "zero3":
            if r <= 1:
                return 0.0
            return (2.0 * self.machine.all_gather_time(w / r, r)
                    + self.machine.reduce_scatter_time(w, r))
        if mem_mode == "offload":
            return 2.0 * w / self.machine.host_bw
        return 0.0

    def resharding_time(self, producer_map: AxisMap, consumer_map: AxisMap,
                        tensor) -> float:
        """Cost to move a tensor from its producer's sharding to what the
        consumer constrains. Zero when maps agree per axis. Collectives over
        DCN-crossing axes are priced at the DCN tier."""
        p = {ax: producer_map.get(ax) for ax in self.mesh_shape}
        c = {ax: consumer_map.get(ax) for ax in self.mesh_shape}
        if p == c:
            return 0.0
        tbytes = tensor.volume() * self.dtype_bytes
        per_chip = tbytes / max(_parts(producer_map, self.mesh_shape), 1)
        cost = 0.0
        for ax in self.mesh_shape:
            if p.get(ax) == c.get(ax):
                continue
            size = self.mesh_shape[ax]
            if size <= 1:
                continue
            if p.get(ax) is not None and c.get(ax) is not None:
                cost += self.machine.all_to_all_time(per_chip, size, ax)
            elif p.get(ax) is not None:  # consumer wants it replicated
                cost += self.machine.all_gather_time(per_chip, size, ax)
            else:  # dynamic-slice, nearly free
                cost += self.machine.ici_latency
        return cost

    def edge_time(self, producer_map: AxisMap, consumer_map: AxisMap,
                  tensor) -> float:
        """One edge of a TRAINING step: the tensor's reshard forward and
        its gradient's reshard back, the transpose of the first (an
        all-gather returns as a slice of a whole gradient, a slice as an
        all-gather, an all-to-all as itself). These run beside the compute
        (the schedule's comm stream); the REDUCTIONS an edge causes are
        `edge_held_time`'s."""
        return (self.resharding_time(producer_map, consumer_map, tensor)
                + self.resharding_time(consumer_map, producer_map, tensor))

    def edge_held_time(self, src_op: Op, src_map: AxisMap, dst_op: Op,
                       dst_map: AxisMap, input_idx: int, tensor) -> float:
        """The reductions one edge of a training step causes, priced by
        what BOTH ends hold (the ops' raw maps: `output_axis_map` has
        forgotten a CONTRACT). This backend runs them synchronously (no
        `-start` / `-done` pair: PERF.md, PR 36 and PR 47), so the schedule
        lets them hold the compute streams of both ends; a tensor pays them
        once however many consumers it has (the largest of its edges).

        Forward, a producer whose output is a partial sum over an axis
        (`Op.partial_sum_axes`: a CONTRACT matmul, a head-split
        attention's output projection): a consumer that shards the axis on
        the dim the matmul produced (the feature dim) takes a
        reduce-scatter (its gradient's all-gather back is `edge_time`'s
        transpose of the slice), any other an all-reduce (and a free
        slice, if it keeps one: on the chip a slice of the BATCH dim is an
        all-reduce and a slice, PERF.md PR 47's control). Backward, a
        consumer whose PARAMETER dim is sharded over an axis (a
        column-parallel Linear, a head-split attention) computes its
        input's gradient as a partial sum over that axis: a producer that
        shards the axis on the dim the consumer contracts takes a
        reduce-scatter (the transpose of the forward all-gather), any
        other an all-reduce. A Megatron pair pays one all-reduce each way,
        as it did when both were booked to the row-parallel op; a
        parameter-sharded head with no such partner pays its own."""
        src_map, dst_map = src_map or {}, dst_map or {}
        pam = src_op.output_axis_map(src_map)
        try:
            want = dst_op.input_axis_map(dst_map, input_idx)
        except Exception:
            want = dst_map
        tbytes = tensor.volume() * self.dtype_bytes

        def reduce(nbytes, ax, scatter):
            f = (self.machine.reduce_scatter_time if scatter
                 else self.machine.all_reduce_time)
            return f(nbytes, self.mesh_shape[ax], ax)

        t = 0.0
        psum_axes = [ax for ax in src_op.partial_sum_axes(src_map)
                     if self.mesh_shape.get(ax, 1) > 1]
        if psum_axes:
            # one chip's term of the sum: the tensor over the axes that
            # shard it apart from the ones being summed over
            partial = tbytes / max(_parts(
                {ax: d for ax, d in pam.items() if ax not in psum_axes},
                self.mesh_shape), 1)
            made = {d % tensor.num_dims
                    for d in src_op._contracted_output_dims}
            t += sum(reduce(partial, ax, want.get(ax) in made)
                     for ax in psum_axes)
        if not np.issubdtype(np.dtype(tensor.np_dtype()), np.floating):
            return t          # an index tensor has no gradient to reduce
        nd_out = dst_op.outputs[0].num_dims
        produced = {d % nd_out for d in dst_op._contracted_output_dims}
        contracted = dst_op.contract_input_dim(input_idx)
        if contracted is None:       # an attention's projections: the last
            contracted = tensor.num_dims - 1
        partial = tbytes / max(_parts(want, self.mesh_shape), 1)
        for ax, d in dst_map.items():
            if (d in produced and want.get(ax) is None
                    and self.mesh_shape.get(ax, 1) > 1):
                t += reduce(partial, ax, pam.get(ax) == contracted)
        return t

    # ---- whole strategy ------------------------------------------------------

    def iteration_time(self, strategy: Dict[str, AxisMap],
                       places: Optional[Dict[str, int]] = None) -> float:
        """Estimated seconds per training iteration under `strategy` (+
        optional per-op device-block placement). Exact Python mirror of the
        C++ per-device list schedule (csrc/sim.cc schedule())."""
        D = self.num_devices
        dev_compute = [0.0] * D
        dev_comm = [0.0] * D
        # grad all-reduce rides its own per-device stream: XLA's latency
        # hiding overlaps grad sync with backward compute, and the reference
        # prices NCCL post-hoc (simulator.cc:548-594) — never interleaved
        # with forward resharding traffic
        dev_sync = [0.0] * D
        dev_mem = [0.0] * D
        finish: Dict[str, float] = {}
        blocks: Dict[str, tuple] = {}
        paid: Dict[int, float] = {}   # per tensor: reductions already paid

        def block_of(op, am):
            ndev = max(1, min(_parts(am, self.mesh_shape), D))
            place = align_place((places or {}).get(op.name, 0), ndev, D)
            return place, ndev

        for op in self.model.ops:
            if isinstance(op, InputOp):
                continue
            am = strategy.get(op.name, {})
            pi, ni = block_of(op, am)
            blocks[op.name] = (pi, ni)
            ready = 0.0
            for input_idx, t in enumerate(op.inputs):
                if t.owner_op is None or isinstance(t.owner_op, InputOp):
                    continue
                src = t.owner_op.name
                # consumers see the producer's OUTPUT sharding: CONTRACT
                # axes deliver psum-replicated outputs
                pam = t.owner_op.output_axis_map(strategy.get(src, {}))
                try:
                    want = op.input_axis_map(am, input_idx)
                except Exception:
                    want = am
                c = self.edge_time(pam, want, t)
                ps, ns = blocks.get(src, (0, D))
                if ps != pi:
                    c += (t.volume() * self.dtype_bytes / max(ns, 1)
                          / self.machine.ici_bw) + self.machine.ici_latency
                arrive = finish.get(src, 0.0)
                if c > 0.0:
                    start = arrive
                    for d in range(ps, ps + ns):
                        start = max(start, dev_comm[d])
                    for d in range(pi, pi + ni):
                        start = max(start, dev_comm[d])
                    arrive = start + c
                    for d in range(ps, ps + ns):
                        dev_comm[d] = arrive
                    for d in range(pi, pi + ni):
                        dev_comm[d] = arrive
                # the edge's reductions: what this tensor has not paid on
                # an earlier edge, where nothing else computes on either
                # block
                h = self.edge_held_time(t.owner_op, strategy.get(src, {}),
                                        op, am, input_idx, t) \
                    - paid.get(id(t), 0.0)
                if h > 0.0:
                    paid[id(t)] = paid.get(id(t), 0.0) + h
                    start = arrive
                    for d in range(ps, ps + ns):
                        start = max(start, dev_compute[d])
                    for d in range(pi, pi + ni):
                        start = max(start, dev_compute[d])
                    arrive = start + h
                    for d in range(ps, ps + ns):
                        dev_compute[d] = arrive
                    for d in range(pi, pi + ni):
                        dev_compute[d] = arrive
                ready = max(ready, arrive)
            start = ready
            for d in range(pi, pi + ni):
                start = max(start, dev_compute[d])
            end = start + self.op_compute_time(op, am)
            held = end + self.op_exposed_sync_time(op, am)
            for d in range(pi, pi + ni):
                dev_compute[d] = held
            finish[op.name] = end
            sync = self.op_grad_sync_time(op, am)
            if sync > 0.0:
                cstart = end
                for d in range(pi, pi + ni):
                    cstart = max(cstart, dev_sync[d])
                for d in range(pi, pi + ni):
                    dev_sync[d] = cstart + sync
            m = self.op_mem_bytes(op, am)
            for d in range(pi, pi + ni):
                dev_mem[d] += m

        total = max(max(dev_compute), max(dev_comm), max(dev_sync)) \
            if D else 0.0
        for d in range(D):
            over = dev_mem[d] - self.machine.hbm_bytes
            if over > 0.0:
                total += over * MEM_PENALTY_PER_BYTE
        return total
