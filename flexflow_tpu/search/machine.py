"""TPU machine model for the simulator.

Replaces the reference's hardcoded GPU constants (simulator.cu:43-45:
inter-GPU 20 MB/ms, inter-node 12/numNodes, GPU<->DRAM 16) with TPU-class
numbers. Defaults are v5e-ish; override per target. Collective costs use ring
formulas over the mesh axis being reduced (scaling-book recipe) instead of
the reference's flat volume/bw (simulator.cc:548-594).

Two-tier topology (reference: intra-node 1-hop vs inter-node 3-hop transfers,
simulator.cc:252-285): `dcn_axes` maps a mesh axis name to the number of
hosts it spans. A collective over such an axis decomposes hierarchically —
ring over ICI within the host, then ring over DCN across hosts — so a
{data: 8} axis spanning 2 hosts is priced ICI(4) + DCN(2), not ICI(8).
The axis->tier mapping comes from FFConfig.dcn_mesh_shape.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass
class MachineModel:
    # per-chip compute
    peak_flops: float = 197e12  # bf16 MXU FLOP/s (v5e ~197 TFLOPs)
    peak_flops_f32: float = 49e12
    hbm_bw: float = 819e9  # bytes/s
    hbm_bytes: float = 16e9  # capacity per chip
    # interconnect
    # bytes/s per link per direction. Fitted on the four-chip v5e host
    # (PERF.md, PR 36): a 134 MB bf16 all-reduce over one axis of the 2 x 2
    # takes 3.25 ms in the training step's trace, a 268 MB all-gather 6.5 ms
    # alone: 41 GB/s a direction either way (45 is the published rate)
    ici_bw: float = 4.1e10
    dcn_bw: float = 6.25e9  # bytes/s per host
    ici_latency: float = 1e-6  # seconds per hop
    dcn_latency: float = 1e-5  # seconds per hop (host NIC + switch)
    # host<->device (PCIe-class) bandwidth: prices the search's per-op
    # host-offload memory mode (cost_model.mem_mode_time, ISSUE 19)
    host_bw: float = 1.6e10  # bytes/s
    # achievable fraction of peak on real shapes. Fitted: the four-chip
    # step's bf16 matmuls, each with what XLA fused around it, run at 130
    # (ffn_down forward) to 180 TFLOP/s (the head's weight gradient) of 197
    # (PERF.md, PR 36)
    mxu_efficiency: float = 0.7
    # share of a gradient all-reduce's time that nothing hides: XLA:TPU runs
    # an all-reduce synchronously (no -start/-done pair), and in the
    # four-chip step's trace each gradient all-reduce's own time is its
    # whole duration (PERF.md, PR 36); all-gathers run beside the compute,
    # and so is an all-reduce over a DCN axis priced (not measured)
    all_reduce_exposed: float = 1.0
    # mesh axis name -> number of hosts the axis spans (1 = pure ICI)
    dcn_axes: Dict[str, int] = dataclasses.field(default_factory=dict)

    def compute_time(self, flops: float, bytes_moved: float,
                     dtype_bytes: int = 4) -> float:
        """Roofline: max(FLOP time, HBM time)."""
        f = self.peak_flops if dtype_bytes <= 2 else self.peak_flops_f32
        return max(flops / (f * self.mxu_efficiency),
                   bytes_moved / self.hbm_bw)

    # ---- tier decomposition -------------------------------------------------

    def _tiers(self, axis_size: int, axis_name: Optional[str]):
        """(intra_host_degree, cross_host_degree) for one mesh axis."""
        hosts = self.dcn_axes.get(axis_name, 1) if axis_name else 1
        hosts = max(1, min(hosts, axis_size))
        while hosts > 1 and axis_size % hosts != 0:
            hosts -= 1  # degenerate config: clamp to a divisor
        return axis_size // hosts, hosts

    @staticmethod
    def _lanes(size: int) -> int:
        """Directions a ring of `size` chips moves data in at once: two,
        except that a ring of two is ONE link between two neighbours (both
        "directions" of it are the same wire: measured, PERF.md PR 36)."""
        return 2 if size > 2 else 1

    @classmethod
    def _ring(cls, bytes_per_chip: float, size: int, bw: float,
              lat: float) -> float:
        """Bidirectional ring all-reduce over one tier."""
        if size <= 1:
            return 0.0
        return (2.0 * (size - 1) / size * bytes_per_chip
                / (cls._lanes(size) * bw) + size * lat)

    # ---- collectives --------------------------------------------------------

    def all_reduce_time(self, bytes_per_chip: float, axis_size: int,
                        axis_name: Optional[str] = None) -> float:
        """Hierarchical ring all-reduce: ICI within the host, DCN across."""
        if axis_size <= 1:
            return 0.0
        intra, hosts = self._tiers(axis_size, axis_name)
        t = self._ring(bytes_per_chip, intra, self.ici_bw, self.ici_latency)
        t += self._ring(bytes_per_chip, hosts, self.dcn_bw, self.dcn_latency)
        return t

    def all_gather_time(self, bytes_per_chip: float, axis_size: int,
                        axis_name: Optional[str] = None) -> float:
        if axis_size <= 1:
            return 0.0
        intra, hosts = self._tiers(axis_size, axis_name)
        t = 0.0
        if intra > 1:
            t += ((intra - 1) / intra * bytes_per_chip * intra
                  / (self._lanes(intra) * self.ici_bw)
                  + intra * self.ici_latency)
        if hosts > 1:
            # each host gathers the other hosts' (already intra-gathered) parts
            t += ((hosts - 1) / hosts * bytes_per_chip * axis_size / hosts
                  / self.dcn_bw + hosts * self.dcn_latency)
        return t

    def reduce_scatter_time(self, bytes_per_chip: float, axis_size: int,
                            axis_name: Optional[str] = None) -> float:
        """Hierarchical ring reduce-scatter — the bucketed grad-sync
        primitive (FFConfig.overlap_grad_sync) and FSDP's gradient
        collective: the ring's reduce phase without the all-gather return
        trip, so each tier costs half an all-reduce's wire time plus the
        full per-hop latency."""
        if axis_size <= 1:
            return 0.0
        intra, hosts = self._tiers(axis_size, axis_name)
        t = 0.0
        if intra > 1:
            t += ((intra - 1) / intra * bytes_per_chip
                  / (self._lanes(intra) * self.ici_bw)
                  + intra * self.ici_latency)
        if hosts > 1:
            t += ((hosts - 1) / hosts * bytes_per_chip
                  / (self._lanes(hosts) * self.dcn_bw)
                  + hosts * self.dcn_latency)
        return t

    def all_to_all_time(self, bytes_per_chip: float, axis_size: int,
                        axis_name: Optional[str] = None) -> float:
        if axis_size <= 1:
            return 0.0
        intra, hosts = self._tiers(axis_size, axis_name)
        t = 0.0
        if intra > 1:
            # each chip sends (size-1)/size of its shard, both ring dirs
            t += (bytes_per_chip * (intra - 1) / intra
                  / (self._lanes(intra) * self.ici_bw)
                  + intra * self.ici_latency)
        if hosts > 1:
            t += (bytes_per_chip * (hosts - 1) / hosts / self.dcn_bw
                  + hosts * self.dcn_latency)
        return t

    def p2p_time(self, nbytes: float, cross_host: bool = False) -> float:
        if cross_host:
            return nbytes / self.dcn_bw + self.dcn_latency
        return nbytes / self.ici_bw + self.ici_latency
