"""North-star acceptance (BASELINE.md rebuild targets): the MCMC-discovered
strategy must beat pure data parallelism by >=1.5x on the reference workload
configs, simulated on a v5e-32 (4 hosts x 8 chips, two-tier ICI/DCN).

The reference's own acceptance is the same experiment on its simulator: the
search objective is simulated per-iteration runtime (model.cc:1687-1690),
and the SysML'19 headline is the discovered-strategy speedup over DP. These
tests run the full pipeline — graph build, cost tables, native C++ annealer,
per-device timelines — at the reference's default configs (batch 64,
model.cc:1917-1938; DLRM per run_summit.sh).
"""

import dataclasses
import os
import sys

import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.model import FFModel
from flexflow_tpu.models.cnn import inception_v3, resnet50
from flexflow_tpu.models.dlrm import dlrm
from flexflow_tpu.models.transformer import (TransformerConfig,
                                             build_reference_transformer)
from flexflow_tpu.ops.base import InputOp
from flexflow_tpu.parallel.pconfig import CONTRACT, STAGE
from flexflow_tpu.search.cost_model import CostModel
from flexflow_tpu.search.csim import get_search_problem
from flexflow_tpu.search.machine import MachineModel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOSTS = 4
CHIPS_PER_HOST = 8  # v5e-32: 4 hosts x 8 chips


def full_dp_strategy(model, mesh_shape):
    """Pure data parallelism over EVERY mesh axis (the honest DP-32
    baseline): each axis shards the sample dim where divisible."""
    out = {}
    for op in model.ops:
        if isinstance(op, InputOp):
            continue
        am, deg = {}, 1
        dims = op.outputs[0].dims
        for ax, size in mesh_shape.items():
            if size > 1 and dims and dims[0] % (deg * size) == 0 \
                    and 0 in op.partitionable_output_dims():
                am[ax] = 0
                deg *= size
        out[op.name] = am
    return out


def build_workload(name, batch=None):
    """(model, mesh_shape) at the reference's own default configs (batch
    64, model.cc:1917-1938): the regime the reference's search targets,
    where pure DP is gradient-sync-bound."""
    mesh = {"data": HOSTS, "model": CHIPS_PER_HOST}
    if name == "llama8b":
        # the 8B decoder shape (hidden 4096, 32 layers, 32 heads / 8 kv,
        # ffn 14336, vocab 128256) on a simulated 64-chip two-tier pod; 16
        # x 4096 tokens is the regime where pure DP both exceeds HBM and
        # cannot shard 64 ways
        from flexflow_tpu.models.llama import llama_lm

        mesh = {"data": 8, "model": 8}
        cfg = FFConfig(batch_size=batch or 16, mesh_shape=mesh)
        ff = FFModel(cfg)
        llama_lm(ff, cfg.batch_size, seq_len=4096, hidden=4096, layers=32,
                 heads=32, kv_heads=8, ffn_hidden=14336, vocab_size=128_256)
        return ff, mesh
    if name == "dlrm":
        # reference run_summit.sh: 512 samples/device, 1M-row x 64-dim
        # tables, mlp-bot 64-512-512-64, mlp-top 576-1024-1024-1024-1
        cfg = FFConfig(batch_size=512 * 32, mesh_shape=mesh)
        ff = FFModel(cfg)
        dlrm(ff, cfg.batch_size, embedding_size=64,
             embedding_entries=1_000_000, num_tables=8,
             mlp_bot=(512, 512, 64), mlp_top=(1024, 1024, 1024, 1))
        return ff, mesh
    cfg = FFConfig(batch_size=batch or 64, mesh_shape=mesh)
    ff = FFModel(cfg)
    if name == "transformer":
        # reference examples/cpp/Transformer defaults (hidden 512, 16
        # heads, 12 layers, seq 128)
        build_reference_transformer(ff, cfg.batch_size, TransformerConfig())
    elif name == "bert_fx":
        # the BERT-base-shaped torch encoder through the FX frontend
        pt_examples = os.path.join(REPO, "examples", "pytorch")
        if pt_examples not in sys.path:
            sys.path.append(pt_examples)  # append: don't shadow stdlib/pkgs
        from bert_fx import BertEncoder

        from flexflow_tpu.torch import PyTorchModel

        x = ff.create_tensor([cfg.batch_size, 128, 768], name="x")
        PyTorchModel(model=BertEncoder(hidden=768, heads=12, layers=12,
                                       seq=128, classes=2)).apply(ff, [x])
    elif name == "resnet50":
        resnet50(ff, cfg.batch_size)
    elif name == "inception":
        inception_v3(ff, cfg.batch_size, num_classes=1000)
    else:
        raise ValueError(f"unknown workload {name!r}")
    return ff, mesh


def run_one(name, budget, seed=0, batch=None):
    """The full search pipeline (graph build, analytic cost tables, native
    annealer, per-device timelines, two-tier ICI/DCN machine model) on a
    simulated pod: the best-found strategy's simulated iteration time
    against pure DP's, and what the winner is made of."""
    ff, mesh = build_workload(name, batch)
    if name == "llama8b":
        # an 8B can't replicate weights per chip: price under ZeRO-3
        ff.config.fsdp_axis = "data"
        machine = MachineModel(dcn_axes={"data": mesh["data"]})
        machine_desc = "simulated 64-chip pod (8 hosts x 8 chips, ICI+DCN)"
    else:
        # ICI within each 8-chip host slice, DCN across the 4 hosts
        machine = MachineModel(dcn_axes={"data": HOSTS})
        machine_desc = "simulated v5e-32 (4 hosts x 8 chips, ICI+DCN)"
    # dtype_bytes=2: strategies are priced at bf16 compute + activations
    cost = CostModel(ff, mesh, machine=machine, dtype_bytes=2)
    prob = get_search_problem(ff, cost, mesh)
    dp_map = full_dp_strategy(ff, mesh)
    dp_choices = prob.choices_for(dp_map)
    dp_cost = prob.simulate(dp_choices)
    # memory honesty: when pure DP does not FIT per-chip HBM its simulated
    # time is dominated by the over-capacity penalty (simulator.cc:595-620):
    # a second DP number on an infinite-HBM machine lets the speedup be
    # read as feasibility + time, not conflated
    dp_mem = sum(cost.op_mem_bytes(op, dp_map.get(op.name, {}))
                 for op in ff.ops if not isinstance(op, InputOp))
    dp_fits = dp_mem <= machine.hbm_bytes
    dp_nopenalty_cost = None
    if not dp_fits:
        cost_inf = CostModel(
            ff, mesh, machine=dataclasses.replace(machine, hbm_bytes=1e18),
            dtype_bytes=2)
        dp_nopenalty_cost = cost_inf.iteration_time(dp_map)
    # FSDP pricing disables placement proposals (csim.native semantics)
    best_c, best_p, best_cost = prob.mcmc(dp_choices, budget, 0.05, seed,
                                          restarts=4,
                                          allow_place=not cost.fsdp_axis)
    # which PARALLELISM KINDS the winner uses per mesh axis (dp = sample
    # dim, tp = non-sample output dim, contract = row-parallel weight
    # shard, stage = pipeline)
    n_tp = n_placed = 0
    axes_used = {}
    for i in range(len(prob.ops)):
        am = prob.op_maps[i][int(best_c[i])]
        if any(d is not None and d != 0 for d in am.values()):
            n_tp += 1
        if int(best_p[i]) != 0:
            n_placed += 1
        for ax, d in am.items():
            if d is None:
                continue
            kind = ("dp" if d == 0 else "contract" if d == CONTRACT
                    else "stage" if d == STAGE else "tp")
            axes_used.setdefault(ax, set()).add(kind)
    # per-chip bytes of the winner: exact only when no op is placed on a
    # proper device block (blocks don't co-reside)
    best_mem = (sum(cost.op_mem_bytes(op, prob.op_maps[i][int(best_c[i])])
                    for i, op in enumerate(prob.ops))
                if n_placed == 0 else None)
    return {
        "machine": machine_desc,
        "speedup_vs_dp": round(dp_cost / max(best_cost, 1e-12), 3),
        "ops_with_model_parallel_dims": n_tp,
        "ops_placed_off_block0": n_placed,
        "axes_used": {k: sorted(v) for k, v in axes_used.items()},
        "dp_mem_gb_per_chip": round(dp_mem / 1e9, 1),
        "best_mem_gb_per_chip": (round(best_mem / 1e9, 1)
                                 if best_mem is not None else None),
        "hbm_gb_per_chip": round(machine.hbm_bytes / 1e9, 1),
        "dp_fits_hbm": dp_fits,
        "speedup_vs_dp_nopenalty": (
            round(dp_nopenalty_cost / max(best_cost, 1e-12), 3)
            if dp_nopenalty_cost is not None else None),
    }


BUDGET = 60_000


@pytest.mark.parametrize("workload,min_speedup", [
    ("transformer", 1.5),
    ("bert_fx", 1.5),  # BASELINE names "BERT-base via FX import" explicitly
    ("resnet50", 1.5),
    ("inception", 1.5),
    ("dlrm", 10.0),  # embedding-partitioned hybrid crushes DP (OOM + sync)
])
def test_search_beats_dp_on_reference_config(workload, min_speedup):
    r = run_one(workload, BUDGET, seed=0)
    assert r["speedup_vs_dp"] >= min_speedup, r
    # the win must come from real strategy structure, not noise
    assert r["ops_with_model_parallel_dims"] > 0 or \
        r["ops_placed_off_block0"] > 0, r


def test_large_batch_regime_is_honest():
    """At 16 samples/chip the transformer is activation-dominated and DP is
    near-optimal — the search must still never be WORSE than DP, and the
    simulator should honestly show the win shrinking."""
    r = run_one("transformer", 20_000, seed=0, batch=16 * 32)
    assert 1.0 <= r["speedup_vs_dp"] < 1.5, r


@pytest.mark.slow  # 13 s 64-chip scale variant; smaller search tests stay tier-1
def test_llama8b_64chip_search_combines_parallelism_axes():
    """VERDICT r4 #7, the scale-shaped joint search: the REAL Llama-8B
    shape (hidden 4096, 32 layers, GQA 32/8, ffn 14336, vocab 128k) over
    a simulated 64-chip two-tier pod (8 hosts x 8 chips). Pure DP cannot
    hold replicated 8B weights per chip (reported infeasible) and cannot
    shard batch 16 across 64 devices; the MCMC winner must COMBINE at
    least two distinct parallelism axes — TP over the ICI 'model' axis
    with DP+FSDP over the DCN 'data' axis — and beat even a
    penalty-free DP on simulated time."""
    r = run_one("llama8b", 20_000, seed=0)
    assert r["machine"].startswith("simulated 64-chip pod"), r
    # DP is memory-infeasible at this scale and the row says so
    assert not r["dp_fits_hbm"], r
    assert r["dp_mem_gb_per_chip"] > r["hbm_gb_per_chip"], r
    # the winner fits
    assert r["best_mem_gb_per_chip"] <= r["hbm_gb_per_chip"], r
    # >= 2 distinct mesh axes carry parallelism, with model-parallel
    # structure on the ICI axis and data/fsdp structure on the DCN axis
    used = r["axes_used"]
    assert len(used) >= 2, r
    assert "tp" in used.get("model", []) or \
        "contract" in used.get("model", []), r
    # search-CHOSEN sample sharding on the DCN axis ('fsdp' alone would be
    # config-imposed pricing, not a discovered combination)
    assert "dp" in used.get("data", []), r
    assert r["ops_with_model_parallel_dims"] > 100, r
    # and the time win is real even granting DP infinite memory
    assert r["speedup_vs_dp_nopenalty"] >= 1.5, r
