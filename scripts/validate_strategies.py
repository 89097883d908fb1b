#!/usr/bin/env python
"""Whole-program validation of search winners (SURVEY §7 hard-part 5 /
VERDICT r2 #5): after the MCMC, run the top candidate strategies AND pure
data parallelism as REAL short whole-program training runs on the attached
backend, and report simulated-vs-real rank agreement.

Design notes:
  * Whole-program only — per-dispatch latency swamps per-op timings of
    small ops, but an N-step jitted training loop amortizes dispatch into
    one number.
  * The simulator side uses costs MEASURED on the same backend the real
    runs execute on (costs=measure), so both columns describe the same
    machine. On the 8-device virtual CPU mesh this validates the
    simulator's composition (do measured per-op costs + the comm model
    compose into correct whole-program rankings?); on a TPU slice it
    validates the production stack end to end.
  * Candidates: DP, the full-budget MCMC winner, and small-budget /
    different-seed runs (distinct local optima), deduplicated.

Usage:
  FLEXFLOW_FORCE_CPU_DEVICES=8 python scripts/validate_strategies.py \
      [--budget 4000] [--steps 10] [--seq 64] [--hidden 128] [--layers 2]

Single-chip leg (--single-chip): a 1-device attachment cannot run the
8-device candidate strategies for real, so ranking *strategies* is not
measurable there. What IS measurable — and is the half of the validation
the CPU mesh can never give — is calibration of the measured-cost
pipeline against the real machine: measure per-op costs on the chip,
compose them through the full simulator (same CostModel/csim path the
search uses), and compare the predicted whole-program step time against
a real jitted training run, across several model shapes. Reports
per-shape sim/real ratio and rank agreement (does the simulator order
model shapes by real cost?). Together the two legs cover SURVEY §7 hard
part 5: CPU mesh = multi-device ranking; chip = per-op measurement
fidelity on the machine that matters.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

MESH = {"data": 4, "model": 2}


def build(args, strategies=None, mesh=None):
    from flexflow_tpu import (FFConfig, FFModel, LossType, MetricsType,
                              SGDOptimizer, SingleDataLoader)
    from flexflow_tpu.models.transformer import build_encoder_classifier

    batch = args.batch
    cfg = FFConfig(batch_size=batch, mesh_shape=dict(mesh or MESH), seed=5)
    if strategies:
        cfg.strategies.update(strategies)
    ff = FFModel(cfg)
    x, out = build_encoder_classifier(ff, batch, args.seq, args.hidden,
                                      args.layers, 4)
    ff.compile(SGDOptimizer(lr=0.01),
               LossType.LOSS_SPARSE_CATEGORICAL_CROSSENTROPY,
               [MetricsType.METRICS_ACCURACY], final_tensor=out)
    rs = np.random.RandomState(0)
    SingleDataLoader(ff, x, rs.randn(batch * 2, args.seq, args.hidden)
                     .astype(np.float32))
    SingleDataLoader(ff, ff.label_tensor,
                     rs.randint(0, 16, (batch * 2, 1)).astype(np.int32))
    return ff


def real_time_s(ff, steps: int, scan: bool = False) -> float:
    """Best-of-3 whole-program step time (fetch-synced).
    scan=True runs the steps as ONE lax.scan device program — the
    dispatch-free number, required where per-step host dispatch would
    otherwise dominate small models (the simulator prices compute, not
    dispatch latency)."""
    if scan:
        from flexflow_tpu.search.measure import _dispatch_floor

        losses, _ = ff.train_scanned(steps)  # compile + warmup
        float(losses[-1])
        floor = _dispatch_floor()  # sampled in the same drift window
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            losses, _ = ff.train_scanned(steps)
            float(losses[-1])
            best = min(best, (time.perf_counter() - t0 - floor) / steps)
        return max(best, 1e-9)
    ff._run_train_step(ff._stage_batch())  # compile + warmup
    ff._run_train_step(ff._stage_batch())
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss, _ = ff._run_train_step(ff._stage_batch())
        float(loss)
        best = min(best, (time.perf_counter() - t0) / steps)
    return best


def lint_strategy(ff, strategies, label: str,
                  mesh: dict = None) -> bool:
    """fflint gate (flexflow_tpu/analysis): statically validate a candidate
    before spending real device time on it — a broken candidate is named
    here in milliseconds instead of hanging a collective rendezvous.
    Returns False (candidate must be skipped) on error-severity findings."""
    from flexflow_tpu.analysis import analyze

    report = analyze(ff, strategies=strategies, mesh_shape=mesh or MESH)
    if report.errors():
        print(f"[validate] {label}: fflint REJECTED the candidate:")
        for v in report.errors():
            print(f"[validate]   {v}")
        return False
    if report.warnings():
        for v in report.warnings():
            print(f"[validate] {label}: {v}")
    print(f"[validate] {label}: fflint clean "
          f"({len(report.notes())} note(s))")
    return True


def kendall_tau(a, b) -> float:
    n = len(a)
    conc = disc = 0
    for i in range(n):
        for j in range(i + 1, n):
            s = (a[i] - a[j]) * (b[i] - b[j])
            conc += s > 0
            disc += s < 0
    denom = conc + disc
    return (conc - disc) / denom if denom else 1.0


# (batch, seq, hidden, layers) ladder for the single-chip calibration:
# distinct FLOP scales so rank agreement is meaningful, small enough that
# each compiles in seconds
CALIB_CONFIGS = [
    (16, 128, 256, 2),
    (16, 256, 512, 2),
    (16, 256, 512, 4),
    (8, 512, 1024, 4),
]
if os.environ.get("FF_VALIDATE_TINY"):  # CPU smoke of the script itself
    CALIB_CONFIGS = [(4, 16, 32, 1), (4, 32, 64, 1), (4, 32, 64, 2)]


def single_chip_calibration(args):
    import math

    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.csim import get_search_problem
    from flexflow_tpu.search.driver import data_parallel_strategy
    from flexflow_tpu.search.measure import measure_op_costs

    mesh = {"data": 1}
    rows = []
    for batch, seq, hidden, layers in CALIB_CONFIGS:
        c = argparse.Namespace(**{**vars(args), "batch": batch, "seq": seq,
                                  "hidden": hidden, "layers": layers})
        ff = build(c, mesh=mesh)
        if not rows:  # same default-DP table for every shape: lint once
            lint_strategy(ff, {}, "dp", mesh=mesh)
        print(f"[validate/chip] b{batch} s{seq} h{hidden} L{layers}: "
              f"measuring...", flush=True)
        measured = measure_op_costs(ff, mesh)
        cost = CostModel(ff, mesh, measured=measured)
        prob = get_search_problem(ff, cost, mesh)
        sim_s = prob.simulate(
            prob.choices_for(data_parallel_strategy(ff, mesh)))
        real_s = real_time_s(ff, args.steps, scan=True)
        rows.append({"batch": batch, "seq": seq, "hidden": hidden,
                     "layers": layers, "sim_ms": round(sim_s * 1e3, 3),
                     "real_ms": round(real_s * 1e3, 3),
                     "real_over_sim": round(real_s / max(sim_s, 1e-12), 3),
                     "_sim": sim_s, "_real": real_s})
        print(f"[validate/chip]   sim {rows[-1]['sim_ms']} ms, "
              f"real {rows[-1]['real_ms']} ms", flush=True)
    # stats from UNROUNDED values: 3-dp rounding can collapse a deep sim
    # undershoot to 0.0 — log(0) would discard the run, and zero-ties would
    # make kendall_tau report perfect agreement with no ordering information
    sims = [r.pop("_sim") for r in rows]
    reals = [r.pop("_real") for r in rows]
    ratios = [rl / max(s, 1e-12) for s, rl in zip(sims, reals)]
    result = {
        "mode": "single_chip_calibration",
        "rows": rows,
        "kendall_tau": round(kendall_tau(sims, reals), 3),
        # geometric stats: the simulator is a *ranker* (reference tolerance,
        # SURVEY §7 hard part 5) so spread matters more than absolute level
        "ratio_geomean": round(
            math.exp(sum(math.log(x) for x in ratios) / len(ratios)), 3),
        "ratio_spread": round(max(ratios) / min(ratios), 3),
        "backend": _backend(),
        "config": vars(args),
    }
    print(json.dumps(result), flush=True)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=4000)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--single-chip", action="store_true",
                    help="1-device calibration leg (see module docstring)")
    args = ap.parse_args()
    if args.single_chip:
        return single_chip_calibration(args)

    from flexflow_tpu.search.cost_model import CostModel
    from flexflow_tpu.search.csim import get_search_problem, native_optimize
    from flexflow_tpu.search.driver import data_parallel_strategy
    from flexflow_tpu.search.measure import measure_op_costs

    ff = build(args)
    print("[validate] measuring op costs on the attached backend...",
          flush=True)
    measured = measure_op_costs(ff, MESH)
    cost = CostModel(ff, MESH, measured=measured)
    prob = get_search_problem(ff, cost, MESH)

    candidates = {"dp": data_parallel_strategy(ff, MESH)}
    for label, (budget, seed) in {
            "mcmc_full": (args.budget, 1),
            "mcmc_alt1": (max(args.budget // 20, 50), 2),
            "mcmc_alt2": (max(args.budget // 50, 20), 3)}.items():
        found = native_optimize(ff, cost, MESH, budget=budget, alpha=0.05,
                                seed=seed)
        candidates[label] = {n: pc.axis_map for n, pc in found.items()}

    # dedup identical strategies (alternates often converge)
    rows = []
    seen = {}
    for label, strat in candidates.items():
        key = tuple(prob.choices_for(strat).tolist())
        if key in seen:
            print(f"[validate] {label} duplicates {seen[key]}; skipped")
            continue
        seen[key] = label
        sim_s = prob.simulate(prob.choices_for(strat))
        pcs = {n: _to_pc(ff, n, am, MESH) for n, am in strat.items()}
        if not lint_strategy(ff, pcs, label):
            continue
        print(f"[validate] {label}: simulated {sim_s * 1e3:.3f} ms; "
              f"running {args.steps} real steps x3...", flush=True)
        ff_c = build(args, strategies=pcs)
        real_s = real_time_s(ff_c, args.steps)
        rows.append({"strategy": label, "sim_ms": round(sim_s * 1e3, 3),
                     "real_ms": round(real_s * 1e3, 3)})

    sims = [r["sim_ms"] for r in rows]
    reals = [r["real_ms"] for r in rows]
    tau = kendall_tau(sims, reals)
    sim_winner = rows[int(np.argmin(sims))]["strategy"]
    real_winner = rows[int(np.argmin(reals))]["strategy"]
    result = {
        "rows": rows,
        "kendall_tau": round(tau, 3),
        "sim_winner": sim_winner,
        "real_winner": real_winner,
        "winner_agrees": sim_winner == real_winner,
        "backend": _backend(),
        "config": vars(args),
    }
    print(json.dumps(result), flush=True)
    return 0


def _to_pc(ff, name, axis_map, mesh):
    from flexflow_tpu.parallel.pconfig import ParallelConfig

    op = next(o for o in ff.ops if o.name == name)
    return ParallelConfig.from_axis_map(op.outputs[0].num_dims, mesh,
                                        axis_map)


def _backend():
    import jax

    return jax.default_backend()


if __name__ == "__main__":
    sys.exit(main())
