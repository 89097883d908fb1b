#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: several offered rates on ONE
warm engine in one process, the prefix cache flushed between them.

    python3 benchmark/knee_sweep.py --workload <cell> --rates 3,5,7,9 \
        --seconds 20 [--probe-seeds 3]

Not part of a benchmark run: the cell's traffic file records the sweep, the
knee and the fixed rate as numbers, and `run.py` offers that rate and never
searches for one. The knee is the highest rate at which the queue does not
grow over the window and at least the traffic file's `limits.share` of the
requests offered meet both of its limits (time to first token from when the
request was due, and time per output token).

With `--probe-seeds n` the sweep then offers `--probe-factor` x the knee it
found under both arrival processes on n seeds each, and prints how far the
end-to-end metrics spread: the choice between them is made from that.

Every line it prints is a JSON object; the file chiprun_out/knee_<cell>.jsonl
holds them too.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def one_window(h, eng, gen, traffic, seed, seconds):
    """Offer one window of `traffic` and reduce it."""
    from benchmark import stats

    eng.flush_prefix_cache()
    sched = gen.generate(traffic, seed, seconds, h.vocab)
    st0 = eng.stats()
    records, late, t_end = gen.drive(
        eng, sched, seconds, float(traffic["drain_grace_s"]), h.annotate)
    st1 = eng.stats()
    e2e = gen.latency_metrics(records, seconds)
    lim = traffic["limits"]
    done = sorted((r for r in records if r["state"] == "done"),
                  key=lambda r: r["due"])
    third = max(1, len(done) // 3)

    def med_ttft(rs):
        return stats.median([r["t_first"] - r["due"] for r in rs]) \
            if rs else None

    row = {
        "rate_per_s": traffic["rate_per_s"], "arrivals": traffic["arrivals"],
        "seed": seed, "seconds": seconds, **e2e,
        "attainment": gen.attainment(records, lim["ttft_s"], lim["tpot_s"]),
        "ttft_p50_first_third_s": med_ttft(done[:third]),
        "ttft_p50_last_third_s": med_ttft(done[-third:]),
        "drain_s": t_end - seconds,
        "decode_occupancy": (st1["occupied_slot_steps"]
                             - st0["occupied_slot_steps"])
        / max(1, (st1["decode_steps"] - st0["decode_steps"]) * eng.slots),
        "recompiles": st1["recompiles"] - st0["recompiles"],
        "late_median_ms": late["median_s"] * 1e3,
        "late_max_ms": late["max_s"] * 1e3,
    }
    first, last = row["ttft_p50_first_third_s"], row["ttft_p50_last_third_s"]
    row["queue_grows"] = bool(
        e2e["failed"] or (last is not None and last > 2.0 * first
                          and last > 0.25 * lim["ttft_s"]))
    row["sustained"] = bool(not row["queue_grows"]
                            and row["attainment"] >= lim["share"])
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--probe-seeds", type=int, default=0)
    ap.add_argument("--probe-factor", type=float, default=0.7)
    ap.add_argument("--probe-seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run
    from benchmark import spec, stats

    if jax.devices()[0].platform != "tpu":
        print("knee_sweep: not a TPU - a knee is a device quantity",
              file=sys.stderr)
        return 2
    bench_run.place_compile_cache()
    h = bench_run.load_cell(spec.load_benchmark(ROOT), args.workload,
                            seconds=args.seconds)
    traffic = h.traffic
    gen = spec.load_module("generators", traffic["kind"])
    ff, eng = gen.build_engine(h)
    gen.warm(h, eng, traffic)
    h.log(f"set-up {time.perf_counter() - bench_run.T_PROCESS:.1f} s")

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            f"knee_{args.workload}.jsonl"), "a")

    def emit(row):
        line = json.dumps(row)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        row = one_window(h, eng, gen, {**traffic, "rate_per_s": rate}, 0,
                         args.seconds)
        emit({"phase": "sweep", **row})
        if row["sustained"]:
            knee = max(knee or 0.0, rate)
    emit({"phase": "knee", "knee_per_s": knee})
    if knee and args.probe_seeds:
        rate = round(args.probe_factor * knee, 2)
        for arrivals in ("poisson", "jittered"):
            rows = []
            for seed in range(1, args.probe_seeds + 1):
                rows.append(one_window(
                    h, eng, gen, {**traffic, "rate_per_s": rate,
                                  "arrivals": arrivals}, seed,
                    args.probe_seconds))
                emit({"phase": "probe", **rows[-1]})
            emit({"phase": "probe_spread", "arrivals": arrivals,
                  "rate_per_s": rate, "seconds": args.probe_seconds,
                  **{k: {"median": stats.median([r[k] for r in rows]),
                         "min": min(r[k] for r in rows),
                         "max": max(r[k] for r in rows)}
                     for k in ("ttft_p90_s", "tpot_p50_s",
                               "serve_tokens_per_s")}})
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
