#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once, on the machine this is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process, one JAX. It fails (non-zero, no result line) unless
`jax.devices()[0].platform == "tpu"` and the chips the cell asks for are
attached: there is no fallback, and no number from another backend is ever
printed under a metric's name. The last line of standard output is one JSON
object with the keys `correct`, `attempted`, `failed`, `metrics`, `device`
and, with `--trace 1`, `breakdown`; everything else is on earlier lines.
With `--trace 0` the metrics are the cell's end-to-end metrics (tracing off),
with `--trace 1` its per-layer metrics (a slice of the window is traced).

The cell's configuration, traffic mix, builder, generator and per-layer
metric readers are found by name under benchmark/ (see README.md): this file
knows none of them.

`--rehearsal` walks the same code on the CPU at a tiny size with interpreted
kernels, to find faults before chip time is spent. It prints no result line
and exits 64, never 0.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is clocked from here

import argparse     # noqa: E402
import contextlib   # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import shutil       # noqa: E402
import sys          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import spec  # noqa: E402

REHEARSAL_EXIT = 64
TRACE_SLICE_S = 5.0         # the window's last seconds; traces are large
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(msg):
    print(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {msg}",
          flush=True)


class Harness:
    """What a generator's `run(h)` and a metric's `read(ctx)` get from the
    harness: the cell's data, the clock marks, the compile counter and the
    trace slice."""

    def __init__(self, args, bench, workload, config, traffic):
        self.args, self.bench, self.workload = args, bench, workload
        self.config, self.traffic = config, traffic
        self.rehearsal = args.rehearsal
        self.cut = spec.cut_for(config, workload["chips"])
        self.builder = spec.load_module("builders", config["builder"])
        self.scale = self.builder.REHEARSAL_SCALE if self.rehearsal else 1
        self.vocab = self.builder.sizes_of(config, self.cut,
                                           self.rehearsal)["vocab_size"]
        self.log = log
        self.setup_s = None
        self._lowerings = 0
        self._win = [None, None]        # lowerings at window start / end
        # a traffic file may end its window before --seconds (`max_seconds`)
        self.seconds = min(float(args.seconds),
                           float(traffic.get("max_seconds", "inf")))
        # the slice is the END of the window: stop_trace() holds the calling
        # thread for seconds, so it may only run once the loop has returned
        self._trace = {"on": bool(args.trace), "state": "idle", "ann": None,
                       "dir": os.path.join(TRACE_DIR, workload["name"]),
                       "start": max(0.0, self.seconds - TRACE_SLICE_S),
                       "stop": self.seconds}

    # ---- compile counter --------------------------------------------------

    def count_lowering(self, event, duration, **kw):
        if event == LOWERING_EVENT:
            self._lowerings += 1

    def compiles_in_window(self):
        """Programs lowered (every jit cache miss, persistent-cache hit or
        not) between setup_done() and window_done()."""
        return self._win[1] - self._win[0]

    # ---- clock marks --------------------------------------------------------

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_PROCESS
        self._win[0] = self._lowerings
        log(f"set-up done: {self.setup_s:.3f} s")

    def window_done(self):
        """The generator's loop has returned: nothing is timed any more."""
        self._win[1] = self._lowerings
        tr = self._trace
        if tr["state"] in ("tracing", "sliced"):
            import jax

            self.trace_poll(float("inf"))
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            tr["state"] = "done"
            log(f"profiler: start_trace() held the loop {tr['stall_s']:.2f} s "
                f"inside the window; stop_trace() took "
                f"{time.perf_counter() - t0:.2f} s after it")

    # ---- trace slice --------------------------------------------------------

    def annotate(self, name):
        """A profiler annotation while the slice is being traced, nothing
        otherwise: end-to-end runs carry no tracing at all."""
        if self._trace["state"] != "tracing":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def trace_poll(self, now):
        """Called by the generator's loop with the seconds since the window
        began: starts the trace when the window reaches the slice and closes
        the slice's annotation (`bench.trace_window`, which the reduction
        clips to) when it is over. The trace itself is stopped by
        window_done(), so the loop never waits for the profiler to write."""
        tr = self._trace
        if not tr["on"]:
            return
        import jax

        if tr["state"] == "idle" and now >= tr["start"]:
            shutil.rmtree(tr["dir"], ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # no per-call Python events
            t0 = time.perf_counter()
            jax.profiler.start_trace(tr["dir"], profiler_options=opts)
            tr["stall_s"] = time.perf_counter() - t0
            tr["state"] = "tracing"
            tr["ann"] = jax.profiler.TraceAnnotation("bench.trace_window")
            tr["ann"].__enter__()
        elif tr["state"] == "tracing" and now >= tr["stop"]:
            tr["ann"].__exit__(None, None, None)
            tr["state"] = "sliced"

    def reduced_trace(self):
        from benchmark import trace_reduce

        if self._trace["state"] != "done":
            raise RuntimeError("the window ended before the trace slice began")
        t0 = time.perf_counter()
        path = trace_reduce.find_xplane(self._trace["dir"])
        red = trace_reduce.reduce_trace(trace_reduce.load_xplane(path))
        log(f"trace {path} ({os.path.getsize(path) / 1e6:.1f} MB) reduced in "
            f"{time.perf_counter() - t0:.1f} s: window {red['window_s']:.3f} "
            f"s, busy {red['busy_s']:.3f} s, idle share (worst chip) "
            f"{red['idle_share']:.4f}")
        log(f"idle seconds by host annotation: {red['idle_by_host']}")
        return red


def load_cell(bench, name, seed=0, seconds=1.0, trace=0, rehearsal=False):
    """The Harness of the cell called `name`: its workload entry, its
    configuration and traffic files (run.py, aot_check.py and knee_sweep.py
    all start here)."""
    workload, config_entry = spec.find_workload(bench, name)
    traffic = spec.load_traffic(workload["traffic"])
    if rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds,
                              trace=trace, rehearsal=rehearsal)
    return Harness(args, bench, workload,
                   spec.load_config(ROOT, config_entry), traffic)


def place_compile_cache():
    """jax's persistent cache where `_env.resolve_compilation_cache()` puts
    it, with the persistence thresholds at 0: the harness owns its process,
    so EVERY program is kept, also the hundreds of sub-second ones
    (compile()'s per-weight init jits) that jax's 1 s threshold would compile
    again in every run."""
    import jax

    from flexflow_tpu import _env

    cache = _env.resolve_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def device_report(devices, chips):
    peak = 0
    for d in devices[:chips]:
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny size, interpreted kernels: counts only, "
                         "no result line, exits 64")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(ROOT)
    h = load_cell(bench, args.workload, args.seed, args.seconds, args.trace,
                  args.rehearsal)
    workload, config, traffic = h.workload, h.config, h.traffic
    chips = workload["chips"]

    if args.rehearsal:
        log("CPU REHEARSAL - NOT A CHIP RESULT: tiny size, interpreted "
            "kernels, counts only")
        os.environ["FF_PALLAS_INTERPRET"] = "1"
        os.environ["FF_FORCE_FLASH_ATTENTION"] = "1"
    import jax

    from flexflow_tpu import _env

    if args.rehearsal:
        _env.force_cpu_devices(chips)
    devices = jax.devices()
    log(f"workload {args.workload}: config {workload['config']}, traffic "
        f"{workload['traffic']}, chips {chips}; jax sees {len(devices)} x "
        f"{devices[0].device_kind} ({devices[0].platform})")
    if not args.rehearsal and devices[0].platform != "tpu":
        print(f"benchmark/run.py: jax found platform "
              f"{devices[0].platform!r}, not a TPU: nothing is measured",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"benchmark/run.py: the cell needs {chips} chips, "
              f"{len(devices)} attached", file=sys.stderr)
        return 2
    if not args.rehearsal:
        from benchmark import peaks

        peaks.peaks_for(devices[0].device_kind)     # unknown kind raises
        cache = place_compile_cache()
        log(f"compile cache {cache}: "
            f"{_env.compilation_cache_entries(cache)} entries at start")

    jax.monitoring.register_event_duration_secs_listener(h.count_lowering)
    generator = spec.load_module("generators", traffic["kind"])
    result = generator.run(h)
    ctx = result["ctx"]
    log(f"correct={result['correct']} attempted={result['attempted']} "
        f"failed={result['failed']} compiles in window "
        f"{ctx['compiles_in_window']}")

    device = device_report(devices, chips)
    metrics = {"setup_s": h.setup_s, **result["end_to_end"]}
    out = {"correct": result["correct"], "attempted": result["attempted"],
           "failed": result["failed"]}
    if args.trace:
        trace = None
        if not args.rehearsal:      # a CPU trace has no device plane
            trace = h.reduced_trace()
            device.update(busy_s=trace["busy_s"],
                          window_s=trace["window_s"])
            out["breakdown"] = {"device_ops": trace["device_ops"],
                                "idle_gaps": trace["idle_gaps"]}
        ctx.update(trace=trace, device=device, config=config, cut=h.cut,
                   device_kind=devices[0].device_kind)
        metrics = {}
        for m in spec.metrics_for(bench, "per_layer", args.workload):
            value = spec.load_module("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = value
    else:
        log(f"end-to-end: {metrics}")
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    out["metrics"] = {k: {"value": float(v), "unit": units[k]}
                      for k, v in metrics.items()}
    out["device"] = device
    if not args.rehearsal:
        log(f"compile cache: {_env.compilation_cache_entries(cache)} entries "
            f"at end; total {time.perf_counter() - T_PROCESS:.1f} s")
    if args.rehearsal:
        log(f"REHEARSAL PASSED - not a chip result; would have printed: "
            f"{ {k: out[k] for k in ('correct', 'attempted', 'failed')} } "
            f"metrics {sorted(out['metrics'])}")
        return REHEARSAL_EXIT
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
