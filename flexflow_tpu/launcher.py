"""Multi-host launch driver.

Reference: python/flexflow.py — wraps `mpirun -npernode 1 flexflow_python
-ll:py 1 -ll:gpu N -ll:fsize ...` (flexflow.py:24-99). TPU analog: multi-
controller JAX. On a TPU pod each host runs the SAME script;
`jax.distributed.initialize()` wires the hosts; GSPMD handles cross-host
(DCN) collectives. This driver:

  * single host: exec the script with the requested device env
  * multi host (--coordinator given or TPU pod env detected): call
    jax.distributed.initialize(...) then exec

Elastic relaunch (runtime/elastic.py): ``--elastic`` (or FF_ELASTIC=1)
turns a failed ``jax.distributed.initialize`` — the surviving host of a
shrunk pool waiting on peers that are never coming back — into a logged
single-process continuation instead of a crash: the script then sees the
actual (smaller) topology and auto-resume re-shards per
``FFConfig.on_topology_change``. Workers TCP-probe the coordinator before
handing control to jax (a failed rendezvous hard-terminates, not raises,
on this class of build); the coordinator itself has nothing to probe, so
it binds the rendezvous port and waits for a peer to KNOCK — silence
means the pool shrank around it. The world size the job *expected* is
detected up front (``--num-processes`` / the pod env) and compared against
what initialize actually produced, so a changed topology is diagnosed at
startup rather than as an opaque rendezvous timeout. The
``shrink(<k>)@resume:<n>`` fault (FF_FAULT) is consumed HERE, before the
backend exists, so a relaunch drill genuinely starts with k devices.

Usage: python -m flexflow_tpu.launcher script.py [--num-processes N]
       [--process-id I] [--coordinator host:port] [--elastic]
       [-- script args...]
"""

from __future__ import annotations

import argparse
import os
import runpy
import sys


def _retried_initialize(jax):
    """jax.distributed.initialize under retry/backoff: on a preempted pool
    the coordinator host often comes back seconds after the workers, and
    the raw call fails once and kills the whole relaunch. Attempts/delay
    tunable for restart loops via FF_INIT_ATTEMPTS / FF_INIT_DELAY_S;
    FF_INIT_TIMEOUT_S bounds each rendezvous attempt (the elastic relaunch
    path needs 'peers are gone' diagnosed in seconds, not jax's default
    300 s)."""
    import functools

    from flexflow_tpu.runtime.resilience import retry

    init = jax.distributed.initialize
    timeout = os.environ.get("FF_INIT_TIMEOUT_S", "")
    if timeout:
        init = functools.partial(
            init, initialization_timeout=int(float(timeout)))
    return retry(attempts=int(os.environ.get("FF_INIT_ATTEMPTS", "3")),
                 base_delay=float(os.environ.get("FF_INIT_DELAY_S", "2")),
                 max_delay=30.0, retryable=(RuntimeError, OSError),
                 name="jax.distributed.initialize")(init)


def _coordinator_reachable(addr: str, timeout_s: float) -> bool:
    """TCP probe of the rendezvous address. On this class of jax build a
    failed rendezvous TERMINATES the process from inside the distributed
    client (absl fatal, no Python exception to catch) — so the elastic
    relaunch must find out the coordinator is gone BEFORE handing control
    to jax, not after. timeout_s is a DEADLINE, not a per-connect timeout:
    a refused connect returns instantly, and on a preempted pool the
    coordinator host often binds its port seconds after the workers start,
    so the probe keeps retrying until the window closes."""
    import socket
    import time

    host, _, port = addr.rpartition(":")
    deadline = time.monotonic() + timeout_s
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            return False
        try:
            with socket.create_connection((host or "127.0.0.1", int(port)),
                                          timeout=remaining):
                return True
        except ValueError:
            return False
        except OSError:
            if time.monotonic() + 0.5 >= deadline:
                return False
            time.sleep(0.5)


def _await_peer_knock(addr: str, timeout_s: float) -> bool:
    """Coordinator-side (process 0) flavor of the dead-peer diagnosis:
    process 0 cannot probe anything (it IS the rendezvous address), so it
    binds the port itself and waits for any peer to knock — a relaunched
    worker's elastic probe and a plain worker's initialize both TCP-connect
    here. No knock within the window means the pool shrank around the
    coordinator; falling back BEFORE jax starts the coordination service
    matters because a failed rendezvous hard-terminates the process (see
    _coordinator_reachable). If the port cannot be bound (something else
    holds it), assume infrastructure exists and let initialize decide.
    One knock is enough — this socket closes right before jax re-binds
    the port, and a worker whose probe lands in that gap just retries
    (the probe loops until its own deadline) and hits jax's service; the
    wide backlog keeps simultaneous probes from being refused outright."""
    import socket

    host, _, port = addr.rpartition(":")
    try:
        with socket.socket() as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            s.bind((host or "127.0.0.1", int(port)))
            s.listen(16)
            s.settimeout(timeout_s)
            try:
                conn, _peer = s.accept()
                conn.close()
                return True
            except socket.timeout:
                return False
    except (OSError, ValueError):
        return True


def _reset_cpu_collectives(jax):
    """Undo the gloo CPU-collectives selection after an elastic fallback
    to single-process: without a distributed client the gloo backend
    refuses to initialize, so the fallback must restore the default."""
    try:
        jax.config.update("jax_cpu_collectives_implementation", "none")
    except Exception:
        pass


def main(argv=None):
    p = argparse.ArgumentParser(prog="flexflow_tpu.launcher")
    p.add_argument("script")
    p.add_argument("--num-processes", type=int, default=None,
                   help="total controller processes (hosts)")
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--coordinator", type=str, default=None,
                   help="host:port of process 0")
    p.add_argument("--cpu-devices", type=int, default=None,
                   help="emulate N CPU devices (testing)")
    p.add_argument("--elastic", action="store_true",
                   help="continue single-process (and let auto-resume "
                        "reshard) when multi-host initialize fails — the "
                        "surviving-host relaunch path; also FF_ELASTIC=1")
    args, rest = p.parse_known_args(argv)
    if rest and rest[0] == "--":
        rest = rest[1:]
    elastic = args.elastic or os.environ.get("FF_ELASTIC", "") not in ("",
                                                                       "0")

    # deterministic topology-change drill: FF_FAULT shrink(<k>)@resume:<n>
    # presents only k visible devices to this (fresh) process — consumed
    # before any backend exists so force_cpu_devices genuinely applies
    from flexflow_tpu.runtime import faultinject

    plan = faultinject.active_plan()
    if plan.fire("shrink", "resume") and plan.last_value:
        print(f"[launcher] FF_FAULT shrink@resume: presenting "
              f"{plan.last_value} visible devices", file=sys.stderr)
        args.cpu_devices = int(plan.last_value)

    if args.cpu_devices:
        import jax

        from flexflow_tpu._env import force_cpu_devices

        force_cpu_devices(args.cpu_devices)
        if args.num_processes and args.num_processes > 1:
            # CPU cross-process collectives need an explicit backend
            jax.config.update("jax_cpu_collectives_implementation", "gloo")

    # multi-host pod detection: require an actual multi-worker signal (a
    # single-chip dev box can still carry TPU env vars)
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    pod_env = (not args.cpu_devices) and (
        "," in hostnames or bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")))
    if args.coordinator or (args.num_processes and args.num_processes > 1):
        if args.coordinator and (args.num_processes is None
                                 or args.process_id is None) and not pod_env:
            p.error("--coordinator requires --num-processes and --process-id "
                    "(they cannot be auto-detected outside TPU/SLURM "
                    "environments)")
        import jax

        skip_init = False
        if elastic and args.coordinator:
            probe_s = float(os.environ.get("FF_INIT_TIMEOUT_S", "10") or 10)
            if args.process_id in (None, 0):
                # coordinator-side relaunch: nothing to probe (we ARE the
                # rendezvous address) — listen on the port and wait for a
                # peer to knock instead; silence means the pool shrank
                # around the coordinator
                if (args.num_processes or 0) > 1 and \
                        not _await_peer_knock(args.coordinator, probe_s):
                    skip_init = True
                    _reset_cpu_collectives(jax)
                    print(f"[launcher] elastic: no peer knocked on "
                          f"{args.coordinator} within {probe_s:.0f}s — "
                          f"expected world size {args.num_processes}, "
                          f"continuing SINGLE-process; auto-resume will "
                          f"reshard per on_topology_change",
                          file=sys.stderr)
            elif not _coordinator_reachable(args.coordinator, probe_s):
                # non-coordinator relaunch: probe the rendezvous address
                # first. An unreachable coordinator means the pool shrank
                # around us — initialize would hard-terminate the process
                # (see _coordinator_reachable), so fall back HERE, cleanly
                skip_init = True
                _reset_cpu_collectives(jax)
                print(f"[launcher] elastic: coordinator "
                      f"{args.coordinator} unreachable — expected world "
                      f"size {args.num_processes}, continuing "
                      f"SINGLE-process; auto-resume will reshard per "
                      f"on_topology_change", file=sys.stderr)
        try:
            if not skip_init:
                _retried_initialize(jax)(
                    coordinator_address=args.coordinator,
                    num_processes=args.num_processes,
                    process_id=args.process_id)
        except Exception as e:
            if not elastic:
                raise
            # the surviving-host relaunch: peers (or the coordinator) are
            # gone for good, so retrying the rendezvous forever IS the
            # outage. Continue single-process — the script sees the actual
            # topology and FFConfig.on_topology_change decides what resume
            # does with it (runtime/elastic.py)
            _reset_cpu_collectives(jax)
            print(f"[launcher] elastic: multi-host initialize failed "
                  f"({type(e).__name__}: {e}) — expected world size "
                  f"{args.num_processes}, continuing SINGLE-process; "
                  f"auto-resume will reshard per on_topology_change",
                  file=sys.stderr)
        else:
            # world-size sanity at startup (not deep inside a collective):
            # initialize succeeded, but a pod env can legitimately come up
            # smaller than the job expected — diagnose it here
            actual = jax.process_count()
            if args.num_processes and actual != args.num_processes:
                print(f"[launcher] topology change detected at startup: "
                      f"expected {args.num_processes} processes, "
                      f"initialize produced {actual}", file=sys.stderr)
    elif pod_env:
        # TPU pod: every host runs this same script; initialize with full
        # auto-detection (docstring's 'TPU pod env detected' path)
        import jax

        try:
            _retried_initialize(jax)()
        except Exception as e:
            if not elastic:
                raise
            _reset_cpu_collectives(jax)
            print(f"[launcher] elastic: pod initialize failed "
                  f"({type(e).__name__}: {e}) — continuing SINGLE-process",
                  file=sys.stderr)

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # the launched script's persistent compilation cache is placed
        # from outside; checked HERE, before the script's first trace, so
        # an unusable directory stops the launch instead of costing every
        # compile
        from flexflow_tpu._env import resolve_compilation_cache

        resolve_compilation_cache()

    sys.argv = [args.script] + rest
    runpy.run_path(args.script, run_name="__main__")


if __name__ == "__main__":
    main()
