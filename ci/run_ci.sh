#!/bin/bash
# CI matrix (analog of the reference's .circleci/config.yml: build matrix
# {parameter-server, NCCL} x {build, 4-GPU tests} + nightly accuracy runs).
#
# Our matrix replaces gradient-sync backends (one XLA path here) with
# execution tiers:
#   lint      — fflint --strict over every shipped example strategy, ruff
#   unit      — pytest on the 8-device virtual CPU mesh (tests/conftest.py),
#               `slow`-marked tests included: the fleet drills (failover,
#               handoff, rolling deploy, autoscale, preemption), the kernel
#               and quantized token identities and the 2-process runs
#   sweep     — every example end-to-end on the virtual mesh
#   accuracy  — accuracy-gated training runs (nightly tier)
#   native    — C shim + C++ apps build & run
#   docs      — the documentation builds
#   resilience — fault-injection tests (FF_FAULT: kill-and-resume, NaN
#               skip/rewind, IO retry) + the 2-process multihost runs
#               when the jax build has gloo CPU collectives
#   sanitize  — ffsan plane (ISSUE 16): static concurrency/
#               tracestability passes clean over runtime/ (tiered exit:
#               warnings fail too) + the seeded-violation harness
#
# Speed is not measured here: the one benchmark is `benchmark/run.py` on
# the chip (PERF.md), and `python chip_smoke.py` through the chip tool is
# the bring-up check.
#
# Usage: ci/run_ci.sh [lint|unit|sweep|accuracy|native|docs|resilience|sanitize|all]
set -e

ROOT="$(cd "$(dirname "$0")/.." && pwd)"
cd "$ROOT"
TIER="${1:-all}"

# All CI tiers are CPU-only: pin the CPU platform for every child process
# in this script, and ask for the Pallas kernels in interpret mode (tests
# force the kernel paths; nothing interprets unasked). The chip check is
# `python chip_smoke.py` through the chip tool, not a CI tier.
export JAX_PLATFORMS=cpu
export FF_PALLAS_INTERPRET=1

run_unit()     { python -m pytest tests/ -x -q; }
run_sweep()    { bash tests/multi_device_tests.sh "${NDEV:-8}"; }
# accuracy tier defaults to 2 virtual devices: XLA CPU collectives need all
# participants at a rendezvous within 40 s, and 8 devices on a small host
# can starve one (see tests/accuracy_tests.sh)
run_accuracy() { bash tests/accuracy_tests.sh "${ACC_NDEV:-2}"; }
run_native()   {
  make -C flexflow_tpu/capi
  make -C examples/cpp
  FFT_JAX_PLATFORMS=cpu FFT_NUM_CPU_DEVICES=4 FFT_REPO_ROOT="$ROOT" \
    ./examples/cpp/alexnet 16 1 32
}
run_docs()     { make -C docs html; }
# lint tier: (1) fflint --strict over every shipped example strategy (the
# MANIFEST pairs each file with its model graph + mesh), (2) ruff over the
# Python package when the tool is available (config in pyproject.toml; the
# minimal CI image has no ruff — gate, don't fail, per the no-new-deps rule)
run_lint()     {
  local manifest="examples/strategies/MANIFEST"
  [ -f "$manifest" ] || { echo "lint: $manifest missing"; return 1; }
  while IFS='|' read -r f m mesh margs; do
    f=$(echo "$f" | xargs); m=$(echo "$m" | xargs)
    mesh=$(echo "$mesh" | xargs); margs=$(echo "$margs" | xargs)
    [ -z "$f" ] && continue
    case "$f" in \#*) continue ;; esac
    local extra=""
    for a in $margs; do extra="$extra --model-arg $a"; done
    echo "lint: fflint $m examples/strategies/$f (mesh $mesh)"
    # shellcheck disable=SC2086
    python -m flexflow_tpu.analysis "$m" "examples/strategies/$f" \
      --mesh "$mesh" --strict --quiet $extra
  done < <(grep -v '^#' "$manifest")
  if command -v ruff >/dev/null 2>&1; then
    ruff check flexflow_tpu
  elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check flexflow_tpu
  else
    echo "lint: ruff not installed in this image — skipping style gate"
  fi
}

# resilience tier: the fault-injection suite (every FF_FAULT path:
# kill-and-resume bitwise, NaN skip-step + rewind, injected orbax IO
# failure + retry, SIGTERM checkpoint-then-stop, watchdog), then the
# 2-process multihost runs: training that round-trips a sharded orbax
# checkpoint across controllers, serving restored from it, overlapped
# grad sync preempted and resumed bitwise, and a 2-process run resumed
# resharded on one survivor. They need gloo CPU collectives; probe and
# skip (loudly) where this jax build lacks them.
has_gloo() {
  JAX_PLATFORMS="" python -c "
import jax
jax.config.update('jax_cpu_collectives_implementation', 'gloo')" \
      >/dev/null 2>&1
}

run_resilience() {
  python -m pytest tests/test_resilience.py -q
  if has_gloo; then
    python -m pytest tests/test_multihost.py -q
  else
    echo "resilience: no gloo CPU collectives in this jax build —" \
         "skipping the 2-process runs"
  fi
}

# sanitize tier (ISSUE 16): the ffsan plane. The concurrency +
# tracestability source passes must be CLEAN over flexflow_tpu/runtime
# (severity-tiered exit codes: any error OR warning fails the tier) and
# the seeded-violation harness in tests/test_ffsan.py must still catch
# every planted bug class. The dynamic half (a fleet crash drill under the
# order-asserting lock proxies and armed retrace sentinels, zero
# violations and zero post-warmup retraces) is tests/test_router.py
# test_crash_failover_exactly_once_token_identity[on], in `unit`.
run_sanitize() {
  python -m flexflow_tpu.analysis \
    --passes concurrency,tracestability --tiered-exit
  python -m pytest tests/test_ffsan.py -q
}

case "$TIER" in
  unit)     run_unit ;;
  sweep)    run_sweep ;;
  accuracy) run_accuracy ;;
  native)   run_native ;;
  docs)     run_docs ;;
  lint)     run_lint ;;
  resilience) run_resilience ;;
  sanitize) run_sanitize ;;
  all)      run_lint; run_unit; run_resilience; run_sanitize; run_native; run_docs; run_sweep ;;
  *) echo "unknown tier $TIER"; exit 2 ;;
esac
echo "ci($TIER): PASSED"
