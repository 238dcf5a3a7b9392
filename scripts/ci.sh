#!/usr/bin/env bash
# CI pipeline: tier-1 first (the gate every PR must keep green), then the
# marker suites as separate named stages so a sharding or ragged failure
# is attributable at a glance. Stages re-select subsets of tier-1 —
# cheap, since the jit caches are per-process and each stage is its own
# pytest process anyway.
#
#   scripts/ci.sh            # all stages
#   scripts/ci.sh tier1      # just the gate
#   scripts/ci.sh multidevice ragged clientshard faults
#   scripts/ci.sh kernels    # Pallas kernel suites + bench smoke
#   scripts/ci.sh serve      # manifest/service suites + serve-bench smoke
#   scripts/ci.sh serve-resume  # SIGKILL-and-recover + resume bench smoke
#   scripts/ci.sh multihost  # simulated 2-process jax.distributed suite
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# The suites run on the CPU backend, where Pallas kernels run in
# interpret mode; chip_smoke.py is the on-chip check.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

stage() {
    echo "=== stage: $1 ==="
    shift
    python -m pytest -q "$@"
}

run_stage() {
    case "$1" in
        tier1)       stage tier1 -x ;;
        multidevice) stage multidevice -m multidevice ;;
        ragged)      stage ragged -m ragged ;;
        clientshard) stage clientshard -m clientshard ;;
        faults)      stage faults -m faults ;;
        kernels)
            # Kernel correctness (interpret-mode vs oracles) plus a bench
            # harness smoke: the micro-bench suite must run end-to-end and
            # emit schema-valid JSON (timing-attribution guard included).
            stage kernels tests/test_kernels.py tests/test_kernels_properties.py \
                tests/test_fused_update.py
            python -m benchmarks.run --only kernels_bench --fast \
                --json /tmp/bench_kernels_smoke.json >/dev/null
            ;;
        serve)
            # Study-as-a-service: manifest round-trips + the batching
            # service suite, then a serve-bench smoke (the serve_* series
            # must emit and pass their schema validator end-to-end).
            stage serve -m serve
            python -m benchmarks.run --only serve_bench --fast \
                --json /tmp/bench_serve_smoke.json >/dev/null
            ;;
        serve-resume)
            # Preemption-safe serving (DESIGN.md §12): the SIGKILLed
            # serve subprocess must recover bitwise on a fresh service,
            # and the serve_resume_* bench series must emit and pass
            # the zero-recompile / bitwise validator end-to-end.
            stage serve-resume \
                tests/test_resumable.py::test_service_kill9_and_recover_bitwise \
                tests/test_service.py \
                -k "kill9 or resumable or recover or drain or response_store or concurrent or checkpoint"
            python -m benchmarks.run --only serve_bench --fast \
                --json /tmp/bench_serve_resume_smoke.json >/dev/null
            ;;
        multihost)
            # Multi-host execution (DESIGN.md §13): the simulated
            # 2-process jax.distributed run must stay bitwise (gather)
            # against the single-process vmap engine, and the
            # multihost_* bench series must emit and pass their
            # validator end-to-end.
            stage multihost -m multihost
            python -m benchmarks.run --only multihost --fast \
                --json /tmp/bench_multihost_smoke.json >/dev/null
            ;;
        *) echo "unknown stage: $1 (have tier1 multidevice ragged clientshard faults kernels serve serve-resume multihost)" >&2
           exit 2 ;;
    esac
}

if [ "$#" -eq 0 ]; then
    set -- tier1 multidevice ragged clientshard faults kernels serve serve-resume multihost
fi
for s in "$@"; do
    run_stage "$s"
done
echo "=== all stages green ==="
