#!/usr/bin/env bash
# Tier-1 verification gate — the EXACT command from ROADMAP.md, so
# builders and reviewers run the identical check.  Prints
# DOTS_PASSED=<n> (count of passing-test dots in the pytest progress
# lines) and exits with pytest's return code.
#
# Usage: bash tools/verify_t1.sh             (from anywhere; cd's to repo root)
#        bash tools/verify_t1.sh --with-gate (also run the perf-regression
#                                             gate's self-test afterwards —
#                                             covers the wall/HBM/quality
#                                             checks AND the measured
#                                             dispatch-latency gate)
#        bash tools/verify_t1.sh --serve-smoke (also run a ~2s open-loop
#                                             loadgen burst on the CPU
#                                             asserting the serve
#                                             health stream parses, the
#                                             coalescing window engages
#                                             under load, and every reply
#                                             stays bit-identical — plus
#                                             a hot-swap cell: 3 atomic
#                                             swaps under live traffic
#                                             with zero failed replies,
#                                             every reply bit-identical
#                                             to a live generation and
#                                             the flip pause p99 bounded;
#                                             writes no artifacts.
#                                             tools/bench_serve.py needs
#                                             a TPU and is not part of it)
#        bash tools/verify_t1.sh --sched-smoke (also run the
#                                             multi-tenant scheduler
#                                             smoke: 3 jobs — binary,
#                                             multiclass, lambdarank —
#                                             time-sliced under the fair
#                                             policy in a temp dir, with
#                                             health-stream
#                                             well-formedness assertions;
#                                             writes no artifacts)
#        bash tools/verify_t1.sh --fleet-smoke (also run the fleet
#                                             observability smoke: a real
#                                             2-rank localhost CPU fleet
#                                             with periodic collective
#                                             window syncs, per-rank
#                                             Chrome traces merged onto
#                                             one skew-corrected timeline
#                                             by fleet_trace.py, the
#                                             all-streams fleet_monitor
#                                             view, and a
#                                             fleet_summary.json accepted
#                                             by bench_gate.py; then the
#                                             gate's own self-test;
#                                             writes no repo artifacts)
#        bash tools/verify_t1.sh --with-kernel-checks (also run the
#                                             default path's three kernel
#                                             self-checks — fused route,
#                                             route window, score kernel —
#                                             on the
#                                             CPU interpret backend so CI
#                                             catches parity regressions;
#                                             on-chip runs catch lowering
#                                             drift the interpreter can't)
cd "$(dirname "$0")/.." || exit 1
set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
if [ "$1" = "--with-gate" ]; then
    python tools/bench_gate.py --self-test || exit 1
fi
if [ "$1" = "--serve-smoke" ]; then
    timeout -k 10 330 env JAX_PLATFORMS=cpu python tools/loadgen.py --smoke || exit 1
fi
if [ "$1" = "--sched-smoke" ]; then
    timeout -k 10 330 env JAX_PLATFORMS=cpu python tools/submit_jobs.py --smoke || exit 1
fi
if [ "$1" = "--fleet-smoke" ]; then
    timeout -k 10 330 env JAX_PLATFORMS=cpu python tools/fleet_monitor.py --smoke || exit 1
    python tools/bench_gate.py --self-test || exit 1
fi
if [ "$1" = "--with-kernel-checks" ]; then
    timeout -k 10 330 env JAX_PLATFORMS=cpu python -c 'import sys; from lightgbm_tpu.ops.pallas_histogram import run_kernel_self_checks; sys.exit(run_kernel_self_checks())' || exit 1
fi
exit $rc
