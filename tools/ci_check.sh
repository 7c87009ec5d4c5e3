#!/usr/bin/env bash
# CI entry: static-analysis gate first (fast, ~10 s — catches program
# hazards and repo drift before spending minutes on tests), then the
# tier-1 pytest suite exactly as ROADMAP.md specifies it.
#
# Usage: tools/ci_check.sh [--gate-only|--tests-only]
set -u -o pipefail

REPO_ROOT="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO_ROOT"

# the acceptance platform: 8-virtual-device CPU mesh (a real TPU run
# exports its own JAX_PLATFORMS and skips these defaults)
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export XLA_FLAGS="${XLA_FLAGS:---xla_force_host_platform_device_count=8}"

mode="${1:-all}"

if [[ "$mode" != "--tests-only" ]]; then
    # The gate's acceptance programs must audit clean and every seeded
    # violation of the regression corpus must still fire
    # (docs/static_analysis.md) before any benchmark runs.
    echo "== staticcheck gate (tools/staticcheck.py, docs/static_analysis.md) =="
    python tools/staticcheck.py gate
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: staticcheck gate FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" != "--tests-only" ]]; then
    # lockset race detector over the real threaded control plane +
    # the seeded conc.* corpus (docs/static_analysis.md §Concurrency)
    echo "== staticcheck races (lockset sanitizer) =="
    python tools/staticcheck.py races
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: staticcheck races FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" != "--tests-only" ]]; then
    # deterministic schedule fuzzer: MXNET_TPU_CONC_SCHEDULES seeded
    # interleavings per hot concurrent scenario, byte-identity asserted
    # under every one; failures print a replayable (scenario, seed)
    echo "== staticcheck schedules (deterministic fuzzer) =="
    python tools/staticcheck.py schedules
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: staticcheck schedules FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" != "--tests-only" ]]; then
    # quick end-to-end check that the telemetry seams still emit: a
    # tiny instrumented train must produce a valid Perfetto trace and
    # a metrics stream --diff-metrics can read (docs/observability.md)
    echo "== telemetry smoke (tools/telemetry_smoke.py) =="
    python tools/telemetry_smoke.py
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: telemetry smoke FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" != "--tests-only" ]]; then
    # end-to-end check of the serving tier: 8 concurrent streams
    # through the paged-KV continuous-batching engine, decode warm
    # after step 1, serve spans in a valid trace (docs/serving.md)
    echo "== serve smoke (tools/serve_smoke.py) =="
    python tools/serve_smoke.py
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: serve smoke FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" != "--tests-only" ]]; then
    # end-to-end gameday: a scaled-down diurnal trace replayed in
    # virtual time with closed-loop autoscaling (1..3 replicas) and a
    # mid-ramp replica kill; scale-up AND scale-down must both fire,
    # the kill must fail over cleanly, zero post-warmup retraces, no
    # KV leak (docs/serving.md §Traffic simulation & autoscaling)
    echo "== gameday smoke (tools/gameday_smoke.py) =="
    python tools/gameday_smoke.py
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: gameday smoke FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" != "--tests-only" ]]; then
    # end-to-end check of the elastic-training tier: a real launch_local
    # membership cluster loses a SIGKILLed worker mid-run; the trainer
    # must resize 8->4 with zero lost updates and zero retraces
    # (docs/elastic.md)
    echo "== elastic smoke (tools/elastic_smoke.py) =="
    python tools/elastic_smoke.py
    rc=$?
    if [[ $rc -ne 0 ]]; then
        echo "ci_check: elastic smoke FAILED (rc=$rc)" >&2
        exit "$rc"
    fi
fi

if [[ "$mode" == "--gate-only" ]]; then
    exit 0
fi

echo "== tier-1 tests (ROADMAP.md) =="
rm -f /tmp/_t1.log
timeout -k 10 870 python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
exit "$rc"
