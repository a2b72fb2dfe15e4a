#!/bin/sh
# Tier-1 CI entry: run the test suite exactly as ROADMAP.md specifies
# (tests/test_compaction.py, tests/test_kernel_runtime.py,
# tests/test_scheduler.py and the runtime/controller suites are part of
# the default collection).
# A second pytest process then runs the multi-device lane: XLA_FLAGS
# must create the 8 virtual CPU devices *before jax initializes*, so the
# sharded-tier equivalence tests (tests/test_sharded_tiers.py — SPMD
# trajectory identity, 1-sync invariant, policy lowering across all
# configs) cannot share the first process.  The lane runs the *whole*
# suite under the 8-device mesh — the existing tier-1 tests double as a
# does-everything-still-hold-with-devices-visible check (they pass
# unchanged; only mesh-marked tests actually shard anything).
# Usage: tools/ci.sh [extra pytest args]
#   REPRO_CI_SHARDED=0 skips the multi-device lane;
#   REPRO_CI_SHARDED=fast restricts it to tests/test_sharded_tiers.py.
set -e
cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
if [ "${REPRO_CI_SHARDED:-1}" != "0" ]; then
    if [ "${REPRO_CI_SHARDED:-1}" = "fast" ]; then
        sharded_targets="tests/test_sharded_tiers.py"
    else
        sharded_targets=""
    fi
    XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
        PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m pytest -x -q $sharded_targets
fi
