"""Names of the host spans the served path writes into the profiler's trace.

Each site wraps its work in ``jax.profiler.TraceAnnotation(NAME)`` (the
scheduler step in a ``StepTraceAnnotation`` carrying ``step_num``).  An
annotation is a no-op while no trace is running, so the profiler is the
only recorder: take a trace with ``jax.profiler.trace`` (or a
``jax.profiler.start_server`` session from TensorBoard / XProf) and the
spans sit on the host plane, on the clock the device planes share.

Span keyword arguments carry only integers already at hand (a segment
index, a bucket width), so nothing on the hot path builds a string.

The jitted programs are named after their cache keys (see
``TierExecutor``): ``decode_l{lo}_{hi}_b{bucket|full}[_probe][_degrade{L}]``,
``prefill_p{plen}_n{n}`` and ``reset_rows_n{n}``; XLA names their modules
``jit_<name>``.
"""

from __future__ import annotations

#: One ``RequestScheduler.step`` (``StepTraceAnnotation``, ``step_num`` =
#: the scheduler's step count before the step).
SCHED_STEP = "sched.step"
#: Admission: host packing of prompts, prefill dispatch, first-token scatter.
SCHED_ADMIT = "sched.admit"
#: Per-slot token and result bookkeeping after the decode step.
SCHED_EMIT = "sched.emit"
#: Retirement hygiene: the ``reset_rows`` dispatch.
SCHED_RETIRE = "sched.retire"

#: One ``TierExecutor.step``, overflow re-runs included.
TIER_STEP = "tier.step"
#: Host planning before any dispatch: hop health, probe rows, buckets.
TIER_PLAN = "tier.plan"
#: Host-to-device staging of the step's small input arrays.
TIER_STAGE = "tier.stage"
#: Enqueue of one segment program (``seg``, ``bucket`` kwargs).
TIER_DISPATCH = "tier.dispatch"
#: The step's device->host fetch (one per run of the segments).
TIER_SYNC = "tier.sync"
#: One overflow re-run, holding its own stage, dispatches and sync.
TIER_RETRY = "tier.retry"
#: Host bookkeeping after a sync: survivors, attribution, hops, result.
TIER_ACCOUNT = "tier.account"
#: ``TierExecutor.prefill_rows``: the admission prefill dispatch.
TIER_PREFILL = "tier.prefill"
#: ``TierExecutor.reset_rows``: the row reset dispatch.
TIER_RESET = "tier.reset"

ALL = (
    SCHED_STEP, SCHED_ADMIT, SCHED_EMIT, SCHED_RETIRE,
    TIER_STEP, TIER_PLAN, TIER_STAGE, TIER_DISPATCH, TIER_SYNC,
    TIER_RETRY, TIER_ACCOUNT, TIER_PREFILL, TIER_RESET,
)
