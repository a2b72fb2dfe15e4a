"""repro.serving — BranchyNet serving on the unified K-tier runtime.

    TierExecutor / TierSegment   device-resident exit/transfer core
    ServingEngine                K=1 (monolithic, calibration source)
    PartitionedServer            K>=2 tiers over a plan of cuts (an int
                                 cut is the paper's edge/cloud system)
    StepReport                   one decode step: the executor's
                                 TierStepResult + the server's estimate
    RepartitionController        live p_k -> solver -> hot swap
    RequestScheduler             continuous-batching request lifecycle
                                 (submit/run/drain over recycled KV slots)
    LinkFaultModel / HopPolicy   seeded hop fault injection + retry/breaker
                                 policy (degraded steps, edge fallback)
"""

from repro.serving.controller import RepartitionController
from repro.serving.engine import ExitStats, ServingEngine
from repro.serving.faults import (
    CircuitBreaker,
    FaultEvent,
    FlapWindow,
    HopPolicy,
    LinkDownError,
    LinkFaultModel,
)
from repro.serving.partitioned import PartitionedServer
from repro.serving.scheduler import (
    Request,
    RequestResult,
    RequestScheduler,
    SchedulerStepReport,
)
from repro.serving.tiers import (
    HopCompaction,
    StepReport,
    TierExecutor,
    TierSegment,
    TierStepResult,
    bytes_per_sequence,
    segments_for_cuts,
)

__all__ = [
    "ExitStats",
    "ServingEngine",
    "PartitionedServer",
    "StepReport",
    "RepartitionController",
    "Request",
    "RequestResult",
    "RequestScheduler",
    "SchedulerStepReport",
    "HopCompaction",
    "TierExecutor",
    "TierSegment",
    "TierStepResult",
    "bytes_per_sequence",
    "segments_for_cuts",
    "CircuitBreaker",
    "FaultEvent",
    "FlapWindow",
    "HopPolicy",
    "LinkDownError",
    "LinkFaultModel",
]
