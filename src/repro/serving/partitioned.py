"""Partitioned BranchyNet serving over a K-tier plan — the paper's system
and its K >= 3 generalization on one server.

A plan is ``cuts`` (K-1 layers after which a hop happens) and ``tiers``
(K :class:`~repro.core.multitier.TierSpec`).  One decode step runs the
:class:`~repro.serving.tiers.TierExecutor` over the segments the plan
cuts: tier j runs trunk layers ``(c_j, c_{j+1}]`` and the side branches
strictly inside them; sequences whose branch entropy clears the threshold
*exit* there and are never shipped (the ``surv(s) * t_s^net`` term of the
paper's Eq. 5); survivors cross each hop with per-hop byte accounting.

A single int ``cuts`` is the paper's K=2 edge/cloud split ``v_s``
(0 = cloud-only, L = edge-only); its two tiers are derived from
``network``, ``cost_profile``, ``tier_devices`` and ``ici_bps``.  A tuple
with ``tiers=`` is any K (a ``core.multitier`` lattice plan).

On one host this is a simulation of the tiers (all run locally), but the
tier boundary is real in the compiled program: each tier is a separate
jitted segment function with an explicit tensor handoff, the same
structure a real deployment lowers.  ``install_cuts`` swaps the cuts at
runtime; a segment whose (layer range, branches) is unchanged re-uses its
compiled function.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.latency import expected_time
from repro.core.multitier import TierSpec, expected_time_multitier
from repro.core.partitioner import Partitioner
from repro.core.profiler import branch_head_cost
from repro.core.types import CostProfile, NetworkProfile
from repro.launch.mesh import mesh_devices
from repro.serving.scheduler import ServesRequests
from repro.serving.tiers import (
    StepReport,
    TierExecutor,
    TierStepResult,
    segments_for_cuts,
    transfer_seconds,
)

__all__ = ["PartitionedServer", "paper_profile", "paper_route"]


def paper_route(
    tiers: Sequence[TierSpec], overlap: bool, batch: int | None
) -> bool:
    """Which cost model prices a plan: the one rule the server's estimate
    and :meth:`RepartitionController.solve` share.

    The paper's Eq. 5 (estimate) / Dijkstra (solve) price a K=2 plan run
    serially, unbatched (``batch`` is None unless the runtime compacts and
    a batch is priced) over a healthy hop.  The lattice of
    :mod:`repro.core.multitier` prices every other plan: K >= 3, pipelined
    overlap (the bottleneck stage), bucketed compaction (padding-honest),
    a degraded hop (availability).  Where both could apply they agree,
    except that the lattice leaves out branch-head compute: a profile with
    ``include_branch_compute=True`` is priced with the ``gamma * t_b``
    edge terms here and without them on the lattice."""
    return (
        len(tiers) == 2
        and not overlap
        and batch is None
        and tiers[0].availability >= 1.0
    )


def paper_profile(profile: CostProfile, edge: TierSpec) -> CostProfile:
    """``profile`` pricing the edge tier's gamma and uplink (the K=2
    plan's own link)."""
    return dataclasses.replace(
        profile,
        gamma=edge.gamma,
        network=dataclasses.replace(
            profile.network, bandwidth_bps=edge.uplink_bps
        ),
    )


@dataclasses.dataclass
class PartitionedServer(ServesRequests):
    cfg: ModelConfig
    params: Any
    cuts: int | Sequence[int]  # layer after which each hop happens (K-1,)
    # The plan's K tiers; None = the paper's edge/cloud pair, derived once
    # from ``cost_profile`` and the init-only ``network`` /
    # ``tier_devices`` / ``ici_bps`` (``tiers`` is then the one record).
    tiers: Sequence[TierSpec] | None = None
    network: dataclasses.InitVar[NetworkProfile | None] = None  # K=2 uplink
    # Per-layer times and sizes for est_latency_s (None = no estimate); its
    # gamma and network price the derived K=2 edge tier.
    cost_profile: CostProfile | None = None
    compaction: str = "bucketed"  # "off" = legacy masked full-batch tiers
    simulate_network: bool = False  # sleep each hop's transfer time
    overlap: str = "serial"  # "pipelined" = overlap transfers with compute
    use_kernels: bool | None = None  # Pallas decode path; None = cfg/auto
    # Batched exit heads: one (K, B, D) projection + one multi-head fused
    # entropy-exit launch per segment instead of K head evaluations
    # (serving.tiers "Batched exit heads").  Bitwise identical tokens /
    # masks either way; False keeps the sequential per-head path.  The
    # same knob selects the branch-head pricing mode
    # (core.profiler.branch_head_cost) when ``price_heads`` is on.
    heads_batched: bool = True
    # Add the branch-head compute term (priced through ``heads_batched``)
    # to est_latency_s' lattice cost.  Off by default: the historical
    # estimate prices trunk layers + hops only.
    price_heads: bool = False
    hint_window: int = 8  # windowed-max bucket hints (1 = last step only)
    bucket_headroom: float = 0.0  # fractional bucket padding vs retries
    slots: int = 8  # request-scheduler KV slots (submit/run/drain API)
    context_len: int = 4096  # scheduler cache capacity per slot
    # Device mesh (+ optional explicit ShardingPolicy): a tier is a mesh
    # slice, not a chip — segments run SPMD (serving.tiers "Mesh-sharded
    # tier segments").  Which tier is priced as sharded lives in each
    # TierSpec's ``devices`` / ``ici_bps``; for the derived K=2 pair,
    # ``tier_devices`` is the (edge, cloud) shard width (None = (1, mesh
    # size)) and ``ici_bps`` the intra-mesh interconnect.
    mesh: Any = None
    sharding: Any = None
    tier_devices: dataclasses.InitVar[tuple[int, int] | None] = None
    ici_bps: dataclasses.InitVar[float] = 0.0
    # Fault plane (serving.faults): a seeded LinkFaultModel arms hop
    # fault injection + breaker-gated retries + exit-head degradation;
    # hop_policy overrides the retry/timeout/breaker defaults.
    fault_model: Any = None
    hop_policy: Any = None

    def __post_init__(self, network, tier_devices, ici_bps):
        cuts = (self.cuts,) if np.ndim(self.cuts) == 0 else self.cuts
        self.cuts = tuple(int(c) for c in cuts)
        if self.tiers is None:
            if len(self.cuts) != 1:
                raise ValueError(f"cuts {self.cuts} need tiers=")
            devices = tier_devices or (
                1, mesh_devices(self.mesh) if self.mesh is not None else 1
            )
            prof = self.cost_profile
            network = network or (prof.network if prof else None)
            self.tiers = (
                TierSpec("edge", prof.gamma if prof else 1.0,
                         network.bandwidth_bps if network else 0.0,
                         devices=devices[0], ici_bps=ici_bps),
                TierSpec("cloud", 1.0, devices=devices[1], ici_bps=ici_bps),
            )
        self.tiers = tuple(self.tiers)
        self._check(self.cuts)
        self.executor = TierExecutor(
            self.cfg, self.params, self._segments(self.cuts),
            compaction=self.compaction,
            simulate_network=self.simulate_network,
            overlap=self.overlap,
            use_kernels=self.use_kernels,
            batched_heads=self.heads_batched,
            hint_window=self.hint_window,
            bucket_headroom=self.bucket_headroom,
            mesh=self.mesh,
            sharding=self.sharding,
            fault_model=self.fault_model,
            hop_policy=self.hop_policy,
        )
        self.params = self.executor.params

    def _check(self, cuts: tuple[int, ...]) -> None:
        if len(cuts) != len(self.tiers) - 1:
            raise ValueError(
                f"{len(self.tiers)} tiers need {len(self.tiers) - 1} cuts, "
                f"got {cuts}"
            )

    def _segments(self, cuts: tuple[int, ...]):
        return segments_for_cuts(
            self.cfg, cuts,
            names=tuple(t.name for t in self.tiers),
            uplinks=tuple(t.uplink_bps for t in self.tiers),
            devices=tuple(t.devices for t in self.tiers),
        )

    def install_cuts(self, cuts: Sequence[int]) -> None:
        """Hot-swap the hop points; unchanged tier segments keep their
        compiled functions (no re-jit)."""
        cuts = tuple(int(c) for c in cuts)
        self._check(cuts)
        if cuts == self.cuts:
            return
        self.executor.install(self._segments(cuts))
        self.cuts = cuts

    # ------------------------------------------------------------------
    def step(
        self, tok: jax.Array, pos, caches: Any, *, active=None
    ) -> tuple[StepReport, Any]:
        res, caches = self.executor.step(tok, pos, caches, active=active)
        rep = StepReport(
            tier_result=res,
            est_latency_s=self._estimate(res),
            transfer_s_per_hop=tuple(
                transfer_seconds(nb, t.uplink_bps)
                for nb, t in zip(res.bytes_per_hop, self.tiers)
            ),
            overflow_retries=self.executor.overflow_retries,
            pipeline_fallbacks=self.executor.pipeline_fallbacks,
        )
        return rep, caches

    def _estimate(self, res: TierStepResult) -> float | None:
        """The plan's expected step time at the installed cuts, with the
        step's *measured* per-branch conditional exit probabilities
        substituted for p (closing the calibration loop).

        A branch's conditional probability is its exits over the
        sequences still alive when they reached it (``res.branch_take``,
        in layer order); a branch the installed plan never evaluates reads
        p = 0, the probability the executed plan experiences.  The cost
        model is the one :func:`paper_route` picks.  On the lattice, a
        compacting runtime is priced padding-honest at the step's batch, a
        pipelined one at its bottleneck stage, and under continuous
        batching the step's live width feeds the occupancy term."""
        prof = self.cost_profile
        if prof is None:
            return None
        batch = res.tokens.shape[0]
        live = res.live or batch
        p = np.zeros(len(prof.t_c))
        alive = float(live)
        for layer in sorted(res.branch_take):
            took = float(res.branch_take[layer].sum())
            p[layer] = took / alive if alive > 0 else 0.0
            alive -= took
        bucketed = self.compaction == "bucketed"
        overlap = self.overlap == "pipelined"
        if paper_route(self.tiers, overlap, batch if bucketed else None):
            prof = Partitioner(prof).with_exit_probs(
                [p[b.after_layer] for b in prof.branches]
            ).profile
            return expected_time(
                paper_profile(prof, self.tiers[0]), self.cuts[0]
            )
        head_cost = (
            branch_head_cost(self.cfg, batch, heads_batched=self.heads_batched)
            if self.price_heads else None
        )
        return expected_time_multitier(
            prof.t_c, prof.alpha, p, list(self.tiers), self.cuts,
            batch=batch if bucketed else None,
            overlap=overlap,
            occupancy=live / batch if bucketed else None,
            head_cost=head_cost,
            branch_layers=self.cfg.branch_layers,
        )
