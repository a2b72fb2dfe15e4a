"""Repartition controller: live exit statistics -> solver -> hot swap.

The paper's loop (Sec. IV-C): exit probabilities are an input-data
property, so the deployment estimates them online and re-runs the
partition optimizer whenever they (or the network) drift.  This module
closes that loop against the unified tier runtime:

    ExitStats.conditional_probs() -> Partitioner / solve_multitier
        -> PartitionedServer.install_cuts

Three triggers are supported:

  * **explicit** — ``update(stats)`` re-solves unconditionally;
  * **drift** — ``observe(report)`` accumulates per-step exit counts from
    the serving loop; every ``every_n_steps`` steps it compares the
    measured exit distribution against the one the installed plan was
    solved for and re-solves when the KL divergence exceeds
    ``kl_threshold`` (``None`` = re-solve on every check);
  * **network** — ``update_tiers(specs)`` (any K) / ``update_network(
    profile)`` (its K=2 form: the one hop's link) re-solve with the last
    measured probabilities when a link changes.

Swaps go through ``TierExecutor.install``, which re-uses the compiled
function of every tier segment whose (layer range, branches) is unchanged
— repartitioning never pays a full re-jit.  Solves price the server's
plan (its ``tiers``) with the cost model
:func:`~repro.serving.partitioned.paper_route` picks from what the plan
is — the paper's Dijkstra for a serial, unbatched K=2 plan over a healthy
hop, the lattice (core.multitier) otherwise.  When ``batch`` is set and
the server compacts, the lattice is the bucketed cost, honest about the
compacted runtime's padding waste.  A server running
``overlap="pipelined"`` is re-solved against the pipeline-bottleneck
steady-state cost (``overlap=True`` in core.multitier) — the optimal cut
generally moves when transfers overlap compute.

Continuous batching: step reports carry the live width and the dead-slot
mask, so observe() (wired as a ``RequestScheduler.on_step`` callback)
counts arrivals over live rows only and feeds a decaying occupancy
estimate into batched solves (``occupancy=`` in core.multitier) — the
controller prices the steady-state live batch, not the nominal one.
``probe_sample_frac`` makes epsilon probes evaluate the extra branch
heads on a sampled sub-batch; the executor reports which rows were
covered and the window stays unbiased.

Hop health (fault plane): when the server runs with a
``LinkFaultModel`` attached, step reports carry ``fault_events`` and a
``degraded_hop``.  ``observe()`` ingests them into per-hop EWMAs —
availability (success fraction of *attempted* hops) and observed
transfer seconds — and, on a breaker state change (a hop's circuit
opening or closing), immediately re-solves with each hop's ``TierSpec``
availability set from the EWMA (0 for a breaker-open hop, which the
solver prices as an unusable link).  The re-solve prices a degraded
hop on the lattice and resets the drift window; the new plan moves the
cut off the sick hop.  A hop the breaker skipped is
*not* an observation (no EWMA update), and a failed half-open probe
updates availability only — never the transfer-time EWMA — so breaker
probing cannot corrupt the cost estimates.  ``fault_resolve=False``
keeps the ingestion but disables the automatic re-solve (call
``apply_hop_health()`` explicitly).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.multitier import TierSpec, solve_multitier
from repro.core.partitioner import Partitioner
from repro.core.types import CostProfile, NetworkProfile
from repro.serving.engine import ExitStats
from repro.serving.partitioned import (
    PartitionedServer,
    paper_profile,
    paper_route,
)

__all__ = ["RepartitionController", "exit_distribution", "exit_drift_kl"]


def exit_distribution(p_k: np.ndarray) -> np.ndarray:
    """Conditional per-branch exit probs -> categorical distribution over
    (exit at branch 1, ..., exit at branch K, reach the main head)."""
    p_k = np.asarray(p_k, float)
    out = np.empty(len(p_k) + 1)
    alive = 1.0
    for j, p in enumerate(p_k):
        out[j] = alive * p
        alive *= 1.0 - p
    out[-1] = alive
    return out


def exit_drift_kl(
    measured_p: np.ndarray, installed_p: np.ndarray, eps: float = 1e-6
) -> float:
    """KL(measured || installed) between the two exit distributions."""
    m = exit_distribution(measured_p) + eps
    q = exit_distribution(installed_p) + eps
    m /= m.sum()
    q /= q.sum()
    return float(np.sum(m * np.log(m / q)))


@dataclasses.dataclass
class RepartitionController:
    """Feeds measured ``p_k`` back through the solver and installs the
    result on the server's K-tier plan.  ``profile`` gives the per-layer
    times and sizes; ``tiers`` the tiers priced.  By default they are the
    server's, except that a K=2 plan's edge prices the profile's own
    ``gamma`` and ``network`` (the paper's contract: the profile states
    the link a solve is for)."""

    server: PartitionedServer
    profile: CostProfile
    tiers: list[TierSpec] | None = None
    kl_threshold: float | None = None  # drift gate for observe()-driven solves
    every_n_steps: int = 0  # decode-loop hook cadence (0 = explicit only)
    batch: int | None = None  # priced when the server compacts (bucketed)
    window_steps: int = 256  # drift-window decay horizon (see observe())
    # Epsilon exploration schedule: every ``explore_every_n`` observed
    # steps, request a PROBE step from the executor — the next decode step
    # evaluates every branch head (would-exit masks reported, trajectory
    # untouched), so branches the installed plan discarded keep fresh
    # measured probabilities instead of carrying the installed estimate.
    # 0 disables exploration.
    explore_every_n: int = 0
    # Fraction of the batch a probe step evaluates the extra branch heads
    # on (1.0 = every row).  Sampled probes price exploration at a
    # sub-batch of branch-head FLOPs; the executor reports which rows were
    # covered and observe() counts arrivals over those rows only, so the
    # conditional estimates stay unbiased.
    probe_sample_frac: float = 1.0
    # Steady-state occupancy override for continuous-batching servers
    # (None = track the live width from observed step reports).  Solves
    # price the occupancy-weighted expected batch, not the nominal one.
    occupancy: float | None = None
    # Hop-health ingestion (fault plane): EWMA smoothing factor for the
    # per-hop availability / observed-transfer estimates, and whether a
    # breaker state change triggers an automatic availability re-solve.
    hop_alpha: float = 0.3
    fault_resolve: bool = True

    def __post_init__(self):
        if self.tiers is None:
            self.tiers = list(self.server.tiers)
            if len(self.tiers) == 2:
                self.tiers[0] = dataclasses.replace(
                    self.tiers[0], gamma=self.profile.gamma,
                    uplink_bps=self.profile.network.bandwidth_bps,
                )
        self.tiers = list(self.tiers)
        k = len(self.server.cfg.branch_layers)
        # Per-branch (arrivals, exits) over the current window.  A branch
        # the installed plan never evaluates (discarded at a cut, or inside
        # the final tier) accrues no arrivals — its probability is then
        # carried over from the installed estimate rather than read as 0,
        # so re-solves don't lock in on fictitious p=0 branches.  (A plan
        # that evaluates *no* branches observes nothing at all; escaping
        # that state needs an explicit update() from a K=1 calibration
        # pass or a network trigger — drift alone cannot see it.)
        self._arrivals = np.zeros(k, np.float64)
        self._exits = np.zeros(k, np.float64)
        self._steps_observed = 0
        self._window_age = 0
        self._installed_p: np.ndarray | None = None
        if not 0.0 < self.probe_sample_frac <= 1.0:
            raise ValueError(
                f"probe_sample_frac must be in (0, 1]: {self.probe_sample_frac}"
            )
        # Decaying estimate of the live fraction (continuous batching);
        # lock-step reports keep it at 1.
        self._occ_est: float | None = None
        # Per-hop health state (fault plane).  Keyed by hop index (tier
        # boundary j, stable across repartitions).  ``_hop_avail`` is the
        # EWMA success fraction over *attempted* hops (breaker-skipped
        # hops are not observations); ``_hop_xfer`` the EWMA of observed
        # per-hop simulated transfer seconds over successful non-empty
        # shipments only (a failed half-open probe never touches it);
        # ``_hop_open`` the hops whose breaker is currently open (priced
        # as availability 0 by re-solves).
        self._hop_avail: dict[int, float] = {}
        self._hop_xfer: dict[int, float] = {}
        self._hop_open: set[int] = set()
        self.fault_resolves = 0

    # ------------------------------------------------------------ solving
    def _solve_occupancy(self) -> float | None:
        """The live-width fraction batched solves should price: the
        explicit ``occupancy`` override, else the decaying estimate from
        observed continuous-batching step reports, else None (nominal)."""
        occ = self.occupancy if self.occupancy is not None else self._occ_est
        if occ is None:
            return None
        return float(min(max(occ, 1e-6), 1.0))

    def _priced_tiers(self) -> list[TierSpec]:
        """The plan's tiers with each hop's availability from the health
        EWMAs where observed (0 for a breaker-open hop)."""
        last = len(self.tiers) - 1
        return [
            t if j == last else dataclasses.replace(
                t,
                availability=(
                    0.0 if j in self._hop_open
                    else self._hop_avail.get(j, t.availability)
                ),
            )
            for j, t in enumerate(self.tiers)
        ]

    def solve(self, p_k: np.ndarray) -> tuple[int, ...]:
        """Optimal cut vector for the profile with live exit probs, priced
        by the model :func:`paper_route` picks.  A server running
        ``overlap="pipelined"`` is solved against the pipeline-bottleneck
        steady-state cost (the optimal cut can move under overlap), a
        serial server against the serial chain sum.  Batched solves price
        the occupancy-weighted steady-state live width (see
        ``occupancy``); a mesh-sharded tier's shard width and interconnect
        ride in its ``TierSpec``, and a degraded hop's availability pushes
        the cut toward keeping work before it."""
        prof = Partitioner(self.profile).with_exit_probs(p_k).profile
        p = prof.branch_exit_probs()
        tiers = self._priced_tiers()
        overlap = self.server.overlap == "pipelined"
        batch = self.batch if self.server.compaction == "bucketed" else None
        if paper_route(tiers, overlap, batch):
            plan = Partitioner(paper_profile(prof, tiers[0])).solve()
            return (plan.split_layer,)
        plan = solve_multitier(
            prof.t_c, prof.alpha, p, tiers,
            batch=batch,
            overlap=overlap,
            occupancy=self._solve_occupancy() if batch is not None else None,
        )
        return plan.cut_after

    def _install(self, p_k: np.ndarray) -> tuple[int, ...]:
        cuts = self.solve(p_k)
        self._installed_p = np.asarray(p_k, float)
        # Start a fresh measurement window: drift is judged against the
        # traffic seen *under the new plan*, and the lifetime-average bias
        # (old regimes drowning out new ones) is bounded by the window.
        self._arrivals[:] = 0
        self._exits[:] = 0
        self._window_age = 0
        self.server.install_cuts(cuts)
        return self.server.cuts

    def update(self, stats: ExitStats) -> tuple[int, ...]:
        """Re-solve from live stats and hot-swap the split if it moved.
        Returns the installed cut vector."""
        return self._install(stats.conditional_probs())

    # ----------------------------------------------------- drift detection
    def observe(self, report) -> tuple[int, ...] | None:
        """Decode-loop hook: accumulate one step's exit outcome (any report
        carrying ``branch_take`` + ``tokens``).  Every ``every_n_steps``
        observed steps, re-solve if the measured exit distribution drifted
        past ``kl_threshold``.  Returns the new cuts when a swap happened.

        Continuous-batching reports carry ``active``/``live``: dead slots
        never count as arrivals, and the live width feeds the decaying
        occupancy estimate batched solves price.  Sampled probe reports
        carry ``branch_probe_mask``: a probed branch's arrivals are
        counted over its covered rows only, so sampling never reads an
        unevaluated head as "arrived without exiting".  (When several
        probed branches sit on different compacted segments their
        coverage sets can differ; a row uncovered at an earlier branch
        whose counterfactual exit is therefore unknown still counts at a
        later branch it is covered on — a second-order conditioning
        approximation that vanishes at ``probe_sample_frac=1``.)
        """
        batch = report.tokens.shape[0]
        active = getattr(report, "active", None)
        alive = (
            np.ones((batch,), bool) if active is None
            else np.asarray(active, bool).copy()
        )
        probe_cover = getattr(report, "branch_probe_mask", {}) or {}
        for j, layer in enumerate(self.server.cfg.branch_layers):
            take = report.branch_take.get(layer)
            if take is None:
                continue  # branch not evaluated under this plan (nor probed)
            cover = probe_cover.get(layer)
            counted = alive if cover is None else (alive & cover)
            self._arrivals[j] += float(counted.sum())
            # Intersect with the running alive mask: on a probe step an
            # earlier (discarded) branch's would-exit rows have left
            # `alive`, but the executor computed this branch's take under
            # *plan* semantics, so the masks can overlap — counting the
            # overlap would push the conditional estimate past 1.
            self._exits[j] += float((take & counted).sum())
            alive &= ~take
        live = getattr(report, "live", None)
        if live:
            occ = live / batch
            self._occ_est = (
                occ if self._occ_est is None
                else 0.9 * self._occ_est + 0.1 * occ
            )
        self._steps_observed += 1
        self._window_age += 1
        if (
            self.explore_every_n
            and self._steps_observed % self.explore_every_n == 0
        ):
            # Epsilon step: the next decode step probes every branch head
            # (on a probe_sample_frac sub-batch).  Its report carries
            # would-exit masks for the discarded branches too, which the
            # loop above folds into the window.
            self.server.executor.probe_next = True
            self.server.executor.probe_sample_frac = self.probe_sample_frac
        if self._window_age >= self.window_steps:
            # Exponential decay: halve the window so the measured
            # distribution tracks regime changes in O(window_steps) steps
            # instead of degrading with controller lifetime.
            self._arrivals *= 0.5
            self._exits *= 0.5
            self._window_age = 0
        fault_cuts = self._ingest_faults(report)
        if fault_cuts is not None:
            # A breaker state change re-solved and swapped the plan (which
            # also reset the drift window); it takes precedence over the
            # periodic drift check this step.
            return fault_cuts
        if self.every_n_steps and self._steps_observed % self.every_n_steps == 0:
            return self.maybe_update()
        return None

    # -------------------------------------------------------- hop health
    def _ingest_faults(self, report) -> tuple[int, ...] | None:
        """Fold one step's fault-plane outputs into the per-hop health
        EWMAs; re-solve (availability-aware) on a breaker state change.

        Only *attempted* hops are observations: a hop the breaker skipped
        (``breaker_skip`` event), and hops downstream of the broken one
        (never dispatched), leave both EWMAs untouched.  Transfer seconds
        are ingested only from successful non-empty shipments, so a failed
        half-open probe moves availability but can never corrupt the
        transfer-time estimate.
        """
        events = getattr(report, "fault_events", None)
        if not events and getattr(report, "degraded_hop", None) is None:
            return None
        events = events or ()
        broken = getattr(report, "degraded_hop", None)
        skipped = {e.hop for e in events if e.kind == "breaker_skip"}
        failed_hops = {e.hop for e in events if e.kind == "exhausted"}
        nb = getattr(report, "bytes_per_hop", ()) or ()
        sim = getattr(report, "sim_transfer_s", ()) or ()
        a = self.hop_alpha
        for j in range(len(nb)):
            if j in skipped or (broken is not None and j > broken):
                continue  # not attempted: no observation
            ok = j not in failed_hops
            prev = self._hop_avail.get(j, 1.0)
            self._hop_avail[j] = (1.0 - a) * prev + a * (1.0 if ok else 0.0)
            if ok and float(nb[j]) > 0 and j < len(sim) and sim[j] > 0:
                prev_x = self._hop_xfer.get(j)
                self._hop_xfer[j] = (
                    float(sim[j]) if prev_x is None
                    else (1.0 - a) * prev_x + a * float(sim[j])
                )
        resolve = False
        for e in events:
            if e.kind == "breaker_open" and e.hop not in self._hop_open:
                self._hop_open.add(e.hop)
                resolve = True
            elif e.kind == "breaker_closed" and e.hop in self._hop_open:
                self._hop_open.discard(e.hop)
                # The link recovered: forgive the failure history so the
                # re-solve prices it healthy instead of replaying the EWMA
                # tail of the outage.
                self._hop_avail[e.hop] = 1.0
                resolve = True
        if resolve and self.fault_resolve:
            return self.apply_hop_health()
        return None

    def hop_health(self) -> dict[int, dict[str, float | bool]]:
        """Per-hop health snapshot: availability EWMA, observed-transfer
        EWMA (None until a successful shipment), breaker-open flag."""
        hops = set(self._hop_avail) | set(self._hop_xfer) | self._hop_open
        return {
            j: {
                "availability": self._hop_avail.get(j, 1.0),
                "transfer_s": self._hop_xfer.get(j),
                "open": j in self._hop_open,
            }
            for j in sorted(hops)
        }

    def apply_hop_health(self) -> tuple[int, ...]:
        """Re-solve with each hop's ``TierSpec.availability`` set from the
        health EWMAs (0 for a breaker-open hop) and hot-swap the result.
        Fires automatically on breaker state changes when
        ``fault_resolve`` is set; callable explicitly otherwise.

        The server's tier specs take the new availabilities (its estimate
        prices them); the drift window resets as for any re-solve.  Note
        that once the re-solve moves the cut off a sick hop, that hop is
        no longer exercised — its breaker never half-opens again, so
        recovery needs an explicit ``update_tiers`` with the restored
        specs (or ``fault_resolve=False`` with manual control)."""
        self.fault_resolves += 1
        self.server.tiers = tuple(
            dataclasses.replace(t, availability=priced.availability)
            for t, priced in zip(self.server.tiers, self._priced_tiers())
        )
        return self._install(self._best_p())

    def measured_probs(self) -> np.ndarray:
        """Conditional p_k per branch from the observed window.  Branches
        with no observed arrivals fall back to the installed estimate."""
        out = []
        for j in range(len(self._arrivals)):
            if self._arrivals[j] > 0:
                out.append(self._exits[j] / self._arrivals[j])
            elif self._installed_p is not None:
                out.append(float(self._installed_p[j]))
            else:
                out.append(0.0)
        return np.asarray(out)

    def drift_kl(self) -> float:
        """KL between measured and installed exit distributions
        (+inf when nothing was installed through this controller yet)."""
        if self._installed_p is None:
            return float("inf")
        return exit_drift_kl(self.measured_probs(), self._installed_p)

    def maybe_update(self, force: bool = False) -> tuple[int, ...] | None:
        """Re-solve from the observed counts if drift warrants it."""
        if self._arrivals.sum() == 0:
            return None  # nothing observed under this plan yet
        drifted = (
            force
            or self.kl_threshold is None
            or self.drift_kl() > self.kl_threshold
        )
        if not drifted:
            return None
        return self._install(self.measured_probs())

    # ------------------------------------------------------ network drift
    def update_network(self, network: NetworkProfile) -> tuple[int, ...]:
        """The K=2 form of :meth:`update_tiers`: the one hop's link
        changed.  Re-solve with the last measured (or installed) exit
        probs against the new bandwidth."""
        if len(self.tiers) != 2:
            raise ValueError(
                f"update_network re-prices the one hop of a K=2 plan; this "
                f"plan has {len(self.tiers)} tiers: use update_tiers"
            )
        self.profile = dataclasses.replace(self.profile, network=network)
        if self.server.cost_profile is not None:
            self.server.cost_profile = self.profile
        edge, cloud = self.tiers
        return self.update_tiers([
            dataclasses.replace(edge, uplink_bps=network.bandwidth_bps), cloud
        ])

    def update_tiers(self, tiers: list[TierSpec]) -> tuple[int, ...]:
        """The plan's tier specs (uplinks, speeds, availability) changed:
        re-solve and hot-swap."""
        self.tiers = list(tiers)
        self.server.tiers = tuple(tiers)
        cuts = self._install(self._best_p())
        # Refresh segments even when the cuts didn't move: the new uplinks
        # must reach the executor's per-hop byte/latency accounting.
        self.server.executor.install(self.server._segments(self.server.cuts))
        return cuts

    def _best_p(self) -> np.ndarray:
        """Most recent exit-prob estimate: measured > installed > zeros."""
        if self._arrivals.sum() > 0:
            return self.measured_probs()
        if self._installed_p is not None:
            return self._installed_p
        return np.zeros(len(self.server.cfg.branch_layers))
