"""Unified K-tier execution runtime for BranchyNet serving.

One decode step crosses up to K tiers (device -> edge -> ... -> cloud).
Every tier runs a contiguous trunk segment, evaluates the side branches
that live strictly inside it, and ships survivors across its uplink.  The
monolithic :class:`~repro.serving.engine.ServingEngine` (K=1) and
:class:`~repro.serving.partitioned.PartitionedServer` (the paper's K=2
and any K >= 3 plan) are thin configurations of the same
:class:`TierExecutor`.

Branch placement follows the paper's semantics (Sec. IV-B, Fig. 2(c)):

  * a branch sitting exactly at a cut is discarded — the residual stream
    ships immediately;
  * the final tier evaluates no side branches (the cloud classifies at the
    output layer), except in the single-tier case where the whole
    BranchyNet runs in one place.

Survivor compaction (compact -> run -> scatter)
-----------------------------------------------
The paper's cost model banks on downstream work shrinking with exits, so
downstream tiers must not burn FLOPs on masked-out rows.  With
``compaction="bucketed"`` (the default) every downstream tier segment is a
single fused jitted call that

  1. **compacts**: a stable device-resident ``argsort`` of the exit mask
     orders survivors first; the leading ``bucket`` rows (survivors plus,
     if the bucket is larger, already-exited padding rows) are gathered
     into a dense sub-batch — hidden state only.  KV caches stay
     full-batch resident: the sub-batch reads/writes its rows *in place*
     through the ``rows`` plumbing of :func:`repro.models.model.run_trunk`
     (per-sequence slot validity masks the skipped rows' holes later);
  2. **runs** the tier's trunk layers, branches and (on the last tier)
     the head on the ``(bucket, 1, d)`` sub-batch, so tier FLOPs scale
     with the padded survivor count instead of the full batch;
  3. **scatters** tokens / exit masks / entropies / logits back to
     original batch order — so the step still ends in exactly ONE
     device->host sync of the packed full-batch pytree, and
     :class:`TierStepResult`'s contract is unchanged.

Pipelined overlap (``overlap="pipelined"``)
-------------------------------------------
Serial mode charges one decode step the full chain latency: every tier's
compute plus, under ``simulate_network``, every hop's transfer, back to
back.  A real edge->cloud deployment overlaps tier j's uplink transfer
with tier j+1's compute and double-buffers decode steps: the edge starts
token t+1 as soon as token t is emitted, while token t's hidden-state
handoffs are still draining down the chain.  ``overlap="pipelined"``
reproduces that steady state.  Tier segments are dispatched eagerly (jax
dispatch is asynchronous, so tier j+1's jitted segment is enqueued the
moment tier j's hidden-state handoff is traced — nothing blocks on the
simulated wire), and the simulated per-hop transfers are moved off the
step's critical path onto per-hop link clocks: hop j's transfer for token
t occupies the link for ``transfer_j`` seconds starting when both the
payload has arrived (token t cleared hop j-1) and the link is free (token
t-1's transfer finished).  A step returns once the *previous* token's
transfers have fully drained (double-buffer depth 1), so steady-state
step wall time is the pipeline bottleneck ``max_j(compute_j,
transfer_j)`` instead of the serial sum.

One single-host caveat: every tier's segment runs on the *same* device
here, so tier computes serialize and the measured steady state is
``max(sum_j compute_j, max_j transfer_j)``.  The cost model's
``overlap=True`` bottleneck takes the max over *per-tier* computes — that
is the real multi-host deployment the solver plans for, where tier j and
tier j+1 compute concurrently on different machines.  The two agree
whenever transfers dominate (the regime the benchmark smoke asserts); on
compute-bound profiles the simulator cannot deliver the compute overlap
the model credits.

The pipelined contract extends the one-sync invariant: still exactly one
fetch per emitted token (the single device->host sync is unchanged, and
tokens / exit masks / per-hop byte accounting are bitwise identical to
serial mode — pipelining reorders only the simulated sleeps, never the
computation).  An overflow-retry step falls back to serial for that step:
the pipeline is drained first, the step re-runs with measured buckets and
pays its transfers inline (counted in ``pipeline_fallbacks``), and
pipelining resumes on the next step.  ``install`` (a repartition) and
``drain()`` also drain the pipeline so no old-plan transfer overlaps the
new plan.

Fault plane and degraded steps (``fault_model`` / ``hop_policy``)
-----------------------------------------------------------------
A hop in a real deployment drops, flaps and slows down; the runtime's
answer is the BranchyNet one — *answer from the deepest exit head below
the broken link* — rather than an exception.  Attaching a
:class:`~repro.serving.faults.LinkFaultModel` (and optionally a
:class:`~repro.serving.faults.HopPolicy`) arms a two-phase fault plane:

  * **Phase A (pre-dispatch, host-side, sync-free):** before any segment
    is dispatched, every hop the plan would cross is health-checked in
    order — circuit-breaker gate, then up to ``1 + max_retries``
    simulated attempts (each failing on a scripted flap, a sampled drop,
    or a worst-case-payload transfer-time estimate exceeding
    ``timeout_s``; retries charge exponential backoff).  All decisions
    are deterministic functions of ``(seed, fault-step, hop)`` — never
    of the batch's live trajectory — so fault traces replay bit-exactly
    and an overflow retry re-uses the same plan.
  * **Phase B (post-sync):** under ``simulate_network`` the surviving
    hops charge their (multiplier-scaled, spike-added) transfer time
    plus any retry overhead to the wall clock; a broken hop charges only
    the overhead its failed attempts burned.

The degraded-step contract:

  * **Healthy steps are bitwise untouched.**  With no fault model the
    code path is identical to before; with a benign model attached
    (no flaps/drops/spikes, multiplier 1) every token, exit mask, cache
    write and byte count is bitwise identical to a run without it.
  * On breaker-open or retry exhaustion at hop ``j``, the step runs only
    the segments up to the one holding the **deepest exit head at or
    below hop j's cut** (a branch sitting exactly at the cut — normally
    discarded — is re-enabled as the fallback head) and every still-live
    row is finalized from that head via the normal per-branch exit
    masking: rows that exited upstream keep their exact tokens, forced
    rows emit the fallback head's argmax.  The step still performs
    exactly ONE device->host sync, still bumps the cache clock once, and
    reports the forced rows in ``TierStepResult.degraded`` with
    ``exit_tier`` = the fallback tier.  Forced exits are *not* counted
    in ``branch_take`` (controller exit-probability estimates only ever
    see genuine threshold exits).
  * If no exit head exists at or below the broken hop, nothing useful
    can be computed: the step dispatches nothing (no sync), emits no
    tokens, and reports every live row in ``TierStepResult.failed`` —
    the scheduler retires (or requeues) those requests with a terminal
    ``failed`` status and reclaims their KV slots.
  * ``fault_events`` carries the replayable per-step trace (attempts,
    retries, breaker transitions) and ``degraded_hop`` the broken hop;
    the :class:`~repro.serving.controller.RepartitionController` ingests
    both to EWMA per-hop health and re-solve toward a cut that avoids
    the sick link (``TierSpec.availability`` prices it in the lattice).

Zero-uplink hops under ``simulate_network`` are part of the same
contract: a hop that must ship bytes but has no usable ``uplink_bps``
raises :class:`~repro.serving.faults.LinkDownError` when no fault model
is attached (previously it silently slept 0 s — a dead link looked
free), and degrades through the fault plane when one is.

Bucket ladder and the one-sync invariant.  jit needs static shapes, so
sub-batches are padded to :func:`repro.core.multitier.bucket_ladder`
(powers of two, plus the full batch).  The bucket for step ``t`` is chosen
host-side from step ``t-1``'s survivor counts (fetched in the same single
sync) — no extra mid-step sync.  Step 0 runs full-batch buckets.  If a
step's true survivors overflow the planned bucket (exit-rate spike), the
host detects it from the fetched masks and *re-runs the whole step* from
the entry caches with measured buckets until nothing overflows (at most K
runs): results are always bitwise faithful, at the cost of one extra sync
per (counted) ``overflow_retries`` iteration.

The resident cache (donation and one layout)
--------------------------------------------
Every program that returns the cache — each decode segment variant, the
admission prefill, ``reset_rows`` — takes it *donated*: XLA updates it in
place and the caller's handle is consumed (``step``, ``prefill_rows`` and
``reset_rows`` return the live cache; the one handed in must not be used
again).  Where the compiled Pallas decode kernel reads the cache
(``use_kernels`` on the TPU), :meth:`TierExecutor.new_caches` also
stores K/V with head_dim zero-padded to the TPU's 128 lanes
(:func:`repro.models.model.pad_kv_lanes`): the TPU's default layout for
that shape is the one the kernel and the in-place slot writes read, so no
program re-lays out the whole cache on entry or exit (for phi3's
head_dim of 96 the default layout otherwise puts the slots in the lanes).
Elsewhere (the jnp path, sharded executors, interpret mode) the cache
keeps its shape and is donated only.

The weights follow the same rule: where the head width is not a multiple
of the lanes, ``TierExecutor.params`` holds the served tree of
:func:`repro.models.model.served_params` (the q/k/v projections stored
``[L, out, in]`` as ``wq_t`` / ``wk_t`` / ``wv_t``), so no program copies
the three weight stacks into the layout its dots read.  Every assignment
to ``params`` derives it (``served_transposed`` names the leaves).

Since an overflow re-run can no longer read the entry caches, the entry
segment's program first gathers what the step can write (slot
``pos % C`` of every row in every layer, the recurrent state and the
step counters: :func:`repro.models.model.snapshot_decode_writes`, a few
MB) and the re-run starts from a small donated restore of those entries
(``cache_restores`` counts them; it equals ``overflow_retries``), bitwise
the step's entry caches.  The restore program is compiled at the first
step of each cache shape, with the step's own programs.

Defined divergence from the masked path: an exited sequence contributes
no downstream-tier KV for that step (the masked path, which runs every
row everywhere, does write it).  Downstream attention masks the hole via
per-sequence slot validity, so the semantics are deterministic and
independent of bucket/padding choices; single-step outputs are bitwise
identical to the masked path, multi-step outputs are identical whenever
an exited sequence does not later re-enter the downstream tiers.

Kernel-backed hot path (``use_kernels``)
----------------------------------------
``use_kernels=None`` (auto) turns the Pallas kernel suite on on TPU and
keeps the pure-jnp lowering elsewhere; ``True`` forces the kernels (CPU
runs them in interpret mode — the equivalence tests).  Inside every
segment function the flag swaps three hot spots, leaving the dataflow,
the one-sync contract and the emitted trajectory unchanged:

  * decode attention runs :func:`repro.kernels.ops.flash_decode`, which
    scalar-prefetches the survivor ``rows`` map and streams those rows
    straight out of the full-batch resident KV cache (the jnp path
    gathers ``cache["k"][rows]`` and hopes XLA fuses it);
  * the per-branch BranchyNet confidence test runs the fused
    :func:`repro.kernels.ops.entropy_exit_argmax` kernel — normalized
    entropy, threshold flag and exit token in ONE pass over the (B, V)
    branch logits, so exiting rows never materialize a separate
    softmax + argmax;
  * Mamba2 decode steps run :func:`repro.kernels.ops.ssd_update` with the
    same ``rows`` plumbing into the resident SSM state.

Kernels recompile per *bucket* exactly like the jnp segment functions
(the (spec, bucket) cache below); a survivor-count change within a bucket
never re-traces either path.

Batched exit heads (``batched_heads``)
--------------------------------------
A tier that keeps K branches historically evaluated them one at a time:
K branch-norm + unembedding projections (each re-streaming the shared
(D, V) unembedding) and K entropy/argmax decisions.  With
``batched_heads=True`` (the default) a segment evaluates ALL of its
heads jointly:

  * the kept branches' hiddens are stacked to (K, B, D); the per-branch
    norm params are applied to the stack (rmsnorm scales are gathered to
    (K, 1, D) and broadcast; nonparametric norms are parameter-free) and
    ONE einsum against the shared unembedding yields (K, B, V) logits —
    the unembedding's bandwidth is paid once, amortized over K heads
    (:func:`repro.models.model.branch_logits_stacked`);
  * the K confidence tests run as ONE decision — the multi-head fused
    :func:`repro.kernels.ops.entropy_exit_argmax_heads` kernel under
    ``use_kernels`` (grid gains a K dimension; per-head thresholds ride
    in SMEM), or one vectorized jnp pass otherwise.  Mesh-sharded
    segments always take the jnp lowering (``resolve_use_kernels``'s
    ``sharded=True`` contract), which partitions cleanly under SPMD.

The layout contract: heads are stacked in ascending branch-layer order;
each head's (entropy, flag, argmax) row is *independent* of the running
exit mask, so first-exit precedence is applied after the joint decision
exactly as the sequential loop applied it (``take = flag & ~exited`` per
head, in layer order) — tokens, exit masks, ``branch_take`` /
``branch_probe_mask`` and degraded-mode forced finalization are all
bitwise identical to ``batched_heads=False`` (asserted in
``tests/test_batched_heads.py``); the ``branch_entropy`` float
diagnostic matches to within a few ULP (XLA may tile the stacked
``(K*B, D) x (D, V)`` projection GEMM differently from the per-head
one on some device configurations).

Probe-cost semantics are unchanged: an all-heads probe step folds the
probe heads into the same stacked projection (kept + probe heads = one
launch), while a *sampled* probe (``probe_sample_frac`` < 1) stacks the
probe heads over the sampled rows only as a second, smaller joint
evaluation — probe FLOPs still price at the sampled sub-batch, never the
full batch.  The cost layer prices all of this through
:func:`repro.core.profiler.branch_head_cost` (``heads_batched=`` picks
the joint vs per-head roofline) feeding the ``head_cost`` term of
:func:`repro.core.multitier.expected_time_multitier`.

Bucket hints.  The bucket planned for a downstream tier comes from a
*windowed max* of the last ``hint_window`` steps' survivor counts
(default 8) inflated by ``bucket_headroom`` (a fraction; 0.0 = exact
fit).  ``hint_window=1, bucket_headroom=0.0`` reproduces the historical
last-step-only exact-fit policy; wider windows / headroom trade padding
waste for fewer ``overflow_retries`` under fluctuating exit rates.

Probe steps (exploration).  A plan only evaluates the branches it kept,
so drift detection is blind to discarded branches.  Setting
``probe_next = True`` (the :class:`RepartitionController`'s epsilon
schedule does this every ``explore_every_n`` steps) makes the *next*
step evaluate every ``cfg.branch_layers`` head — the extra branches are
report-only: their would-exit masks and entropies appear in
``branch_take`` / ``branch_entropy`` so the controller refreshes their
probabilities, but exits, tokens, caches and byte accounting are bitwise
those of a normal step.

Segment functions are cached by ``(spec, bucket)`` where spec is
``(layer_lo, layer_hi, branches, head, probe)``: a repartition that moves
one cut re-uses the jitted callables of every unchanged tier segment, and
a survivor-count change *within* a bucket re-jits nothing
(``trace_counts`` exposes this for tests).  Each program is named after its
key — ``decode_l{lo}_{hi}_b{bucket|full}`` (plus ``_probe`` /
``_degrade{layer}``), ``prefill_p{plen}_n{n}``, ``reset_rows_n{n}`` — and
the step's host phases carry profiler spans (:mod:`repro.serving.spans`),
so a trace charges device time and idle gaps to both.

Mesh-sharded tier segments (``mesh`` / ``sharding``)
----------------------------------------------------
A tier in a production fleet is a pod slice, not a chip.  Passing a device
``mesh`` (optionally with an explicit :class:`~repro.sharding.policy
.ShardingPolicy`; default :func:`~repro.sharding.policy.make_policy`)
turns every segment function into an SPMD program:

  * **params** are placed once at construction under the policy's
    per-architecture ``param_spec`` rules (attention heads / FFN hidden /
    MoE expert dim / vocab on the ``model`` axis, FSDP over ``data`` where
    configured, indivisible dims cleanly replicated);
  * **KV/SSM caches** are created by :meth:`TierExecutor.new_caches`
    under ``cache_spec`` — kv-heads on ``model`` when divisible, else
    head_dim — each device making only its own shard; sharded layouts
    then persist across decode steps through XLA's propagation;
  * **activations** inside every segment fn are constrained through the
    :mod:`repro.sharding.ctx` context, which the model stack's existing
    ``constrain`` call sites pick up at trace time;
  * **kernels** resolve to the pure-jnp lowering
    (``resolve_use_kernels(..., sharded=True)``): the Pallas kernels are
    single-device programs and must not see a mesh-global batch.

The sharded-segment contract: every unsharded invariant holds — exactly
one host sync per decode step, survivor compaction with the same bucket
ladder, the (spec, bucket) segment cache (hot-swapping a cut never
re-jits an unchanged sharded segment), per-request trajectory isolation —
and the *token/exit-mask trajectory* matches the single-device runtime.
Logits are not bitwise identical: SPMD partial-sum all-reduces reorder
float accumulation, so equivalence is at the argmax/threshold-decision
level (the sharded equivalence tests pin exact token and exit-mask
trajectories over full decode runs).

On one host every segment shares the same mesh (the single-host SPMD
caveat, like the pipelined-overlap one above): "which tier is sharded" is
a cost-model property carried by ``TierSegment.devices`` /
``TierSpec.devices``.  The cost model prices a sharded tier as per-layer
compute scaled ``1/devices`` plus an intra-tier ring-all-reduce term
``2 * 2*(d-1)/d * alpha_i / ici_bps`` per layer (two collectives — the
attention-out and MLP-down partial sums), so ``solve_multitier`` can
trade "shard tier j over d chips" against "add a hop"; see
:mod:`repro.core.multitier`.

Continuous batching (request slots)
-----------------------------------
The executor also serves as the data plane of the request scheduler
(:mod:`repro.serving.scheduler`): the batch dimension becomes ``B`` KV
*slots* whose occupants change over time.  Three extensions make that
possible without ever reshaping a cache or re-jitting a segment:

  * ``step(..., pos=(B,), active=(B,))`` — per-sequence absolute
    positions (each request decodes at its own RoPE position and ring
    slot) and a live mask: dead slots enter the step pre-exited, so the
    entry tier masks them and downstream compaction drops them — the
    bucket ladder naturally tracks live occupancy;
  * :meth:`TierExecutor.prefill_rows` — admit waiting prompts by
    prefilling them *into* freed cache rows in place (each row ends
    exactly as a fresh solo prefill: stale slots reset to empty);
  * :meth:`TierExecutor.reset_rows` — optional retirement hygiene that
    invalidates a row's slots without touching its neighbors.

The invariant all three preserve: a request's token/exit trajectory is
bitwise identical to running it alone from its admission state,
independent of which slot it recycled or who occupied it before (the
scheduler tests pin this for K in {1, 2, 3}, compaction on/off, and the
kernel path in interpret mode).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.calibration import normalized_entropy
from repro.core.multitier import bucket_for, bucket_ladder
from repro.kernels import ops as kernel_ops
from repro.serving import spans
from repro.serving.faults import (
    CircuitBreaker,
    FaultEvent,
    HEALTHY,
    HopOutcome,
    HopPolicy,
    LinkDownError,
    LinkFaultModel,
    attempt_hop,
)
from repro.launch.mesh import mesh_devices
from repro.models.layers import norm_apply
from repro.sharding.ctx import activation_sharding
from repro.sharding.policy import make_policy
from repro.models.model import (
    _unembed,
    branch_logits_per_head,
    branch_logits_stacked,
    embed_decode,
    init_caches,
    pad_kv_lanes,
    prefill,
    restore_decode_writes,
    run_trunk,
    served_params,
    snapshot_decode_writes,
    trunk_layout,
)

__all__ = [
    "TierSegment",
    "TierStepResult",
    "StepReport",
    "TierExecutor",
    "HopCompaction",
    "segments_for_cuts",
    "bytes_per_sequence",
    "transfer_seconds",
    "TOKEN_ID_BYTES",
    "LinkDownError",
]

#: Per-sequence payload of a hop taken before any trunk layer ran: the raw
#: token id (the prompt itself crossed at prefill time).
TOKEN_ID_BYTES = 4.0


@dataclasses.dataclass(frozen=True)
class TierSegment:
    """One tier's share of the trunk: layers ``[layer_lo, layer_hi)``
    (absolute, 0-based), the 1-based branch collect points it evaluates,
    the uplink to the next tier (bits/s; ``None`` on the last tier), and
    the tier's shard width (``devices > 1`` = the tier is a mesh slice;
    carried into the segment-fn cache key so a repartition that changes a
    tier's width recompiles exactly that tier)."""

    name: str
    layer_lo: int
    layer_hi: int
    branches: tuple[int, ...] = ()
    uplink_bps: float | None = None
    devices: int = 1

    @property
    def is_empty(self) -> bool:
        return self.layer_hi == self.layer_lo

    def spec(self, head: bool) -> tuple:
        """Cache key for the compiled segment function."""
        return (self.layer_lo, self.layer_hi, self.branches, head,
                self.devices)


@dataclasses.dataclass(frozen=True)
class HopCompaction:
    """Per-hop compaction accounting: who survived, what shape ran."""

    survivors: int  # true survivors crossing the hop
    bucket: int  # static sub-batch width the downstream tier ran

    @property
    def padded_waste(self) -> int:
        """Padding rows the downstream tier computed but did not need."""
        return self.bucket - self.survivors


def transfer_seconds(nbytes: float, uplink_bps: float | None) -> float:
    """Wall seconds to ship ``nbytes`` over a hop, with the runtime's
    zero-uplink policy: an unset/zero bandwidth reports 0.0 for *byte
    accounting* (the hop is unaccounted, not priced infinite — the cost
    model prices unusable hops at inf via
    :func:`repro.core.multitier._hop_seconds`).  The wall-clock
    ``simulate_network`` path never reaches here with a dead uplink and
    a nonzero payload: :meth:`TierExecutor.step` raises
    :class:`~repro.serving.faults.LinkDownError` (no fault model) or
    degrades through the fault plane (model attached) instead of
    pricing the dead hop free."""
    if not uplink_bps or uplink_bps <= 0.0:
        return 0.0
    return nbytes * 8.0 / uplink_bps


def bytes_per_sequence(cfg: ModelConfig, cut_layer: int) -> float:
    """Payload one surviving sequence ships at a cut after ``cut_layer``
    (1-based; 0 = before any trunk layer -> raw token id)."""
    if cut_layer == 0:
        return TOKEN_ID_BYTES
    return cfg.d_model * 2.0  # bf16 residual stream


def segments_for_cuts(
    cfg: ModelConfig,
    cuts: Sequence[int],
    *,
    names: Sequence[str] | None = None,
    uplinks: Sequence[float] | None = None,
    devices: Sequence[int] | None = None,
) -> tuple[TierSegment, ...]:
    """Generic plan -> runtime adapter: monotone 1-based cut points
    ``(c_1 .. c_{K-1})`` become K :class:`TierSegment` specs.

    Tier j runs layers ``(c_j, c_{j+1}]`` (1-based).  Branch placement per
    the module docstring: strictly inside a tier, never on the final tier
    of a K>=2 stack, and a branch at a cut is discarded.
    """
    total = sum(n for _, _, n in trunk_layout(cfg))
    bounds = (0, *(int(c) for c in cuts), total)
    if any(b > a for a, b in zip(bounds[1:], bounds[:-1])):
        raise ValueError(f"cuts must be non-decreasing in [0, {total}]: {cuts}")
    k = len(bounds) - 1
    segs = []
    for j in range(k):
        lo, hi = bounds[j], bounds[j + 1]
        if j == k - 1 and k > 1:
            brs: tuple[int, ...] = ()  # the cloud evaluates no branches
        else:
            # Strict at the cut (branch there is discarded); at the trunk
            # end there is no cut, so the deepest branch is evaluated.
            brs = tuple(
                b for b in cfg.branch_layers
                if lo < b and (b <= hi if hi == total else b < hi)
            )
        name = names[j] if names else f"tier{j}"
        up = uplinks[j] if uplinks and j < len(uplinks) else None
        dev = int(devices[j]) if devices and j < len(devices) else 1
        segs.append(
            TierSegment(name, lo, hi, brs, up if j < k - 1 else None, dev)
        )
    return tuple(segs)


@dataclasses.dataclass
class TierStepResult:
    """Everything a server needs from one decode step, fetched in one
    device->host sync (except the device-resident feedback arrays).

    In compacted mode, ``branch_entropy`` rows and ``last_logits`` rows of
    sequences that exited upstream and were not selected as padding are
    zero (they were never computed); ``tokens``/``exited``/``branch_take``
    are always exact for every sequence.
    """

    tokens: np.ndarray  # (B,) chosen token per sequence
    exited: np.ndarray  # (B,) bool — exited at some side branch
    exit_tier: np.ndarray  # (B,) int32 tier index of the exit, -1 = main head
    branch_take: dict[int, np.ndarray]  # layer -> (B,) bool first-exit mask
    branch_entropy: dict[int, np.ndarray]  # layer -> (B,) normalized entropy
    shipped_per_hop: tuple[int, ...]  # survivors crossing each executed hop
    bytes_per_hop: tuple[float, ...]
    tokens_dev: jax.Array  # device copy for the next step's input
    last_logits: jax.Array  # (B, V) main-head logits, device-resident
    compaction: tuple[HopCompaction, ...] = ()  # per executed hop
    sim_transfer_s: tuple[float, ...] = ()  # simulated uplink time per hop
    #: Sequences live at step entry (== B under lock-step; the scheduler's
    #: occupied slots under continuous batching).  ``active`` is the host
    #: mask the step ran with (None = every row live); dead slots read
    #: exited=True and garbage tokens — callers index by their live slots.
    live: int = 0
    active: np.ndarray | None = None
    #: Sampled probe steps only: layer -> (B,) bool mask of the rows whose
    #: branch head was actually evaluated (``probe_sample_frac`` < 1) — the
    #: controller must count arrivals over covered rows only.  Empty for
    #: full probes and normal steps.
    branch_probe_mask: dict[int, np.ndarray] = dataclasses.field(
        default_factory=dict
    )
    #: Fault-plane outputs (see the module docstring's degraded-step
    #: contract).  ``degraded`` marks rows finalized from the fallback
    #: exit head below a broken hop (their token is real, just shallower
    #: than planned); ``failed`` marks rows that could not emit at all
    #: (no exit head at or below the broken hop).  Both None on healthy
    #: steps with no fault plane armed.  ``fault_events`` is the step's
    #: replayable trace; ``degraded_hop`` the hop that broke (None =
    #: healthy step).
    degraded: np.ndarray | None = None
    failed: np.ndarray | None = None
    fault_events: tuple[FaultEvent, ...] = ()
    degraded_hop: int | None = None


@dataclasses.dataclass
class StepReport:
    """One decode step of a server (:class:`~repro.serving.partitioned.
    PartitionedServer`, :class:`~repro.serving.engine.ServingEngine`): the
    executor's result and what the server adds."""

    tier_result: TierStepResult
    #: The plan's cost model at the installed cuts with the step's measured
    #: exit fractions (None without a ``cost_profile``).
    est_latency_s: float | None = None
    #: bytes * 8 / uplink_bps per executed hop (0.0 for an unset uplink).
    transfer_s_per_hop: tuple[float, ...] = ()
    # Cumulative executor health counters (bucket-policy observability):
    # steps re-run on bucket overflow, and pipelined steps that fell back
    # to serial because of one.
    overflow_retries: int = 0
    pipeline_fallbacks: int = 0



class TierExecutor:
    """Compiles one jitted segment per (tier, bucket) and runs the K-hop
    decode step with survivor compaction at every hop.

    ``install`` swaps the segment list in place; segment functions are
    cached by (spec, bucket) so an unchanged tier is never re-jitted.

    ``compaction``: "bucketed" (default) runs each downstream tier on a
    dense survivor sub-batch padded to the bucket ladder; "off" keeps the
    legacy masked full-batch execution on every tier.

    ``simulate_network``: opt-in wall-clock simulation — after the step's
    single host sync, sleep for each hop's ``shipped_bytes * 8 /
    uplink_bps`` so measured step time (not just byte accounting) reflects
    the bandwidth cliff.

    ``overlap``: "serial" (default) pays the simulated transfers inline, so
    a step costs the chain sum; "pipelined" runs the transfers on per-hop
    link clocks overlapped with the next step's compute and double-buffers
    decode steps (see the module docstring) — steady-state step wall time
    is the bottleneck stage, tokens stay bitwise identical.

    ``use_kernels``: dispatch the decode hot path to the Pallas kernels
    (flash_decode / fused entropy-exit+argmax / ssd_update).  None = the
    config's ``cfg.use_kernels``; a still-None config means auto (kernels
    on TPU, jnp elsewhere).

    ``hint_window`` / ``bucket_headroom``: bucket hint policy — plan each
    downstream tier's bucket from the max survivor count of the last
    ``hint_window`` steps, inflated by ``bucket_headroom`` (fractional;
    see the module docstring).

    ``mesh`` / ``sharding``: execute the segment fns SPMD across a device
    mesh (see the module docstring's sharded-segment contract).  Params
    are placed at construction; callers get caches from
    :meth:`new_caches`, which also gives them the shape whose layout
    the programs keep (see the module docstring's resident-cache
    section).  ``sharding=None`` derives the policy via
    :func:`~repro.sharding.policy.make_policy`.  A 1-device mesh is
    treated as unsharded.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        segments: Sequence[TierSegment],
        *,
        compaction: str = "bucketed",
        simulate_network: bool = False,
        overlap: str = "serial",
        use_kernels: bool | None = None,
        batched_heads: bool = True,
        hint_window: int = 8,
        bucket_headroom: float = 0.0,
        mesh: Any = None,
        sharding: Any = None,
        fault_model: LinkFaultModel | None = None,
        hop_policy: HopPolicy | None = None,
    ):
        if compaction not in ("bucketed", "off"):
            raise ValueError(f"unknown compaction mode: {compaction!r}")
        if overlap not in ("serial", "pipelined"):
            raise ValueError(f"unknown overlap mode: {overlap!r}")
        if hint_window < 1:
            raise ValueError(f"hint_window must be >= 1: {hint_window}")
        if bucket_headroom < 0.0:
            raise ValueError(f"bucket_headroom must be >= 0: {bucket_headroom}")
        self.cfg = cfg
        self.mesh = mesh
        self.sharded = mesh is not None and mesh_devices(mesh) > 1
        self.policy = None
        if self.sharded:
            self.policy = (
                sharding if sharding is not None else make_policy(mesh, cfg)
            )
        #: Paths of the weight leaves served transposed (``params``).
        self.served_transposed: tuple[str, ...] = ()
        self.params = params
        self.compaction = compaction
        self.simulate_network = simulate_network
        self.overlap = overlap
        self.use_kernels = kernel_ops.resolve_use_kernels(
            cfg.use_kernels if use_kernels is None else use_kernels,
            sharded=self.sharded,
        )
        #: Batched exit heads (default): a segment's kept branches + probe
        #: heads evaluate as ONE stacked (K, B, D) projection against the
        #: shared unembedding and ONE multi-head entropy-exit launch.
        #: ``False`` keeps the sequential per-head lowering — the parity
        #: baseline tests and benchmarks compare against; both paths are
        #: bitwise identical (see the module docstring).
        self.batched_heads = bool(batched_heads)
        self.hint_window = hint_window
        self.bucket_headroom = bucket_headroom
        #: Set to make the NEXT step a probe: every cfg.branch_layers head
        #: is evaluated and reported (would-exit masks + entropies) without
        #: touching exits/tokens/caches.  Consumed by step().
        self.probe_next = False
        #: Fraction of the batch a probe step evaluates the extra branch
        #: heads on (1.0 = every row).  Sampled probes price exploration at
        #: a sub-batch of head FLOPs; the evaluated rows are reported in
        #: ``TierStepResult.branch_probe_mask`` so the controller counts
        #: arrivals over covered rows only.  On a compacted tier the sample
        #: indexes the dense sub-batch (the survivor permutation lives on
        #: device), so *which* batch rows a probe covers follows the
        #: compaction order — always reported, estimates stay unbiased.
        self.probe_sample_frac = 1.0
        self._probe_offset = 0  # rotation cursor so samples cycle the batch
        self.total_layers = sum(n for _, _, n in trunk_layout(cfg))
        self._fn_cache: dict[tuple, Any] = {}
        #: Compiled restore programs, by the shapes of (caches, snapshot).
        self._restorers: dict[tuple, Any] = {}
        #: Sharded cache creators, by (batch, context_len).
        self._cache_makers: dict[tuple, Any] = {}
        self.host_syncs = 0
        self.overflow_retries = 0
        #: Overflow re-runs that started from the restored entry caches
        #: (equal to ``overflow_retries``).
        self.cache_restores = 0
        #: pipelined steps that fell back to serial (overflow retry drained
        #: the pipeline and paid its transfers inline).
        self.pipeline_fallbacks = 0
        #: (spec, bucket) -> number of jax traces (a survivor-count change
        #: within a bucket must not add one).
        self.trace_counts: dict[tuple, int] = {}
        #: Pipelined-mode simulated network state: per-hop link-free wall
        #: clocks, and when the previous step's last transfer completes.
        self._link_free: list[float] = []
        self._inflight_done = 0.0
        # Fault plane (armed iff a fault model is attached; a policy alone
        # arms it with an all-healthy model so timeouts/breakers still
        # apply to the real uplinks).
        if fault_model is None and hop_policy is not None:
            fault_model = LinkFaultModel()
        self.fault_model = fault_model
        self.hop_policy = (
            hop_policy if hop_policy is not None
            else (HopPolicy() if fault_model is not None else None)
        )
        #: Per-hop circuit breakers, keyed by hop index.  Hop identity is
        #: the tier-boundary position, which survives repartitions —
        #: breaker state deliberately persists across ``install`` so a
        #: re-solve cannot reset an open breaker.
        self._breakers: dict[int, CircuitBreaker] = {}
        #: The fault plane's step clock (drives seeded draws and flap
        #: windows); advances once per ``step()`` when the plane is armed.
        self.fault_step = 0
        self.degraded_steps = 0
        self.failed_steps = 0
        self.fault_retries = 0
        self.install(segments)

    @property
    def params(self) -> Any:
        """The served weight tree (module docstring, resident cache)."""
        return self._params

    @params.setter
    def params(self, params: Any) -> None:
        """Place ``params`` (a canonical or a served tree; ``None`` frees
        them) under the policy on a mesh, then derive the served tree."""
        if params is not None:
            if self.sharded:
                params = self.policy.shard_params(params)
            params = served_params(self.cfg, params)
            self.served_transposed = tuple(
                f"{stack}/attn/{leaf}"
                for stack, _, _ in trunk_layout(self.cfg)
                for leaf in params[stack].get("attn", ())
                if leaf.endswith("_t")
            )
        self._params = params

    # -------------------------------------------------------------- plan
    def install(self, segments: Sequence[TierSegment]) -> None:
        """Install a new tier plan, re-using compiled unchanged segments.
        Outstanding pipelined transfers are drained first so no old-plan
        hop overlaps the new plan."""
        self.drain()
        segments = tuple(segments)
        if not segments or segments[0].layer_lo != 0:
            raise ValueError("first segment must start at layer 0")
        if segments[-1].layer_hi != self.total_layers:
            raise ValueError("last segment must end at the trunk tail")
        for a, b in zip(segments, segments[1:]):
            if a.layer_hi != b.layer_lo:
                raise ValueError("segments must tile the trunk contiguously")
        self.segments = segments
        # The final head runs on the last tier that runs any layers.
        self._head_idx = max(
            i for i, s in enumerate(segments) if not s.is_empty
        )
        self._fns = [
            self._segment_fn(seg, head=(i == self._head_idx))
            if not seg.is_empty else None
            for i, seg in enumerate(segments)
        ]
        # Survivor-count hints (segment index -> windowed-max survivor
        # count over the last hint_window steps) are plan-specific; a fresh
        # plan starts conservatively at full batch.  ``_hints`` is the
        # effective per-segment hint the planner consumes (tests may pin
        # it); ``_hint_hist`` is the observation window feeding it.
        self._hints = {}
        self._hint_hist = {}

    def segment_fn(self, index: int, bucket: int | None = None):
        """The compiled callable for segment ``index`` (None if the
        segment is empty): full-batch, or the compacted variant that runs
        a ``bucket``-row sub-batch."""
        if bucket is None or self.segments[index].is_empty:
            return self._fns[index]
        return self._segment_fn(
            self.segments[index], index == self._head_idx, bucket
        )

    # ---------------------------------------------------------- sharding
    def shard_caches(self, caches: Any) -> Any:
        """Place a freshly initialized cache pytree where the programs
        keep it: per the sharding policy's cache rules on a mesh (the
        layouts then persist across decode steps through XLA's
        propagation), and, where the compiled (Mosaic) decode kernel
        reads it, with K/V head_dim padded to the TPU's lanes
        (:func:`repro.models.model.pad_kv_lanes`), so that no program
        re-lays the cache out.  No-op otherwise.  Servers create their
        caches with :meth:`new_caches`; on a mesh this route, which
        first holds the whole cache where it was made, only places
        caches that tests build themselves."""
        if self.sharded:
            return self.policy.shard_caches(caches)
        if self.use_kernels and kernel_ops.on_tpu():
            return pad_kv_lanes(caches)
        return caches

    def new_caches(self, batch: int, context_len: int) -> Any:
        """Fresh caches for ``batch`` rows of ``context_len`` positions,
        placed as :meth:`shard_caches` places them (the servers' one way
        to get caches).  On a mesh each device creates only its own shard
        (:meth:`cache_maker`)."""
        if self.sharded:
            return self.cache_maker(batch, context_len)()
        return self.shard_caches(init_caches(self.cfg, batch, context_len))

    def cache_maker(self, batch: int, context_len: int):
        """The jitted program that creates a sharded executor's fresh
        caches straight into the policy's cache shardings (those that
        :meth:`shard_caches` places by), so the whole cache never sits on
        one device: at qwen3-8b's widths 32 rows x 2048 positions are
        9.7 GB, of which each of four chips keeps a 2.4 GB shard."""
        key = (batch, context_len)
        fn = self._cache_makers.get(key)
        if fn is None:

            def make():
                return init_caches(self.cfg, batch, context_len)

            make.__name__ = make.__qualname__ = f"init_caches_n{batch}"
            shardings = self.policy.cache_shardings(jax.eval_shape(make))
            fn = self._cache_makers[key] = jax.jit(
                make, out_shardings=shardings
            )
        return fn

    def _jit(self, fn, name: str, *, cache_arg: int):
        """``jax.jit`` of ``fn`` as the program ``name`` (its module is
        ``jit_<name>`` in HLO dumps and profiler traces), with the
        executor's activation-sharding context active at trace time (jit
        executes the traced body once), so the model stack's ``constrain``
        call sites emit real constraints on a sharded executor and stay
        no-ops otherwise.  Positional argument ``cache_arg`` is the cache,
        donated: the program writes it in place and consumes the caller's
        handle."""
        if self.sharded:
            pol = self.policy
            body = fn

            def fn(*args):
                with activation_sharding(pol.mesh, pol.batch_axes,
                                         pol.model_axis):
                    return body(*args)

        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn, donate_argnums=cache_arg)

    def _segment_fn(
        self,
        seg: TierSegment,
        head: bool,
        bucket: int | None = None,
        probe: tuple[int, ...] = (),
        probe_m: int | None = None,
        degrade: int | None = None,
    ):
        """Build (or fetch) the jitted callable for one tier segment.

        ``bucket=None``: masked full-batch execution (the entry tier, and
        every tier in compaction="off" mode).  ``bucket=b``: the fused
        compact(b) -> run -> scatter step described in the module
        docstring.  ``probe``: extra branch layers evaluated report-only
        (would-exit masks + entropies; exits/tokens untouched);
        ``probe_m`` samples those heads on ``probe_m`` rows instead of the
        whole sub-batch (the evaluated rows come back as a coverage mask).
        ``degrade``: a degraded step's terminal segment — after the plan
        branches run their normal exit masking, every still-unexited row
        is force-finalized from the exit head at 1-based layer ``degrade``
        (the deepest head at or below the broken hop; re-enables a head
        sitting exactly at the cut), and the cache step clock is bumped
        here since no head tier runs.  All variants share the signature
        ``fn(params, x, pos, exited, chosen, caches[, probe_rows])`` with
        full-batch x; ``pos`` is the shared () step position or the
        continuous-batching per-sequence (B,) positions.  ``caches`` is
        donated and written in place (``out["caches"]``).  The entry
        segment of a plan with a compacted downstream tier also returns
        ``out["snap"]``, what the step can write, gathered before it
        writes (the overflow re-run's restore point).
        """
        key = ((*seg.spec(head), probe, probe_m, degrade), bucket)
        if key in self._fn_cache:
            return self._fn_cache[key]
        cfg = self.cfg
        lo, hi, branches = seg.layer_lo, seg.layer_hi, seg.branches
        plan_set = frozenset(branches)
        probe_set = frozenset(probe)
        extra = () if degrade is None else (degrade,)
        eval_layers = tuple(sorted({*branches, *probe, *extra}))
        use_kernels = self.use_kernels
        batched_heads = self.batched_heads
        trace_counts = self.trace_counts
        snapshot = lo == 0 and not head and self.compaction == "bucketed"

        def exit_decision(logits_b, ex):
            """(take mask, entropy, exit token) for one branch head.  The
            kernel path fuses all three into one pass over (B, V); both
            paths break argmax ties identically (first occurrence), so the
            emitted token is bitwise path-independent."""
            if use_kernels:
                e, flag, btok = kernel_ops.entropy_exit_argmax(
                    logits_b, cfg.exit_threshold
                )
            else:
                e = normalized_entropy(logits_b)
                flag = e < cfg.exit_threshold
                btok = jnp.argmax(logits_b, -1).astype(jnp.int32)
            return flag & ~ex, e, btok

        def head_decisions(layers, logits_k):
            """Per-head (entropy, raw exit flag, argmax token) for a
            stacked (K, B, V) head pile in ONE launch (the multi-head
            kernel; jnp reductions over the trailing axis otherwise —
            the fallback sharded segments resolve to).  Per-head slices
            are bitwise the single-head ``exit_decision`` inputs: the
            flag is mask-independent, so precedence can be applied to
            the cheap (B,) rows afterwards."""
            if use_kernels:
                e, flag, btok = kernel_ops.entropy_exit_argmax_heads(
                    logits_k, cfg.exit_threshold
                )
            else:
                e = normalized_entropy(logits_k)
                flag = e < cfg.exit_threshold
                btok = jnp.argmax(logits_k, -1).astype(jnp.int32)
            return {
                layer: (e[r], flag[r], btok[r])
                for r, layer in enumerate(layers)
            }

        def fn(params, x, pos, exited, chosen, caches, probe_rows=None):
            trace_counts[key] = trace_counts.get(key, 0) + 1
            batch = x.shape[0]
            # Shared () step position -> (1,); continuous-batching (B,)
            # per-sequence positions -> (B, 1) (each row decodes at its own
            # absolute position).
            positions = (
                pos[None].astype(jnp.int32) if pos.ndim == 0
                else pos[:, None].astype(jnp.int32)
            )
            with jax.named_scope("compact"):
                if bucket is None:
                    xb, ex, ch, rows, rows_rw = x, exited, chosen, None, None
                else:
                    # ---- compact: survivors first (stable -> original order),
                    # then already-exited padding rows up to the bucket width.
                    order = jnp.argsort(exited, stable=True)
                    rows = order[:bucket]
                    xb = x[rows]
                    ex, ch = exited[rows], chosen[rows]
                    if positions.ndim == 2:
                        positions = positions[rows]
                    # Padding rows read clamped garbage (discarded) and carry
                    # an out-of-bounds sentinel so their cache writes drop:
                    # downstream KV validity is a pure function of exits, not
                    # of which rows happened to pad the bucket.
                    rows_rw = jnp.where(ex, batch, rows).astype(jnp.int32)
            snap = None
            if snapshot:
                with jax.named_scope("snapshot"):
                    snap = snapshot_decode_writes(caches, pos)
                    # The trunk's in-place writes are ordered after the
                    # gather, so XLA keeps no copy of the cache for it.
                    snap, caches = jax.lax.optimization_barrier(
                        (snap, caches)
                    )
            with jax.named_scope("layers"):
                h = embed_decode(params, xb, positions, cfg) if lo == 0 else xb
                h, new_caches, _, collected = run_trunk(
                    params, h, cfg, positions, caches,
                    layer_range=(lo, hi), collect=eval_layers, rows=rows_rw,
                    use_kernels=use_kernels,
                )
            with jax.named_scope("exit_heads"):
                sub = xb.shape[0]
                if probe_m is not None:
                    # Sampled probe: the extra heads run on probe_m rows only.
                    # probe_rows are original-batch indices; fold them into the
                    # sub-batch coordinate space (compacted tiers run a dense
                    # permutation of it) and remember which batch rows that
                    # covers for the report.
                    pr_idx = probe_rows.astype(jnp.int32) % sub
                else:
                    pr_idx = None
                if batched_heads:
                    # ---- batched heads: the segment's kept branches, probe
                    # heads and degrade fallback evaluate as ONE stacked
                    # (K, sub, D) projection against the shared unembedding +
                    # ONE multi-head entropy/flag/argmax launch.  A sampled
                    # probe (probe_m) runs at a different width, so its heads
                    # form a second (K_probe, probe_m, D) stack — still one
                    # projection + one launch for all probe heads.  Exit
                    # precedence is applied afterwards on the per-head (B,)
                    # rows in the same sorted-layer order as the sequential
                    # path; the per-head kernel outputs are mask-independent,
                    # so the result is bitwise identical.
                    main_layers = (
                        eval_layers if probe_m is None
                        else tuple(sorted({*branches, *extra}))
                    )
                    mls, mlg = branch_logits_stacked(
                        params, collected, cfg, main_layers
                    )
                    dec = (
                        {} if mlg is None
                        else head_decisions(mls, mlg[:, :, 0])
                    )
                    pdec = dec
                    if probe_m is not None and probe:
                        probe_hidden = {l: collected[l][pr_idx] for l in probe}
                        pls, plg = branch_logits_stacked(
                            params, probe_hidden, cfg, tuple(sorted(probe))
                        )
                        pdec = head_decisions(pls, plg[:, :, 0])
                    bl = blp = None
                else:
                    # ---- sequential reference path: one projection + one
                    # exit-decision launch per head (the parity baseline).
                    if probe_m is not None:
                        plan_hidden = {
                            l: collected[l] for l in {*branches, *extra}
                        }
                        probe_hidden = {l: collected[l][pr_idx] for l in probe}
                        bl = branch_logits_per_head(params, plan_hidden, cfg)
                        blp = branch_logits_per_head(params, probe_hidden, cfg)
                    else:
                        bl = branch_logits_per_head(params, collected, cfg)
                        blp = bl
                takes, ents, ptakes, pents = [], [], [], []
                for layer in eval_layers:
                    if layer in plan_set:
                        if batched_heads:
                            e, flag, btok = dec[layer]
                            take = flag & ~ex
                        else:
                            take, e, btok = exit_decision(bl[layer][:, 0], ex)
                        ch = jnp.where(take, btok, ch)
                        ex = ex | take
                        takes.append(take)
                        ents.append(e)
                    elif layer in probe_set:
                        # probe: report-only, never alters the trajectory
                        exp = ex if pr_idx is None else ex[pr_idx]
                        if batched_heads:
                            e, flag, _ = pdec[layer]
                            take = flag & ~exp
                        else:
                            take, e, _ = exit_decision(blp[layer][:, 0], exp)
                        ptakes.append(take)
                        pents.append(e)
                    # else: the degrade fallback head, consumed below.
                if degrade is not None:
                    # Degraded terminal segment: force-finalize every
                    # still-unexited row from the fallback head (threshold
                    # ignored — the link below is dead, this IS the answer)
                    # and advance the cache step clock, which normally
                    # happens on the head tier.  The batched path reads the
                    # fallback token from the stacked launch's argmax row
                    # (bitwise jnp.argmax, see kernels/entropy_exit.py).
                    dtok = (
                        dec[degrade][2] if batched_heads
                        else jnp.argmax(bl[degrade][:, 0], -1)
                        .astype(jnp.int32)
                    )
                    ch = jnp.where(ex, ch, dtok)
                    ex = jnp.ones_like(ex)
                    new_caches = dict(new_caches)
                    new_caches["length"] = caches["length"] + 1
                psub = sub if probe_m is None else probe_m
                take_s = (
                    jnp.stack(takes) if takes else jnp.zeros((0, sub), bool)
                )
                ents_s = (
                    jnp.stack(ents) if ents
                    else jnp.zeros((0, sub), jnp.float32)
                )
                ptake_s = (
                    jnp.stack(ptakes) if ptakes else jnp.zeros((0, psub), bool)
                )
                pents_s = (
                    jnp.stack(pents) if pents
                    else jnp.zeros((0, psub), jnp.float32)
                )
            out: dict[str, Any] = {"caches": new_caches}
            if snap is not None:
                out["snap"] = snap
            logits = None
            with jax.named_scope("head"):
                if head:
                    hF = norm_apply(cfg.norm_type, params["final_norm"], h)
                    logits = _unembed(params, hF, cfg)[:, 0]
                    ch = jnp.where(
                        ex, ch, jnp.argmax(logits, -1).astype(jnp.int32)
                    )
                    out["caches"] = dict(out["caches"])
                    out["caches"]["length"] = caches["length"] + 1
            if bucket is None:
                out["exited"], out["chosen"] = ex, ch
                out["take"], out["ents"] = take_s, ents_s
                if probe_m is None:
                    out["ptake"], out["pents"] = ptake_s, pents_s
                else:
                    out["ptake"] = (
                        jnp.zeros((len(probe), batch), bool)
                        .at[:, pr_idx].set(ptake_s)
                    )
                    out["pents"] = (
                        jnp.zeros((len(probe), batch), jnp.float32)
                        .at[:, pr_idx].set(pents_s)
                    )
                    out["pcover"] = (
                        jnp.zeros((batch,), bool).at[pr_idx].set(True)
                    )
                if head:
                    out["logits"] = logits
                elif degrade is None:
                    out["hidden"] = h
            else:
                with jax.named_scope("scatter"):
                    # ---- scatter back to original batch order (device-side).
                    nbr = len(branches)
                    out["exited"] = exited.at[rows].set(ex)
                    out["chosen"] = chosen.at[rows].set(ch)
                    out["take"] = (
                        jnp.zeros((nbr, batch), bool).at[:, rows].set(take_s)
                    )
                    out["ents"] = (
                        jnp.zeros((nbr, batch), jnp.float32)
                        .at[:, rows].set(ents_s)
                    )
                    pcols = rows if probe_m is None else rows[pr_idx]
                    out["ptake"] = (
                        jnp.zeros((len(probe), batch), bool)
                        .at[:, pcols].set(ptake_s)
                    )
                    out["pents"] = (
                        jnp.zeros((len(probe), batch), jnp.float32)
                        .at[:, pcols].set(pents_s)
                    )
                    if probe_m is not None:
                        out["pcover"] = (
                            jnp.zeros((batch,), bool).at[pcols].set(True)
                        )
                    if head:
                        out["logits"] = (
                            jnp.zeros((batch, logits.shape[-1]), logits.dtype)
                            .at[rows].set(logits)
                        )
                    elif degrade is None:
                        out["hidden"] = (
                            jnp.zeros((batch, 1, h.shape[-1]), h.dtype)
                            .at[rows].set(h)
                        )
            return out

        name = f"decode_l{lo}_{hi}_b{'full' if bucket is None else bucket}"
        if probe:
            name += "_probe"
        if degrade is not None:
            name += f"_degrade{degrade}"
        jitted = self._jit(fn, name, cache_arg=5)
        self._fn_cache[key] = jitted
        return jitted

    # --------------------------------------------------------- pipelining
    def drain(self) -> None:
        """Block until every outstanding pipelined simulated transfer has
        completed, then reset the link clocks.  No-op in serial mode or
        when nothing is in flight."""
        target = max([self._inflight_done, *self._link_free], default=0.0)
        wait = target - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        self._link_free = []
        self._inflight_done = 0.0

    def _pipeline_transfers(self, sim: tuple[float, ...]) -> None:
        """Schedule this step's simulated hop transfers on the per-hop link
        clocks and pace the decode loop at double-buffer depth 1: the step
        returns once the *previous* step's transfers have drained, so the
        steady-state step period is the pipeline bottleneck stage."""
        now = time.perf_counter()
        if len(self._link_free) < len(sim):
            self._link_free += [0.0] * (len(sim) - len(self._link_free))
        arrive = now  # payload leaves the entry tier at the sync
        for j, t in enumerate(sim):
            # The link takes the payload when it has both arrived (cleared
            # hop j-1) and the link is free (previous token's hop j done).
            depart = max(arrive, self._link_free[j])
            self._link_free[j] = depart + t
            arrive = self._link_free[j]
        prev_done, self._inflight_done = self._inflight_done, arrive
        wait = prev_done - time.perf_counter()
        if wait > 0:
            time.sleep(wait)

    # ------------------------------------------------- request admission
    def prefill_fn(self, plen: int, n: int):
        """The jitted admission prefill program for ``n`` prompts of
        ``plen`` tokens: ``fn(params, tokens, rows, caches) -> (first
        tokens, caches)``, ``caches`` donated (see :meth:`prefill_rows`)."""
        key = ("prefill", plen, n)
        fn = self._fn_cache.get(key)
        if fn is None:
            cfg = self.cfg
            trace_counts = self.trace_counts

            def prefill_fn(params, toks, rows_, caches_):
                trace_counts[key] = trace_counts.get(key, 0) + 1
                with jax.named_scope("prefill"):
                    logits, new_caches = prefill(
                        params, {"tokens": toks}, cfg, caches_, rows=rows_,
                    )
                    tok0 = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)
                return tok0, new_caches

            fn = self._fn_cache[key] = self._jit(
                prefill_fn, f"prefill_p{plen}_n{n}", cache_arg=3
            )
        return fn

    def prefill_rows(
        self, caches: Any, tokens: jax.Array, rows
    ) -> tuple[Any, jax.Array]:
        """Admit a block of waiting prompts into freed cache rows.

        ``tokens`` (n, P) prompt token ids; prompt row ``i`` prefills into
        row ``rows[i]`` of the resident full-batch caches *in place* — the
        row ends exactly as a fresh solo prefill of that prompt (stale
        slots from the previous occupant reset to empty), so no cache
        reshape or re-jit of any decode segment is ever needed.  Rows with
        an out-of-bounds sentinel (>= batch) drop their writes, letting
        callers pad admission groups to reusable (P, n) jit shapes.

        ``caches`` is donated to the prefill program, which writes the rows
        in place: the caller's handle is consumed, the returned one is the
        live cache.

        Returns (new caches, first decode-step input token per prompt row
        (n,), device-resident — admission performs no host sync)."""
        with TraceAnnotation(spans.TIER_PREFILL):
            tokens = jnp.asarray(tokens, jnp.int32)
            n, plen = tokens.shape
            tok0, caches = self.prefill_fn(plen, n)(
                self.params, tokens, jnp.asarray(rows, jnp.int32), caches
            )
        return caches, tok0

    def reset_fn(self, n: int):
        """The jitted ``reset_rows`` program for ``n`` rows:
        ``fn(caches, rows) -> caches``, ``caches`` donated."""
        key = ("reset", n)
        fn = self._fn_cache.get(key)
        if fn is None:

            def reset_fn(caches_, rows_):
                def walk(tree):
                    out = {}
                    for k, v in tree.items():
                        if isinstance(v, dict):
                            out[k] = walk(v)
                        elif k == "pos":
                            out[k] = v.at[:, rows_].set(-1, mode="drop")
                        elif k in ("conv", "ssm"):
                            out[k] = v.at[:, rows_].set(
                                jnp.zeros((), v.dtype), mode="drop"
                            )
                        else:
                            out[k] = v
                    return out

                return walk(caches_)

            fn = self._fn_cache[key] = self._jit(
                reset_fn, f"reset_rows_n{n}", cache_arg=0
            )
        return fn

    def reset_rows(self, caches: Any, rows) -> Any:
        """Mark cache rows empty without moving anything: per-sequence slot
        validity (``pos``) -> -1 and SSM/conv state -> 0 for the given rows
        (K/V payloads stay in place — unreachable once their slot is
        invalid).  Retirement hygiene; admission prefill also resets its
        row implicitly, so this is optional between occupants.  ``caches``
        is donated and consumed; the returned cache is the live one."""
        with TraceAnnotation(spans.TIER_RESET):
            rows = jnp.asarray(rows, jnp.int32)
            return self.reset_fn(int(rows.shape[0]))(caches, rows)

    # -------------------------------------------------------------- step
    def _restorer(self, caches: Any, snap: Any):
        """The compiled program that puts a step's entry caches back from
        the entry segment's snapshot (:func:`restore_decode_writes`),
        consuming ``caches``.  Compiled ahead of time at the first step of
        each cache shape, so an overflow re-run never compiles."""
        key = tuple(
            (a.shape, a.dtype)
            for a in jax.tree_util.tree_leaves((caches, snap))
        )
        fn = self._restorers.get(key)
        if fn is None:

            def restore(caches_, snap_):
                return restore_decode_writes(caches_, snap_)

            fn = self._jit(
                restore, "restore_decode_writes", cache_arg=0
            ).lower(caches, snap).compile()
            self._restorers[key] = fn
        return fn

    def _plan_buckets(self, batch: int) -> dict[int, int]:
        """Host-side bucket plan for this step: the windowed-max survivor
        hint per downstream segment (full batch where no hint exists yet),
        inflated by ``bucket_headroom`` and rounded up the bucket ladder."""
        if self.compaction != "bucketed":
            return {}
        executed = [
            i for i, s in enumerate(self.segments) if not s.is_empty
        ]
        buckets = {}
        for i in executed[1:]:
            hint = self._hints.get(i, batch)
            padded = min(batch, math.ceil(hint * (1.0 + self.bucket_headroom)))
            buckets[i] = bucket_for(padded, batch)
        return buckets

    def _observe_hints(self, entering: dict[int, int]) -> None:
        """Feed this step's entering-survivor counts into the hint window
        and refresh the effective per-segment hints (windowed max)."""
        for i, count in entering.items():
            hist = self._hint_hist.get(i)
            if hist is None or hist.maxlen != self.hint_window:
                hist = collections.deque(hist or (), maxlen=self.hint_window)
                self._hint_hist[i] = hist
            hist.append(count)
        self._hints = {
            i: max(hist) for i, hist in self._hint_hist.items() if hist
        }

    def _probe_layers(self) -> dict[int, tuple[int, ...]]:
        """Branch layers a probe step evaluates on top of the plan, keyed
        by segment index: every cfg.branch_layers head lands on the tier
        whose layer range contains it (a branch at a cut probes on the
        upstream tier; the final tier probes its interior branches)."""
        out: dict[int, tuple[int, ...]] = {}
        for i, seg in enumerate(self.segments):
            if seg.is_empty:
                continue
            extra = tuple(sorted(
                b for b in self.cfg.branch_layers
                if seg.layer_lo < b <= seg.layer_hi and b not in seg.branches
            ))
            if extra:
                out[i] = extra
        return out

    def _plan_hops(
        self, batch: int
    ) -> tuple[int | None, dict[int, HopOutcome], tuple[FaultEvent, ...]]:
        """Phase A of the fault plane: health-check every hop the plan
        would cross, in order, *before* any segment dispatches.

        Per hop: circuit-breaker gate (open + cooling -> skip the hop
        entirely, a fast degrade that is NOT a link observation; open +
        cooled -> one half-open probe attempt), then the policy's attempt
        loop against this step's drawn hop condition, with the transfer
        deadline evaluated on the worst-case full-batch payload so the
        decision never depends on the live trajectory.  The first hop
        that fails breaks the chain (later hops are not attempted).

        Returns (broken hop index or None, per-hop outcomes for attempted
        hops, the step's event trace)."""
        pol = self.hop_policy
        model = self.fault_model
        step = self.fault_step
        events: list[FaultEvent] = []
        outcomes: dict[int, HopOutcome] = {}
        broken: int | None = None
        for j in range(self._head_idx):
            br = self._breakers.get(j)
            if br is None:
                br = self._breakers[j] = CircuitBreaker(pol)
            gate = br.gate(step)
            if gate == "skip":
                events.append(FaultEvent(step, j, "breaker_skip"))
                broken = j
                break
            if gate == "probe":
                events.append(FaultEvent(step, j, "breaker_half_open"))
            attempts = 1 if gate == "probe" else 1 + pol.max_retries
            cond, jitter_u, drops = model.draw(step, j, attempts)
            est_bytes = batch * bytes_per_sequence(
                self.cfg, self.segments[j].layer_hi
            )
            out = attempt_hop(
                pol, cond, drops, jitter_u, step=step, hop=j,
                est_bytes=est_bytes,
                uplink_bps=self.segments[j].uplink_bps or 0.0,
                attempts=attempts,
            )
            events.extend(out.events)
            outcomes[j] = out
            self.fault_retries += sum(
                1 for e in out.events if e.kind == "retry"
            )
            was = br.state
            br.record(step, out.ok)
            if br.state != was:
                events.append(FaultEvent(step, j, f"breaker_{br.state}"))
            if not out.ok:
                broken = j
                break
        return broken, outcomes, tuple(events)

    def _run_once(
        self, tok: jax.Array, pos, caches: Any, buckets: dict[int, int],
        probe_map: dict[int, tuple[int, ...]] | None = None,
        probe_rows: jax.Array | None = None,
        probe_m: int | None = None,
        active_np: np.ndarray | None = None,
        degrade: tuple[int, int] | None = None,
    ) -> tuple:
        """Dispatch all tier segments and perform the single host sync.
        Returns (host dict, caches, entering-survivor counts per segment,
        chosen, logits, alive-after-segment counts, plan-exit mask, the
        entry segment's snapshot or None).  ``caches`` is consumed.
        ``active_np`` seeds the exit mask with the dead slots of a
        continuous-batching step (they compact away downstream exactly
        like early exits).  ``degrade=(seg_idx, layer)`` truncates the
        step at ``seg_idx``, whose fn force-finalizes survivors from the
        exit head at ``layer`` (broken-hop fallback; no head tier runs)."""
        probe_map = probe_map or {}
        batch = tok.shape[0]
        with TraceAnnotation(spans.TIER_STAGE):
            posj = jnp.asarray(pos, jnp.int32)
            exited = (
                jnp.zeros((batch,), bool) if active_np is None
                else jnp.asarray(~active_np)
            )
            chosen = jnp.zeros((batch,), jnp.int32)
        x: jax.Array = tok
        fetch: dict[str, Any] = {}
        logits = None
        snap = None
        last_idx = len(self.segments) if degrade is None else degrade[0] + 1

        for i, seg in enumerate(self.segments):
            if i >= last_idx:
                break
            if seg.is_empty:
                continue
            head = i == self._head_idx
            b = buckets.get(i)
            pr = probe_map.get(i, ())
            deg = (
                degrade[1] if degrade is not None and i == degrade[0]
                else None
            )
            with TraceAnnotation(
                spans.TIER_DISPATCH, seg=i,
                bucket=batch if b is None else min(b, batch),
            ):
                if b is None and not pr and deg is None:
                    fn = self._fns[i]
                else:
                    # Downstream tiers always run the compact->run->scatter
                    # fn in bucketed mode — even at bucket == batch — so
                    # exited rows' downstream cache writes are always
                    # dropped and KV validity stays a pure function of
                    # exits, never of which fn variant a hint happened to
                    # select.
                    fn = self._segment_fn(
                        seg, head, None if b is None else min(b, batch),
                        probe=pr, probe_m=probe_m if pr else None,
                        degrade=deg,
                    )
                if pr and probe_m is not None:
                    out = fn(
                        self.params, x, posj, exited, chosen, caches,
                        probe_rows,
                    )
                else:
                    out = fn(self.params, x, posj, exited, chosen, caches)
            caches = out["caches"]
            snap = out.get("snap", snap)
            exited, chosen = out["exited"], out["chosen"]
            if seg.branches:
                fetch[f"take{i}"] = out["take"]
                fetch[f"ents{i}"] = out["ents"]
            if pr:
                fetch[f"ptake{i}"] = out["ptake"]
                fetch[f"pents{i}"] = out["pents"]
                if probe_m is not None:
                    fetch[f"pcover{i}"] = out["pcover"]
            if head:
                logits = out["logits"]
            elif deg is None:
                # A degrade-terminal segment force-finalized every row and
                # emits no handoff hidden state (and the loop breaks next
                # iteration anyway).
                x = out["hidden"]

        fetch["tokens"] = chosen
        fetch["exited"] = exited
        with TraceAnnotation(spans.TIER_SYNC):
            host = jax.device_get(fetch)  # the step's single sync
        self.host_syncs += 1

        # Host-side bookkeeping on the fetched masks (no further syncs):
        # cumulative exits -> survivors entering each segment.  Dead slots
        # are never alive, so they neither ship nor widen buckets.  On a
        # degraded step segments past the truncation have no masks; their
        # counts carry the last executed segment's survivors (the rows the
        # fallback head force-finalized).
        with TraceAnnotation(spans.TIER_ACCOUNT):
            exited_run = (
                np.zeros((batch,), bool) if active_np is None
                else ~np.asarray(active_np, bool)
            )
            alive_after_seg = {}
            for i, seg in enumerate(self.segments):
                for row, _layer in enumerate(seg.branches):
                    if f"take{i}" in host:
                        exited_run |= host[f"take{i}"][row]
                alive_after_seg[i] = int(batch - exited_run.sum())
            entering = {
                i: alive_after_seg[i - 1]
                for i in range(1, last_idx)
                if not self.segments[i].is_empty
            }
        return host, caches, entering, chosen, logits, alive_after_seg, \
            exited_run, snap

    def step(
        self, tok: jax.Array, pos, caches: Any, *, active=None
    ) -> tuple[TierStepResult, Any]:
        """One decode step across all tiers: exactly one host sync (plus
        one per rare overflow-retry iteration, see module docstring).

        ``pos`` is the shared step position (lock-step) or a per-sequence
        (B,) vector of absolute positions (continuous batching).
        ``active`` (B,) bool marks live request slots: dead slots enter the
        step pre-exited — the entry tier masks them, downstream tiers
        compact them away, and they never count as survivors or ship.

        ``caches`` is consumed: the step's programs update it in place
        (donated) and the returned cache is the live one.  A step that
        dispatches nothing (every hop broken with no exit head below)
        returns it untouched."""
        with TraceAnnotation(spans.TIER_STEP):
            return self._step(tok, pos, caches, active)

    def _step(
        self, tok: jax.Array, pos, caches: Any, active
    ) -> tuple[TierStepResult, Any]:
        cfg = self.cfg
        batch = tok.shape[0]
        # Snapshot (never alias) the caller's mask: the scheduler mutates
        # its live mask when requests retire, and this result — including
        # its on_step/controller consumers — must keep the mask the step
        # actually ran with.
        active_np = None if active is None else np.array(active, dtype=bool)
        live = batch if active_np is None else int(active_np.sum())
        # ---- fault plane, phase A: decide hop health before dispatch.
        broken: int | None = None
        outcomes: dict[int, HopOutcome] = {}
        fault_events: tuple[FaultEvent, ...] = ()
        degrade: tuple[int, int] | None = None
        if self.fault_model is not None:
            with TraceAnnotation(spans.TIER_PLAN):
                broken, outcomes, fault_events = self._plan_hops(batch)
            self.fault_step += 1
            if broken is not None:
                self.degraded_steps += 1
                cut = self.segments[broken].layer_hi
                # Deepest exit head at or below the broken hop's cut —
                # including a head sitting exactly at the cut, which the
                # healthy plan discards (Sec. IV-B) but degradation
                # re-enables as the fallback.
                deg_layer = max(
                    (b for b in cfg.branch_layers if b <= cut), default=-1
                )
                if deg_layer >= 1:
                    deg_idx = next(
                        i for i, s in enumerate(self.segments)
                        if not s.is_empty
                        and s.layer_lo < deg_layer <= s.layer_hi
                    )
                    degrade = (deg_idx, deg_layer)
        if broken is not None and degrade is None:
            # No exit head at or below the broken hop: nothing upstream
            # can emit, so dispatch nothing (no sync, no cache-clock
            # advance) — every live row fails this step and the caches
            # are returned untouched.
            self.failed_steps += 1
            failed_mask = (
                np.ones((batch,), bool) if active_np is None
                else active_np.copy()
            )
            sim = ()
            if self.simulate_network:
                # Charge only the pre-flight overhead the attempts burned
                # (no payload ever left the entry tier).
                sim = tuple(
                    outcomes[j].overhead_s if j in outcomes else 0.0
                    for j in range(self._head_idx)
                )
                if self.overlap == "pipelined":
                    self.pipeline_fallbacks += 1
                    self.drain()
                total = sum(sim)
                if total > 0:
                    time.sleep(total)
            result = TierStepResult(
                tokens=np.zeros((batch,), np.int32),
                exited=(
                    np.zeros((batch,), bool) if active_np is None
                    else ~active_np
                ),
                exit_tier=np.full((batch,), -1, np.int32),
                branch_take={},
                branch_entropy={},
                shipped_per_hop=(0,) * self._head_idx,
                bytes_per_hop=(0.0,) * self._head_idx,
                tokens_dev=jnp.zeros((batch,), jnp.int32),
                last_logits=None,
                compaction=tuple(
                    HopCompaction(0, 0) for _ in range(self._head_idx)
                ),
                sim_transfer_s=sim,
                live=live,
                active=active_np,
                degraded=np.zeros((batch,), bool),
                failed=failed_mask,
                fault_events=fault_events,
                degraded_hop=broken,
            )
            return result, caches
        with TraceAnnotation(spans.TIER_PLAN):
            probe_map = self._probe_layers() if self.probe_next else {}
            self.probe_next = False
            probe_rows = None
            probe_m = None
            if probe_map and self.probe_sample_frac < 1.0:
                pool = (
                    np.flatnonzero(active_np)
                    if active_np is not None and active_np.any()
                    else np.arange(batch)
                )
                # Sample size: the configured fraction of the nominal batch,
                # capped at the live pool (no duplicate rows burning head
                # FLOPs at low occupancy) and floored to the bucket ladder so
                # the probe-fn shape set stays bounded as occupancy drifts.
                want = min(
                    max(1, math.ceil(self.probe_sample_frac * batch)),
                    len(pool),
                )
                m = max(
                    b for b in bucket_ladder(batch) if b <= want
                )
                if m < batch:
                    # Deterministic rotation over the live rows: successive
                    # probes cycle the pool so every row's entropy gets
                    # sampled without an RNG in the hot loop.
                    sel = pool[(self._probe_offset + np.arange(m)) % len(pool)]
                    self._probe_offset = (self._probe_offset + m) % len(pool)
                    probe_rows = jnp.asarray(sel, jnp.int32)
                    probe_m = m
            buckets = self._plan_buckets(batch)
        (host, new_caches, entering, chosen, logits, alive, exited_plan,
         snap) = self._run_once(
            tok, pos, caches, buckets, probe_map,
            probe_rows, probe_m, active_np, degrade,
        )
        restore = None if snap is None else self._restorer(new_caches, snap)
        used = {
            i: min(buckets.get(i, batch), batch) for i in entering
        }
        # Exit-rate spike: true survivors overflowed a planned bucket, so
        # excluded survivors carry garbage.  Re-run the whole step from the
        # entry caches (restored in place from the entry segment's
        # snapshot) with measured buckets — correctness is never traded
        # for the fast path.  One pass is NOT always enough: an excluded
        # survivor's garbage forward pass can spuriously "exit" at a later
        # segment's branch, undercounting that segment's true survivors,
        # so re-check after every run.  Segments before the earliest
        # overflow have exact counts, so each iteration fixes at least
        # that segment (buckets are merged monotonically non-decreasing)
        # and the loop terminates in <= K runs; a belt-and-braces cap
        # falls back to guaranteed-fit full-batch buckets.
        attempts = 0
        while any(entering[i] > used[i] for i in entering):
            with TraceAnnotation(spans.TIER_RETRY):
                self.overflow_retries += 1
                attempts += 1
                if attempts >= len(self.segments):
                    buckets = {i: batch for i in entering}
                else:
                    buckets = {
                        i: max(
                            min(buckets.get(i, 1), batch),
                            bucket_for(entering[i], batch),
                        )
                        for i in entering
                    }
                caches = restore(new_caches, snap)
                self.cache_restores += 1
                (host, new_caches, entering, chosen, logits, alive,
                 exited_plan, snap) = self._run_once(
                    tok, pos, caches, buckets, probe_map,
                    probe_rows, probe_m, active_np, degrade,
                )
                used = {i: min(buckets.get(i, batch), batch) for i in entering}
        with TraceAnnotation(spans.TIER_ACCOUNT):
            self._observe_hints(entering)

            # Per-branch attribution from the fetched masks.  Probe branches
            # report would-exit masks/entropies only — they never touch
            # exit_tier (the trajectory is that of a normal step).
            exit_tier = np.full((batch,), -1, np.int32)
            branch_take: dict[int, np.ndarray] = {}
            branch_entropy: dict[int, np.ndarray] = {}
            branch_probe_mask: dict[int, np.ndarray] = {}
            for i, seg in enumerate(self.segments):
                for row, layer in enumerate(seg.branches):
                    if f"take{i}" not in host:  # truncated degraded step
                        continue
                    mask = host[f"take{i}"][row]
                    branch_take[layer] = mask
                    branch_entropy[layer] = host[f"ents{i}"][row]
                    exit_tier[mask] = i
                for row, layer in enumerate(probe_map.get(i, ())):
                    if f"ptake{i}" not in host:
                        continue
                    branch_take[layer] = host[f"ptake{i}"][row]
                    branch_entropy[layer] = host[f"pents{i}"][row]
                    if probe_m is not None:
                        branch_probe_mask[layer] = host[f"pcover{i}"]

            # Degraded rows: exited in the fetch but not through any plan
            # branch — the fallback head force-finalized them.  Their tokens
            # are real (the fallback head's argmax); ``exit_tier`` points at
            # the fallback tier; they are deliberately NOT added to
            # ``branch_take`` so exit-probability estimates see only genuine
            # threshold exits.
            degraded_mask = None
            failed_mask = None
            if broken is not None:
                degraded_mask = np.asarray(host["exited"], bool) & ~exited_plan
                exit_tier[degraded_mask] = degrade[0]
                failed_mask = np.zeros((batch,), bool)

            # Hops: one per cut that still has layers (or the head) downstream.
            # A degraded step truncates at the fallback tier, so hops from it
            # onward carried nothing (phase A burned their retry overhead
            # pre-flight; no payload ever reached the broken link).
            stop_hop = self._head_idx if degrade is None else degrade[0]
            shipped, nbytes, compaction = [], [], []
            for j in range(self._head_idx):
                if j >= stop_hop:
                    shipped.append(0)
                    nbytes.append(0.0)
                    compaction.append(HopCompaction(0, 0))
                    continue
                cut = self.segments[j].layer_hi
                alive_j = alive[j]
                shipped.append(alive_j)
                nbytes.append(alive_j * bytes_per_sequence(cfg, cut))
                nxt = next(
                    i for i in range(j + 1, len(self.segments))
                    if not self.segments[i].is_empty
                )
                compaction.append(HopCompaction(alive_j, used.get(nxt, batch)))

        sim = ()
        if self.simulate_network:
            sim_list = []
            for j, nb in enumerate(nbytes):
                o = outcomes.get(j)
                if o is None:
                    up = self.segments[j].uplink_bps
                    if nb > 0 and (not up or up <= 0.0):
                        # Satellite fix: a dead uplink with bytes queued
                        # used to price the hop at 0 s (a dead link looked
                        # free).  With no fault model to degrade through,
                        # fail loudly instead.
                        raise LinkDownError(
                            f"hop {j} ({self.segments[j].name}) must ship "
                            f"{nb:.0f} bytes but uplink_bps is unset/zero; "
                            "attach a LinkFaultModel to degrade instead"
                        )
                    sim_list.append(transfer_seconds(nb, up))
                else:
                    t = 0.0
                    if o.ok and nb > 0:
                        eff = (
                            (self.segments[j].uplink_bps or 0.0)
                            * o.bandwidth_mult
                        )
                        t = o.latency_s + nb * 8.0 / eff
                    sim_list.append(o.overhead_s + t)
            sim = tuple(sim_list)
            if self.overlap == "pipelined" and attempts == 0 and broken is None:
                self._pipeline_transfers(sim)
            else:
                if self.overlap == "pipelined":
                    # Overflow retry or degraded step: fall back to serial
                    # for this step — drain the pipeline, then pay the
                    # transfers inline.
                    self.pipeline_fallbacks += 1
                    self.drain()
                total = sum(sim)
                if total > 0:
                    time.sleep(total)

        result = TierStepResult(
            tokens=host["tokens"],
            exited=host["exited"],
            exit_tier=exit_tier,
            branch_take=branch_take,
            branch_entropy=branch_entropy,
            shipped_per_hop=tuple(shipped),
            bytes_per_hop=tuple(nbytes),
            tokens_dev=chosen,
            last_logits=logits,
            compaction=tuple(compaction),
            sim_transfer_s=sim,
            live=live,
            active=active_np,
            branch_probe_mask=branch_probe_mask,
            degraded=degraded_mask,
            failed=failed_mask,
            fault_events=fault_events,
            degraded_hop=broken,
        )
        return result, new_caches
