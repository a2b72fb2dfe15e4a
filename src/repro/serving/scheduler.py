"""Continuous-batching request scheduler: the serving stack's request
lifecycle over the unified K-tier runtime.

The paper picks a partition point per *deployment*, but its value is
realized per *request*: a real edge/cloud serving system faces a stream
of arrivals, not one synchronized batch (cf. Parthasarathy & Rupprecht
2022 on throughput-maximizing DNN partitioning, and Li et al.'s
on-demand edge/cloud co-inference).  The lock-step loop this repo served
with until now decodes a fixed batch in unison — a request that needs 3
tokens while its neighbor needs 30 pins a dead KV slot for 27 steps, so
measured throughput badly understates what BranchyNet partitioning buys.

:class:`RequestScheduler` replaces the lock-step batch with a request
lifecycle over ``slots`` full-batch-resident KV rows:

    submit()  -> admission queue (prompt, max_new_tokens, arrival)
    admit     -> :meth:`TierExecutor.prefill_rows` prefills waiting
                 prompts *into freed cache rows* between decode steps —
                 per-sequence slot validity (``pos: (B, C)``) and the
                 ``rows`` plumbing make a recycled slot safe to overwrite
                 in place, so no cache reshape or re-jit ever happens
    step      -> one fused decode step over the live slots
                 (``TierExecutor.step(pos=(B,), active=...)``): each
                 request decodes at its own absolute position; dead slots
                 enter pre-exited and compact away downstream, so the
                 bucket ladder tracks *live occupancy*
    retire    -> a request leaves when its token budget is spent (or, for
                 classification-style traffic, at its first early exit
                 with ``stop_on_exit=True``); its slot is immediately
                 reusable

The scheduler preserves the runtime's two contracts:

  * **one device->host sync per decode step** — admission prefill keeps
    everything device-resident (the first input token is an argmax of the
    prefill logits on device) and retirement bookkeeping reads only the
    step's already-fetched masks;
  * **trajectory isolation** — each request's token/exit trajectory is
    bitwise identical to running it alone from its admission state,
    independent of which slot it recycled, who occupied it before, or
    who shares the batch with it.

Admission policy: ``policy="continuous"`` (the point of this module)
fills any free slot as soon as a queued request's arrival step has
passed; ``policy="gang"`` only admits when *all* slots are free — the
lock-step degenerate case, kept as the benchmark baseline.

Per-request accounting: TTFT (arrival -> first decoded token on host)
and end-to-end latency land in :class:`RequestResult`; per-step
admissions/retirements/occupancy land in :class:`SchedulerStepReport`.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Sequence

import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.core.multitier import bucket_for
from repro.serving import spans

__all__ = [
    "Request",
    "RequestResult",
    "RequestScheduler",
    "SchedulerStepReport",
    "ServesRequests",
]


@dataclasses.dataclass
class Request:
    """One unit of serving work: a prompt and a decode budget."""

    prompt: np.ndarray  # (P,) int32 token ids
    max_new_tokens: int
    rid: int = -1  # assigned by submit()
    #: Retire at the first token that early-exits at a side branch (the
    #: paper's classification semantics: the answer is ready).  False
    #: decodes the full budget; exits then only make tokens cheaper.
    stop_on_exit: bool = False
    #: Earliest decode-step index admission may happen (simulated arrival
    #: for reproducible workloads; 0 = admissible immediately).
    arrival_step: int = 0
    #: Wall clock when the request became admissible: submit() time, or —
    #: for a simulated future ``arrival_step`` — the moment the step
    #: clock reaches it (so TTFT/latency measure queueing + serving, not
    #: pre-arrival simulation time).
    arrival_s: float = 0.0
    _arrived: bool = True  # arrival_s already stamped
    #: Times this request was re-queued after a slot fault (capped by the
    #: scheduler's ``max_requeues``; exceeded -> terminal ``failed``).
    _requeues: int = 0


@dataclasses.dataclass
class RequestResult:
    """Everything known about a finished (or in-flight) request."""

    rid: int
    prompt_len: int
    tokens: list[int]  # decoded token ids, in order
    exit_tiers: list[int]  # per token: tier of the first exit, -1 = head
    exited: list[bool]  # per token: did it early-exit at a branch
    slot: int = -1  # KV row it was served in
    admitted_step: int = -1
    retired_step: int = -1
    ttft_s: float | None = None  # arrival -> first decoded token on host
    latency_s: float | None = None  # arrival -> retirement
    done: bool = False
    #: Terminal status: "ok" — every token came from the planned
    #: trajectory; "degraded" — finished, but >= 1 token was finalized
    #: from a fallback exit head below a broken hop (see
    #: ``degraded_tokens``); "failed" — an unrecoverable hop fault ended
    #: the request with no token that step and requeues were exhausted
    #: (or disabled); "requeued" — transient marker on a result whose
    #: request went back in the queue (replaced at re-admission).
    status: str = "ok"
    #: Tokens in ``tokens`` that a degraded step force-finalized from a
    #: fallback head (real tokens, shallower than planned).
    degraded_tokens: int = 0


@dataclasses.dataclass
class SchedulerStepReport:
    """One decode step of the request loop (host-side bookkeeping only —
    everything here derives from the step's single fetched sync)."""

    step: int
    live: int  # occupied slots the step decoded
    admitted: tuple[int, ...]  # rids admitted (prefilled) before the step
    retired: tuple[int, ...]  # rids retired after the step
    emitted: dict[int, int]  # rid -> token decoded this step
    occupancy: float = 0.0  # live / slots
    server_report: Any = None  # the server's StepReport
    #: rids whose token this step came from a fallback exit head
    #: (degraded step) and rids whose slot hit an unrecoverable fault
    #: (retired failed, or re-queued when ``requeue_on_fail``).
    degraded: tuple[int, ...] = ()
    failed: tuple[int, ...] = ()


class RequestScheduler:
    """Admission queue + slot allocator + decode loop over a tier server.

    ``server`` is a :class:`~repro.serving.engine.ServingEngine` or a
    :class:`~repro.serving.partitioned.PartitionedServer` — anything
    exposing ``cfg``, ``executor`` and ``step(tok, pos, caches,
    active=...)`` returning a report whose ``tier_result`` is the
    executor's step result.
    Servers construct one lazily behind ``submit()/run()/drain()``; build
    it directly to control ``slots``/``context_len``/``policy``.

    ``on_step`` callbacks (e.g. ``RepartitionController.observe``) fire
    after every decode step with the underlying tier step result, so drift
    detection and epsilon probes ride the continuous loop unchanged.
    """

    def __init__(
        self,
        server: Any,
        slots: int,
        context_len: int,
        *,
        policy: str = "continuous",
        reset_on_retire: bool = False,
        clock: Callable[[], float] = time.perf_counter,
        on_step: Sequence[Callable[[Any], Any]] = (),
        requeue_on_fail: bool = False,
        max_requeues: int = 1,
    ):
        if policy not in ("continuous", "gang"):
            raise ValueError(f"unknown admission policy: {policy!r}")
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        cfg = server.cfg
        if cfg.frontend != "none" or cfg.arch_type == "audio":
            raise NotImplementedError(
                "request scheduling covers text-frontend trunks (vision "
                "patch embeds / audio encoder states are per-batch, not "
                "per-slot)"
            )

        self.server = server
        self.executor = server.executor
        self.cfg = cfg
        self.slots = slots
        self.context_len = context_len
        self.policy = policy
        self.reset_on_retire = reset_on_retire
        self.clock = clock
        self.on_step = list(on_step)
        #: A request whose slot hits an unrecoverable fault (its row is in
        #: the step result's ``failed`` mask) re-enters the queue head for
        #: a fresh admission instead of retiring ``failed`` — up to
        #: ``max_requeues`` times per request.  Its slot is reclaimed
        #: either way (the allocator invariant the fault tests pin).
        self.requeue_on_fail = requeue_on_fail
        self.max_requeues = max_requeues

        # Mesh-sharded executors create the slot caches in place under the
        # policy's cache rules, each device its shard; admission prefill and
        # decode steps then keep the layouts through propagation.
        self.caches = self.executor.new_caches(slots, context_len)
        self.pos = np.zeros(slots, np.int32)  # next decode position per slot
        self.active = np.zeros(slots, bool)
        self.tok_dev = jnp.zeros((slots, 1), jnp.int32)
        self.queue: collections.deque[Request] = collections.deque()
        self.step_count = 0  # scheduler clock (idle arrival ticks included)
        self.decode_steps = 0  # steps that actually decoded (1 sync each)
        self._next_rid = 0
        self._slot_req: list[Request | None] = [None] * slots
        self._remaining = np.zeros(slots, np.int64)
        self.results: dict[int, RequestResult] = {}
        #: Completed-request rids in retirement order.
        self.finished: list[int] = []
        self.total_tokens = 0  # useful tokens decoded for live requests

    # ------------------------------------------------------------ submit
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        stop_on_exit: bool = False,
        arrival_step: int = 0,
    ) -> int:
        """Queue one request; returns its rid.  Admission happens between
        decode steps, as soon as a slot frees up (policy="continuous")."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if len(prompt) + max_new_tokens > self.context_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + budget ({max_new_tokens}) "
                f"exceeds context_len {self.context_len}"
            )
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            rid=rid,
            stop_on_exit=stop_on_exit,
            arrival_step=int(arrival_step),
            arrival_s=self.clock(),
            _arrived=int(arrival_step) <= self.step_count,
        ))
        return rid

    # --------------------------------------------------------- admission
    def _free_slots(self) -> list[int]:
        return [s for s in range(self.slots) if not self.active[s]]

    def _mark_arrivals(self) -> None:
        """Stamp arrival_s the moment a simulated future arrival becomes
        admissible, so TTFT/latency measure queueing + serving rather than
        pre-arrival simulation time."""
        now = None
        for req in self.queue:
            if not req._arrived and req.arrival_step <= self.step_count:
                now = self.clock() if now is None else now
                req.arrival_s = now
                req._arrived = True

    def _admit(self) -> tuple[int, ...]:
        """Prefill queued requests into freed rows (between decode steps).
        Admission is FIFO among *arrived* requests — a queue head whose
        simulated arrival is still in the future never blocks a later
        submit that has already arrived.  Same-length prompts group into
        one prefill call, padded up the bucket ladder with OOB sentinel
        rows so (P, n) jit shapes recur."""
        free = self._free_slots()
        if self.policy == "gang" and len(free) < self.slots:
            return ()
        ready: list[Request] = []
        if free and self.queue:
            waiting: collections.deque[Request] = collections.deque()
            for req in self.queue:
                if len(ready) < len(free) and req._arrived:
                    ready.append(req)
                else:
                    waiting.append(req)
            self.queue = waiting
        if not ready:
            return ()
        admitted = []
        by_len: dict[int, list[Request]] = {}
        for req in ready:
            by_len.setdefault(len(req.prompt), []).append(req)
        for plen, group in by_len.items():
            rows = [free.pop(0) for _ in group]
            n = bucket_for(len(group), self.slots)
            toks = np.zeros((n, plen), np.int32)
            row_ids = np.full(n, self.slots, np.int32)  # OOB sentinel pad
            for i, req in enumerate(group):
                toks[i] = req.prompt
                row_ids[i] = rows[i]
            self.caches, tok0 = self.executor.prefill_rows(
                self.caches, toks, row_ids
            )
            # First decode input = argmax of the prefill logits, straight
            # from device to the token buffer — no host sync at admission.
            self.tok_dev = self.tok_dev.at[
                jnp.asarray(rows, jnp.int32), 0
            ].set(tok0[: len(group)])
            for slot, req in zip(rows, group):
                self.active[slot] = True
                self.pos[slot] = plen
                self._remaining[slot] = req.max_new_tokens
                self._slot_req[slot] = req
                self.results[req.rid] = RequestResult(
                    rid=req.rid,
                    prompt_len=plen,
                    tokens=[],
                    exit_tiers=[],
                    exited=[],
                    slot=slot,
                    admitted_step=self.step_count,
                )
                admitted.append(req.rid)
        return tuple(admitted)

    # -------------------------------------------------------------- step
    def step(self) -> SchedulerStepReport | None:
        """Admit into freed rows, then run one decode step over the live
        slots.  Returns None when there is nothing to do (idle step: no
        live request and nothing admissible yet advances the step clock,
        so simulated arrivals keyed on ``arrival_step`` still progress)."""
        with StepTraceAnnotation(spans.SCHED_STEP, step_num=self.step_count):
            return self._step()

    def _step(self) -> SchedulerStepReport | None:
        with TraceAnnotation(spans.SCHED_ADMIT):
            self._mark_arrivals()
            admitted = self._admit()
        if not self.active.any():
            if self.queue:
                self.step_count += 1  # idle tick toward future arrivals
            return None
        rep, self.caches = self.server.step(
            self.tok_dev, self.pos.copy(), self.caches, active=self.active
        )
        with TraceAnnotation(spans.SCHED_EMIT):
            now = self.clock()
            self.step_count += 1
            self.decode_steps += 1
            res = rep.tier_result
            tokens = np.asarray(res.tokens)
            exited = np.asarray(res.exited)
            exit_tier = np.asarray(res.exit_tier)
            deg_mask, fail_mask = res.degraded, res.failed
            self.tok_dev = res.tokens_dev[:, None]

            emitted: dict[int, int] = {}
            retired: list[int] = []
            degraded: list[int] = []
            failed: list[int] = []
            live = int(self.active.sum())
            for slot in np.flatnonzero(self.active):
                req = self._slot_req[slot]
                r = self.results[req.rid]
                if fail_mask is not None and fail_mask[slot]:
                    # Unrecoverable hop fault: no token this step.  Reclaim
                    # the slot either way; the request re-queues (fresh
                    # admission, fresh result) or retires terminally failed.
                    self.active[slot] = False
                    self._slot_req[slot] = None
                    failed.append(req.rid)
                    if (
                        self.requeue_on_fail
                        and req._requeues < self.max_requeues
                    ):
                        req._requeues += 1
                        r.status = "requeued"
                        self.queue.appendleft(req)
                    else:
                        r.done = True
                        r.status = "failed"
                        r.retired_step = self.step_count
                        r.latency_s = now - req.arrival_s
                        self.finished.append(req.rid)
                        retired.append(req.rid)
                    continue
                tok = int(tokens[slot])
                emitted[req.rid] = tok
                r.tokens.append(tok)
                r.exited.append(bool(exited[slot]))
                r.exit_tiers.append(int(exit_tier[slot]))
                if deg_mask is not None and deg_mask[slot]:
                    r.degraded_tokens += 1
                    degraded.append(req.rid)
                if r.ttft_s is None:
                    r.ttft_s = now - req.arrival_s
                self.pos[slot] += 1
                self._remaining[slot] -= 1
                self.total_tokens += 1
                if self._remaining[slot] <= 0 or (
                    req.stop_on_exit and exited[slot]
                ):
                    r.done = True
                    r.status = "degraded" if r.degraded_tokens else "ok"
                    r.retired_step = self.step_count
                    r.latency_s = now - req.arrival_s
                    self.active[slot] = False
                    self._slot_req[slot] = None
                    self.finished.append(req.rid)
                    retired.append(req.rid)
        if retired and self.reset_on_retire:
            with TraceAnnotation(spans.SCHED_RETIRE):
                rows = np.full(
                    bucket_for(len(retired), self.slots), self.slots, np.int32
                )
                rows[: len(retired)] = [self.results[r].slot for r in retired]
                self.caches = self.executor.reset_rows(self.caches, rows)
        report = SchedulerStepReport(
            step=self.step_count,
            live=live,
            admitted=admitted,
            retired=tuple(retired),
            emitted=emitted,
            occupancy=live / self.slots,
            server_report=rep,
            degraded=tuple(degraded),
            failed=tuple(failed),
        )
        for cb in self.on_step:
            cb(res)
        return report

    # --------------------------------------------------------------- run
    def run(self, max_steps: int | None = None) -> list[SchedulerStepReport]:
        """Step until drained (queue empty and no live slot), or for
        ``max_steps`` *decode* steps (idle ticks waiting on simulated
        arrivals don't count — they always terminate, since the step clock
        advances toward every queued arrival_step).  Returns the per-step
        reports."""
        out: list[SchedulerStepReport] = []
        while self.queue or self.active.any():
            if max_steps is not None and len(out) >= max_steps:
                break
            rep = self.step()
            if rep is not None:
                out.append(rep)
        return out

    def drain(self) -> list[RequestResult]:
        """Run to completion and return every finished request's result in
        retirement order."""
        self.run()
        return [self.results[rid] for rid in self.finished]

    # ------------------------------------------------------------- stats
    @property
    def occupancy(self) -> float:
        return float(self.active.sum()) / self.slots

    def pending(self) -> int:
        return len(self.queue)


class ServesRequests:
    """Mixin giving a tier server the request-lifecycle API: ``submit()``
    / ``run()`` / ``drain()`` on top of a lazily built
    :class:`RequestScheduler` over the server's own ``slots`` and
    ``context_len``.  The lock-step ``step()`` remains available as the
    degenerate one-batch case (the scheduler itself calls it with the
    live mask)."""

    _scheduler: RequestScheduler | None = None

    @property
    def scheduler(self) -> RequestScheduler:
        if self._scheduler is None:
            self._scheduler = RequestScheduler(
                self, self.slots, self.context_len
            )
        return self._scheduler

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        stop_on_exit: bool = False,
        arrival_step: int = 0,
    ) -> int:
        """Queue one request for continuous-batching admission; returns
        its rid (see :meth:`RequestScheduler.submit`)."""
        return self.scheduler.submit(
            prompt, max_new_tokens,
            stop_on_exit=stop_on_exit, arrival_step=arrival_step,
        )

    def run(self, max_steps: int | None = None) -> list[SchedulerStepReport]:
        """Decode up to ``max_steps`` request-loop steps (admitting and
        retiring between steps)."""
        return self.scheduler.run(max_steps)

    def drain(self) -> list[RequestResult]:
        """Run the request loop to completion; returns finished requests
        in retirement order."""
        return self.scheduler.drain()
