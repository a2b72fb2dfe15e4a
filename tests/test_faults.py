"""Fault-injected hops and exit-head degradation (serving/faults.py and
the executor's fault plane):

  * seeded `LinkFaultModel` determinism: identical draws across runs,
    prefix-stable per-attempt streams, scripted flap windows, per-hop
    knob mappings;
  * `HopPolicy` backoff ordering (exponential + jitter) and the pinned
    per-attempt event trace of `attempt_hop`;
  * `CircuitBreaker` transitions: closed -> open at the failure
    threshold, skip during cooldown, half-open probe, close on probe
    success / re-open on probe failure;
  * degraded steps: a benign fault model is bitwise invisible; a link
    kill finalizes survivors from the deepest exit head at or below the
    broken hop (the at-cut head the healthy plan discards included),
    with one host sync and one cache-clock bump per step; forced exits
    never pollute `branch_take`; a hop with no head below it fails the
    step without touching the caches;
  * the `transfer_seconds` dead-uplink regression: a wall-clock hop
    with bytes queued and no uplink raises `LinkDownError` instead of
    sleeping zero seconds (satellite: silent-free dead links);
  * `RequestScheduler` retirement under faults: terminal `failed` /
    `degraded` statuses, requeue-on-fail, and the KV-slot allocator
    invariant (no leaked slots across fault churn);
  * `RepartitionController` hop health: EWMA purity (breaker skips are
    not observations; a failed half-open probe never touches the
    transfer-time estimate), solver avoidance of availability-0 hops,
    drift-window reset on fault-driven re-solves, and the end-to-end
    breaker-open -> re-solve -> cut-moves-off-the-sick-link loop.
"""

import dataclasses
import types

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LayerCost, build_cost_profile
from repro.core.multitier import TierSpec, _hop_seconds, solve_multitier
from repro.models import model as M
from repro.serving import (
    PartitionedServer,
    RepartitionController,
    RequestScheduler,
    TierExecutor,
    segments_for_cuts,
)
from repro.serving.faults import (
    HEALTHY,
    CircuitBreaker,
    FaultEvent,
    FlapWindow,
    HopCondition,
    HopPolicy,
    LinkDownError,
    LinkFaultModel,
    attempt_hop,
)

B = 8


@pytest.fixture(scope="module")
def deep_model():
    """4 trunk layers, branches after v_1 and v_3, threshold calibrated to
    a mixed exit regime (as in test_scheduler)."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
    tok = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size)
    res, _ = ex.step(tok, 0, M.init_caches(cfg, B, 32))
    ents = np.concatenate([res.branch_entropy[l] for l in cfg.branch_layers])
    cfg = dataclasses.replace(
        cfg, exit_threshold=float((ents.min() + ents.max()) / 2)
    )
    return cfg, params


def _tok0(cfg):
    return jax.random.randint(
        jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab_size
    )


def _decode(cfg, params, cuts, *, fm=None, hp=None, steps=5, **kw):
    """Drive `steps` lock-step decode steps; return (executor, history)."""
    ex = TierExecutor(
        cfg, params,
        segments_for_cuts(cfg, cuts, uplinks=(1e9,) * len(cuts)),
        simulate_network=True, fault_model=fm, hop_policy=hp, **kw,
    )
    caches = M.init_caches(cfg, B, 32)
    tok = _tok0(cfg)
    hist = []
    for i in range(steps):
        res, caches = ex.step(tok, i, caches)
        hist.append(res)
        tok = res.tokens_dev[:, None]
    return ex, hist


KILL_HOP1 = LinkFaultModel(
    seed=0, flaps=(FlapWindow(hop=1, start_step=2, end_step=10_000),)
)
FAST_POLICY = HopPolicy(
    timeout_s=0.01, max_retries=1, backoff_s=0.001,
    breaker_threshold=2, breaker_cooldown_steps=3,
)


class TestLinkFaultModel:
    def test_draw_deterministic_and_prefix_stable(self):
        m = LinkFaultModel(seed=3, drop_p=0.5, spike_p=0.3, spike_s=0.01)
        c1, j1, d1 = m.draw(2, 0, 3)
        c2, j2, d2 = m.draw(2, 0, 3)
        assert c1 == c2 and j1 == j2 and np.array_equal(d1, d2)
        # PCG64 stream is prefix-stable: a policy allowing more attempts
        # sees the same leading drop flags, so retry budgets never shift
        # the fault schedule.
        _, _, d5 = m.draw(2, 0, 5)
        assert np.array_equal(d1, d5[:3])
        # Different (step, hop) keys draw independent streams.
        assert not all(
            np.array_equal(m.draw(s, h, 8)[2], m.draw(2, 0, 8)[2])
            for s, h in [(3, 0), (2, 1)]
        )

    def test_flap_windows(self):
        m = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=1, start_step=5, end_step=8),)
        )
        assert m.flapped(5, 1) and m.flapped(7, 1)
        assert not m.flapped(8, 1)  # end exclusive
        assert not m.flapped(6, 0)  # other hops untouched
        assert m.condition(6, 1).flapped
        assert not m.condition(4, 1).flapped

    def test_per_hop_mapping_knobs(self):
        m = LinkFaultModel(seed=0, drop_p={0: 1.0}, bandwidth_mult={1: 0.5})
        _, _, d0 = m.draw(0, 0, 4)
        _, _, d1 = m.draw(0, 1, 4)
        assert d0.all() and not d1.any()  # unlisted hop gets the default
        assert m.condition(0, 0).bandwidth_mult == 1.0
        assert m.condition(0, 1).bandwidth_mult == 0.5


class TestHopPolicy:
    def test_backoff_exponential_with_jitter(self):
        p = HopPolicy(backoff_s=0.01, backoff_mult=2.0, jitter_frac=0.5)
        assert p.backoff(1) == pytest.approx(0.01)
        assert p.backoff(2) == pytest.approx(0.02)
        assert p.backoff(3) == pytest.approx(0.04)
        assert p.backoff(1, jitter_u=1.0) == pytest.approx(0.015)

    def test_attempt_hop_event_ordering_when_down(self):
        """Pinned trace for a hard-down hop with one retry:
        link_down(0), retry(1), link_down(1), exhausted — and the
        overhead is two timeouts plus the first backoff."""
        p = HopPolicy(timeout_s=0.01, max_retries=1, backoff_s=0.002)
        out = attempt_hop(
            p, HopCondition(flapped=True), [False, False], 0.0,
            step=4, hop=1, est_bytes=100.0, uplink_bps=1e9, attempts=2,
        )
        assert not out.ok and out.attempts == 2
        assert [e.kind for e in out.events] == [
            "link_down", "retry", "link_down", "exhausted",
        ]
        assert [e.attempt for e in out.events[:3]] == [0, 1, 1]
        assert all(e.step == 4 and e.hop == 1 for e in out.events)
        assert out.overhead_s == pytest.approx(2 * 0.01 + 0.002)

    def test_attempt_hop_drop_then_success(self):
        p = HopPolicy(timeout_s=0.05, max_retries=2, backoff_s=0.001)
        out = attempt_hop(
            p, HEALTHY, [True, False, False], 0.0,
            step=0, hop=0, est_bytes=1000.0, uplink_bps=1e9, attempts=3,
        )
        assert out.ok and out.attempts == 2
        assert [e.kind for e in out.events] == ["drop", "retry"]
        assert out.overhead_s == pytest.approx(0.05 + 0.001)

    def test_attempt_hop_timeout_admission(self):
        """The estimated transfer of the worst-case payload exceeding the
        deadline fails the attempt without any device work."""
        p = HopPolicy(timeout_s=0.001, max_retries=0)
        out = attempt_hop(
            p, HEALTHY, [False], 0.0,
            step=0, hop=0, est_bytes=10e6, uplink_bps=1e6, attempts=1,
        )
        assert not out.ok
        assert [e.kind for e in out.events] == ["timeout", "exhausted"]


class TestCircuitBreaker:
    def test_transitions(self):
        b = CircuitBreaker(HopPolicy(breaker_threshold=3,
                                     breaker_cooldown_steps=4))
        assert b.gate(0) == "attempt"
        for s in range(3):
            b.record(s, ok=False)
        assert b.state == "open"
        assert b.gate(3) == "skip"  # cooling down
        assert b.gate(2 + 4) == "probe"  # cooldown elapsed -> half-open
        assert b.state == "half_open"
        b.record(6, ok=True)
        assert b.state == "closed" and b.failures == 0

    def test_half_open_failure_reopens(self):
        b = CircuitBreaker(HopPolicy(breaker_threshold=2,
                                     breaker_cooldown_steps=2))
        b.record(0, ok=False)
        b.record(1, ok=False)
        assert b.gate(1 + 2) == "probe"
        b.record(3, ok=False)  # one probe failure re-opens immediately
        assert b.state == "open"
        assert b.gate(4) == "skip"  # cooldown restarted from the re-open
        assert b.gate(3 + 2) == "probe"


class TestDegradedSteps:
    @pytest.mark.parametrize("cuts", [(2,), (1, 3)])
    @pytest.mark.parametrize("compaction", ["bucketed", "off"])
    def test_benign_model_is_bitwise_invisible(self, deep_model, cuts,
                                               compaction):
        """An armed fault plane with a benign model (no drops, mult 1,
        no spikes) must not perturb the trajectory by one bit."""
        cfg, params = deep_model
        _, base = _decode(cfg, params, cuts, compaction=compaction)
        _, ben = _decode(cfg, params, cuts, fm=LinkFaultModel(seed=0),
                         compaction=compaction)
        for a, b in zip(base, ben):
            assert np.array_equal(a.tokens, b.tokens)
            assert np.array_equal(a.exit_tier, b.exit_tier)
            assert b.degraded is None or not b.degraded.any()
            assert b.degraded_hop is None

    def test_benign_model_is_bitwise_invisible_with_kernels(self, deep_model):
        cfg, params = deep_model
        _, base = _decode(cfg, params, (1, 3), steps=3, use_kernels=True)
        _, ben = _decode(cfg, params, (1, 3), steps=3, use_kernels=True,
                         fm=LinkFaultModel(seed=0))
        for a, b in zip(base, ben):
            assert np.array_equal(a.tokens, b.tokens)

    def test_link_kill_degrades_via_fallback_head(self, deep_model):
        """Mid-run hop-1 kill (cuts (1,3)): the broken hop's cut is layer
        3, so survivors are force-finalized from the branch-3 head on the
        mid tier — the head the healthy plan discards at the cut."""
        cfg, params = deep_model
        ex, base = _decode(cfg, params, (1, 3), steps=6)
        ex2, hist = _decode(cfg, params, (1, 3), fm=KILL_HOP1,
                            hp=FAST_POLICY, steps=6)
        # Healthy prefix identical; faulted steps all-exited.
        for a, b in zip(base[:2], hist[:2]):
            assert np.array_equal(a.tokens, b.tokens)
        assert ex2.degraded_steps > 0 and ex2.failed_steps == 0
        assert ex2.fault_retries > 0
        saw = False
        for s, res in enumerate(hist[2:], start=2):
            assert res.exited.all()  # every live row finalized
            if res.degraded is not None and res.degraded.any():
                saw = True
                assert res.degraded_hop == 1
                # Forced rows exit on the tier holding the fallback head
                # and are never reported as genuine branch exits.
                assert (res.exit_tier[res.degraded] == 1).all()
                for take in res.branch_take.values():
                    assert not (take & res.degraded).any()
                # Nothing shipped on or past the broken hop.
                assert res.shipped_per_hop[1] == 0
                assert res.bytes_per_hop[1] == 0.0
        assert saw
        # Breaker lifecycle in the trace: retries exhaust, the breaker
        # opens, then cooldown steps skip the hop without touching it.
        kinds = [e.kind for res in hist for e in res.fault_events]
        for k in ("link_down", "retry", "exhausted", "breaker_open",
                  "breaker_skip"):
            assert k in kinds, k

    def test_forced_tokens_are_fallback_head_argmax(self, deep_model):
        """Step-0 hop-0 kill (cuts (2,): branch 1 lives below the cut) vs
        a healthy run whose threshold makes every row genuinely exit at
        branch 1: identical tokens, because forced finalization takes the
        same branch-head argmax the threshold exit would have taken."""
        cfg, params = deep_model
        all_exit = dataclasses.replace(cfg, exit_threshold=float("inf"))
        _, ref = _decode(all_exit, params, (2,), steps=1)
        assert ref[0].exited.all()  # the reference exits genuinely
        fm = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=0, start_step=0, end_step=10),)
        )
        _, forced = _decode(cfg, params, (2,), fm=fm, hp=FAST_POLICY,
                            steps=1)
        assert np.array_equal(ref[0].tokens, forced[0].tokens)
        assert forced[0].exited.all()
        # Degraded rows are exactly the complement of the genuine branch-1
        # exits — forced finalization and threshold exit share the head.
        assert forced[0].degraded is not None
        assert np.array_equal(forced[0].degraded,
                              ~forced[0].branch_take[1])

    def test_degraded_step_bumps_cache_clock_once(self, deep_model):
        """One KV ring-buffer advance per degraded step — the fallback
        segment variant owns the bump the absent head tier would have
        made."""
        cfg, params = deep_model
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (1, 3), uplinks=(1e9, 1e9)),
            simulate_network=True, fault_model=KILL_HOP1,
            hop_policy=FAST_POLICY,
        )
        caches = M.init_caches(cfg, B, 32)
        tok = _tok0(cfg)
        for i in range(4):
            before = int(np.asarray(caches["length"]).max())
            res, caches = ex.step(tok, i, caches)
            after = int(np.asarray(caches["length"]).max())
            assert after == before + 1
            tok = res.tokens_dev[:, None]
        assert ex.degraded_steps > 0

    def test_one_sync_per_degraded_step(self, deep_model):
        cfg, params = deep_model
        ex, _ = _decode(cfg, params, (1, 3), fm=KILL_HOP1, hp=FAST_POLICY,
                        steps=6)
        assert ex.host_syncs == 6
        assert ex.degraded_steps > 0

    def test_no_head_below_hop_fails_step(self, deep_model):
        """Branch only at layer 3, cut after 2: a hop-0 kill leaves no
        exit head at or below the cut — the step fails every live row,
        emits nothing, and leaves the caches (clock included) untouched
        with zero device syncs."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, branch_layers=(3,), exit_threshold=0.0)
        fm = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=0, start_step=0, end_step=10),)
        )
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (2,), uplinks=(1e9,)),
            simulate_network=True, fault_model=fm, hop_policy=FAST_POLICY,
        )
        caches = M.init_caches(cfg, B, 32)
        before = np.asarray(caches["length"]).copy()
        res, caches = ex.step(_tok0(cfg), 0, caches)
        assert res.failed.all() and not res.degraded.any()
        assert (res.exit_tier == -1).all()
        assert np.array_equal(np.asarray(caches["length"]), before)
        assert ex.host_syncs == 0 and ex.failed_steps == 1

    def test_seeded_fault_runs_are_deterministic(self, deep_model):
        """Satellite: same model seed + schedule -> identical fault
        events, retry counts, degraded masks, and tokens across runs."""
        cfg, params = deep_model
        fm = LinkFaultModel(
            seed=7, drop_p=0.3, spike_p=0.2, spike_s=0.005,
            flaps=(FlapWindow(hop=1, start_step=3, end_step=5),),
        )
        ex1, h1 = _decode(cfg, params, (1, 3), fm=fm, hp=FAST_POLICY, steps=6)
        ex2, h2 = _decode(cfg, params, (1, 3), fm=fm, hp=FAST_POLICY, steps=6)
        assert ex1.fault_retries == ex2.fault_retries
        assert ex1.degraded_steps == ex2.degraded_steps
        for a, b in zip(h1, h2):
            assert a.fault_events == b.fault_events
            assert np.array_equal(a.tokens, b.tokens)
            assert (a.degraded is None) == (b.degraded is None)
            if a.degraded is not None:
                assert np.array_equal(a.degraded, b.degraded)


class TestDeadUplink:
    def test_unset_uplink_raises_instead_of_free_transfer(self, deep_model):
        """Satellite: simulate_network with bytes queued on a hop whose
        uplink_bps is unset/zero must raise, not price the hop at zero
        seconds."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=0.0)  # nobody exits
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (2,)),  # uplink defaults 0
            simulate_network=True,
        )
        with pytest.raises(LinkDownError, match="hop 0"):
            ex.step(_tok0(cfg), 0, M.init_caches(cfg, B, 32))

    def test_no_payload_no_raise(self, deep_model):
        """A dead uplink that never ships (every row exits below the cut)
        stays silent — the regression only triggers on queued bytes."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=float("inf"))
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (2,)), simulate_network=True,
        )
        res, _ = ex.step(_tok0(cfg), 0, M.init_caches(cfg, B, 32))
        assert res.exited.all() and res.bytes_per_hop[0] == 0.0

    def test_fault_model_degrades_instead_of_raising(self, deep_model):
        """With a LinkFaultModel attached the same dead uplink becomes a
        planned link-down: retries burn out and the step degrades."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=0.0)
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (2,)),
            simulate_network=True, fault_model=LinkFaultModel(seed=0),
            hop_policy=FAST_POLICY,
        )
        res, _ = ex.step(_tok0(cfg), 0, M.init_caches(cfg, B, 32))
        assert res.degraded_hop == 0
        assert res.exited.all()


def _profile(cfg):
    costs = [
        LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
        for i in range(cfg.num_layers)
    ]
    return build_cost_profile(
        costs, cfg.branch_layers, np.array([0.2, 0.2]), "3g", 50.0, 64.0
    )


def _prompts(cfg, n, plen, seed=5):
    r = np.random.default_rng(seed)
    return [
        r.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        for _ in range(n)
    ]


def _fault_server(cfg, params, fm, hp, *, tiers=None, cuts=(1, 3), slots=4):
    tiers = tiers or [
        TierSpec("edge", 4.0, 1e9),
        TierSpec("mid", 2.0, 1e9),
        TierSpec("cloud", 1.0),
    ]
    return PartitionedServer(
        cfg, params, cuts, tiers=tiers, simulate_network=True,
        slots=slots, context_len=64, fault_model=fm, hop_policy=hp,
    )


class TestSchedulerFaults:
    def test_drain_completes_under_link_kill(self, deep_model):
        """Every in-flight and queued request finishes despite a mid-run
        hop kill; no slot leaks; degraded tokens are attributed."""
        cfg, params = deep_model
        fm = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=1, start_step=4, end_step=10_000),)
        )
        srv = _fault_server(cfg, params, fm, FAST_POLICY)
        sched = RequestScheduler(srv, 4, 64)
        for p in _prompts(cfg, 8, 6):
            sched.submit(p, 8)
        results = sched.drain()
        assert len(results) == 8 and all(r.done for r in results)
        assert {r.status for r in results} <= {"ok", "degraded"}
        assert sum(r.degraded_tokens for r in results) > 0
        assert sched.active.sum() == 0
        assert all(r is None for r in sched._slot_req)

    def test_terminal_failed_reclaims_slots(self, deep_model):
        """No fallback head below the broken hop and requeue disabled:
        requests retire with status 'failed', their slots are reclaimed,
        and queued requests still cycle through (and fail) — the drain
        terminates with the allocator empty."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, branch_layers=(3,), exit_threshold=0.0)
        fm = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=0, start_step=0, end_step=10_000),)
        )
        tiers = [TierSpec("edge", 4.0, 1e9), TierSpec("cloud", 1.0)]
        srv = _fault_server(cfg, params, fm, FAST_POLICY,
                            tiers=tiers, cuts=(2,), slots=2)
        sched = RequestScheduler(srv, 2, 64)
        for p in _prompts(cfg, 4, 6):
            sched.submit(p, 4)
        results = sched.drain()
        assert len(results) == 4
        assert all(r.done and r.status == "failed" for r in results)
        assert all(r.tokens == [] for r in results)
        assert sched.active.sum() == 0
        assert all(r is None for r in sched._slot_req)

    def test_requeue_on_fail_recovers_after_flap(self, deep_model):
        """A finite flap with requeue_on_fail: failed requests re-enter
        the queue, re-admit after the link recovers, and complete
        cleanly from a fresh admission."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, branch_layers=(3,), exit_threshold=0.0)
        fm = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=0, start_step=2, end_step=5),)
        )
        hp = HopPolicy(timeout_s=0.01, max_retries=0,
                       breaker_threshold=100)  # no breaker: probe the flap
        tiers = [TierSpec("edge", 4.0, 1e9), TierSpec("cloud", 1.0)]
        srv = _fault_server(cfg, params, fm, hp,
                            tiers=tiers, cuts=(2,), slots=2)
        sched = RequestScheduler(srv, 2, 64, requeue_on_fail=True,
                                 max_requeues=8)
        for p in _prompts(cfg, 2, 6):
            sched.submit(p, 4)
        saw_fail_step = False
        for _ in range(200):
            rep = sched.step()
            if rep is not None and rep.failed:
                saw_fail_step = True
            if not sched.queue and not sched.active.any():
                break
        results = [sched.results[r] for r in sorted(sched.results)]
        assert saw_fail_step
        assert all(r.done and r.status == "ok" for r in results)
        assert all(len(r.tokens) == 4 for r in results)
        assert sched.active.sum() == 0
        assert all(r is None for r in sched._slot_req)


class TestControllerHopHealth:
    def test_hop_seconds_availability_math(self):
        assert _hop_seconds(8e9, 1e9) == pytest.approx(8.0)
        assert _hop_seconds(8e9, 1e9, availability=0.5) == pytest.approx(16.0)
        assert _hop_seconds(8e9, 1e9, availability=0.0) == float("inf")
        assert _hop_seconds(0.0, 1e9, availability=0.0) == 0.0

    def test_solver_avoids_dead_hop(self):
        """availability=0 on a hop prices any payload across it at +inf;
        the optimal plan ships zero bytes on it (cut at L)."""
        L = 6
        t_c = np.concatenate([[0.0], np.full(L, 1e-3)])
        alpha = np.concatenate([[64.0], np.full(L, 64.0)])
        p = np.zeros(L + 1)
        p[2] = 0.6
        tiers = [
            TierSpec("edge", 2.0, 1e8),
            TierSpec("mid", 1.5, 1e8, availability=0.0),
            TierSpec("cloud", 1.0),
        ]
        plan = solve_multitier(t_c, alpha, p, tiers)
        assert plan.cut_after[1] == L  # nothing may cross the dead hop
        healthy = [dataclasses.replace(t, availability=1.0) for t in tiers]
        ref = solve_multitier(t_c, alpha, p, healthy)
        assert ref.cut_after[1] < L  # ...which the healthy plan uses

    def _controller(self, deep_model, **kw):
        cfg, params = deep_model
        tiers = [
            TierSpec("edge", 4.0, 1e9),
            TierSpec("mid", 2.0, 1e9),
            TierSpec("cloud", 1.0),
        ]
        srv = PartitionedServer(cfg, params, (1, 3), tiers=tiers, slots=4,
                                context_len=64)
        return RepartitionController(srv, _profile(cfg), tiers=tiers, **kw), srv

    @staticmethod
    def _report(events=(), broken=None, nb=(100.0, 100.0),
                sim=(1e-4, 1e-4)):
        return types.SimpleNamespace(
            fault_events=tuple(events), degraded_hop=broken,
            bytes_per_hop=tuple(nb), sim_transfer_s=tuple(sim),
        )

    def test_breaker_skip_is_not_an_observation(self, deep_model):
        ctl, _ = self._controller(deep_model, fault_resolve=False)
        ctl._ingest_faults(self._report(
            events=[FaultEvent(0, 0, "breaker_skip")], broken=0,
        ))
        assert 0 not in ctl._hop_avail and 0 not in ctl._hop_xfer

    def test_probe_failure_never_touches_xfer_ewma(self, deep_model):
        """Satellite: a failed half-open probe moves availability but the
        transfer-time EWMA only ever ingests successful shipments."""
        ctl, _ = self._controller(deep_model, fault_resolve=False)
        ctl._hop_xfer[0] = 5.0
        ctl._ingest_faults(self._report(
            events=[FaultEvent(3, 0, "breaker_half_open"),
                    FaultEvent(3, 0, "link_down", 0),
                    FaultEvent(3, 0, "exhausted", 0)],
            broken=0, nb=(0.0, 0.0), sim=(0.0, 0.0),
        ))
        assert ctl._hop_xfer[0] == 5.0
        assert ctl._hop_avail[0] == pytest.approx(1.0 - ctl.hop_alpha)

    def test_successful_hops_feed_both_ewmas(self, deep_model):
        ctl, _ = self._controller(deep_model, fault_resolve=False)
        ctl._ingest_faults(self._report(
            events=[FaultEvent(0, 0, "drop", 0)],  # any event arms ingest
            nb=(1000.0, 1000.0), sim=(2e-3, 4e-3),
        ))
        assert ctl._hop_avail[0] == 1.0 and ctl._hop_avail[1] == 1.0
        assert ctl._hop_xfer[0] == pytest.approx(2e-3)
        assert ctl._hop_xfer[1] == pytest.approx(4e-3)
        health = ctl.hop_health()
        assert not health[0]["open"]

    def test_breaker_open_resolves_and_resets_drift_window(self, deep_model):
        """Satellite: a breaker_open event re-solves through update_tiers
        — availability 0 lands in the server's specs and the drift window
        restarts under the new plan."""
        ctl, srv = self._controller(deep_model)
        ctl._installed_p = np.array([0.2, 0.2])
        ctl._arrivals[:] = [8.0, 4.0]
        ctl._exits[:] = [2.0, 1.0]
        ctl._window_age = 7
        cuts = ctl._ingest_faults(self._report(
            events=[FaultEvent(2, 1, "exhausted", 1),
                    FaultEvent(2, 1, "breaker_open")],
            broken=1,
        ))
        assert cuts is not None and ctl.fault_resolves == 1
        assert srv.tiers[1].availability == 0.0
        assert srv.cuts[1] == cfg_layers(srv)  # nothing crosses the hop
        assert ctl._arrivals.sum() == 0 and ctl._exits.sum() == 0
        assert ctl._window_age == 0
        assert ctl.hop_health()[1]["open"]

    def test_breaker_closed_forgives_and_can_stay_manual(self, deep_model):
        """Recovery with fault_resolve=False: ingestion tracks the closed
        breaker (open set cleared, availability forgiven to 1.0) but
        never re-solves on its own."""
        ctl, srv = self._controller(deep_model, fault_resolve=False)
        before = srv.cuts
        ctl._ingest_faults(self._report(
            events=[FaultEvent(2, 1, "exhausted", 1),
                    FaultEvent(2, 1, "breaker_open")],
            broken=1,
        ))
        assert ctl.hop_health()[1]["open"] and ctl.fault_resolves == 0
        ctl._ingest_faults(self._report(
            events=[FaultEvent(6, 1, "breaker_half_open"),
                    FaultEvent(6, 1, "breaker_closed")],
        ))
        assert not ctl._hop_open
        assert ctl._hop_avail[1] == 1.0
        assert ctl.fault_resolves == 0 and srv.cuts == before

    def test_e2e_breaker_open_moves_cut_off_sick_link(self, deep_model):
        """The loop the tentpole promises: link kill -> retries exhaust ->
        breaker opens -> controller re-solves -> the new cuts ship zero
        bytes on the sick hop -> requests keep completing."""
        cfg, params = deep_model
        fm = LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=1, start_step=4, end_step=10_000),)
        )
        srv = _fault_server(cfg, params, fm, FAST_POLICY)
        ctl = RepartitionController(srv, _profile(cfg),
                                    tiers=list(srv.tiers))
        sched = RequestScheduler(srv, 4, 64, on_step=[ctl.observe])
        for p in _prompts(cfg, 8, 6):
            sched.submit(p, 10)
        results = sched.drain()
        assert all(r.done for r in results)
        assert ctl.fault_resolves >= 1
        assert srv.tiers[1].availability == 0.0
        assert srv.cuts[1] == cfg.num_layers  # hop 1 carries nothing now
        assert sched.active.sum() == 0
        assert all(r is None for r in sched._slot_req)


def cfg_layers(srv) -> int:
    return srv.cfg.num_layers
