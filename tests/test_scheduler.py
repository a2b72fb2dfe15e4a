"""Continuous-batching request scheduler (serving/scheduler.py) and its
supporting refactors:

  * THE invariant: a request admitted into a recycled KV slot produces a
    token/exit-trajectory bitwise identical to running it alone from its
    admission state — K in {1, 2, 3}, compaction on/off, the Pallas
    kernel path in interpret mode, and a Mamba2 (SSD) trunk;
  * row-targeted prefill writes == fresh solo prefill caches, per-row
    reset, and the one-sync-per-decode-step contract under admission /
    retirement churn;
  * bucket-hint sanity across a mass-retirement + re-admission wave
    (buckets shrink to the live width, recover through a counted
    overflow retry);
  * gang (lock-step) vs continuous admission policies, TTFT / latency
    accounting, stop_on_exit retirement;
  * the occupancy-weighted expected-batch term in core.multitier and its
    threading through est_latency_s and the RepartitionController;
  * RepartitionController.probe_sample_frac: sampled epsilon probes with
    unbiased arrival accounting via branch_probe_mask;
  * core.profiler.profile_decode_layers: kernel-aware per-layer decode
    costs (interpret mode off-TPU).
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LayerCost, build_cost_profile, profile_decode_layers
from repro.core.multitier import (
    TierSpec,
    bucket_for,
    expected_time_multitier,
    solve_multitier,
)
from repro.models import model as M
from repro.serving import (
    PartitionedServer,
    RepartitionController,
    RequestScheduler,
    ServingEngine,
    TierExecutor,
    segments_for_cuts,
)


@pytest.fixture(scope="module")
def deep_model():
    """4 trunk layers, branches after v_1 and v_3, threshold calibrated to
    a mixed exit regime (as in test_compaction / test_kernel_runtime)."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
    tok = jax.random.randint(jax.random.PRNGKey(2), (8, 1), 0, cfg.vocab_size)
    res, _ = ex.step(tok, 0, M.init_caches(cfg, 8, 32))
    ents = np.concatenate([res.branch_entropy[l] for l in cfg.branch_layers])
    cfg = dataclasses.replace(
        cfg, exit_threshold=float((ents.min() + ents.max()) / 2)
    )
    return cfg, params


@pytest.fixture(scope="module")
def ssm_model():
    """Mamba2 smoke trunk with one side branch (SSD state scatter path)."""
    cfg = dataclasses.replace(get_smoke_config("mamba2_130m"), branch_layers=(1,))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, n, plen, seed=5):
    r = np.random.default_rng(seed)
    return [
        r.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        for _ in range(n)
    ]


def _target(cfg, plen=6, seed=9):
    return (
        np.random.default_rng(seed)
        .integers(0, cfg.vocab_size, size=plen)
        .astype(np.int32)
    )


def _server(cfg, params, cuts, *, compaction="bucketed", use_kernels=None,
            slots=4, context_len=64, **kw):
    """K=1/2/3 server over the same scheduler API."""
    if len(cuts) == 0:
        return ServingEngine(
            cfg, params, context_len=context_len, slots=slots,
            use_kernels=use_kernels,
        )
    if len(cuts) == 1:
        return PartitionedServer(
            cfg, params, cuts[0], compaction=compaction,
            use_kernels=use_kernels, slots=slots, context_len=context_len,
            **kw,
        )
    tiers = [TierSpec(f"t{j}", 1.0, 1e9) for j in range(len(cuts))]
    tiers.append(TierSpec("cloud", 1.0))
    return PartitionedServer(
        cfg, params, cuts, tiers=tiers, compaction=compaction,
        use_kernels=use_kernels, slots=slots, context_len=context_len,
    )


def _solo(cfg, params, cuts, budget=5, **kw):
    srv = _server(cfg, params, cuts, **kw)
    srv.submit(_target(cfg), budget)
    return srv.drain()[0]


def _recycled(cfg, params, cuts, budget=5, **kw):
    """Fill every slot with mixed-length/mixed-budget traffic, then submit
    the target so it lands in a recycled slot mid-flight."""
    srv = _server(cfg, params, cuts, **kw)
    for p in _prompts(cfg, 6, 4):
        srv.submit(p, 3)
    for p in _prompts(cfg, 2, 6, seed=7):
        srv.submit(p, 4)
    rid = srv.submit(_target(cfg), budget)
    srv.drain()
    res = srv.scheduler.results[rid]
    assert res.admitted_step > 0, "target must not be admitted at step 0"
    return res


def _assert_same_request(a, b):
    assert a.tokens == b.tokens
    assert a.exited == b.exited
    assert a.exit_tiers == b.exit_tiers


class TestSlotReuseBitwise:
    """The tentpole invariant: trajectory is a pure function of the
    request, independent of slot history and batch neighbors."""

    @pytest.mark.parametrize("cuts", [(), (2,), (1, 3)])
    @pytest.mark.parametrize("compaction", ["bucketed", "off"])
    def test_recycled_slot_matches_solo(self, deep_model, cuts, compaction):
        cfg, params = deep_model
        if not cuts and compaction == "off":
            pytest.skip("ServingEngine has no compaction knob")
        kw = {} if not cuts else {"compaction": compaction}
        solo = _solo(cfg, params, cuts, **kw)
        rec = _recycled(cfg, params, cuts, **kw)
        _assert_same_request(solo, rec)

    @pytest.mark.parametrize("cuts", [(2,), (1, 3)])
    def test_recycled_slot_matches_solo_with_kernels(self, deep_model, cuts):
        """use_kernels=True off-TPU runs the Pallas kernels in interpret
        mode — flash_decode's per-row q_pos scalar prefetch included."""
        cfg, params = deep_model
        solo = _solo(cfg, params, cuts, use_kernels=True)
        rec = _recycled(cfg, params, cuts, use_kernels=True)
        _assert_same_request(solo, rec)

    @pytest.mark.parametrize("use_kernels", [False, True])
    def test_ssm_recycled_slot_matches_solo(self, ssm_model, use_kernels):
        """Mamba2: the recycled slot's conv window + SSM state come from
        the row-targeted prefill scatter, not the previous occupant."""
        cfg, params = ssm_model
        solo = _solo(cfg, params, (), budget=4, use_kernels=use_kernels)
        rec = _recycled(cfg, params, (), budget=4, use_kernels=use_kernels)
        _assert_same_request(solo, rec)

    def test_mla_moe_recycled_slot_matches_solo(self):
        """MLA latent-cache rows (per-row ckv/k_rope ring writes + the
        absorbed decode's per-sequence positions) through a MoE trunk."""
        cfg = get_smoke_config("deepseek_v3_671b")
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        tgt = _target(cfg, plen=5, seed=3)

        def run(fill):
            eng = ServingEngine(cfg, params, context_len=32, slots=3)
            if fill:
                for p in _prompts(cfg, 4, 3, seed=1):
                    eng.submit(p, 2)
            rid = eng.submit(tgt, 4)
            eng.drain()
            res = eng.scheduler.results[rid]
            if fill:
                assert res.admitted_step > 0
            return res

        _assert_same_request(run(False), run(True))

    def test_trajectory_independent_of_neighbors(self, deep_model):
        """Same slot, different co-resident traffic -> same trajectory."""
        cfg, params = deep_model
        a = _recycled(cfg, params, (2,))
        srv = _server(cfg, params, (2,))
        for p in _prompts(cfg, 8, 3, seed=11):
            srv.submit(p, 2)
        rid = srv.submit(_target(cfg), 5)
        srv.drain()
        b = srv.scheduler.results[rid]
        _assert_same_request(a, b)


class TestRowTargetedPrefill:
    def test_prefill_rows_matches_solo_prefill(self, deep_model):
        """Every cache leaf of a recycled row equals a fresh solo prefill:
        prompt slots written, stale tail slots reset to empty."""
        cfg, params = deep_model
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
        caches = M.init_caches(cfg, 4, 32)
        # Dirty every row first (simulate previous occupants).
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 1), 0, cfg.vocab_size)
        for i in range(3):
            res, caches = ex.step(tok, np.full(4, i, np.int32), caches)
            tok = res.tokens_dev[:, None]
        prompts = np.stack(_prompts(cfg, 2, 7))
        caches, tok0 = ex.prefill_rows(caches, prompts, np.array([2, 0]))
        solo = M.init_caches(cfg, 2, 32)
        logits, solo = jax.jit(
            lambda p, i, c: M.prefill(p, i, cfg, c)
        )(params, {"tokens": prompts}, solo)
        np.testing.assert_array_equal(
            np.asarray(tok0),
            np.asarray(jax.numpy.argmax(logits[:, 0], -1)),
        )
        got = np.asarray(caches["blocks"]["self"]["k"])[:, [2, 0]]
        np.testing.assert_array_equal(got, np.asarray(solo["blocks"]["self"]["k"]))
        got_pos = np.asarray(caches["blocks"]["self"]["pos"])[:, [2, 0]]
        np.testing.assert_array_equal(
            got_pos, np.asarray(solo["blocks"]["self"]["pos"])
        )
        # Rows 1 and 3 were not touched by the admission.
        assert (np.asarray(caches["blocks"]["self"]["pos"])[:, [1, 3]] >= 0).any()

    def test_reset_rows_invalidates_slots(self, deep_model):
        cfg, params = deep_model
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
        caches = M.init_caches(cfg, 3, 16)
        res, caches = ex.step(
            jax.random.randint(jax.random.PRNGKey(1), (3, 1), 0, cfg.vocab_size),
            np.zeros(3, np.int32), caches,
        )
        caches = ex.reset_rows(caches, np.array([1]))
        pos = np.asarray(caches["blocks"]["self"]["pos"])
        assert (pos[:, 1] == -1).all()
        assert (pos[:, 0] == 0).any() and (pos[:, 2] == 0).any()


class TestSchedulerMechanics:
    def test_one_sync_per_decode_step(self, deep_model):
        """Admission prefill and retirement bookkeeping add no syncs: the
        request loop fetches exactly once per decode step (+ counted
        overflow retries)."""
        cfg, params = deep_model
        srv = _server(cfg, params, (2,), slots=4)
        for p in _prompts(cfg, 7, 4):
            srv.submit(p, 3)
        ex = srv.executor
        syncs0, retries0 = ex.host_syncs, ex.overflow_retries
        reports = srv.run()
        steps = len(reports)
        assert steps > 0
        assert ex.host_syncs - syncs0 == steps + (
            ex.overflow_retries - retries0
        )

    def test_ttft_and_latency_accounting(self, deep_model):
        cfg, params = deep_model
        srv = _server(cfg, params, (2,), slots=2)
        rids = [srv.submit(p, 3) for p in _prompts(cfg, 4, 4)]
        done = srv.drain()
        assert len(done) == 4
        for rid in rids:
            r = srv.scheduler.results[rid]
            assert r.done and len(r.tokens) == 3
            assert r.ttft_s is not None and r.latency_s is not None
            assert 0 < r.ttft_s <= r.latency_s
        # Queued-behind requests waited longer to first token.
        assert (
            srv.scheduler.results[rids[-1]].ttft_s
            >= srv.scheduler.results[rids[0]].ttft_s
        )

    def test_stop_on_exit_retires_at_first_branch_exit(self, deep_model):
        cfg, params = deep_model
        # Threshold above every entropy -> every token exits at branch 1.
        cfg_all = dataclasses.replace(cfg, exit_threshold=1.5)
        srv = _server(cfg_all, params, (2,), slots=2)
        rid = srv.submit(_target(cfg_all), 10, stop_on_exit=True)
        done = srv.drain()
        r = srv.scheduler.results[rid]
        assert r.done and len(r.tokens) == 1 and r.exited == [True]

    def test_gang_policy_is_lockstep_and_slower(self, deep_model):
        """gang admission (the lock-step degenerate case) pins freed slots
        until the whole wave drains; continuous admission finishes the
        same mixed-budget workload in fewer decode steps."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=0.0)  # no early exits

        def run(policy):
            srv = _server(cfg, params, (2,), slots=4)
            sched = RequestScheduler(srv, 4, 64, policy=policy)
            for i, p in enumerate(_prompts(cfg, 8, 4)):
                sched.submit(p, 2 if i % 2 else 8)
            sched.run()
            assert len(sched.finished) == 8
            assert sched.total_tokens == 4 * (2 + 8)
            return sched.step_count

        gang_steps = run("gang")
        cont_steps = run("continuous")
        assert gang_steps == 16  # two full waves of max(budget) steps
        assert cont_steps < gang_steps

    def test_arrival_step_gates_admission(self, deep_model):
        cfg, params = deep_model
        srv = _server(cfg, params, (2,), slots=2)
        rid = srv.submit(_target(cfg), 2, arrival_step=3)
        srv.drain()
        assert srv.scheduler.results[rid].admitted_step >= 3

    def test_result_active_mask_is_a_snapshot(self, deep_model):
        """TierStepResult.active must not alias the caller's mask: the
        scheduler clears retiring slots before on_step callbacks (the
        controller) read the result."""
        cfg, params = deep_model
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        caches = M.init_caches(cfg, 4, 16)
        active = np.array([True, True, False, True])
        res, _ = ex.step(
            jax.random.randint(jax.random.PRNGKey(1), (4, 1), 0, cfg.vocab_size),
            np.zeros(4, np.int32), caches, active=active,
        )
        active[0] = False  # retirement mutates the scheduler's mask
        assert res.active[0]  # ...but the step's snapshot is unchanged

    def test_future_arrival_does_not_block_arrived_requests(self, deep_model):
        """Admission is FIFO among *arrived* requests: a queue head whose
        simulated arrival is far out never head-of-line-blocks a later
        submit that is already admissible."""
        cfg, params = deep_model
        srv = _server(cfg, params, (2,), slots=2)
        late = srv.submit(_target(cfg, seed=1), 2, arrival_step=50)
        early = srv.submit(_target(cfg, seed=2), 2)
        srv.drain()
        res = srv.scheduler.results
        assert res[early].admitted_step == 0
        assert res[late].admitted_step >= 50
        # TTFT of the simulated late arrival is measured from its
        # arrival, not from submit(): it can't exceed the early request's
        # whole wall-clock span plus its own serving time.
        assert res[late].ttft_s < res[late].latency_s + res[early].latency_s

    def test_submit_validates_budget(self, deep_model):
        cfg, params = deep_model
        srv = _server(cfg, params, (2,), slots=2, context_len=16)
        with pytest.raises(ValueError, match="context_len"):
            srv.submit(_target(cfg, plen=10), 10)
        with pytest.raises(ValueError, match="max_new_tokens"):
            srv.submit(_target(cfg, plen=4), 0)


class TestBucketHintWave:
    def test_hints_track_mass_retirement_and_readmission(self, deep_model):
        """After a retirement wave the downstream bucket shrinks to the
        live width; a re-admission wave overflows once (counted, bitwise
        safe) and the bucket recovers to the full slot count."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=0.0)  # survivors = live
        srv = PartitionedServer(
            cfg, params, 2, slots=8, context_len=64, hint_window=1
        )
        sched = srv.scheduler
        for i in range(8):
            sched.submit(_target(cfg, seed=i), 3 if i < 4 else 9)
        buckets = []
        retries = []
        while sched.active.any() or sched.queue:
            rep = sched.step()
            if rep is None:
                continue
            res = rep.server_report.tier_result
            buckets.append(res.compaction[0].bucket if res.compaction else 0)
            retries.append(srv.executor.overflow_retries)
            if rep.step == 6:
                # Re-admission wave into the 4 freed slots.
                for j in range(4):
                    sched.submit(_target(cfg, seed=20 + j), 3)
        # Full occupancy first: the cloud tier ran the full batch.
        assert buckets[0] == 8
        # After the short-budget half retired, the hint shrank the bucket
        # to the live width...
        assert bucket_for(4, 8) in buckets[3:6]
        # ...and the re-admission wave grew it back (through a counted
        # overflow retry, never a wrong answer).
        assert buckets[-1] == 8
        assert retries[-1] >= 1


class TestOccupancyCost:
    def test_occupancy_one_is_identity(self):
        t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
        alpha = np.full(5, 64e3)
        p = np.zeros(5)
        tiers = [TierSpec("e", 4.0, 1e6), TierSpec("c", 1.0)]
        for cut in range(5):
            a = expected_time_multitier(t_c, alpha, p, tiers, (cut,), batch=8)
            b = expected_time_multitier(
                t_c, alpha, p, tiers, (cut,), batch=8, occupancy=1.0
            )
            assert a == b

    def test_low_occupancy_shrinks_downstream_and_transfer(self):
        t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
        alpha = np.full(5, 64e3)
        p = np.zeros(5)
        tiers = [TierSpec("e", 4.0, 1e6), TierSpec("c", 1.0)]
        full = expected_time_multitier(t_c, alpha, p, tiers, (2,), batch=8)
        quarter = expected_time_multitier(
            t_c, alpha, p, tiers, (2,), batch=8, occupancy=0.25
        )
        assert quarter < full
        # Edge-only plans ship nothing downstream: occupancy can't help.
        edge_full = expected_time_multitier(t_c, alpha, p, tiers, (4,), batch=8)
        edge_q = expected_time_multitier(
            t_c, alpha, p, tiers, (4,), batch=8, occupancy=0.25
        )
        assert edge_q == edge_full

    def test_occupancy_validation(self):
        t_c = np.array([0.0, 0.01])
        tiers = [TierSpec("e", 1.0, 1e6), TierSpec("c", 1.0)]
        with pytest.raises(ValueError, match="batch"):
            expected_time_multitier(
                t_c, np.zeros(2), np.zeros(2), tiers, (1,), occupancy=0.5
            )
        with pytest.raises(ValueError, match="occupancy"):
            expected_time_multitier(
                t_c, np.zeros(2), np.zeros(2), tiers, (1,), batch=4,
                occupancy=1.5,
            )

    def test_occupancy_moves_the_solved_cut(self):
        """A fat downstream tier is worth paying for at full occupancy but
        not at low occupancy (the entry tier still computes the nominal
        batch, the downstream tier only the live survivors)."""
        n = 4
        t_c = np.concatenate([[0.0], np.full(n, 0.01)])
        alpha = np.full(n + 1, 1e3)
        p = np.zeros(n + 1)
        tiers = [TierSpec("edge", 2.0, 1e9), TierSpec("cloud", 1.0)]
        plan_full = solve_multitier(t_c, alpha, p, tiers, batch=8)
        plan_low = solve_multitier(
            t_c, alpha, p, tiers, batch=8, occupancy=1.0 / 8.0
        )
        # Full occupancy: ship everything at layer 0 (cloud is 2x faster
        # per row and rows are everything).  1/8 occupancy: the bucketed
        # cloud still computes 1 row while the edge always pays the full
        # batch — the cut must not move backward, and costs drop.
        assert plan_low.expected_time_s <= plan_full.expected_time_s
        assert plan_low.cut_after >= plan_full.cut_after

    def test_estimator_prices_live_width(self, deep_model):
        """PartitionedServer.est_latency_s under continuous batching uses
        the step's live width: a half-occupied batch reports a cheaper
        (never costlier) step than the same batch fully occupied."""
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=0.0)
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.zeros(2), "3g", 50.0, 64.0
        )
        srv = PartitionedServer(
            cfg, params, 2, cost_profile=profile, slots=4, context_len=64
        )
        sched = srv.scheduler
        for p in _prompts(cfg, 2, 4):
            sched.submit(p, 6)
        half = sched.step().server_report
        assert half.tier_result.live == 2
        for p in _prompts(cfg, 2, 4, seed=8):
            sched.submit(p, 6)
        full = sched.step().server_report
        assert full.tier_result.live == 4
        assert half.est_latency_s <= full.est_latency_s

    def test_controller_tracks_occupancy(self, deep_model):
        """observe() feeds the live width into a decaying estimate that
        batched solves consume (explicit occupancy= overrides it)."""
        cfg, params = deep_model
        srv = PartitionedServer(cfg, params, 2, slots=4, context_len=64)
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.array([0.2, 0.2]), "3g", 50.0, 64.0
        )
        ctrl = RepartitionController(srv, profile, batch=4)
        sched = RequestScheduler(srv, 4, 64, on_step=[ctrl.observe])
        sched.submit(_target(cfg), 4)
        sched.run()
        assert ctrl._occ_est is not None
        assert 0 < ctrl._occ_est <= 0.5  # one live slot of four, decayed
        ctrl.occupancy = 0.75
        assert ctrl._solve_occupancy() == 0.75


class TestSampledProbes:
    def test_probe_mask_covers_sampled_rows_only(self, deep_model):
        """probe_sample_frac=0.5 evaluates the discarded branch's head on
        half the batch, reports the coverage mask, and never touches the
        trajectory."""
        cfg, params = deep_model
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        exf = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        caches = M.init_caches(cfg, 8, 32)
        cachesf = M.init_caches(cfg, 8, 32)
        tok = jax.random.randint(jax.random.PRNGKey(2), (8, 1), 0, cfg.vocab_size)
        ex.probe_next = True
        ex.probe_sample_frac = 0.5
        exf.probe_next = True  # full probe reference
        res, caches = ex.step(tok, 0, caches)
        resf, cachesf = exf.step(tok, 0, cachesf)
        np.testing.assert_array_equal(res.tokens, resf.tokens)
        np.testing.assert_array_equal(res.exited, resf.exited)
        # Branch 3 is discarded by the split-2 plan -> probed, sampled.
        cover = res.branch_probe_mask[3]
        assert cover.sum() == 4
        # Covered rows agree with the full probe; uncovered read False.
        np.testing.assert_array_equal(
            res.branch_take[3][cover], resf.branch_take[3][cover]
        )
        assert not res.branch_take[3][~cover].any()

    def test_probe_rotation_cycles_the_batch(self, deep_model):
        """Uncompacted tiers sample batch rows directly: the rotation
        cursor cycles every row across successive probes.  (Compacted
        tiers sample the dense sub-batch — the survivor permutation lives
        on device — so coverage there follows compaction order and is
        asserted via the reported mask, not a fixed rotation.)"""
        cfg, params = deep_model
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (2,)), compaction="off"
        )
        caches = M.init_caches(cfg, 8, 32)
        tok = jax.random.randint(jax.random.PRNGKey(2), (8, 1), 0, cfg.vocab_size)
        ex.probe_sample_frac = 0.25
        seen = np.zeros(8, bool)
        for i in range(4):
            ex.probe_next = True
            res, caches = ex.step(tok, i, caches)
            seen |= res.branch_probe_mask[3]
            tok = res.tokens_dev[:, None]
        assert seen.all()  # 4 probes x 2 rows rotate over all 8 rows

    def test_controller_sampled_probe_accounting(self, deep_model):
        """Arrivals at a sampled probed branch count covered rows only, so
        the conditional estimate stays a valid probability."""
        cfg, params = deep_model
        srv = PartitionedServer(cfg, params, 2, slots=8)
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.array([0.2, 0.2]), "3g", 50.0, 64.0
        )
        ctrl = RepartitionController(
            srv, profile, explore_every_n=2, probe_sample_frac=0.5
        )
        caches = M.init_caches(cfg, 8, 32)
        tok = jax.random.randint(jax.random.PRNGKey(2), (8, 1), 0, cfg.vocab_size)
        covered = 0
        for i in range(6):
            rep, caches = srv.step(tok, i, caches)
            res = rep.tier_result
            if 3 in res.branch_probe_mask:
                covered += int(res.branch_probe_mask[3].sum())
            ctrl.observe(rep.tier_result)
            tok = res.tokens_dev[:, None]
        assert covered > 0
        j3 = list(cfg.branch_layers).index(3)
        assert ctrl._arrivals[j3] <= covered  # never counts uncovered rows
        probs = ctrl.measured_probs()
        assert (probs >= 0).all() and (probs <= 1).all()

    def test_probe_sample_frac_validation(self, deep_model):
        cfg, params = deep_model
        srv = PartitionedServer(cfg, params, 2)
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.zeros(2), "3g", 50.0, 64.0
        )
        with pytest.raises(ValueError, match="probe_sample_frac"):
            RepartitionController(srv, profile, probe_sample_frac=0.0)


class TestKernelAwareProfiler:
    def test_profile_decode_layers_analyze(self, deep_model):
        """Both lowerings produce one cost per trunk layer with the
        residual stream as alpha; the kernel path runs in interpret mode
        off-TPU."""
        cfg, params = deep_model
        for kernels in (False, True):
            costs = profile_decode_layers(
                cfg, params, batch=2, context_len=16, use_kernels=kernels
            )
            assert len(costs) == cfg.num_layers
            for c in costs:
                assert np.isfinite(c.time_s) and c.time_s >= 0
                # alpha_i = the (B, 1, d) bf16 residual stream.
                assert c.output_bytes == 2 * 1 * cfg.d_model * 2.0

    def test_profile_decode_layers_measure(self, deep_model):
        cfg, params = deep_model
        costs = profile_decode_layers(
            cfg, params, batch=2, context_len=16,
            use_kernels=True, mode="measure", iters=2, warmup=1,
        )
        assert len(costs) == cfg.num_layers
        assert all(c.time_s > 0 for c in costs)

    def test_profile_mode_validation(self, deep_model):
        cfg, params = deep_model
        with pytest.raises(ValueError, match="mode"):
            profile_decode_layers(cfg, params, 2, 16, mode="wat")
