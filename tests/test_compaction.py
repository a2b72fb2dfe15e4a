"""Survivor-compacted tier runtime (serving/tiers.py compact->run->scatter):

  * bitwise token/exit equivalence vs the masked path, K in {1, 2, 3},
    single-step from identical cache state and multi-step in the no-exit /
    all-exit extremes;
  * bucket-boundary batches (B=1, B=bucket, B=bucket+1);
  * the 1-sync invariant and the overflow-retry escape hatch;
  * no re-jit when only survivor counts change within a bucket;
  * per-hop compaction stats, bucketed cost model, simulated uplink
    latency, and the repartition controller's drift detection;
  * the pipelined overlap mode composed with compaction: bitwise
    equivalence, the overflow-retry serial fallback, and the
    steady-state bottleneck-stage wall clock.
"""

import dataclasses
import time

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (
    BranchSpec,
    CostProfile,
    LayerCost,
    NetworkProfile,
    build_cost_profile,
)
from repro.core.multitier import (
    TierSpec,
    bucket_for,
    bucket_ladder,
    expected_time_multitier,
    solve_multitier,
)
from repro.models import model as M
from repro.serving import (
    PartitionedServer,
    RepartitionController,
    TierExecutor,
    segments_for_cuts,
)
from repro.serving.controller import exit_drift_kl


@pytest.fixture(scope="module")
def deep_model():
    """4 trunk layers, branches after v_1 and v_3 — enough structure for
    K=3 cuts, mid-tier exits, and bucket-boundary batches."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _toks(cfg, batch, seed=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, 1), 0, cfg.vocab_size
    )


def _mixed_threshold(cfg, params, batch=8):
    """A threshold between the observed branch entropies so exits are a
    deterministic mix (some rows exit, some survive) on the fixed seed."""
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
    res, _ = ex.step(_toks(cfg, batch), 0, M.init_caches(cfg, batch, 32))
    ents = np.concatenate([res.branch_entropy[l] for l in cfg.branch_layers])
    lo, hi = float(ents.min()), float(ents.max())
    assert hi > lo, "degenerate entropies; pick another seed"
    return (lo + hi) / 2


def _run(cfg, params, cuts, *, batch, steps, compaction, seed=2):
    ex = TierExecutor(
        cfg, params, segments_for_cuts(cfg, cuts), compaction=compaction
    )
    caches = M.init_caches(cfg, batch, 64)
    tok = _toks(cfg, batch, seed)
    out = []
    for i in range(steps):
        res, caches = ex.step(tok, i, caches)
        out.append(res)
        tok = res.tokens_dev[:, None]
    return ex, out


class TestBitwiseEquivalence:
    @pytest.mark.parametrize("cuts", [(), (2,), (1, 3), (2, 3)])
    def test_single_step_identical(self, deep_model, cuts):
        """K in {1,2,3}: one step from identical caches, mixed exits."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(
            cfg0, exit_threshold=_mixed_threshold(cfg0, params)
        )
        _, [rm] = _run(cfg, params, cuts, batch=8, steps=1, compaction="off")
        exc, [rc] = _run(cfg, params, cuts, batch=8, steps=1,
                         compaction="bucketed")
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        np.testing.assert_array_equal(rm.exited, rc.exited)
        np.testing.assert_array_equal(rm.exit_tier, rc.exit_tier)
        for layer in rm.branch_take:
            np.testing.assert_array_equal(
                rm.branch_take[layer], rc.branch_take[layer]
            )

    def test_single_step_identical_with_warm_buckets(self, deep_model):
        """Equivalence also when compaction actually engages (bucket < B):
        warm the hints with one step, then compare a step from fresh
        identical caches on both paths."""
        cfg0, params = deep_model
        # A midpoint threshold doesn't guarantee >half exit *at branch 1*
        # (the only branch before the cut); sit between the 6th and 7th
        # smallest branch-1 entropies so 6 of 8 exit on the edge and the
        # cloud bucket really shrinks below the 8-row batch.
        ex0 = TierExecutor(cfg0, params, segments_for_cuts(cfg0, ()))
        r0, _ = ex0.step(_toks(cfg0, 8), 0, M.init_caches(cfg0, 8, 32))
        b1 = np.sort(r0.branch_entropy[1])
        cfg = dataclasses.replace(
            cfg0, exit_threshold=float((b1[5] + b1[6]) / 2)
        )
        exm = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)),
                           compaction="off")
        exc = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        exc.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))  # warm hints
        rm, _ = exm.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))
        rc, _ = exc.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        np.testing.assert_array_equal(rm.exited, rc.exited)
        assert rc.compaction[0].bucket < 8  # compaction really engaged
        assert rc.compaction[0].survivors <= rc.compaction[0].bucket

    @pytest.mark.parametrize("cuts", [(2,), (2, 3)])
    @pytest.mark.parametrize("threshold", [0.0, 1.5])
    def test_multistep_extremes_identical(self, deep_model, cuts, threshold):
        """No-exit (threshold 0) and all-exit (1.5) regimes stay bitwise
        identical to the masked path across autoregressive steps."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=threshold)
        _, outs_m = _run(cfg, params, cuts, batch=4, steps=5, compaction="off")
        exc, outs_c = _run(cfg, params, cuts, batch=4, steps=5,
                           compaction="bucketed")
        for a, b in zip(outs_m, outs_c):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.exited, b.exited)
        assert exc.overflow_retries == 0
        assert exc.host_syncs == 5

    def test_mixed_multistep_is_bucket_history_independent(self, deep_model):
        """The compacted semantics are a pure function of exits, never of
        bucket/hint/retry history: two executors whose hints disagree (one
        cold, one seeded with tiny stale hints that force overflow
        retries) must produce bitwise-identical trajectories.  The first
        step additionally matches the masked path exactly (after that,
        survivor rows may diverge from masked via the documented
        hole semantics — which is why this invariant matters)."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(
            cfg0, exit_threshold=_mixed_threshold(cfg0, params)
        )
        _, outs_m = _run(cfg, params, (2,), batch=8, steps=4, compaction="off")
        exa, outs_a = _run(cfg, params, (2,), batch=8, steps=4,
                           compaction="bucketed")

        exb = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        caches = M.init_caches(cfg, 8, 64)
        tok = _toks(cfg, 8)
        outs_b = []
        for i in range(4):
            exb._hints = {1: 1}  # stale hint: forces retry when >1 survive
            res, caches = exb.step(tok, i, caches)
            outs_b.append(res)
            tok = res.tokens_dev[:, None]

        np.testing.assert_array_equal(outs_m[0].tokens, outs_a[0].tokens)
        np.testing.assert_array_equal(outs_m[0].exited, outs_a[0].exited)
        saw_exit = False
        for a, b in zip(outs_a, outs_b):
            saw_exit |= bool(a.exited.any())
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.exited, b.exited)
        assert saw_exit
        assert exb.overflow_retries > exa.overflow_retries


class TestBucketBoundaries:
    @pytest.mark.parametrize("batch", [1, 4, 5])
    def test_boundary_batches(self, deep_model, batch):
        """B=1, B=bucket (power of two), B=bucket+1 all stay correct in the
        all-exit regime (bucket shrinks to the 1-row floor)."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=1.5)
        _, outs_m = _run(cfg, params, (2,), batch=batch, steps=3,
                         compaction="off")
        exc, outs_c = _run(cfg, params, (2,), batch=batch, steps=3,
                           compaction="bucketed")
        for a, b in zip(outs_m, outs_c):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.exited, b.exited)
        assert outs_c[-1].compaction[0].bucket == 1
        assert outs_c[-1].compaction[0].survivors == 0
        assert outs_c[-1].compaction[0].padded_waste == 1

    def test_ladder(self):
        assert bucket_ladder(8) == (1, 2, 4, 8)
        assert bucket_ladder(6) == (1, 2, 4, 6)
        assert bucket_ladder(1) == (1,)
        assert bucket_for(0, 8) == 1  # 1-row floor keeps cache slots moving
        assert bucket_for(3, 8) == 4
        assert bucket_for(8, 8) == 8
        assert bucket_for(5, 6) == 6


class TestSyncsAndRetries:
    def test_one_sync_per_step_with_compaction_engaged(self, deep_model):
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=1.5)  # all exit
        exc, outs = _run(cfg, params, (2,), batch=8, steps=6,
                         compaction="bucketed")
        assert exc.host_syncs == 6
        assert exc.overflow_retries == 0
        assert outs[-1].compaction[0].bucket == 1  # really compacted

    def test_overflow_retry_is_bitwise_correct(self, deep_model):
        """An exit-rate spike (hint says 1 survivor, 8 arrive) triggers one
        retry: results still match the masked path bitwise, and the extra
        sync is counted."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)  # no exits
        exm = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)),
                           compaction="off")
        exc = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        cm, cc = M.init_caches(cfg, 8, 32), M.init_caches(cfg, 8, 32)
        tok = _toks(cfg, 8)
        rm, cm = exm.step(tok, 0, cm)
        rc, cc = exc.step(tok, 0, cc)
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        exc._hints = {1: 1}  # fake a stale all-exit hint
        rm, cm = exm.step(rm.tokens_dev[:, None], 1, cm)
        rc, cc = exc.step(rc.tokens_dev[:, None], 1, cc)
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        np.testing.assert_array_equal(rm.exited, rc.exited)
        assert exc.overflow_retries == 1
        assert exc.host_syncs == 3  # 1 + (1 + 1 retry)

    def test_overflow_retry_fixes_all_segments(self, deep_model):
        """Stale hints on *every* downstream segment of a K=3 stack are
        repaired by the retry loop in one pass (exact measured counts),
        with results bitwise equal to the masked path."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)
        exm = TierExecutor(cfg, params, segments_for_cuts(cfg, (2, 3)),
                           compaction="off")
        exc = TierExecutor(cfg, params, segments_for_cuts(cfg, (2, 3)))
        cm, cc = M.init_caches(cfg, 8, 32), M.init_caches(cfg, 8, 32)
        tok = _toks(cfg, 8)
        rm, cm = exm.step(tok, 0, cm)
        rc, cc = exc.step(tok, 0, cc)
        exc._hints = {1: 1, 2: 1}  # both downstream tiers under-provisioned
        rm, cm = exm.step(rm.tokens_dev[:, None], 1, cm)
        rc, cc = exc.step(rc.tokens_dev[:, None], 1, cc)
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        np.testing.assert_array_equal(rm.exited, rc.exited)
        assert exc.overflow_retries == 1  # one loop iteration fixed both
        assert all(c.bucket == 8 for c in rc.compaction)

    def test_no_rejit_when_survivors_change_within_bucket(self, deep_model):
        """Steps whose survivor count moves within one bucket reuse the
        compiled segment: the trace counter must not grow."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=1.5)  # 0 survivors
        exc = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        caches = M.init_caches(cfg, 8, 64)
        tok = _toks(cfg, 8)
        res, caches = exc.step(tok, 0, caches)  # full-batch buckets (step 0)
        # Hints 0 and 1 both land in bucket 1; hints 3 and 4 in bucket 4.
        # With zero true survivors no step retries, so the planned bucket
        # is exactly what runs.
        for step, hint in enumerate((0, 1, 3, 4), start=1):
            exc._hints = {1: hint}
            res, caches = exc.step(res.tokens_dev[:, None], step, caches)
            assert res.compaction[0].bucket == bucket_for(hint, 8)
        assert exc.overflow_retries == 0
        # Every (spec, bucket) pair traced exactly once: the second visit
        # to bucket 1 (hint 1) and to bucket 4 (hint 4) re-jitted nothing.
        # (Bucket 8 is step 0's conservative full-batch-width compact fn.)
        assert all(v == 1 for v in exc.trace_counts.values())
        traced_buckets = sorted(
            b for (_spec, b) in exc.trace_counts if b is not None
        )
        assert traced_buckets == [1, 4, 8]

    def test_compaction_off_is_legacy(self, deep_model):
        cfg, params = deep_model
        exm, outs = _run(cfg, params, (2,), batch=4, steps=2, compaction="off")
        assert exm.overflow_retries == 0
        assert all(c.bucket == 4 for r in outs for c in r.compaction)


class TestBucketedCostModel:
    def test_bucketed_at_least_ideal_and_exact_at_zero_exit(self):
        t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
        alpha = np.full(5, 1e4)
        tiers = [TierSpec("e", 20.0, 1e7), TierSpec("c", 1.0)]
        p = np.array([0.0, 0.6, 0.0, 0.5, 0.0])
        ideal = expected_time_multitier(t_c, alpha, p, tiers, (2,))
        buck = expected_time_multitier(t_c, alpha, p, tiers, (2,), batch=8)
        assert buck >= ideal - 1e-12  # padding waste never helps
        p0 = np.zeros(5)
        a = expected_time_multitier(t_c, alpha, p0, tiers, (2,))
        b = expected_time_multitier(t_c, alpha, p0, tiers, (2,), batch=8)
        assert a == pytest.approx(b, rel=1e-12)

    def test_padding_waste_shrinks_with_batch(self):
        """Bigger batches amortize bucket rounding: the bucketed cost
        approaches the full-batch-entry ideal from above."""
        t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
        alpha = np.full(5, 1e4)
        tiers = [TierSpec("e", 20.0, 1e7), TierSpec("c", 1.0)]
        p = np.array([0.0, 0.55, 0.0, 0.0, 0.0])
        costs = [
            expected_time_multitier(t_c, alpha, p, tiers, (2,), batch=b)
            for b in (4, 64, 4096)
        ]
        assert costs[0] >= costs[1] >= costs[2] - 1e-12

    def test_bucketed_solver_returns_valid_plan(self):
        rng = np.random.default_rng(3)
        tiers = [TierSpec("d", 100.0, 1e6), TierSpec("e", 10.0, 1e7),
                 TierSpec("c", 1.0)]
        for _ in range(20):
            n = int(rng.integers(2, 9))
            t_c = np.concatenate([[0.0], rng.uniform(1e-4, 1e-1, n)])
            alpha = rng.uniform(1e2, 1e6, n + 1)
            p = np.zeros(n + 1)
            p[1] = rng.uniform(0, 1)
            plan = solve_multitier(t_c, alpha, p, tiers, batch=16)
            assert len(plan.cut_after) == 2
            assert 0 <= plan.cut_after[0] <= plan.cut_after[1] <= n
            # The solver's optimum is achievable by some fixed-cut cost.
            best = min(
                expected_time_multitier(t_c, alpha, p, tiers, (s1, s2),
                                        batch=16)
                for s1 in range(n + 1) for s2 in range(s1, n + 1)
            )
            # Pointwise-vs-entry-frozen padding means the DP may differ
            # from the exact fixed-cut minimum, but never by more than the
            # padding of one bucket step (factor 2 on downstream compute).
            assert plan.expected_time_s <= best + 1e-12 or (
                plan.expected_time_s <= 2 * best
            )


class TestSimulatedNetwork:
    def test_step_wall_clock_reflects_uplink(self, deep_model):
        cfg, params = deep_model
        # ~8 KiB residual payload at d_model x 2 bytes x 4 rows; pick a
        # bandwidth that makes the transfer ~40 ms.
        per_seq = cfg.d_model * 2.0
        bw = per_seq * 4 * 8.0 / 0.04
        srv = PartitionedServer(
            cfg, params, 2,
            network=NetworkProfile("slow", bw),
            simulate_network=True,
            compaction="off",
        )
        caches = M.init_caches(cfg, 4, 32)
        tok = _toks(cfg, 4)
        rep, caches = srv.step(tok, 0, caches)  # warm the jit
        t0 = time.perf_counter()
        rep, caches = srv.step(tok, 1, caches)
        dt = time.perf_counter() - t0
        res = rep.tier_result
        expected = res.bytes_per_hop[0] * 8.0 / bw
        assert res.sim_transfer_s == (pytest.approx(expected),)
        if res.shipped_per_hop[0]:
            assert dt >= 0.9 * expected

    def test_no_simulation_by_default(self, deep_model):
        cfg, params = deep_model
        srv = PartitionedServer(cfg, params, 2,
                                network=NetworkProfile("fast", 1e9))
        rep, _ = srv.step(_toks(cfg, 4), 0, M.init_caches(cfg, 4, 32))
        assert rep.tier_result.sim_transfer_s == ()


class TestDriftController:
    def _profile(self, cfg, p_k):
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        return build_cost_profile(
            costs, cfg.branch_layers, p_k, "3g", 50.0, 64.0
        )

    def test_kl_zero_on_identical_distributions(self):
        p = np.array([0.3, 0.2])
        assert exit_drift_kl(p, p) == pytest.approx(0.0, abs=1e-9)
        assert exit_drift_kl(np.array([0.9, 0.0]), np.array([0.0, 0.0])) > 0.1

    def test_observe_accumulates_and_triggers_on_drift(self, deep_model):
        cfg, params = deep_model
        profile = self._profile(cfg, np.array([0.1, 0.1]))
        srv = PartitionedServer(cfg, params, 2, cost_profile=profile,
                                network=NetworkProfile("3g", 1.1e6))
        ctl = RepartitionController(
            srv, profile, kl_threshold=0.05, every_n_steps=2
        )
        ctl._install(np.array([0.1, 0.1]))  # plan solved for mild exits

        class FakeReport:
            def __init__(self, batch, takes):
                self.tokens = np.zeros(batch, np.int64)
                self.branch_take = takes

        # Matching traffic: no swap on the every-N check.
        b = 10
        match = {1: np.zeros(b, bool), 3: np.zeros(b, bool)}
        match[1][:1] = True  # ~0.1 conditional at branch 1
        match[3][1:2] = True  # ~0.11 at branch 3
        swaps = [ctl.observe(FakeReport(b, match)) for _ in range(2)]
        assert swaps[0] is None  # cadence not reached
        assert ctl.drift_kl() < 0.05
        assert swaps[1] is None  # checked, below threshold

        # Drifted traffic: nearly everything exits at branch 1.  The
        # every-N check must fire a re-solve once, after which the
        # installed distribution tracks the measured one (drift ~ 0).
        drift = {1: np.ones(b, bool), 3: np.zeros(b, bool)}
        swaps = [ctl.observe(FakeReport(b, drift)) for _ in range(40)]
        assert any(s is not None for s in swaps)
        # A swap resets the measurement window; feed a little more traffic
        # and confirm we are re-anchored on the new regime and can force.
        ctl.observe(FakeReport(b, drift))
        assert ctl.drift_kl() < 0.05
        assert ctl.maybe_update(force=True) is not None

    def test_update_network_reinstalls(self, deep_model):
        cfg, params = deep_model
        profile = self._profile(cfg, np.array([0.2, 0.2]))
        srv = PartitionedServer(cfg, params, 0, cost_profile=profile,
                                network=NetworkProfile("wifi", 18.8e6))
        ctl = RepartitionController(srv, profile)
        ctl._install(np.array([0.2, 0.2]))
        cuts = ctl.update_network(NetworkProfile("3g", 0.4e6))
        assert len(cuts) == 1 and 0 <= cuts[0] <= cfg.num_layers
        assert srv.tiers[0].uplink_bps == 0.4e6
        # The executor's installed segments carry the new uplink.
        edge = srv.executor.segments[0]
        if not edge.is_empty and len(srv.executor.segments) > 1:
            assert edge.uplink_bps == 0.4e6

    def test_update_tiers_multitier(self, deep_model):
        cfg, params = deep_model
        profile = self._profile(cfg, np.array([0.2, 0.2]))
        tiers = [TierSpec("d", 50.0, 1e6), TierSpec("e", 10.0, 1e7),
                 TierSpec("c", 1.0)]
        srv = PartitionedServer(cfg, params, (1, 2), tiers=tiers,
                                cost_profile=profile)
        ctl = RepartitionController(srv, profile, tiers, batch=8)
        new_tiers = [TierSpec("d", 50.0, 5e5), TierSpec("e", 10.0, 5e6),
                     TierSpec("c", 1.0)]
        cuts = ctl.update_tiers(new_tiers)
        assert len(cuts) == 2 and cuts[0] <= cuts[1] <= cfg.num_layers
        assert srv.tiers[0].uplink_bps == 5e5
        rep, _ = srv.step(_toks(cfg, 4), 0, M.init_caches(cfg, 4, 32))
        assert rep.tier_result.tokens.shape == (4,)


class TestPipelinedCompaction:
    """overlap="pipelined" composed with survivor compaction: bitwise
    equivalence to the masked serial path across K, the overflow-retry
    serial fallback, and the steady-state wall-clock win."""

    def _run(self, cfg, params, cuts, *, compaction, overlap, steps,
             batch=8):
        # Fast uplinks: microsecond sleeps, so equivalence tests stay quick.
        uplinks = (1e9,) * len(cuts)
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, cuts, uplinks=uplinks),
            compaction=compaction, simulate_network=True, overlap=overlap,
        )
        caches = M.init_caches(cfg, batch, 64)
        tok = _toks(cfg, batch)
        out = []
        for i in range(steps):
            res, caches = ex.step(tok, i, caches)
            out.append(res)
            tok = res.tokens_dev[:, None]
        ex.drain()
        return ex, out

    @pytest.mark.parametrize("cuts", [(2,), (2, 3)])
    def test_bucketed_pipelined_matches_bucketed_serial(self, deep_model, cuts):
        """Pipelining composes with compaction without touching the
        trajectory: bucketed+pipelined is bitwise equal to bucketed+serial
        on every step (and both match the masked path on the first step,
        before the documented exited-row KV-hole divergence can appear)."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(
            cfg0, exit_threshold=_mixed_threshold(cfg0, params)
        )
        _, outs_m = self._run(cfg, params, cuts, compaction="off",
                              overlap="serial", steps=1)
        _, outs_s = self._run(cfg, params, cuts, compaction="bucketed",
                              overlap="serial", steps=4)
        exp, outs_p = self._run(cfg, params, cuts, compaction="bucketed",
                                overlap="pipelined", steps=4)
        np.testing.assert_array_equal(outs_m[0].tokens, outs_p[0].tokens)
        np.testing.assert_array_equal(outs_m[0].exited, outs_p[0].exited)
        saw_exit = False
        for a, b in zip(outs_s, outs_p):
            saw_exit |= bool(a.exited.any())
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.exited, b.exited)
            assert a.shipped_per_hop == b.shipped_per_hop
            assert a.bytes_per_hop == b.bytes_per_hop
            for c, d in zip(a.compaction, b.compaction):
                assert c == d
        assert saw_exit  # the mix regime really exercised compaction
        # The 1-sync invariant survives overlap: exactly one fetch per
        # step, plus one per (counted) overflow-retry iteration.
        assert exp.host_syncs == 4 + exp.overflow_retries

    def test_overflow_retry_falls_back_to_serial(self, deep_model):
        """An overflow-retry step in pipelined mode drains the pipeline and
        pays its transfers inline (counted in pipeline_fallbacks); tokens
        stay bitwise identical to the masked serial path."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)  # no exits
        exm = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)),
                           compaction="off")
        exc = TierExecutor(
            cfg, params,
            segments_for_cuts(cfg, (2,), uplinks=(1e9,)),
            simulate_network=True, overlap="pipelined",
        )
        cm, cc = M.init_caches(cfg, 8, 32), M.init_caches(cfg, 8, 32)
        tok = _toks(cfg, 8)
        rm, cm = exm.step(tok, 0, cm)
        rc, cc = exc.step(tok, 0, cc)
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        exc._hints = {1: 1}  # stale all-exit hint: 8 survivors arrive
        rm, cm = exm.step(rm.tokens_dev[:, None], 1, cm)
        rc, cc = exc.step(rc.tokens_dev[:, None], 1, cc)
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        np.testing.assert_array_equal(rm.exited, rc.exited)
        assert exc.overflow_retries == 1
        assert exc.pipeline_fallbacks == 1
        assert exc._link_free == []  # the fallback drained the pipeline
        # Pipelining resumes on the next (non-retry) step.
        rm, cm = exm.step(rm.tokens_dev[:, None], 2, cm)
        rc, cc = exc.step(rc.tokens_dev[:, None], 2, cc)
        np.testing.assert_array_equal(rm.tokens, rc.tokens)
        assert exc.pipeline_fallbacks == 1

    def test_pipelined_steady_state_beats_serial_sum(self, deep_model):
        """Transfer-dominated K=3: serial pays compute + sum of hops per
        step, pipelined pays ~max(compute, bottleneck hop)."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)  # all ship
        batch = 4
        per_seq = cfg.d_model * 2.0
        uplinks = tuple(
            per_seq * batch * 8.0 / s for s in (0.04, 0.025)
        )
        times = {}
        for overlap in ("serial", "pipelined"):
            ex = TierExecutor(
                cfg, params,
                segments_for_cuts(cfg, (2, 3), uplinks=uplinks),
                compaction="off", simulate_network=True, overlap=overlap,
            )
            caches = M.init_caches(cfg, batch, 64)
            tok = _toks(cfg, batch)
            res, caches = ex.step(tok, 0, caches)  # warm the jit
            ex.drain()
            t0 = time.perf_counter()
            for i in range(1, 5):
                res, caches = ex.step(res.tokens_dev[:, None], i, caches)
            ex.drain()
            times[overlap] = (time.perf_counter() - t0) / 4
            assert res.sim_transfer_s == (
                pytest.approx(0.04), pytest.approx(0.025)
            )
        # Serial sleeps 65 ms/step; pipelined ~40 ms (bottleneck hop) plus
        # a one-step pipeline-fill tail amortized over 4 steps.  Their
        # computes are identical, so a 10 ms margin is comfortable.
        assert times["pipelined"] < times["serial"] - 0.010


class TestServerPlumbing:
    def test_partitioned_report_carries_compaction(self, deep_model):
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=1.5)
        srv = PartitionedServer(cfg, params, 2)
        caches = M.init_caches(cfg, 4, 32)
        tok = _toks(cfg, 4)
        rep, caches = srv.step(tok, 0, caches)
        res = srv.step(tok, 1, caches)[0].tier_result
        assert res.compaction[0].survivors == 0
        assert res.compaction[0].bucket == 1
        assert res.compaction[0].padded_waste == 1
        assert set(res.branch_take) == {1}

    def test_multitier_bucketed_estimate_counts_padding(self, deep_model):
        """With compaction on and exits live, the report's estimate uses
        the bucketed cost model (>= the ideal per-sample estimate)."""
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=1.5)
        profile = CostProfile(
            t_c=np.concatenate([[0.0], np.full(cfg.num_layers, 1e-3)]),
            alpha=np.full(cfg.num_layers + 1, cfg.d_model * 2.0),
            branches=tuple(BranchSpec(b, 0.0) for b in cfg.branch_layers),
            gamma=25.0,
            network=NetworkProfile("e", 1e7),
        )
        tiers = [TierSpec("e", 25.0, 1e7), TierSpec("c", 1.0)]
        on = PartitionedServer(cfg, params, (2,), tiers=tiers,
                               cost_profile=profile)
        off = PartitionedServer(cfg, params, (2,), tiers=tiers,
                                cost_profile=profile, compaction="off")
        caches = M.init_caches(cfg, 8, 32)
        rep_on, _ = on.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))
        rep_off, _ = off.step(_toks(cfg, 8), 0, caches)
        assert rep_on.est_latency_s >= rep_off.est_latency_s - 1e-12
