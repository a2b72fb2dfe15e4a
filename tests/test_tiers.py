"""Unified K-tier runtime (serving/tiers.py): segment planning rules,
tier-count equivalences (K=1 engine vs monolithic decode, a K=2 plan
from a NetworkProfile vs one from two TierSpecs), single-host-sync
invariant, per-hop byte accounting, the repartition controller's
no-re-jit hot swap, the one solve rule (``paper_route``) across K=2
regimes, the pipelined overlap mode (bitwise equivalence + bottleneck
cost model + plan flip), and the latency-estimator regressions
(per-branch conditional probs, zero-uplink transfer guard,
degenerate-profile solver diagnostic)."""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (
    BranchSpec,
    CostProfile,
    LayerCost,
    NetworkProfile,
    build_cost_profile,
    shortest_path_plan,
)
from repro.core.latency import expected_time
from repro.core.multitier import TierSpec, expected_time_multitier, solve_multitier
from repro.models import model as M
from repro.serving import (
    PartitionedServer,
    RepartitionController,
    ServingEngine,
    TierExecutor,
    segments_for_cuts,
)
from repro.serving.partitioned import paper_route


@pytest.fixture(scope="module")
def deep_model():
    """4 trunk layers, branches after v_1 and v_3 — enough structure for
    K=3 cuts and branch-at-cut cases."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _toks(cfg, batch=4, seed=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, 1), 0, cfg.vocab_size
    )


class TestSegmentPlanning:
    def test_two_tier_matches_paper_semantics(self, deep_model):
        cfg, _ = deep_model
        edge, cloud = segments_for_cuts(cfg, (3,))
        assert (edge.layer_lo, edge.layer_hi) == (0, 3)
        assert edge.branches == (1,)  # branch at the cut (3) is discarded
        assert (cloud.layer_lo, cloud.layer_hi) == (3, 4)
        assert cloud.branches == ()  # the cloud evaluates no branches

    def test_edge_only_keeps_all_branches(self, deep_model):
        cfg, _ = deep_model
        edge, cloud = segments_for_cuts(cfg, (4,))
        assert edge.branches == (1, 3)  # no cut -> deepest branch evaluated
        assert cloud.is_empty

    def test_single_tier_is_monolithic_branchynet(self, deep_model):
        cfg, _ = deep_model
        (seg,) = segments_for_cuts(cfg, ())
        assert (seg.layer_lo, seg.layer_hi) == (0, 4)
        assert seg.branches == cfg.branch_layers

    def test_three_tier_partition(self, deep_model):
        cfg, _ = deep_model
        d, e, c = segments_for_cuts(cfg, (1, 3))
        assert d.branches == ()  # branch 1 sits exactly at the first cut
        assert e.branches == ()  # branch 3 sits exactly at the second cut
        d, e, c = segments_for_cuts(cfg, (2, 4))
        assert d.branches == (1,) and e.branches == (3,) and c.is_empty

    def test_rejects_non_monotone_cuts(self, deep_model):
        cfg, _ = deep_model
        with pytest.raises(ValueError):
            segments_for_cuts(cfg, (3, 1))
        with pytest.raises(ValueError):
            segments_for_cuts(cfg, (5,))


def _random_chain(rng, n):
    """Random (t_c, alpha, p) chain with up to two branches (mirrors
    test_multitier.random_chain, hypothesis-free so it always runs)."""
    t_c = np.concatenate([[0.0], rng.uniform(1e-4, 1e-1, n)])
    alpha = rng.uniform(1e2, 1e6, n + 1)
    p = np.zeros(n + 1)
    if n > 2:
        for i in rng.choice(np.arange(1, n), size=min(2, n - 1), replace=False):
            p[i] = rng.uniform(0, 1)
    return t_c, alpha, p


class TestSolverEquivalence:
    """Plain seeded randomized sweeps (no hypothesis dependency) so these
    run in every tier-1 environment."""

    def test_k2_cuts_match_dijkstra(self):
        """The lattice DP at K=2 lands on the same cut (and E[T]) as the
        paper's Dijkstra run over G'_BDNN."""
        rng = np.random.default_rng(7)
        for _ in range(60):
            n = int(rng.integers(2, 13))
            gamma = float(rng.uniform(1.0, 500.0))
            bw = float(rng.uniform(1e5, 1e9))
            t_c, alpha, p = _random_chain(rng, n)
            plan = solve_multitier(
                t_c, alpha, p,
                [TierSpec("edge", gamma, bw), TierSpec("cloud", 1.0)],
            )
            branches = tuple(
                BranchSpec(i, float(p[i])) for i in range(1, n) if p[i] > 0
            )
            prof = CostProfile(
                t_c=t_c, alpha=alpha, branches=branches, gamma=gamma,
                network=NetworkProfile("t", bw),
            )
            ref = shortest_path_plan(prof)
            assert plan.expected_time_s == pytest.approx(
                ref.expected_time_s, rel=1e-9, abs=1e-12
            )
            assert plan.cut_after == (ref.split_layer,)

    def test_solver_optimum_is_min_over_fixed_cuts(self):
        """solve_multitier's E[T] equals the minimum of the closed-form
        fixed-cut cost (the estimate the runtime reports) over all cuts."""
        rng = np.random.default_rng(11)
        tiers = [TierSpec("device", 200.0, 1e6), TierSpec("edge", 20.0, 2e7),
                 TierSpec("cloud", 1.0)]
        for _ in range(40):
            n = int(rng.integers(2, 9))
            t_c, alpha, p = _random_chain(rng, n)
            plan = solve_multitier(t_c, alpha, p, tiers)
            best = min(
                expected_time_multitier(t_c, alpha, p, tiers, (s1, s2))
                for s1 in range(n + 1) for s2 in range(s1, n + 1)
            )
            assert plan.expected_time_s == pytest.approx(
                best, rel=1e-9, abs=1e-12
            )
            assert expected_time_multitier(
                t_c, alpha, p, tiers, plan.cut_after
            ) == pytest.approx(plan.expected_time_s, rel=1e-9, abs=1e-12)


class TestOverlapCostModel:
    """expected_time_multitier(overlap=True): the pipelined steady-state
    step cost is the bottleneck stage, not the serial sum."""

    def test_overlap_is_bottleneck_stage(self):
        t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
        alpha = np.full(5, 1e5)
        p = np.zeros(5)
        tiers = [TierSpec("edge", 3.0, 2e6), TierSpec("cloud", 1.0)]
        s = 2
        edge = 3.0 * 0.02  # 2 layers at gamma 3
        xfer = 1e5 * 8.0 / 2e6
        cloud = 0.02
        serial = expected_time_multitier(t_c, alpha, p, tiers, (s,))
        ovl = expected_time_multitier(t_c, alpha, p, tiers, (s,),
                                      overlap=True)
        assert serial == pytest.approx(edge + xfer + cloud)
        assert ovl == pytest.approx(max(edge, xfer, cloud))

    def test_overlap_never_exceeds_serial(self):
        """max of non-negative stages <= their sum, for every cut vector,
        branch regime, and bucketed/ideal weighting."""
        rng = np.random.default_rng(13)
        tiers = [TierSpec("d", 200.0, 1e6), TierSpec("e", 20.0, 2e7),
                 TierSpec("c", 1.0)]
        for _ in range(30):
            n = int(rng.integers(2, 9))
            t_c, alpha, p = _random_chain(rng, n)
            for batch in (None, 8):
                for s1 in range(n + 1):
                    for s2 in range(s1, n + 1):
                        ser = expected_time_multitier(
                            t_c, alpha, p, tiers, (s1, s2), batch=batch
                        )
                        ovl = expected_time_multitier(
                            t_c, alpha, p, tiers, (s1, s2), batch=batch,
                            overlap=True,
                        )
                        assert ovl <= ser + 1e-12

    def test_overlap_solver_matches_enumeration(self):
        rng = np.random.default_rng(17)
        tiers = [TierSpec("d", 100.0, 1e6), TierSpec("e", 10.0, 1e7),
                 TierSpec("c", 1.0)]
        for _ in range(25):
            n = int(rng.integers(2, 9))
            t_c, alpha, p = _random_chain(rng, n)
            plan = solve_multitier(t_c, alpha, p, tiers, overlap=True)
            best = min(
                expected_time_multitier(t_c, alpha, p, tiers, (s1, s2),
                                        overlap=True)
                for s1 in range(n + 1) for s2 in range(s1, n + 1)
            )
            assert plan.expected_time_s == pytest.approx(
                best, rel=1e-9, abs=1e-12
            )
            assert expected_time_multitier(
                t_c, alpha, p, tiers, plan.cut_after, overlap=True
            ) == pytest.approx(plan.expected_time_s, rel=1e-9, abs=1e-12)

    def test_optimal_cut_moves_under_overlap(self):
        """The benchmark's plan-flip profile: transfers shrink with depth,
        so serial hides on the edge while overlap cuts early (a transfer
        below the bottleneck stage is free when pipelined)."""
        t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
        alpha = np.array([80e3, 40e3, 20e3, 10e3, 5e3])
        p = np.zeros(5)
        tiers = [TierSpec("edge", 2.0, 4e6), TierSpec("cloud", 1.0)]
        plan_s = solve_multitier(t_c, alpha, p, tiers)
        plan_o = solve_multitier(t_c, alpha, p, tiers, overlap=True)
        assert plan_s.cut_after == (4,)  # serial: ship nothing
        assert plan_o.cut_after == (2,)  # overlap: balance the stages
        assert plan_o.expected_time_s < plan_s.expected_time_s

    def test_degenerate_profile_raises_value_error(self):
        """An infeasible profile (unusable entry tier + zero uplink) gets a
        clear diagnostic instead of the historical UnboundLocalError."""
        t_c = np.array([0.0, 1.0])
        alpha = np.array([10.0, 10.0])
        p = np.zeros(2)
        tiers = [TierSpec("dev", np.inf, 0.0), TierSpec("cloud", 1.0)]
        with pytest.raises(ValueError, match="unreachable"):
            solve_multitier(t_c, alpha, p, tiers)

    def test_zero_uplink_with_feasible_edge_plan_solves(self):
        """A zero/unset uplink must not crash the solver when finishing on
        the reachable tiers is feasible (it simply prices the hop inf)."""
        t_c = np.array([0.0, 1.0, 1.0])
        alpha = np.array([10.0, 10.0, 10.0])
        plan = solve_multitier(
            t_c, alpha, np.zeros(3),
            [TierSpec("edge", 2.0, 0.0), TierSpec("cloud", 1.0)],
        )
        assert plan.cut_after == (2,)  # everything on the edge
        assert np.isfinite(plan.expected_time_s)


class TestTierEquivalence:
    """Identical computation regardless of how many tiers execute it."""

    @pytest.mark.parametrize("split", [0, 1, 2, 3, 4])
    def test_multitier_k2_matches_partitioned(self, deep_model, split):
        """A K=2 plan derived from a NetworkProfile and one given as two
        TierSpecs run the same step: tokens, masks, bytes and cuts."""
        cfg, params = deep_model
        tok = _toks(cfg)
        p2 = PartitionedServer(cfg, params, split,
                               network=NetworkProfile("4g", 5.85e6))
        k2 = PartitionedServer(
            cfg, params, (split,),
            tiers=[TierSpec("edge", 25.0, 5.85e6), TierSpec("cloud", 1.0)],
        )
        assert p2.cuts == k2.cuts == (split,)
        assert p2.executor.segments == k2.executor.segments
        res = p2.step(tok, 0, M.init_caches(cfg, 4, 32))[0].tier_result
        mres = k2.step(tok, 0, M.init_caches(cfg, 4, 32))[0].tier_result
        np.testing.assert_array_equal(mres.tokens, res.tokens)
        np.testing.assert_array_equal(mres.exited, res.exited)
        assert mres.shipped_per_hop == res.shipped_per_hop
        assert mres.bytes_per_hop == res.bytes_per_hop

    def test_multitier_k2_matches_partitioned_with_exits(self, deep_model):
        cfg, params = deep_model
        cfg = dataclasses.replace(cfg, exit_threshold=1.5)  # everyone exits
        tok = _toks(cfg)
        p2 = PartitionedServer(cfg, params, 2, network=NetworkProfile("e", 1e7))
        k2 = PartitionedServer(
            cfg, params, (2,),
            tiers=[TierSpec("edge", 25.0, 1e7), TierSpec("cloud", 1.0)],
        )
        assert p2.cuts == k2.cuts == (2,)
        res = p2.step(tok, 0, M.init_caches(cfg, 4, 32))[0].tier_result
        mres = k2.step(tok, 0, M.init_caches(cfg, 4, 32))[0].tier_result
        assert res.exited.all() and mres.exited.all()
        np.testing.assert_array_equal(mres.tokens, res.tokens)
        assert mres.shipped_per_hop == res.shipped_per_hop == (0,)
        assert mres.bytes_per_hop == res.bytes_per_hop
        assert (mres.exit_tier == 0).all() and (res.exit_tier == 0).all()

    def test_k3_survivors_match_monolithic_tokens(self, deep_model):
        cfg, params = deep_model
        tok = _toks(cfg)
        caches = M.init_caches(cfg, 4, 32)
        mono = M.decode_step(params, tok, jnp.asarray(0, jnp.int32), caches, cfg)
        mono_tok = np.asarray(jnp.argmax(mono["logits"], -1))

        srv = PartitionedServer(
            cfg, params, (2, 3),
            tiers=[TierSpec("device", 200.0, 1e6), TierSpec("edge", 20.0, 2e7),
                   TierSpec("cloud", 1.0)],
        )
        res = srv.step(tok, 0, M.init_caches(cfg, 4, 32))[0].tier_result
        crossed = ~res.exited
        np.testing.assert_array_equal(res.tokens[crossed], mono_tok[crossed])

    def test_engine_matches_legacy_decode_loop(self, deep_model):
        """The fused device-resident exit masking reproduces the old
        per-branch host-round-trip loop token for token."""
        cfg, params = deep_model
        engine = ServingEngine(cfg, params, context_len=64)
        prompts = {"tokens": jax.random.randint(
            jax.random.PRNGKey(5), (3, 8), 0, cfg.vocab_size)}
        state = engine.start(prompts)
        toks, stats = engine.decode(state, steps=5)

        # Legacy loop: monolithic decode_step + host-side branch folding.
        state2 = engine.start(prompts)
        tok = jnp.argmax(state2["last_logits"], -1).astype(jnp.int32)[:, None]
        caches, pos = state2["caches"], state2["pos"]
        legacy = []
        counts = np.zeros(len(cfg.branch_layers) + 1, np.int64)
        for _ in range(5):
            out = M.decode_step(params, tok, jnp.asarray(pos, jnp.int32),
                                caches, cfg)
            caches, pos = out["caches"], pos + 1
            chosen = jnp.argmax(out["logits"], -1).astype(jnp.int32)
            exited = jnp.zeros(chosen.shape, bool)
            for j, layer in enumerate(cfg.branch_layers):
                b_tok = jnp.argmax(out["branch_logits"][layer], -1).astype(jnp.int32)
                take = out["branch_exit"][layer] & ~exited
                chosen = jnp.where(take, b_tok, chosen)
                counts[j] += int(np.asarray(take).sum())
                exited = exited | out["branch_exit"][layer]
            counts[-1] += int(np.asarray(~exited).sum())
            legacy.append(np.asarray(chosen))
            tok = chosen[:, None]
        np.testing.assert_array_equal(toks, np.stack(legacy, axis=1))
        np.testing.assert_array_equal(stats.counts, counts)


class TestHostSyncs:
    def test_one_sync_per_partitioned_step(self, deep_model):
        cfg, params = deep_model
        srv = PartitionedServer(cfg, params, 2)
        caches = M.init_caches(cfg, 4, 32)
        tok = _toks(cfg)
        for i in range(4):
            rep, caches = srv.step(tok, i, caches)
            tok = jnp.asarray(rep.tier_result.tokens[:, None])
        assert srv.executor.host_syncs == 4

    def test_one_sync_per_engine_step(self, deep_model):
        cfg, params = deep_model
        engine = ServingEngine(cfg, params, context_len=64)
        state = engine.start({"tokens": jax.random.randint(
            jax.random.PRNGKey(8), (2, 4), 0, cfg.vocab_size)})
        engine.decode(state, steps=6)
        assert engine.host_syncs == 6


class TestByteAccounting:
    def test_k3_per_hop_bytes(self, deep_model):
        cfg, params = deep_model
        tiers = [TierSpec("d", 100.0, 1e6), TierSpec("e", 10.0, 1e7),
                 TierSpec("c", 1.0)]
        srv = PartitionedServer(cfg, params, (2, 3), tiers=tiers)
        rep, _ = srv.step(_toks(cfg), 0, M.init_caches(cfg, 4, 32))
        res = rep.tier_result
        assert len(res.shipped_per_hop) == 2
        per_seq = cfg.d_model * 2.0
        assert res.bytes_per_hop == (
            res.shipped_per_hop[0] * per_seq, res.shipped_per_hop[1] * per_seq
        )
        # Survivors never resurrect across hops.
        assert res.shipped_per_hop[0] >= res.shipped_per_hop[1]
        # Transfer time is bytes over the *hop's own* uplink.
        assert rep.transfer_s_per_hop == (
            res.bytes_per_hop[0] * 8.0 / tiers[0].uplink_bps,
            res.bytes_per_hop[1] * 8.0 / tiers[1].uplink_bps,
        )

    def test_cloud_only_ships_token_ids(self, deep_model):
        cfg, params = deep_model
        srv = PartitionedServer(
            cfg, params, (0,), tiers=[TierSpec("e", 25.0, 1e7), TierSpec("c", 1.0)]
        )
        res = srv.step(_toks(cfg), 0, M.init_caches(cfg, 4, 32))[0].tier_result
        assert res.shipped_per_hop == (4,)
        assert res.bytes_per_hop == (16.0,)  # 4 sequences * 4-byte token id

    def test_trailing_empty_tiers_ship_nothing(self, deep_model):
        cfg, params = deep_model
        srv = PartitionedServer(
            cfg, params, (4, 4),
            tiers=[TierSpec("d", 100.0, 1e6), TierSpec("e", 10.0, 1e7),
                   TierSpec("c", 1.0)],
        )
        res = srv.step(_toks(cfg), 0, M.init_caches(cfg, 4, 32))[0].tier_result
        assert res.shipped_per_hop == () and res.bytes_per_hop == ()


class TestPipelinedRuntime:
    """overlap="pipelined" is a wall-clock re-ordering of the simulated
    transfers only: tokens, exit masks, and per-hop byte accounting are
    bitwise identical to serial mode, and the one-fetch-per-emitted-token
    contract holds."""

    def _run(self, cfg, params, cuts, overlap, steps=3):
        # Fast uplinks: the simulated sleeps are microseconds, so the test
        # exercises the pipelined bookkeeping without slowing the suite.
        segs = segments_for_cuts(cfg, cuts, uplinks=(1e9,) * len(cuts))
        ex = TierExecutor(
            cfg, params, segs, compaction="off",
            simulate_network=True, overlap=overlap,
        )
        caches = M.init_caches(cfg, 4, 64)
        tok = _toks(cfg)
        out = []
        for i in range(steps):
            res, caches = ex.step(tok, i, caches)
            out.append(res)
            tok = res.tokens_dev[:, None]
        ex.drain()
        return ex, out

    @pytest.mark.parametrize("cuts", [(), (2,), (2, 3)])
    def test_bitwise_equivalent_to_serial(self, deep_model, cuts):
        cfg, params = deep_model
        exs, outs_s = self._run(cfg, params, cuts, "serial")
        exp, outs_p = self._run(cfg, params, cuts, "pipelined")
        for a, b in zip(outs_s, outs_p):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.exited, b.exited)
            np.testing.assert_array_equal(a.exit_tier, b.exit_tier)
            assert a.shipped_per_hop == b.shipped_per_hop
            assert a.bytes_per_hop == b.bytes_per_hop
            assert a.sim_transfer_s == b.sim_transfer_s
            for layer in a.branch_take:
                np.testing.assert_array_equal(
                    a.branch_take[layer], b.branch_take[layer]
                )
        # One fetch per emitted token on both paths.
        assert exs.host_syncs == exp.host_syncs == 3
        assert exp.pipeline_fallbacks == 0

    def test_drain_is_idempotent_and_resets(self, deep_model):
        cfg, params = deep_model
        ex, _ = self._run(cfg, params, (2,), "pipelined")
        assert ex._link_free == [] and ex._inflight_done == 0.0
        ex.drain()  # no-op when nothing is in flight
        assert ex._link_free == []

    def test_rejects_unknown_overlap_mode(self, deep_model):
        cfg, params = deep_model
        with pytest.raises(ValueError, match="overlap"):
            TierExecutor(
                cfg, params, segments_for_cuts(cfg, (2,)), overlap="async"
            )


class TestEstimatorRegressions:
    def test_partitioned_estimate_uses_conditional_probs(self, deep_model):
        """PartitionedServer._estimate historically substituted the
        *cumulative* measured exit fraction for every branch's conditional
        exit_prob, overestimating exits whenever two or more branches are
        evaluated.  With branch 1 exiting 4/8 and branch 3 exiting 2 of
        the 4 survivors, the conditionals are (0.5, 0.5) — not the 0.75
        cumulative fraction the old code installed at both branches."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.array([0.3, 0.4]), "3g", 50.0, 64.0
        )
        srv = PartitionedServer(
            cfg, params, 4, cost_profile=profile, compaction="off"
        )
        take1 = np.zeros(8, bool)
        take1[:4] = True
        take3 = np.zeros(8, bool)
        take3[4:6] = True  # 2 of the 4 still alive after branch 1
        res = types.SimpleNamespace(
            tokens=np.zeros(8, np.int64), branch_take={1: take1, 3: take3},
            live=8,
        )
        est = srv._estimate(res)

        def at_probs(p1, p3):
            branches = tuple(
                dataclasses.replace(b, exit_prob={1: p1, 3: p3}[b.after_layer])
                for b in profile.branches
            )
            return expected_time(
                dataclasses.replace(profile, branches=branches), 4
            )

        assert est == pytest.approx(at_probs(0.5, 0.5))
        old_wrong = at_probs(0.75, 0.75)
        assert est != pytest.approx(old_wrong)
        # Inflated exits shed downstream compute -> the old estimate was
        # optimistic (too low).
        assert old_wrong < est

    def test_multitier_unset_uplink_reports_zero_transfer(self, deep_model):
        """TierSpec.uplink_bps defaults to 0.0: a plan whose hop bandwidth
        was never set must report 0.0 transfer time, not ZeroDivisionError
        (mirrors the executor's sim_transfer_s guard)."""
        cfg, params = deep_model
        srv = PartitionedServer(
            cfg, params, (2,), tiers=[TierSpec("e", 25.0), TierSpec("c", 1.0)]
        )
        rep, _ = srv.step(_toks(cfg), 0, M.init_caches(cfg, 4, 32))
        assert rep.transfer_s_per_hop == (0.0,)
        assert rep.tier_result.tokens.shape == (4,)


class TestRepartition:
    def test_swap_reuses_unchanged_segments(self, deep_model):
        cfg, params = deep_model
        srv = PartitionedServer(
            cfg, params, (1, 3),
            tiers=[TierSpec("d", 100.0, 1e6), TierSpec("e", 10.0, 1e7),
                   TierSpec("c", 1.0)],
        )
        cloud_fn = srv.executor.segment_fn(2)
        srv.install_cuts((2, 3))  # move only the first cut
        assert srv.executor.segment_fn(2) is cloud_fn  # cloud never re-jitted
        # Swapping back re-uses both previously compiled edge segments too.
        device_fn = srv.executor.segment_fn(0)
        srv.install_cuts((1, 3))
        srv.install_cuts((2, 3))
        assert srv.executor.segment_fn(0) is device_fn

    def test_controller_closes_the_loop(self, deep_model):
        cfg, params = deep_model
        engine = ServingEngine(cfg, params, context_len=64)
        state = engine.start({"tokens": jax.random.randint(
            jax.random.PRNGKey(9), (4, 6), 0, cfg.vocab_size)})
        _, stats = engine.decode(state, steps=3)

        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, stats.conditional_probs(), "3g", 50.0,
            64.0,
        )
        srv = PartitionedServer(cfg, params, 0, cost_profile=profile)
        ctl = RepartitionController(srv, profile)
        cuts = ctl.update(stats)
        assert len(cuts) == 1 and 0 <= cuts[0] <= cfg.num_layers
        assert srv.cuts == cuts
        rep, _ = srv.step(_toks(cfg), 0, M.init_caches(cfg, 4, 32))
        assert rep.tier_result.tokens.shape == (4,)

    def test_controller_solves_overlap_for_pipelined_server(self, deep_model):
        """A pipelined server is re-solved against the bottleneck-stage
        cost: the controller's installed cut must minimize the overlap
        objective (which can differ from the serial Dijkstra cut)."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        p_k = np.array([0.1, 0.1])
        profile = build_cost_profile(
            costs, cfg.branch_layers, p_k, "3g", 50.0, 64.0
        )
        srv = PartitionedServer(
            cfg, params, 0, cost_profile=profile,
            network=NetworkProfile("3g", 1.1e6), overlap="pipelined",
        )
        ctl = RepartitionController(srv, profile)
        (cut,) = ctl.solve(p_k)
        prof = dataclasses.replace(
            profile,
            branches=tuple(
                dataclasses.replace(b, exit_prob=float(p))
                for b, p in zip(profile.branches, p_k)
            ),
        )
        tiers = [TierSpec("edge", prof.gamma, prof.network.bandwidth_bps),
                 TierSpec("cloud", 1.0)]
        best = min(
            range(cfg.num_layers + 1),
            key=lambda s: expected_time_multitier(
                prof.t_c, prof.alpha, prof.branch_exit_probs(), tiers, (s,),
                overlap=True,
            ),
        )
        assert cut == best
        ctl._install(p_k)
        assert srv.cuts == (cut,)

    def test_controller_bucketed_2tier_solves_lattice_objective(self, deep_model):
        """With batch set and a compacting 2-tier server, solve() optimizes
        the same padding-honest bucketed lattice cost the server's
        est_latency_s reports — not the ideal Dijkstra sum."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        p_k = np.array([0.6, 0.2])
        profile = build_cost_profile(
            costs, cfg.branch_layers, p_k, "3g", 50.0, 64.0
        )
        srv = PartitionedServer(
            cfg, params, 0, cost_profile=profile,
            network=NetworkProfile("3g", 1.1e6), compaction="bucketed",
        )
        ctl = RepartitionController(srv, profile, batch=8)
        (cut,) = ctl.solve(p_k)
        prof = dataclasses.replace(
            profile,
            branches=tuple(
                dataclasses.replace(b, exit_prob=float(p))
                for b, p in zip(profile.branches, p_k)
            ),
        )
        tiers = [TierSpec("edge", prof.gamma, prof.network.bandwidth_bps),
                 TierSpec("cloud", 1.0)]
        best = min(
            range(cfg.num_layers + 1),
            key=lambda s: expected_time_multitier(
                prof.t_c, prof.alpha, prof.branch_exit_probs(), tiers, (s,),
                batch=8,
            ),
        )
        assert cut == best

    def test_controller_multitier(self, deep_model):
        cfg, params = deep_model
        engine = ServingEngine(cfg, params, context_len=64)
        state = engine.start({"tokens": jax.random.randint(
            jax.random.PRNGKey(10), (4, 6), 0, cfg.vocab_size)})
        _, stats = engine.decode(state, steps=3)

        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, stats.conditional_probs(), "3g", 50.0,
            64.0,
        )
        tiers = [TierSpec("d", 50.0, 1e6), TierSpec("e", 10.0, 1e7),
                 TierSpec("c", 1.0)]
        srv = PartitionedServer(cfg, params, (0, 0), tiers=tiers,
                                cost_profile=profile)
        ctl = RepartitionController(srv, profile, tiers)
        cuts = ctl.update(stats)
        assert len(cuts) == 2 and cuts[0] <= cuts[1] <= cfg.num_layers
        rep, _ = srv.step(_toks(cfg), 0, M.init_caches(cfg, 4, 32))
        assert rep.est_latency_s is not None and rep.est_latency_s > 0
        if not rep.tier_result.exited.any():
            # No live exits -> the report's estimate is exactly the lattice
            # cost model at p == 0.
            zero_p = np.zeros(cfg.num_layers + 1)
            assert rep.est_latency_s == pytest.approx(expected_time_multitier(
                profile.t_c, profile.alpha, zero_p, tiers, srv.cuts
            ))


class TestOneSolveRule:
    """``paper_route`` is the one rule that picks the cost model for the
    server's estimate and the controller's solve, from what the plan is."""

    @pytest.mark.parametrize(
        "k, overlap, batch, availability, paper",
        [
            (2, False, None, 1.0, True),  # the paper's Eq. 5 / Dijkstra
            (2, True, None, 1.0, False),  # pipelined: bottleneck stage
            (2, False, 8, 1.0, False),  # bucketed with a batch
            (2, False, None, 0.5, False),  # a degraded hop
            (3, False, None, 1.0, False),  # K >= 3 is lattice-only
        ],
    )
    def test_route_is_a_function_of_the_plan(
        self, k, overlap, batch, availability, paper
    ):
        tiers = [TierSpec(f"t{j}", 2.0, 1e6, availability=availability)
                 for j in range(k - 1)] + [TierSpec("cloud", 1.0)]
        assert paper_route(tiers, overlap, batch) is paper

    def test_branch_compute_priced_on_the_paper_route_only(self, deep_model):
        """Where the two models disagree (a profile pricing branch-head
        compute), each route keeps its own answer: Eq. 5 with the
        gamma * t_b edge terms, the lattice without them."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        branch = [LayerCost("b", 0, 0, 0, 5e-3)] * len(cfg.branch_layers)
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.zeros(2), "3g", 50.0, 64.0,
            branch_costs=branch, include_branch_compute=True,
        )
        res = types.SimpleNamespace(
            tokens=np.zeros(8, np.int64), live=8,
            branch_take={1: np.zeros(8, bool)},
        )
        serial = PartitionedServer(cfg, params, 2, cost_profile=profile,
                                   compaction="off")
        bucketed = PartitionedServer(cfg, params, 2, cost_profile=profile)
        assert serial._estimate(res) == pytest.approx(expected_time(profile, 2))
        lattice = expected_time_multitier(
            profile.t_c, profile.alpha, np.zeros(cfg.num_layers + 1),
            list(bucketed.tiers), (2,), batch=8, occupancy=1.0,
        )
        assert bucketed._estimate(res) == pytest.approx(lattice)
        assert serial._estimate(res) > lattice  # the branch head's cost

    @pytest.mark.parametrize(
        "regime", ["serial", "pipelined", "bucketed", "breaker_open"]
    )
    def test_k2_network_and_tierspec_plans_install_same_cut(
        self, deep_model, regime
    ):
        """A K=2 server built from a NetworkProfile and one built from the
        equivalent TierSpecs get the same cut from the controller in
        every regime: the solve depends on the plan, not the constructor."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        p_k = np.array([0.3, 0.2])
        profile = build_cost_profile(
            costs, cfg.branch_layers, p_k, "3g", 50.0, 64.0
        )
        kw = {"overlap": "pipelined"} if regime == "pipelined" else {}
        batch = 8 if regime == "bucketed" else None
        servers = [
            PartitionedServer(cfg, params, 0, cost_profile=profile,
                              network=NetworkProfile("3g", 1.1e6), **kw),
            PartitionedServer(
                cfg, params, (0,), cost_profile=profile,
                tiers=[TierSpec("edge", 50.0, 1.1e6), TierSpec("cloud", 1.0)],
                **kw,
            ),
        ]
        installed = []
        for srv in servers:
            ctl = RepartitionController(srv, profile, batch=batch)
            if regime == "breaker_open":
                ctl._installed_p = p_k
                ctl._hop_open.add(0)
                installed.append(ctl.apply_hop_health())
                assert srv.tiers[0].availability == 0.0
            else:
                installed.append(ctl._install(p_k))
            assert srv.cuts == installed[-1]
        assert installed[0] == installed[1]
        if regime == "serial":
            assert installed[0] == (
                shortest_path_plan(profile).split_layer,
            )
        if regime == "breaker_open":
            assert installed[0] == (cfg.num_layers,)  # nothing crosses

    def test_k3_update_network_refuses(self, deep_model):
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.zeros(2), "3g", 50.0, 64.0
        )
        srv = PartitionedServer(
            cfg, params, (1, 3),
            tiers=[TierSpec("d", 50.0, 1e6), TierSpec("e", 10.0, 1e7),
                   TierSpec("c", 1.0)],
        )
        ctl = RepartitionController(srv, profile)
        with pytest.raises(ValueError, match="K=2 plan.*3 tiers.*update_tiers"):
            ctl.update_network(NetworkProfile("3g", 0.4e6))
        assert srv.tiers[0].uplink_bps == 1e6  # nothing changed

    @pytest.mark.parametrize("compaction", ["off", "bucketed"])
    def test_k2_controller_prices_its_own_profile_link(
        self, deep_model, compaction
    ):
        """A K=2 server built with no network or cost profile (its uplink
        unset) still solves for the link and gamma of the controller's
        profile: the installed cut is the paper's Dijkstra on that
        profile, not the all-edge plan an unset uplink would price."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        p_k = np.array([0.3, 0.2])
        profile = build_cost_profile(
            costs, cfg.branch_layers, p_k, "3g", 50.0, 64.0
        )
        srv = PartitionedServer(cfg, params, cfg.num_layers,
                                compaction=compaction)
        assert srv.tiers[0].uplink_bps == 0.0
        ctl = RepartitionController(srv, profile)
        want = shortest_path_plan(profile).split_layer
        assert want < cfg.num_layers  # the profile's link is usable
        assert ctl._install(p_k) == (want,)
        assert srv.tiers[0].uplink_bps == 0.0  # the server's link is its own
