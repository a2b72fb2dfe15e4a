"""Kernel-backed decode hot path (``use_kernels``) through the tier
runtime, plus the bucket-hint policy and exploration satellites:

  * full TierExecutor decode trajectories with the Pallas kernels in
    interpret mode are token/exit-mask identical to the pure-jnp path —
    K in {1, 2, 3}, compaction on/off, bucket-boundary batches, GQA and
    Mamba2 (SSD) trunks — and keep exactly one host sync per step;
  * windowed-max bucket hints + the bucket_headroom knob
    (hint_window=1, headroom=0 reproduces last-step exact-fit);
  * overflow_retries / pipeline_fallbacks surfaced in both servers'
    reports;
  * probe steps: all-branch evaluation that never touches the
    trajectory, and the RepartitionController's explore_every_n epsilon
    schedule refreshing discarded-branch probabilities through it.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import LayerCost, NetworkProfile, build_cost_profile
from repro.core.multitier import TierSpec, bucket_for
from repro.models import model as M
from repro.serving import (
    PartitionedServer,
    RepartitionController,
    TierExecutor,
    segments_for_cuts,
)


@pytest.fixture(scope="module")
def deep_model():
    """4 trunk layers, branches after v_1 and v_3 (as in test_compaction),
    with a threshold calibrated to a mixed exit regime."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
    res, _ = ex.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))
    ents = np.concatenate([res.branch_entropy[l] for l in cfg.branch_layers])
    cfg = dataclasses.replace(
        cfg, exit_threshold=float((ents.min() + ents.max()) / 2)
    )
    return cfg, params


@pytest.fixture(scope="module")
def ssm_model():
    """Mamba2 smoke trunk with one side branch (SSD decode kernel path)."""
    cfg = dataclasses.replace(get_smoke_config("mamba2_130m"), branch_layers=(1,))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _toks(cfg, batch, seed=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, 1), 0, cfg.vocab_size
    )


def _run(cfg, params, cuts, *, batch, steps, use_kernels,
         compaction="bucketed"):
    ex = TierExecutor(
        cfg, params, segments_for_cuts(cfg, cuts),
        compaction=compaction, use_kernels=use_kernels,
    )
    caches = M.init_caches(cfg, batch, 64)
    tok = _toks(cfg, batch)
    out = []
    for i in range(steps):
        res, caches = ex.step(tok, i, caches)
        out.append(res)
        tok = res.tokens_dev[:, None]
    return ex, out


def _assert_same_trajectory(outs_a, outs_b):
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.exited, b.exited)
        np.testing.assert_array_equal(a.exit_tier, b.exit_tier)
        assert a.shipped_per_hop == b.shipped_per_hop
        for layer in a.branch_take:
            np.testing.assert_array_equal(
                a.branch_take[layer], b.branch_take[layer]
            )


class TestKernelTrajectoryEquivalence:
    """use_kernels=True (interpret mode on CPU) vs the jnp path: identical
    tokens and exit masks over full decode trajectories, 1 sync/step."""

    @pytest.mark.parametrize("cuts", [(), (2,), (1, 3)])
    @pytest.mark.parametrize("compaction", ["bucketed", "off"])
    def test_gqa_trajectory_identical(self, deep_model, cuts, compaction):
        cfg, params = deep_model
        exj, outs_j = _run(cfg, params, cuts, batch=5, steps=3,
                           use_kernels=False, compaction=compaction)
        exk, outs_k = _run(cfg, params, cuts, batch=5, steps=3,
                           use_kernels=True, compaction=compaction)
        _assert_same_trajectory(outs_j, outs_k)
        # Entropies agree to fp32 reduction-order tolerance.
        for a, b in zip(outs_j, outs_k):
            for layer in a.branch_entropy:
                np.testing.assert_allclose(
                    a.branch_entropy[layer], b.branch_entropy[layer],
                    rtol=1e-5, atol=1e-5,
                )
        # The kernel path keeps the 1-sync-per-step contract.
        assert exk.host_syncs == 3 + exk.overflow_retries
        assert exk.use_kernels and not exj.use_kernels

    @pytest.mark.parametrize("batch", [1, 4])
    def test_bucket_boundary_batches(self, deep_model, batch):
        cfg, params = deep_model
        _, outs_j = _run(cfg, params, (2,), batch=batch, steps=3,
                         use_kernels=False)
        _, outs_k = _run(cfg, params, (2,), batch=batch, steps=3,
                         use_kernels=True)
        _assert_same_trajectory(outs_j, outs_k)

    def test_ssm_trajectory_identical(self, ssm_model):
        """Mamba2 decode runs the ssd_update kernel; trajectory matches."""
        cfg, params = ssm_model
        exj, outs_j = _run(cfg, params, (1,), batch=4, steps=3,
                           use_kernels=False)
        exk, outs_k = _run(cfg, params, (1,), batch=4, steps=3,
                           use_kernels=True)
        _assert_same_trajectory(outs_j, outs_k)
        assert exk.host_syncs == 3 + exk.overflow_retries

    def test_knob_resolution(self, deep_model):
        """None defers to cfg.use_kernels, then to the backend default."""
        cfg, params = deep_model
        segs = segments_for_cuts(cfg, ())
        assert not TierExecutor(cfg, params, segs).use_kernels  # CPU auto
        assert TierExecutor(cfg, params, segs, use_kernels=True).use_kernels
        cfg_on = dataclasses.replace(cfg, use_kernels=True)
        assert TierExecutor(cfg_on, params, segs).use_kernels
        # Constructor override beats the config.
        assert not TierExecutor(
            cfg_on, params, segs, use_kernels=False
        ).use_kernels


class TestBucketHintPolicy:
    def _executor(self, deep_model, **kw):
        cfg, params = deep_model
        return TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)), **kw)

    def test_windowed_max(self, deep_model):
        """The effective hint is the max survivor count over the last
        hint_window observations — a burst keeps the bucket provisioned
        until it ages out."""
        ex = self._executor(deep_model, hint_window=3)
        for count in (5, 2, 1):
            ex._observe_hints({1: count})
        assert ex._hints == {1: 5}
        ex._observe_hints({1: 1})  # the 5 ages out of the 3-wide window
        assert ex._hints == {1: 2}
        assert ex._plan_buckets(8) == {1: bucket_for(2, 8)}

    def test_window_one_is_last_step_only(self, deep_model):
        """hint_window=1, headroom=0 reproduces the historical policy."""
        ex = self._executor(deep_model, hint_window=1)
        for count in (5, 2):
            ex._observe_hints({1: count})
        assert ex._hints == {1: 2}

    def test_headroom_inflates_bucket(self, deep_model):
        ex = self._executor(deep_model, bucket_headroom=0.5)
        ex._observe_hints({1: 3})
        # ceil(3 * 1.5) = 5 -> bucket 8 (ladder 1,2,4,8); exact fit gives 4.
        assert ex._plan_buckets(8) == {1: 8}
        ex0 = self._executor(deep_model)
        ex0._observe_hints({1: 3})
        assert ex0._plan_buckets(8) == {1: 4}

    def test_headroom_cuts_retries_under_fluctuation(self, deep_model):
        """A fluctuating exit rate that overflows exact-fit hints is
        absorbed by headroom (fewer overflow retries, same trajectory)."""
        cfg0, params = deep_model
        # Threshold below every entropy: nobody exits, so every step's
        # true survivor count is the full batch while we feed stale hints.
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)
        runs = {}
        for headroom in (0.0, 1.0):
            ex = TierExecutor(
                cfg, params, segments_for_cuts(cfg, (2,)),
                bucket_headroom=headroom,
            )
            caches = M.init_caches(cfg, 8, 32)
            tok = _toks(cfg, 8)
            res, caches = ex.step(tok, 0, caches)
            ex._hints = {1: 4}  # stale under-estimate; headroom doubles it
            res, caches = ex.step(res.tokens_dev[:, None], 1, caches)
            runs[headroom] = (ex.overflow_retries, res.tokens)
        assert runs[0.0][0] == 1  # exact fit: bucket 4 overflows, retries
        assert runs[1.0][0] == 0  # ceil(4*2)=8 fits the spike
        np.testing.assert_array_equal(runs[0.0][1], runs[1.0][1])

    def test_validation(self, deep_model):
        with pytest.raises(ValueError):
            self._executor(deep_model, hint_window=0)
        with pytest.raises(ValueError):
            self._executor(deep_model, bucket_headroom=-0.1)


class TestReportCounters:
    def test_partitioned_server_surfaces_counters(self, deep_model):
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)  # no exits
        srv = PartitionedServer(cfg, params, 2)
        caches = M.init_caches(cfg, 8, 32)
        rep, caches = srv.step(_toks(cfg, 8), 0, caches)
        assert rep.overflow_retries == 0 and rep.pipeline_fallbacks == 0
        srv.executor._hints = {1: 1}  # stale all-exit hint: 8 arrive
        rep, caches = srv.step(rep.tier_result.tokens[:, None], 1, caches)
        assert rep.overflow_retries == 1

    def test_multitier_server_surfaces_counters(self, deep_model):
        cfg0, params = deep_model
        cfg = dataclasses.replace(cfg0, exit_threshold=0.0)
        tiers = [TierSpec("edge", 2.0, 1e9), TierSpec("mid", 1.5, 1e9),
                 TierSpec("cloud", 1.0)]
        srv = PartitionedServer(cfg, params, (1, 3), tiers=tiers)
        caches = M.init_caches(cfg, 4, 32)
        rep, caches = srv.step(_toks(cfg, 4), 0, caches)
        assert rep.overflow_retries == 0 and rep.pipeline_fallbacks == 0


class TestProbeSteps:
    def test_probe_reports_all_branches_without_touching_trajectory(
        self, deep_model
    ):
        """A probed step emits identical tokens/exits/caches to a normal
        step but reports would-exit masks for every cfg.branch_layers —
        including branch 3, which the (2,) plan discards at the cloud."""
        cfg, params = deep_model
        exp = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        exn = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        cp, cn = M.init_caches(cfg, 8, 32), M.init_caches(cfg, 8, 32)
        tok = _toks(cfg, 8)
        exp.probe_next = True
        rp, cp = exp.step(tok, 0, cp)
        rn, cn = exn.step(tok, 0, cn)
        np.testing.assert_array_equal(rp.tokens, rn.tokens)
        np.testing.assert_array_equal(rp.exited, rn.exited)
        np.testing.assert_array_equal(rp.exit_tier, rn.exit_tier)
        assert sorted(rn.branch_take) == [1]  # plan evaluates branch 1 only
        assert sorted(rp.branch_take) == [1, 3]  # probe adds the discarded
        assert 3 in rp.branch_entropy
        # The flag is one-shot: the following step is a normal one...
        rp2, cp = exp.step(rp.tokens_dev[:, None], 1, cp)
        rn2, cn = exn.step(rn.tokens_dev[:, None], 1, cn)
        assert sorted(rp2.branch_take) == [1]
        # ... and bitwise unaffected by the probe before it.
        np.testing.assert_array_equal(rp2.tokens, rn2.tokens)

    def test_probe_with_kernels(self, deep_model):
        cfg, params = deep_model
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)),
                          use_kernels=True)
        exn = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        ex.probe_next = True
        rp, _ = ex.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))
        rn, _ = exn.step(_toks(cfg, 8), 0, M.init_caches(cfg, 8, 32))
        np.testing.assert_array_equal(rp.tokens, rn.tokens)
        assert sorted(rp.branch_take) == [1, 3]

    def test_controller_explore_refreshes_discarded_branch(self, deep_model):
        """explore_every_n epsilon schedule: the probed step's report gives
        the discarded branch measured arrivals, so measured_probs() stops
        carrying the installed estimate for it."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.array([0.3, 0.7]), "3g", 50.0, 64.0
        )
        srv = PartitionedServer(cfg, params, 2, cost_profile=profile,
                                network=NetworkProfile("3g", 1.1e6))
        ctl = RepartitionController(srv, profile, explore_every_n=2)
        ctl._installed_p = np.array([0.3, 0.7])
        caches = M.init_caches(cfg, 8, 32)
        tok = _toks(cfg, 8)
        pos = 0
        saw_probe = False
        for _ in range(4):
            rep, caches = srv.step(tok, pos, caches)
            res = rep.tier_result
            saw_probe |= 3 in res.branch_take
            ctl.observe(res)
            tok, pos = res.tokens[:, None], pos + 1
        assert saw_probe  # the schedule really probed
        assert ctl._arrivals[1] > 0  # discarded branch observed arrivals
        measured = ctl.measured_probs()
        # Branch 3's probability is now measured, not the installed 0.7
        # carry-over (the fixed seed's mixed regime never exits everyone).
        assert measured[1] != pytest.approx(0.7)

    def test_observe_conditional_accounting_with_probed_early_branch(
        self, deep_model
    ):
        """Regression: a probed (discarded) branch ordered BEFORE a kept
        branch removes its would-exit rows from the controller's alive
        mask, while the later branch's take (computed under plan
        semantics) can still contain them — exits must be intersected
        with alive or the conditional estimate exceeds 1."""
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.array([0.3, 0.3]), "3g", 50.0, 64.0
        )
        srv = PartitionedServer(cfg, params, 2, cost_profile=profile,
                                network=NetworkProfile("3g", 1.1e6))
        ctl = RepartitionController(srv, profile)

        class FakeReport:
            def __init__(self, batch, takes):
                self.tokens = np.zeros(batch, np.int64)
                self.branch_take = takes

        # Probe-style report: rows 0 and 1 would exit at branch 1 AND are
        # marked taken at branch 3 (plan semantics never saw branch 1).
        takes = {
            1: np.array([True, True, False, False]),
            3: np.array([True, True, True, False]),
        }
        ctl.observe(FakeReport(4, takes))
        # Branch 1: 4 arrivals, 2 exits.  Branch 3: rows 2,3 arrive, only
        # row 2 exits among them (rows 0,1 already left at branch 1).
        np.testing.assert_allclose(ctl._arrivals, [4.0, 2.0])
        np.testing.assert_allclose(ctl._exits, [2.0, 1.0])
        measured = ctl.measured_probs()
        assert np.all(measured <= 1.0)
        np.testing.assert_allclose(measured, [0.5, 0.5])

    def test_controller_without_exploration_carries_installed(self, deep_model):
        cfg, params = deep_model
        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        profile = build_cost_profile(
            costs, cfg.branch_layers, np.array([0.3, 0.7]), "3g", 50.0, 64.0
        )
        srv = PartitionedServer(cfg, params, 2, cost_profile=profile,
                                network=NetworkProfile("3g", 1.1e6))
        ctl = RepartitionController(srv, profile)  # explore_every_n=0
        ctl._installed_p = np.array([0.3, 0.7])
        caches = M.init_caches(cfg, 8, 32)
        rep, caches = srv.step(_toks(cfg, 8), 0, caches)
        ctl.observe(rep.tier_result)
        assert ctl._arrivals[1] == 0  # branch 3 never evaluated
        assert ctl.measured_probs()[1] == pytest.approx(0.7)  # carried
