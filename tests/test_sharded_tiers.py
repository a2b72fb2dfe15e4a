"""Mesh-sharded tier segments (serving/tiers.py "Mesh-sharded tier
segments"): sharded-vs-single-device trajectory equivalence on a virtual
CPU mesh, the one-host-sync and no-re-jit invariants under SPMD, mesh
construction overrides, and the sharding-aware partition-cost terms
(``TierSpec.devices`` / ``ici_bps``) in the lattice solver.

The multi-device cases need virtual devices *before jax initializes*:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_sharded_tiers.py

(``make test-sharded`` / the tools/ci.sh multi-device lane do this); under
a plain single-device run they skip.  The cost-model tests always run.

SPMD partial-sum all-reduces may reorder float accumulation, so the
equivalence contract is *trajectory* identity — greedy tokens, exit
masks, shipped counts per step — not bitwise logits.  The dense model
computes in float32: with bf16 activations a reordered sum moves a logit
by up to a bf16 ulp, which flips a near-tied greedy token of a random-init
model (the mesh and one device then diverge legitimately).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.multitier import (
    _COLLECTIVES_PER_LAYER,
    TierSpec,
    _collective_seconds,
    expected_time_multitier,
    solve_multitier,
)
from repro.launch.mesh import make_local_mesh, mesh_devices
from repro.models import model as M
from repro.serving import PartitionedServer

multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@pytest.fixture(scope="module")
def gqa_model():
    """4-layer GQA trunk (qwen3_8b smoke), branches after v_1 and v_3."""
    cfg = dataclasses.replace(
        get_smoke_config("qwen3_8b"), num_layers=4, branch_layers=(1, 3),
        dtype="float32",
    )
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def moe_model():
    """4-layer MoE trunk (qwen3_moe smoke), branches after v_1 and v_3."""
    cfg = dataclasses.replace(
        get_smoke_config("qwen3_moe_30b_a3b"), num_layers=4,
        branch_layers=(1, 3),
    )
    return cfg, M.init_params(jax.random.PRNGKey(1), cfg)


def _toks(cfg, batch=4, seed=2):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, 1), 0, cfg.vocab_size
    )


def _trajectory(srv, cfg, steps=4, batch=4):
    """Greedy-decode ``steps`` and record (tokens, exited, shipped)/step."""
    caches = srv.executor.shard_caches(M.init_caches(cfg, batch, 32))
    tok = _toks(cfg, batch)
    out = []
    for i in range(steps):
        rep, caches = srv.step(tok, i, caches)
        res = rep.tier_result
        out.append((res.tokens.copy(), np.asarray(res.exited).copy(),
                    res.shipped_per_hop))
        tok = jnp.asarray(res.tokens[:, None])
    return out


def _assert_same_trajectory(ref, got):
    assert len(ref) == len(got)
    for step, ((rt, re, rs), (gt, ge, gs)) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(gt, rt, err_msg=f"tokens @ step {step}")
        np.testing.assert_array_equal(ge, re, err_msg=f"exits @ step {step}")
        assert gs == rs, f"shipped @ step {step}"


@multi_device
class TestShardedEquivalence:
    """Sharded segments reproduce the single-device trajectory exactly."""

    @pytest.mark.parametrize("compaction", ["bucketed", "off"])
    def test_k2_partitioned_gqa(self, gqa_model, compaction):
        cfg, params = gqa_model
        ref = _trajectory(
            PartitionedServer(cfg, params, 2, compaction=compaction), cfg
        )
        srv = PartitionedServer(
            cfg, params, 2, compaction=compaction, mesh=make_local_mesh()
        )
        assert srv.executor.sharded
        assert tuple(t.devices for t in srv.tiers) == (1, jax.device_count())
        _assert_same_trajectory(ref, _trajectory(srv, cfg))

    @pytest.mark.parametrize("compaction", ["bucketed", "off"])
    def test_k3_multitier_moe(self, moe_model, compaction):
        cfg, params = moe_model
        tiers = [
            TierSpec("device", 200.0, 1e6),
            TierSpec("edge", 20.0, 2e7),
            TierSpec("cloud", 1.0, devices=jax.device_count(), ici_bps=1e11),
        ]
        ref = _trajectory(
            PartitionedServer(cfg, params, (1, 3), tiers=tiers,
                              compaction=compaction), cfg
        )
        srv = PartitionedServer(
            cfg, params, (1, 3), tiers=tiers, compaction=compaction,
            mesh=make_local_mesh(),
        )
        assert srv.executor.sharded
        _assert_same_trajectory(ref, _trajectory(srv, cfg))

    def test_k1_engine_matches_unsharded(self, gqa_model):
        from repro.serving import ServingEngine

        cfg, params = gqa_model
        prompts = {"tokens": jax.random.randint(
            jax.random.PRNGKey(3), (4, 6), 0, cfg.vocab_size)}

        def run(mesh):
            eng = ServingEngine(cfg, params, context_len=64, mesh=mesh)
            toks, stats = eng.decode(eng.start(prompts), steps=5)
            return np.asarray(toks), eng.host_syncs

        ref, ref_syncs = run(None)
        got, got_syncs = run(make_local_mesh())
        np.testing.assert_array_equal(got, ref)
        assert got_syncs == ref_syncs == 5

    def test_one_host_sync_per_sharded_step(self, gqa_model):
        cfg, params = gqa_model
        srv = PartitionedServer(cfg, params, 2, mesh=make_local_mesh())
        caches = srv.executor.shard_caches(M.init_caches(cfg, 4, 32))
        tok = _toks(cfg)
        for i in range(4):
            rep, caches = srv.step(tok, i, caches)
            tok = jnp.asarray(rep.tier_result.tokens[:, None])
        assert srv.executor.host_syncs == 4

    def test_hot_swap_keeps_sharded_segment_fns(self, moe_model):
        cfg, params = moe_model
        tiers = [TierSpec("d", 100.0, 1e6), TierSpec("e", 10.0, 1e7),
                 TierSpec("c", 1.0)]
        srv = PartitionedServer(
            cfg, params, (1, 3), tiers=tiers, mesh=make_local_mesh()
        )
        cloud_fn = srv.executor.segment_fn(2)
        srv.install_cuts((2, 3))  # move only the first cut
        assert srv.executor.segment_fn(2) is cloud_fn

    def test_sharded_resolves_kernels_off(self, gqa_model):
        """Pallas decode kernels are single-device; sharded segments must
        take the jnp lowering regardless of the requested flag."""
        cfg, params = gqa_model
        srv = PartitionedServer(
            cfg, params, 2, mesh=make_local_mesh(), use_kernels=True
        )
        assert srv.executor.use_kernels is False

    def test_served_projections_shard_their_out_axis(self):
        """A head width off the lanes on a (1, 4) mesh: the served
        ``wq_t`` (``[L, out, in]``) takes the policy's rule, its out axis
        over "model", and the sharded trajectory matches one device's."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = dataclasses.replace(
            get_smoke_config("phi3_mini_3_8b"), num_layers=4,
            branch_layers=(1, 3), head_dim=24, dtype="float32",
        )
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_local_mesh(data=1, model=4)
        srv = PartitionedServer(cfg, params, 2, mesh=mesh)
        ex = srv.executor
        assert ex.served_transposed == (
            "blocks/attn/wq_t", "blocks/attn/wk_t", "blocks/attn/wv_t")
        for name in ex.served_transposed:
            leaf = ex.params["blocks"]["attn"][name.split("/")[-1]]
            spec = ex.policy.param_spec(name, leaf.shape)
            assert spec == P(None, "model", None)
            assert leaf.sharding.is_equivalent_to(
                NamedSharding(mesh, spec), leaf.ndim)
        ref = _trajectory(PartitionedServer(cfg, params, 2), cfg)
        _assert_same_trajectory(ref, _trajectory(srv, cfg))

    def test_sharded_params_actually_span_devices(self, gqa_model):
        """The policy must place at least one trunk tensor across >1
        device — otherwise the "sharded" run is silently replicated."""
        cfg, params = gqa_model
        srv = PartitionedServer(cfg, params, 2, mesh=make_local_mesh())
        widths = {
            len(leaf.sharding.device_set)
            for leaf in jax.tree_util.tree_leaves(srv.params)
        }
        assert max(widths) == jax.device_count()


@multi_device
class TestMeshConstruction:
    def test_local_mesh_axis_overrides(self):
        mesh = make_local_mesh(data=2, model=4)
        assert dict(mesh.shape) == {"data": 2, "model": 4}
        assert mesh_devices(mesh) == 8

    def test_default_is_pure_model_parallel(self):
        mesh = make_local_mesh()
        assert dict(mesh.shape) == {"data": 1, "model": jax.device_count()}

    def test_over_request_raises(self):
        with pytest.raises(ValueError, match="only"):
            make_local_mesh(data=jax.device_count(), model=2)

    def test_partial_override_fills_remainder(self):
        mesh = make_local_mesh(model=2)
        assert dict(mesh.shape) == {"data": jax.device_count() // 2,
                                    "model": 2}


class TestShardedTierCosts:
    """TierSpec.devices/ici_bps: shard-width compute + intra-tier
    collective terms move the optimal cut (and are priced honestly).
    Pure cost model — no devices needed."""

    def _profile(self, n=8):
        t_c = np.concatenate([[0.0], np.full(n, 2e-2)])
        alpha = np.full(n + 1, 4e4)  # 40 KB residual crossing any cut
        p = np.zeros(n + 1)
        return t_c, alpha, p

    def test_shard_width_moves_cut(self):
        """With equal per-chip speed the solver never ships (the hop buys
        nothing); widening the cloud to an 8-way mesh makes shipping pay
        for itself, and the new cut is verified cheaper under the sharded
        cost."""
        t_c, alpha, p = self._profile()
        n = len(t_c) - 1
        uplink = 4e7  # 8 ms hop vs 20 ms/layer saved on the wide tier
        flat = [TierSpec("edge", 1.0, uplink), TierSpec("cloud", 1.0)]
        wide = [
            TierSpec("edge", 1.0, uplink),
            TierSpec("cloud", 1.0, devices=8, ici_bps=1e11),
        ]
        plan_flat = solve_multitier(t_c, alpha, p, flat)
        plan_wide = solve_multitier(t_c, alpha, p, wide)
        assert plan_flat.cut_after == (n,)  # never ship: no compute gain
        assert plan_wide.cut_after != plan_flat.cut_after
        at_wide = expected_time_multitier(
            t_c, alpha, p, wide, plan_wide.cut_after
        )
        at_flat = expected_time_multitier(
            t_c, alpha, p, wide, plan_flat.cut_after
        )
        assert at_wide < at_flat
        assert plan_wide.expected_time_s == pytest.approx(at_wide)

    def test_dead_ici_prices_sharded_tier_unusable(self):
        """devices > 1 with no interconnect = infinite collectives: the
        solver routes every layer off that tier (mirrors _hop_seconds'
        dead-uplink policy)."""
        t_c, alpha, p = self._profile()
        n = len(t_c) - 1
        tiers = [
            TierSpec("edge", 1.0, 4e7),
            TierSpec("cloud", 1.0, devices=8, ici_bps=0.0),
        ]
        plan = solve_multitier(t_c, alpha, p, tiers)
        assert plan.cut_after == (n,)
        assert np.isfinite(plan.expected_time_s)

    def test_collective_term_scales_with_ring(self):
        assert _collective_seconds(1, 8e4, 1e9) == 0.0
        assert _collective_seconds(4, 0.0, 1e9) == 0.0
        assert _collective_seconds(2, 8e4, 0.0) == np.inf
        t2 = _collective_seconds(2, 8e4, 1e9)
        t8 = _collective_seconds(8, 8e4, 1e9)
        # ring factor 2(d-1)/d: t8/t2 = (7/4) / 1 = 1.75
        assert t8 == pytest.approx(t2 * 1.75)

    def test_estimator_matches_manual_sharded_cost(self):
        """expected_time_multitier with a sharded last tier = hand-computed
        per-layer (gamma*t_c/d + collectives) + hop."""
        t_c, alpha, p = self._profile(4)
        d, ici, uplink = 4, 5e10, 1e8
        tiers = [
            TierSpec("edge", 2.0, uplink),
            TierSpec("cloud", 1.0, devices=d, ici_bps=ici),
        ]
        s = 2
        got = expected_time_multitier(t_c, alpha, p, tiers, (s,))
        ring = 2.0 * (d - 1) / d
        coll = _COLLECTIVES_PER_LAYER * ring * alpha[3] * 8.0 / ici
        want = (
            2.0 * (t_c[1] + t_c[2])  # edge layers
            + alpha[s] * 8.0 / uplink  # hop
            + sum(t_c[i] / d + coll for i in (3, 4))  # sharded cloud
        )
        assert got == pytest.approx(want, rel=1e-12)

    def test_profiler_devices_term(self):
        """HardwareSpec.roofline_time/collective_time mirror the lattice
        terms: d-way split plus ring collectives on the output bytes."""
        from repro.core.profiler import HardwareSpec

        hw = HardwareSpec("t", peak_flops=1e12, hbm_bw=1e11, link_bw=1e10)
        base = hw.roofline_time(1e9, 1e7)
        assert hw.roofline_time(1e9, 1e7, devices=4) == pytest.approx(
            base / 4
        )
        assert hw.collective_time(1e6, 1) == 0.0
        want = 2.0 * (2.0 * 3 / 4) * 1e6 / 1e10
        assert hw.collective_time(1e6, 4) == pytest.approx(want)


@multi_device
class TestPolicyLowering:
    """Decode-step lowering under each config's policy never crashes: the
    rule tables may replicate (divisibility fallback) but must never
    produce a spec XLA rejects.  Smoke configs keep the compile cheap;
    the mesh is the real virtual-device mesh, so SPMD propagation runs."""

    @pytest.mark.parametrize(
        "arch", __import__("repro.configs", fromlist=["ARCH_IDS"]).ARCH_IDS
    )
    def test_decode_step_compiles_sharded(self, arch):
        from repro.sharding.ctx import activation_sharding
        from repro.sharding.policy import make_policy

        cfg = get_smoke_config(arch)
        mesh = make_local_mesh()
        pol = make_policy(mesh, cfg)
        params = pol.shard_params(M.init_params(jax.random.PRNGKey(0), cfg))
        caches = pol.shard_caches(M.init_caches(cfg, 4, 32))
        tok = _toks(cfg)
        pos = jnp.asarray(0, jnp.int32)

        def step(p, t, c):
            with activation_sharding(mesh, pol.batch_axes, pol.model_axis):
                return M.decode_step(p, t, pos, c, cfg)

        out = jax.jit(step).lower(params, tok, caches).compile()(
            params, tok, caches
        )
        assert np.all(np.isfinite(np.asarray(out["logits"], np.float32)))
