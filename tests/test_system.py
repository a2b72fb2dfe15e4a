"""End-to-end behaviour tests for the paper's system: calibration ->
cost model -> shortest-path plan -> partitioned execution, plus the
equivalence between partitioned and monolithic decode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import (
    LayerCost,
    Partitioner,
    build_cost_profile,
)
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.partitioned import PartitionedServer


@pytest.fixture(scope="module")
def small_model():
    cfg = get_smoke_config("phi3_mini_3_8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


class TestPartitionedEquivalence:
    """A split must not change the computation — only where it runs."""

    @pytest.mark.parametrize("arch", ["phi3_mini_3_8b", "mamba2_130m", "zamba2_1_2b"])
    def test_partitioned_decode_matches_monolithic(self, arch):
        cfg = get_smoke_config(arch)
        params = M.init_params(jax.random.PRNGKey(1), cfg)
        batch, ctx = 4, 32
        tok = jax.random.randint(jax.random.PRNGKey(2), (batch, 1), 0, cfg.vocab_size)

        # Monolithic decode.
        caches0 = M.init_caches(cfg, batch, ctx)
        mono = M.decode_step(params, tok, jnp.asarray(0, jnp.int32), caches0, cfg)

        # Partitioned at layer 1.
        srv = PartitionedServer(cfg, params, 1)
        caches1 = M.init_caches(cfg, batch, ctx)
        res = srv.step(tok, 0, caches1)[0].tier_result

        mono_tok = np.asarray(jnp.argmax(mono["logits"], -1))
        # Sequences that exited on the edge emit branch tokens; everything
        # that crossed the cut must match the monolithic forward exactly.
        crossed = ~res.exited
        assert crossed.any()
        np.testing.assert_array_equal(res.tokens[crossed], mono_tok[crossed])

    def test_edge_only_and_cloud_only_bytes(self, small_model):
        cfg, params = small_model
        batch = 4
        total = cfg.num_layers
        tok = jnp.zeros((batch, 1), jnp.int32)

        srv0 = PartitionedServer(cfg, params, 0)
        res0 = srv0.step(tok, 0, M.init_caches(cfg, batch, 32))[0].tier_result
        assert res0.shipped_per_hop == (batch,)  # everything goes to the cloud

        srvN = PartitionedServer(cfg, params, total)
        resN = srvN.step(tok, 0, M.init_caches(cfg, batch, 32))[0].tier_result
        assert sum(resN.shipped_per_hop) == 0 and sum(resN.bytes_per_hop) == 0.0


class TestCalibrationLoop:
    def test_engine_stats_feed_partitioner(self, small_model):
        cfg, params = small_model
        engine = ServingEngine(cfg, params, context_len=64)
        state = engine.start(
            {"tokens": jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0,
                                          cfg.vocab_size)}
        )
        _, stats = engine.decode(state, steps=4)
        assert stats.total == 4 * 4
        p_k = stats.conditional_probs()
        assert p_k.shape == (len(cfg.branch_layers),)
        assert ((0 <= p_k) & (p_k <= 1)).all()

        costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
                 for i in range(cfg.num_layers)]
        prof = build_cost_profile(costs, cfg.branch_layers, p_k, "4g", 10.0, 64.0)
        plan = Partitioner(prof).solve()
        assert 0 <= plan.split_layer <= cfg.num_layers

    def test_higher_exit_prob_never_hurts(self):
        """Optimal E[T] is non-increasing in p (more exits, less shipped)."""
        costs = [LayerCost(f"l{i}", 0, 0, 2048.0, 1e-3) for i in range(8)]
        last = np.inf
        for p in (0.0, 0.3, 0.6, 0.9, 1.0):
            prof = build_cost_profile(costs, (2,), [p], "3g", 100.0, 1e6)
            t = Partitioner(prof).solve().expected_time_s
            assert t <= last + 1e-12
            last = t


class TestServingEngine:
    def test_decode_is_deterministic(self, small_model):
        cfg, params = small_model
        engine = ServingEngine(cfg, params, context_len=64)
        toks = jax.random.randint(jax.random.PRNGKey(4), (2, 8), 0, cfg.vocab_size)
        out1, _ = engine.decode(engine.start({"tokens": toks}), steps=6)
        out2, _ = engine.decode(engine.start({"tokens": toks}), steps=6)
        np.testing.assert_array_equal(out1, out2)

    def test_prefill_matches_forward(self, small_model):
        """Prefill last-position logits == trunk forward last position."""
        cfg, params = small_model
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0, cfg.vocab_size)
        caches = M.init_caches(cfg, 2, 32)
        logits, _ = M.prefill(params, {"tokens": toks}, cfg, caches)

        from repro.models.layers import norm_apply
        from repro.models.model import _embed_inputs, _unembed, run_trunk

        h, pos = _embed_inputs(params, {"tokens": toks}, cfg)
        h2, _, _, _ = run_trunk(params, h, cfg, pos, None)
        hF = norm_apply(cfg.norm_type, params["final_norm"], h2)
        ref = _unembed(params, hF[:, -1:], cfg)
        np.testing.assert_allclose(
            np.asarray(logits, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2,
        )

    def test_decode_after_prefill_consistency(self):
        """Stepwise decode logits match teacher-forced prefill logits."""
        cfg = get_smoke_config("olmo_1b")
        params = M.init_params(jax.random.PRNGKey(6), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(7), (2, 8), 0, cfg.vocab_size)

        caches = M.init_caches(cfg, 2, 32)
        logits_p, caches = M.prefill(params, {"tokens": toks}, cfg, caches)
        nxt = jnp.argmax(logits_p[:, 0], -1).astype(jnp.int32)[:, None]
        out = M.decode_step(params, nxt, jnp.asarray(8, jnp.int32), caches, cfg)

        ext = jnp.concatenate([toks, nxt], axis=1)
        caches2 = M.init_caches(cfg, 2, 32)
        logits_tf, _ = M.prefill(params, {"tokens": ext}, cfg, caches2)
        np.testing.assert_allclose(
            np.asarray(out["logits"], np.float32),
            np.asarray(logits_tf[:, 0], np.float32),
            rtol=5e-2, atol=5e-2,
        )
