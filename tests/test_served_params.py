"""The served weight tree (``models.model.served_params``).

Where the head width is not a multiple of the TPU's 128 lanes (phi3: 96),
``TierExecutor`` serves the q/k/v projections transposed, ``[L, out, in]``
under ``wq_t`` / ``wk_t`` / ``wv_t``, so that their default layout is the
one the decode programs read.  The canonical tree (training, the
reference, the benchmark's weights) keeps ``[L, in, out]``.  These tests
pin which leaves change, that serving them changes no token, exit decision
or logit, and that every assignment to ``TierExecutor.params`` derives
the served tree afresh.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving import PartitionedServer, TierExecutor, segments_for_cuts
from repro.serving import tiers as tiers_mod

SERVED = ("blocks/attn/wq_t", "blocks/attn/wk_t", "blocks/attn/wv_t")


def _cfg(head_dim: int, **kw):
    """A 4-layer phi3-like trunk whose head width is ``head_dim``."""
    return dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4,
        branch_layers=(1, 3), head_dim=head_dim, **kw,
    )


@pytest.fixture(scope="module")
def off_lanes():
    cfg = _cfg(24)
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _dicts(tree):
    """Every dict of a tree, outermost first."""
    if isinstance(tree, dict):
        yield tree
        for v in tree.values():
            yield from _dicts(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _dicts(v)


class TestServedTree:
    def test_renames_and_transposes_exactly_q_k_v(self, off_lanes):
        cfg, params = off_lanes
        served = M.served_params(cfg, params)
        attn, canon = served["blocks"]["attn"], params["blocks"]["attn"]
        assert sorted(attn) == ["wk_t", "wo", "wq_t", "wv_t"]
        for name in M.TRANSPOSED_PROJECTIONS:
            np.testing.assert_array_equal(
                attn[name + "_t"], np.swapaxes(canon[name], -1, -2))
        assert attn["wo"] is canon["wo"]
        flat = dict(jax.tree_util.tree_flatten_with_path(params)[0])
        for path, leaf in jax.tree_util.tree_flatten_with_path(served)[0]:
            if path in flat:  # every leaf not transposed is the same array
                assert leaf is flat[path], jax.tree_util.keystr(path)
        # The canonical tree is untouched; no dict is shared with it.
        assert sorted(canon) == ["wk", "wo", "wq", "wv"]
        assert not {id(d) for d in _dicts(served)} & {
            id(d) for d in _dicts(params)}

    def test_served_tree_is_served_again_unchanged(self, off_lanes):
        cfg, params = off_lanes
        served = M.served_params(cfg, params)
        again = M.served_params(cfg, served)
        assert again is not served
        assert jax.tree_util.tree_structure(again) == \
            jax.tree_util.tree_structure(served)
        for a, b in zip(jax.tree_util.tree_leaves(again),
                        jax.tree_util.tree_leaves(served)):
            assert a is b

    def test_head_width_on_the_lanes_is_untouched(self):
        cfg = _cfg(128, num_heads=2, num_kv_heads=2)
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        assert M.served_params(cfg, params) is params
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        assert ex.params is params and ex.served_transposed == ()

    def test_abstract_leaves_swap_their_shape(self, off_lanes):
        cfg, params = off_lanes
        shapes = jax.eval_shape(lambda: params)
        served = M.served_params(cfg, shapes)
        wq = params["blocks"]["attn"]["wq"]
        got = served["blocks"]["attn"]["wq_t"]
        assert isinstance(got, jax.ShapeDtypeStruct)
        assert got.shape == np.swapaxes(wq, -1, -2).shape
        assert got.dtype == wq.dtype


def _canonical_executor(monkeypatch, cfg, params, cuts):
    """An executor that serves the canonical tree as it is."""
    with monkeypatch.context() as m:
        m.setattr(tiers_mod, "served_params", lambda cfg, p: p)
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, cuts))
    assert "wq" in ex.params["blocks"]["attn"]
    return ex


def _admit_and_decode(ex, cfg, steps=4):
    """Admit two prompts into rows 1 and 3 of a 4-row cache, then decode
    ``steps`` steps: (tokens, exited, exit tier, logits, entropies)."""
    caches = ex.shard_caches(M.init_caches(cfg, 4, 32))
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0,
                                 cfg.vocab_size)
    caches, tok0 = ex.prefill_rows(caches, prompts, [1, 3])
    tok = jax.random.randint(jax.random.PRNGKey(2), (4, 1), 0,
                             cfg.vocab_size)
    tok = tok.at[jnp.array([1, 3]), 0].set(tok0)
    pos = np.array([0, 6, 0, 6])
    out = [np.asarray(tok0)]
    for i in range(steps):
        res, caches = ex.step(tok, pos + i, caches)
        out.append((
            res.tokens.copy(), res.exited.copy(), res.exit_tier.copy(),
            np.asarray(res.last_logits, np.float32),
            {k: np.asarray(v) for k, v in res.branch_entropy.items()},
        ))
        tok = jnp.asarray(res.tokens[:, None])
    return out


class TestServedExecutor:
    def test_admission_and_decode_match_the_canonical_tree(
            self, off_lanes, monkeypatch):
        cfg, params = off_lanes
        # Threshold at the median layer-1 entropy: about half the rows
        # exit at the edge head, so compaction runs the cloud on a subset.
        probe = _admit_and_decode(
            _canonical_executor(monkeypatch, cfg, params, (2,)), cfg, 1)
        cfg = dataclasses.replace(
            cfg, exit_threshold=float(np.median(probe[1][4][1])))
        served = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
        assert served.served_transposed == SERVED
        got = _admit_and_decode(served, cfg)
        ref = _admit_and_decode(
            _canonical_executor(monkeypatch, cfg, params, (2,)), cfg)
        np.testing.assert_array_equal(got[0], ref[0])  # admission tokens
        exits = 0
        for step, (g, r) in enumerate(zip(got[1:], ref[1:])):
            for a, b in zip(g[:3], r[:3]):  # tokens, exits, exit tier
                np.testing.assert_array_equal(a, b, err_msg=f"step {step}")
            np.testing.assert_allclose(g[3], r[3], rtol=1e-5, atol=1e-5)
            for layer in r[4]:
                np.testing.assert_allclose(g[4][layer], r[4][layer],
                                           rtol=1e-5, atol=1e-5)
            exits += int(g[1].sum())
        assert 0 < exits < 4 * 4  # some rows exit, not all

    def test_every_assignment_derives_the_served_tree(self, off_lanes):
        cfg, params = off_lanes
        srv = PartitionedServer(cfg, params, 2)
        ex = srv.executor
        assert srv.params is ex.params
        first = ex.params["blocks"]["attn"]["wq_t"]
        # As the benchmark's reseed does: free, then a fresh canonical tree.
        ex.params = None
        assert ex.params is None
        canon = jax.tree_util.tree_map(lambda a: a, params)
        ex.params = canon
        assert ex.served_transposed == SERVED
        assert "wq" in canon["blocks"]["attn"]
        assert ex.params["blocks"]["attn"]["wq_t"] is not first
        np.testing.assert_array_equal(ex.params["blocks"]["attn"]["wq_t"],
                                      first)
        # Scaling an exit head's gain in one tree leaves the other alone
        # (the benchmark scales both trees in place, once each).
        gain = np.asarray(params["branches"]["scale"])
        canon["branches"]["scale"] = canon["branches"]["scale"].at[0].multiply(2.0)
        np.testing.assert_array_equal(ex.params["branches"]["scale"], gain)
        ex.params["branches"]["scale"] = (
            ex.params["branches"]["scale"].at[1].multiply(3.0))
        np.testing.assert_array_equal(canon["branches"]["scale"][1], gain[1])
        np.testing.assert_array_equal(canon["branches"]["scale"][0],
                                      2.0 * gain[0])
        np.testing.assert_array_equal(ex.params["branches"]["scale"][0],
                                      gain[0])
