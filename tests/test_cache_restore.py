"""The resident cache on the CPU: every program that writes the cache
takes it donated (the caller's handle is consumed), and an overflow
re-run starts from the step's entry caches, restored in place from the
entry segment's snapshot — so a re-run step ends bitwise as the same step
run with buckets that never overflow, for K=2 and K=3, on an attention
trunk and on a Mamba2 + shared-attention (hybrid) trunk, with per-sequence
and shared step positions.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving import TierExecutor, segments_for_cuts
from repro.serving.faults import FlapWindow, HopPolicy, LinkFaultModel

B, CONTEXT = 8, 32


@pytest.fixture(scope="module", params=["dense", "hybrid"])
def trunk(request):
    """6 trunk layers, exit heads after 1 and 3: the attention trunk, or
    Mamba2 layers with the shared attention block after every second."""
    if request.param == "dense":
        cfg = get_smoke_config("phi3_mini_3_8b")
    else:
        cfg = dataclasses.replace(get_smoke_config("zamba2_1_2b"),
                                  attn_every=2)
    cfg = dataclasses.replace(cfg, num_layers=6, branch_layers=(1, 3))
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _toks(cfg, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, 1), 0,
                              cfg.vocab_size)


def _entry_caches(ex, per_seq):
    """Caches with a few steps of history in them (every row dirty), and
    the next step's token and positions."""
    caches = M.init_caches(ex.cfg, B, CONTEXT)
    tok = _toks(ex.cfg, 1)
    pos0 = np.arange(B, dtype=np.int32) if per_seq else 0
    for i in range(3):
        res, caches = ex.step(tok, pos0 + i, caches)
        tok = res.tokens_dev[:, None]
    return caches, tok, pos0 + 3


def _assert_same_step(ra, ca, rb, cb):
    np.testing.assert_array_equal(ra.tokens, rb.tokens)
    np.testing.assert_array_equal(ra.exited, rb.exited)
    np.testing.assert_array_equal(ra.exit_tier, rb.exit_tier)
    assert ra.branch_take.keys() == rb.branch_take.keys()
    for layer in ra.branch_take:
        np.testing.assert_array_equal(ra.branch_take[layer],
                                      rb.branch_take[layer])
    la, ta = jax.tree_util.tree_flatten(ca)
    lb, tb = jax.tree_util.tree_flatten(cb)
    assert ta == tb
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _rerun_vs_fitting(cfg, params, cuts, per_seq, fitting=None):
    """One step from the same entry caches twice: bucket hints pinned to
    one row (every downstream tier overflows and re-runs), and pinned to
    ``fitting`` (default: the full batch, nothing overflows)."""
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, cuts))
    caches, tok, pos = _entry_caches(ex, per_seq)
    down = range(1, len(cuts) + 1)
    retries, restores = ex.overflow_retries, ex.cache_restores
    ex._hints = {i: 1 for i in down}
    ra, ca = ex.step(tok, pos, _copy(caches))
    assert ex.overflow_retries > retries
    assert ex.cache_restores - restores == ex.overflow_retries - retries
    if fitting is None:
        fitting = {i: B for i in down}
    elif fitting == "rerun":
        fitting = {i + 1: c.bucket for i, c in enumerate(ra.compaction)}
    retries, restores = ex.overflow_retries, ex.cache_restores
    ex._hints = fitting
    rb, cb = ex.step(tok, pos, caches)
    assert (ex.overflow_retries, ex.cache_restores) == (retries, restores)
    return ra, ca, rb, cb


@pytest.mark.parametrize("cuts", [(2,), (2, 4)], ids=["k2", "k3"])
def test_rerun_restores_entry_caches(trunk, cuts):
    """Every row survives, so the re-run's buckets are the full batch:
    tokens, exit masks and every cache leaf equal the full-batch step."""
    cfg, params = trunk
    cfg = dataclasses.replace(cfg, exit_threshold=0.0)
    _assert_same_step(*_rerun_vs_fitting(cfg, params, cuts, per_seq=True))


def test_rerun_restores_entry_caches_shared_position(trunk):
    """The lock-step form: one shared () position, slot ``length % C``."""
    cfg, params = trunk
    cfg = dataclasses.replace(cfg, exit_threshold=0.0)
    _assert_same_step(*_rerun_vs_fitting(cfg, params, (2, 4), per_seq=False))


def test_rerun_with_exits_matches_its_buckets(trunk):
    """Mixed exits at both exit heads: the re-run equals the step run
    directly at the buckets the re-run settled on."""
    cfg, params = trunk
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, ()))
    res, _ = ex.step(_toks(cfg, 2), 0, M.init_caches(cfg, B, CONTEXT))
    # A quarter of the rows leave at the first head: enough survive to
    # overflow a one-row bucket.
    cfg = dataclasses.replace(
        cfg, exit_threshold=float(np.quantile(res.branch_entropy[1], 0.25))
    )
    ra, ca, rb, cb = _rerun_vs_fitting(cfg, params, (2, 4), per_seq=True,
                                       fitting="rerun")
    assert ra.exited.any() and not ra.exited.all()
    _assert_same_step(ra, ca, rb, cb)


def test_restore_compiles_with_the_step(trunk):
    """The restore is compiled at the first step that could need it, not
    at the first overflow."""
    cfg, params = trunk
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
    assert not ex._restorers
    ex.step(_toks(cfg, 1), np.zeros(B, np.int32),
            M.init_caches(cfg, B, CONTEXT))
    assert len(ex._restorers) == 1 and ex.cache_restores == 0


def _deleted(tree):
    return [a.is_deleted() for a in jax.tree_util.tree_leaves(tree)]


def test_programs_consume_the_cache(trunk):
    """``step``, ``prefill_rows`` and ``reset_rows`` consume the cache
    they are handed (donated to their programs) and return the live one."""
    cfg, params = trunk
    ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)))
    given = M.init_caches(cfg, B, CONTEXT)
    prompts = np.asarray(_toks(cfg, 3)[:2, 0])[:, None].repeat(5, 1)
    caches, tok0 = ex.prefill_rows(given, prompts, np.array([3, B]))
    assert all(_deleted(given)) and not any(_deleted(caches))
    given = caches
    res, caches = ex.step(_toks(cfg, 1), np.full(B, 5, np.int32), given)
    assert all(_deleted(given)) and not any(_deleted(caches))
    given = caches
    caches = ex.reset_rows(given, np.array([3]))
    assert all(_deleted(given)) and not any(_deleted(caches))


def test_failed_step_returns_the_cache_unconsumed():
    """A step that dispatches nothing (hop 0 down, no exit head below it)
    hands the cache back as it came: same buffers, still live."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(3,)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    fm = LinkFaultModel(
        seed=0, flaps=(FlapWindow(hop=0, start_step=0, end_step=10),)
    )
    ex = TierExecutor(
        cfg, params, segments_for_cuts(cfg, (2,), uplinks=(1e9,)),
        fault_model=fm, hop_policy=HopPolicy(timeout_s=0.01, max_retries=0),
    )
    given = M.init_caches(cfg, B, CONTEXT)
    res, caches = ex.step(_toks(cfg, 1), 0, given)
    assert res.failed.all() and ex.failed_steps == 1
    assert caches is given and not any(_deleted(caches))


@pytest.mark.parametrize("use_kernels", [False, True], ids=["jnp", "kernels"])
def test_lane_padded_cache_serves_the_same_tokens(use_kernels):
    """K/V stored with head_dim zero-padded to the lanes (what
    ``shard_caches`` does where the compiled decode kernel reads the
    cache) serve the tokens, exits and logits of the unpadded cache,
    through an admission prefill and decode steps."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.asarray(_toks(cfg, 3)[:4, 0])[:, None].repeat(5, 1)
    runs = []
    for pad in (False, True):
        ex = TierExecutor(cfg, params, segments_for_cuts(cfg, (2,)),
                          use_kernels=use_kernels)
        caches = M.init_caches(cfg, B, CONTEXT)
        if pad:
            caches = M.pad_kv_lanes(caches)
            assert caches["blocks"]["self"]["k"].shape[-1] == M.LANES
        caches, tok0 = ex.prefill_rows(caches, prompts, np.array([5, 1, 2, 6]))
        tok = _toks(cfg, 1).at[np.array([5, 1, 2, 6]), 0].set(tok0)
        out = []
        for i in range(3):
            res, caches = ex.step(tok, np.full(B, 5 + i, np.int32), caches)
            out.append((res.tokens, res.exited, np.asarray(res.last_logits)))
            tok = res.tokens_dev[:, None]
        runs.append(out)
    for (ta, ea, la), (tb, eb, lb) in zip(*runs):
        np.testing.assert_array_equal(ta, tb)
        np.testing.assert_array_equal(ea, eb)
        np.testing.assert_array_equal(la, lb)
