"""Fused softmax-entropy + exit-decision Pallas TPU kernels.

The BranchyNet confidence test (paper Sec. III) runs at every side branch
for every decoded token: H(softmax(logits)) / log V < threshold.  At Qwen3's
151 936-token vocab the naive lowering materializes log_softmax (B, V) in
fp32 — 3 HBM round trips.  These kernels stream the vocab once through VMEM
with an online (max, sum-exp, sum-l*exp) accumulator and emit only (B,)
entropy + exit flags:

    H = lse - (sum_i l_i e^{l_i - m}) / (sum_i e^{l_i - m}),  lse = m + log s

Normalization contract: H is divided by log(V) with V the *width of the
logits array* — exactly what the serving exit threshold compares against
(``core.calibration.normalized_entropy`` divides by ``log(logits.shape[-1])``
too, so padded-vocab configs, whose pad lanes carry -1e30 and contribute 0
to every accumulator, agree between the inline jnp path and the kernel).

``entropy_exit_argmax_pallas`` additionally carries an online (best value,
best index) pair so the branch's exit *token* comes out of the same single
pass — the serving fast path never materializes a separate softmax or
argmax over (B, V).  Tie-breaking matches ``jnp.argmax`` (first occurrence:
strictly-greater updates across tiles, first-index argmax within a tile),
so the emitted token is bitwise identical to the jnp path.

Grid: (K, B_tiles, V_tiles) — K stacked heads (1 for the single-head
entry points); the V dim is the sequential inner loop, carrying the
accumulators in VMEM scratch, finalizing on the last tile.  BlockSpec tiles
are (block_b, block_v) with block_v a multiple of 128 (lane width) and
block_b a multiple of 8 (sublane) — MXU is not involved; these are VPU
reduction kernels.  Per-row results leave as lane-dense (block_b, 128)
output tiles (the TPU lowering tiles the last two block dims in (8, 128)
units, which a rank-1 (block_b,) block cannot satisfy); the wrappers
slice lane 0 and the batch padding off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "entropy_exit_pallas",
    "entropy_exit_argmax_pallas",
    "entropy_exit_argmax_heads_pallas",
]

NEG_INF = -1e30
LANES = 128  # lane width of the per-row output tiles


def _kernel(
    logits_ref,  # (1, block_b, block_v) VMEM — one head's (B, V) tile
    thresh_ref,  # (K, 1) SMEM — per-head exit thresholds
    h_ref,  # (1, block_b, LANES) out
    exit_ref,  # (1, block_b, LANES) out
    *refs,  # [idx_ref (1, block_b, LANES) int32 out,] then scratch:
    #         m (running max), s (sum exp), u (sum l * exp)
    #         [, best value, best index] — each (block_b, 1)
    num_v_blocks: int,
    block_v: int,
    vocab: int,
    argmax: bool,
):
    if argmax:
        idx_ref, m_scr, s_scr, u_scr, bv_scr, bi_scr = refs
    else:
        m_scr, s_scr, u_scr = refs
    k = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        s_scr[...] = jnp.zeros_like(s_scr)
        u_scr[...] = jnp.zeros_like(u_scr)
        if argmax:
            bv_scr[...] = jnp.full_like(bv_scr, NEG_INF)
            bi_scr[...] = jnp.zeros_like(bi_scr)

    l = logits_ref[0].astype(jnp.float32)  # (bb, bv)
    m_old = m_scr[...]
    loc_v = l.max(axis=-1, keepdims=True)
    m_new = jnp.maximum(m_old, loc_v)
    corr = jnp.exp(m_old - m_new)
    e = jnp.exp(l - m_new)
    s_scr[...] = s_scr[...] * corr + e.sum(axis=-1, keepdims=True)
    u_scr[...] = u_scr[...] * corr + (l * e).sum(axis=-1, keepdims=True)
    m_scr[...] = m_new

    if argmax:
        # Online argmax: first occurrence within the tile (jnp.argmax), and
        # a strictly-greater update across tiles, reproduce jnp.argmax over
        # the full row exactly (comparisons are exact; no float error).
        loc_i = (
            jnp.argmax(l, axis=-1, keepdims=True).astype(jnp.int32)
            + j * block_v
        )
        upd = loc_v > bv_scr[...]
        bv_scr[...] = jnp.where(upd, loc_v, bv_scr[...])
        bi_scr[...] = jnp.where(upd, loc_i, bi_scr[...])

    @pl.when(j == num_v_blocks - 1)
    def _finalize():
        s = s_scr[...]
        lse = m_scr[...] + jnp.log(s)
        h = (lse - u_scr[...] / s) / np.log(vocab)  # (bb, 1)
        lanes = (h.shape[0], LANES)
        h_ref[0] = jnp.broadcast_to(h, lanes)
        exit_ref[0] = jnp.broadcast_to(h < thresh_ref[k, 0], lanes)
        if argmax:
            idx_ref[0] = jnp.broadcast_to(bi_scr[...], lanes)


def _entropy_exit_heads(
    logits: jax.Array,  # (K, B, V)
    thresholds: jax.Array | float,  # scalar or (K,)
    *,
    block_b: int,
    block_v: int,
    interpret: bool,
    argmax: bool,
) -> tuple[jax.Array, ...]:
    """Shared launcher: (entropy (K, B), exit (K, B)[, argmax (K, B)])."""
    k, b, v = logits.shape
    vocab = v
    # Pad: batch to block_b; vocab to block_v with -inf (exact no-ops in the
    # online accumulator: e^{-inf} = 0).
    pb = (-b) % block_b
    pv = (-v) % block_v
    if pb or pv:
        logits = jnp.pad(
            logits, ((0, 0), (0, pb), (0, pv)), constant_values=NEG_INF
        )
    _, bb, vv = logits.shape
    grid = (k, bb // block_b, vv // block_v)

    thresh = jnp.broadcast_to(
        jnp.asarray(thresholds, jnp.float32).reshape(-1, 1), (k, 1)
    )
    dtypes = [jnp.float32, jnp.bool_] + ([jnp.int32] if argmax else [])
    out_block = pl.BlockSpec((1, block_b, LANES), lambda k_, i, j: (k_, i, 0))
    scratch = [jnp.float32] * (4 if argmax else 3) + (
        [jnp.int32] if argmax else []
    )
    outs = pl.pallas_call(
        functools.partial(
            _kernel, num_v_blocks=grid[2], block_v=block_v, vocab=vocab,
            argmax=argmax,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, block_v), lambda k_, i, j: (k_, i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[out_block] * len(dtypes),
        out_shape=[
            jax.ShapeDtypeStruct((k, bb, LANES), dt) for dt in dtypes
        ],
        scratch_shapes=[pltpu.VMEM((block_b, 1), dt) for dt in scratch],
        interpret=interpret,
    )(logits, thresh)
    return tuple(o[:, :b, 0] for o in outs)


@functools.partial(jax.jit, static_argnames=("block_b", "block_v", "interpret"))
def entropy_exit_pallas(
    logits: jax.Array,  # (B, V)
    threshold: jax.Array | float,
    *,
    block_b: int = 8,
    block_v: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (normalized entropy (B,), exit flags (B,) bool)."""
    h, ex = _entropy_exit_heads(
        logits[None], threshold, block_b=block_b, block_v=block_v,
        interpret=interpret, argmax=False,
    )
    return h[0], ex[0]


@functools.partial(jax.jit, static_argnames=("block_b", "block_v", "interpret"))
def entropy_exit_argmax_pallas(
    logits: jax.Array,  # (B, V)
    threshold: jax.Array | float,
    *,
    block_b: int = 8,
    block_v: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused exit decision: one pass over (B, V) logits returns
    (normalized entropy (B,), exit flags (B,) bool, argmax token (B,) int32).
    """
    h, ex, idx = _entropy_exit_heads(
        logits[None], threshold, block_b=block_b, block_v=block_v,
        interpret=interpret, argmax=True,
    )
    return h[0], ex[0], idx[0]


@functools.partial(jax.jit, static_argnames=("block_b", "block_v", "interpret"))
def entropy_exit_argmax_heads_pallas(
    logits: jax.Array,  # (K, B, V) stacked branch-head logits
    thresholds: jax.Array | float,  # scalar or (K,) per-head thresholds
    *,
    block_b: int = 8,
    block_v: int = 2048,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-head fused exit decision: ONE launch over the batched-head
    (K, B, V) logits returns (normalized entropy (K, B), exit flags (K, B)
    bool, argmax token (K, B) int32).

    The grid's leading K dim runs heads as independent rows of the same
    streaming reduction, so each (k, i) row group carries its own
    accumulator through the sequential V loop; ``entropy_exit_argmax_pallas``
    is this launch at K = 1, so a per-head slice is bitwise identical to it
    on ``logits[k]``.  Per-head thresholds sit in SMEM ((K, 1), scalar
    broadcast to every head), so K heads with K different calibration
    points still fuse into the single launch.
    """
    return _entropy_exit_heads(
        logits, thresholds, block_b=block_b, block_v=block_v,
        interpret=interpret, argmax=True,
    )
