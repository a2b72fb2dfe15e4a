"""Single-token GQA decode attention Pallas TPU kernel (flash-decode).

The decode hot loop attends one query against a (ring) KV cache of up to
512k slots.  This kernel streams the cache through VMEM in ``block_c``-slot
tiles with the online-softmax accumulator, fusing slot-validity and
sliding-window masking (the paper's long-context serving path).

Layout: q (B, H, D) grouped as (B, K, G, D); cache (Bc, C, K, D) where the
cache batch Bc may exceed the query batch B.  Slot validity ``k_pos`` is
per sequence, (Bc, C) (a shared (C,) vector is broadcast by the wrapper):
the survivor-compacted tier runtime leaves holes (-1) in rows that skipped
a step downstream of their exit.

Survivor compaction: ``rows`` (B,) maps query row i -> cache row rows[i].
It is a *scalar-prefetch* operand (pltpu.PrefetchScalarGridSpec), so the
block index maps read it before the body runs and DMA only the survivor
rows of the full-batch cache — a dense sub-batch attends in-place against
the resident cache with zero gather copies.

Grid: (B, C_tiles) — the cache dim is the sequential inner loop; each
batch row owns its accumulator scratch.  A K/V tile is (block_c, K, D):
every KV head of ``block_c`` slots.  The TPU lowering tiles the last two
block dims, which must be multiples of (8, 128) or whole array dims, so the
tile spans the full (K, D) pair of the (Bc, C, K, D) cache — the cache
is read in its resident layout, never reshaped or copied (any D, e.g.
phi3's 96).  In VMEM the tile is swapped to head-major (K, block_c, D) and
both score and value contractions run as K-batched MXU matmuls; slot
positions ride as a (Bc, 1, C) array so a tile's positions are one
lane-dense (1, block_c) row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_decode_pallas"]

NEG_INF = -1e30
# fp32 MXU contractions (bf16 cache tiles are upcast first).
_HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(
    rows_ref,  # (B,) SMEM scalar-prefetch: query row -> cache row
    qpos_ref,  # (B,) SMEM scalar-prefetch: per-query-row position
    q_ref,  # (1, K, G, D)
    k_ref,  # (1, block_c, K, D)
    v_ref,  # (1, block_c, K, D)
    pos_ref,  # (1, 1, block_c)  int32 per-sequence slot positions
    o_ref,  # (1, K, G, D) out
    m_scr,  # (K, G, 1) scratch
    l_scr,  # (K, G, 1)
    acc_scr,  # (K, G, D)
    *,
    num_c_blocks: int,
    window: int,
    scale: float,
):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # (K, G, D)
    k = jnp.swapaxes(k_ref[0].astype(jnp.float32), 0, 1)  # (K, bc, D)
    v = jnp.swapaxes(v_ref[0].astype(jnp.float32), 0, 1)  # (K, bc, D)
    kpos = pos_ref[0]  # (1, bc)
    qpos = qpos_ref[pl.program_id(0)]

    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (K, G, bc)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window > 0:
        valid &= qpos - kpos < window
    s = jnp.where(valid[None], s, NEG_INF)

    m_old = m_scr[...]
    m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32, precision=_HIGHEST,
    )  # (K, G, D)
    m_scr[...] = m_new

    @pl.when(c == num_c_blocks - 1)
    def _finalize():
        o_ref[0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_c", "scale", "interpret")
)
def flash_decode_pallas(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (Bc, C, K, D)
    v: jax.Array,  # (Bc, C, K, D)
    k_pos: jax.Array,  # (C,) shared or (Bc, C) per-sequence, int32
    q_pos: jax.Array,  # () shared or (B,) per-query-row, int32
    rows: jax.Array | None = None,  # (B,) int32 query row -> cache row
    *,
    window: int = 0,
    block_c: int = 128,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """``block_c`` must be a multiple of 128 (the lane width the slot
    positions are tiled on).  ``scale`` defaults to ``1 / sqrt(D)``; a
    head_dim zero-padded to the lanes passes its true head_dim's."""
    b, h, d = q.shape
    bc, c, kh, _ = k.shape
    g = h // kh
    scale = 1.0 / np.sqrt(d) if scale is None else scale

    if k_pos.ndim == 1:
        k_pos = jnp.broadcast_to(k_pos, (bc, c))
    if rows is None:
        rows = jnp.arange(b, dtype=jnp.int32)

    pc = (-c) % block_c
    if pc:
        k = jnp.pad(k, ((0, 0), (0, pc), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pc), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pc)), constant_values=-1)
    cc = k.shape[1]
    nc = cc // block_c
    k_pos = k_pos.reshape(bc, 1, cc)

    qg = q.reshape(b, kh, g, d)
    # Per-row query positions (continuous batching) ride the same
    # scalar-prefetch path; a shared scalar broadcasts to every row.
    qpos = jnp.broadcast_to(q_pos.astype(jnp.int32), (b,))
    rows = rows.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, kh, g, d), lambda i, c_, rows_, qp_: (i, 0, 0, 0)),
            pl.BlockSpec(
                (1, block_c, kh, d),
                lambda i, c_, rows_, qp_: (rows_[i], c_, 0, 0),
            ),
            pl.BlockSpec(
                (1, block_c, kh, d),
                lambda i, c_, rows_, qp_: (rows_[i], c_, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_c), lambda i, c_, rows_, qp_: (rows_[i], 0, c_)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, kh, g, d), lambda i, c_, rows_, qp_: (i, 0, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, 1), jnp.float32),
            pltpu.VMEM((kh, g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, num_c_blocks=nc, window=window, scale=scale
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
    )(rows, qpos, qg, k, v, k_pos)
    return out.reshape(b, h, d)
