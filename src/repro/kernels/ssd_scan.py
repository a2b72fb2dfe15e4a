"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the Mamba2 GPU kernel (DESIGN.md: no warp-level scan on
TPU — instead the chunk-local work is cast as (chunk x chunk) decay matmuls
that run on the MXU, and the only sequential dependency is the tiny
(P x N) state carried across chunk tiles in VMEM scratch):

    per chunk:  Y_diag = ((C B^T) o exp(segsum(a))) X
                Y_off  = exp(cumsum(a)) * (C h_prev^T)
                h_new  = exp(sum a) h_prev + X^T (exp(sum a - cumsum a) o B)

Grid: (B, H, n_chunks), chunk dim sequential (carries h in scratch).
The wrapper swaps inputs to head-major (B, H, L, .) so a block is one
head's (chunk, P) / (chunk, N) tile: the TPU lowering tiles the last two
block dims in (8, 128) units or whole dims, which a (chunk, 1, P) slice of
the (B, L, H, P) layout cannot satisfy.  Log decays ride as a lane-dense
(1, chunk) row per head; their inclusive cumsum is a lower-triangular
contraction (Mosaic has no scan primitive).

``ssd_update_pallas`` is the decode-time sibling: one recurrent step
``h' = e^a h + x (x) B ; y = h' . C`` per (batch, head).  Like the
flash_decode kernel it takes a scalar-prefetched survivor row map so a
compacted sub-batch reads its rows of the full-batch resident SSM state
copy-free; the updated rows come back dense and the model scatters them in
place (``.at[rows].set(mode="drop")``).  A grid step owns one batch row
and every head: blocks span whole (H, P[, N]) trailing dims, and the
per-head outer product and readout run as small MXU contractions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas", "ssd_update_pallas"]


def _kernel(
    x_ref,  # (1, 1, L, P)
    a_ref,  # (1, 1, 1, L)
    b_ref,  # (1, 1, L, N)
    c_ref,  # (1, 1, L, N)
    y_ref,  # (1, 1, L, P) out
    hout_ref,  # (1, 1, P, N) out (final state)
    h_scr,  # (P, N) scratch fp32
    *,
    num_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    a = a_ref[0, 0].astype(jnp.float32)  # (1, L)
    bm = b_ref[0, 0].astype(jnp.float32)  # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)  # (L, N)
    l = x.shape[0]

    row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    causal = row >= col
    # Inclusive cumsum of a, as a column and as a row.
    a_cum_col = _dot(causal.astype(jnp.float32), a, 1, 1)  # (L, 1)
    a_cum_row = _dot(a, causal.astype(jnp.float32), 1, 1)  # (1, L)
    a_tot = a.sum(axis=-1, keepdims=True)  # (1, 1)
    # decay[i, j] = exp(sum_{k=j+1..i} a_k) for i >= j.
    decay = jnp.where(causal, jnp.exp(a_cum_col - a_cum_row), 0.0)

    scores = _dot(cm, bm, 1, 1)  # (L, L)
    y_diag = _dot(scores * decay, x, 1, 0)  # (L, P)

    h_prev = h_scr[...]
    y_off = jnp.exp(a_cum_col) * _dot(cm, h_prev, 1, 1)  # (L, N)x(P, N)^T

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # State update.
    to_end = jnp.exp(a_tot - a_cum_col)  # (L, 1)
    states = _dot(x, bm * to_end, 0, 0)  # (P, N)
    h_scr[...] = h_prev * jnp.exp(a_tot) + states

    @pl.when(ci == num_chunks - 1)
    def _final():
        hout_ref[0, 0] = h_scr[...].astype(hout_ref.dtype)


def _dot(lhs, rhs, lc: int, rc: int):
    """fp32 2-D contraction of ``lhs`` dim ``lc`` with ``rhs`` dim ``rc``."""
    return jax.lax.dot_general(
        lhs, rhs, (((lc,), (rc,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jax.Array,  # (B, L, H, P)  dt-scaled inputs
    a: jax.Array,  # (B, L, H)     log decays
    b_mat: jax.Array,  # (B, L, H, N)
    c_mat: jax.Array,  # (B, L, H, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,L,H,P), final state (B,H,P,N))."""
    bsz, l, h, p = x.shape
    n = b_mat.shape[-1]
    pad = (-l) % chunk
    if pad:
        # a=0 (decay 1) and x=0 keep the padded tail a state no-op.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ll = x.shape[1]
    nc = ll // chunk
    head_major = lambda t: jnp.swapaxes(t, 1, 2)  # (B, L, H, .) -> (B, H, L, .)

    y, h_final = pl.pallas_call(
        functools.partial(_kernel, num_chunks=nc),
        grid=(bsz, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, c_: (i, j, c_, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda i, j, c_: (i, j, 0, c_)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j, c_: (i, j, c_, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda i, j, c_: (i, j, c_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda i, j, c_: (i, j, c_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, c_: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, ll, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(
        head_major(x), jnp.swapaxes(a, 1, 2)[:, :, None, :],
        head_major(b_mat), head_major(c_mat),
    )
    return head_major(y)[:, :l], h_final


def _update_kernel(
    rows_ref,  # (B,) SMEM scalar-prefetch: sub-batch row -> state row
    h_ref,  # (1, H, P, N)   resident state row
    x_ref,  # (1, H, P)
    ea_ref,  # (1, 1, H)  exp(dt * A) per head
    b_ref,  # (1, G, N)
    c_ref,  # (1, G, N)
    y_ref,  # (1, H, P) out
    hout_ref,  # (1, H, P, N) out (updated state row, dense order)
    *,
    rep: int,  # heads per B/C group
):
    for hh in range(h_ref.shape[1]):
        g = hh // rep
        h_prev = h_ref[0, hh].astype(jnp.float32)  # (P, N)
        x = x_ref[0, hh:hh + 1].astype(jnp.float32)  # (1, P)
        ea = ea_ref[0, :, hh:hh + 1].astype(jnp.float32)  # (1, 1)
        bv = b_ref[0, g:g + 1].astype(jnp.float32)  # (1, N)
        cv = c_ref[0, g:g + 1].astype(jnp.float32)  # (1, N)

        h_new = h_prev * ea + _dot(x, bv, 0, 0)  # (P, N)
        y_ref[0, hh:hh + 1] = _dot(cv, h_new, 1, 1).astype(y_ref.dtype)
        hout_ref[0, hh] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_update_pallas(
    h_state: jax.Array,  # (Bc, H, P, N) full-batch resident SSM state
    x: jax.Array,  # (B, H, P)  dt-scaled input, B <= Bc
    a: jax.Array,  # (B, H)     dt * A (negative)
    b_vec: jax.Array,  # (B, G, N)
    c_vec: jax.Array,  # (B, G, N)
    rows: jax.Array | None = None,  # (B,) int32 sub-batch row -> state row
    *,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One recurrent SSD decode step against the resident state.

    Returns (y (B, H, P) fp32, new state rows (B, H, P, N) fp32) in the
    *sub-batch* order — the caller scatters the state rows back.  ``rows``
    is a scalar-prefetch operand: the block index maps DMA only the
    survivor rows of the full state, no gather copy.
    """
    b, h, p = x.shape
    g, n = b_vec.shape[1], b_vec.shape[2]
    if rows is None:
        rows = jnp.arange(b, dtype=jnp.int32)
    rows = rows.astype(jnp.int32)
    ea = jnp.exp(a.astype(jnp.float32))[:, None, :]  # (B, 1, H)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, p, n), lambda i, rows_: (rows_[i], 0, 0, 0)),
            pl.BlockSpec((1, h, p), lambda i, rows_: (i, 0, 0)),
            pl.BlockSpec((1, 1, h), lambda i, rows_: (i, 0, 0)),
            pl.BlockSpec((1, g, n), lambda i, rows_: (i, 0, 0)),
            pl.BlockSpec((1, g, n), lambda i, rows_: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, p), lambda i, rows_: (i, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda i, rows_: (i, 0, 0, 0)),
        ],
    )
    y, h_new = pl.pallas_call(
        functools.partial(_update_kernel, rep=h // g),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(rows, h_state, x, ea, b_vec, c_vec)
    return y, h_new
