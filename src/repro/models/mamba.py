"""Mamba2 (SSD — state-space duality) block [arXiv:2405.21060].

Train/prefill uses the chunked SSD algorithm (matmul-rich, MXU-friendly —
this is the TPU adaptation of the paper's GPU scan: work is blocked into
(chunk x chunk) decay matmuls instead of a warp-level scan).  Decode uses
the O(1) recurrent step on the cached state.

Block structure (Mamba2):
    in_proj -> [z | xBC | dt]; causal depthwise conv over xBC;
    SSD(x * dt, A * dt, B, C) + D skip; RMSNorm(y * silu(z)); out_proj.

State cache for decode:
    conv: (B, W-1, conv_dim)  last inputs of the depthwise conv window
    ssm:  (B, H, P, N)        the SSM state
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models.layers import dense, dense_init, rmsnorm

__all__ = ["mamba_init", "mamba_apply", "init_ssm_state", "ssd_chunked", "ssd_step"]

Params = dict


def _dims(cfg: ModelConfig):
    inner = cfg.ssm_inner
    h = cfg.ssm_num_heads or inner // cfg.ssm_head_dim
    p = inner // h
    n = cfg.ssm_state_dim
    g = cfg.ssm_num_groups
    conv_dim = inner + 2 * g * n
    return inner, h, p, n, g, conv_dim


def mamba_init(key, cfg: ModelConfig) -> Params:
    inner, h, p, n, g, conv_dim = _dims(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    # A init in [1, 16) as in the reference implementation.
    a = jnp.exp(
        jax.random.uniform(ks[2], (h,), jnp.float32, np.log(1.0), np.log(16.0))
    )
    # The fused in_proj of the reference impl is split into three separately
    # shardable projections: z and xBC shard over the model axis; dt (H) is
    # tiny and stays replicated.
    return {
        "w_z": dense_init(ks[0], d, inner),
        "w_xbc": dense_init(ks[4], d, conv_dim),
        "w_dt": dense_init(ks[5], d, h),
        "conv_w": 0.1
        * jax.random.normal(ks[1], (cfg.ssm_conv_width, conv_dim), jnp.float32),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(a),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            ks[3], (h,), jnp.float32, np.log(1e-3), np.log(1e-1))))),
        "norm_scale": jnp.ones((inner,), jnp.float32),
        "out_proj": dense_init(jax.random.fold_in(key, 7), inner, d),
    }


def init_ssm_state(batch: int, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    inner, h, p, n, g, conv_dim = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, h, p, n), jnp.float32),
        "length": jnp.zeros((), jnp.int32),
    }


def _segsum(a: jax.Array) -> jax.Array:
    """(..., L) -> (..., L, L) with out[i, j] = sum_{k=j+1..i} a_k (i >= j),
    -inf above the diagonal.  exp() of this is the decay matrix."""
    l = a.shape[-1]
    c = jnp.cumsum(a, axis=-1)
    diff = c[..., :, None] - c[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,  # (B, L, H, P) already multiplied by dt
    a: jax.Array,  # (B, L, H)    log-decay per step (dt * A, negative)
    b_mat: jax.Array,  # (B, L, G, N)
    c_mat: jax.Array,  # (B, L, G, N)
    chunk: int,
    h0: jax.Array | None = None,  # (B, H, P, N) initial state
) -> tuple[jax.Array, jax.Array]:
    """Chunked SSD scan.  Returns (y (B,L,H,P), final state (B,H,P,N))."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    pad = (-l) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = x.shape[1] // chunk
    rep = h // g  # heads per B/C group

    xc = x.reshape(bsz, nc, chunk, h, p).astype(jnp.float32)
    ac = a.reshape(bsz, nc, chunk, h).astype(jnp.float32)
    bc = b_mat.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    cc = c_mat.reshape(bsz, nc, chunk, g, n).astype(jnp.float32)
    bh = jnp.repeat(bc, rep, axis=3)  # (B,nc,L,H,N) — broadcast group to heads
    ch = jnp.repeat(cc, rep, axis=3)

    # Intra-chunk (diagonal blocks): Y = (C B^T  *  decay) X
    lmat = jnp.exp(_segsum(ac.transpose(0, 1, 3, 2)))  # (B,nc,H,L,L)
    scores = jnp.einsum("bclhn,bcshn->bchls", ch, bh)
    y_diag = jnp.einsum("bchls,bcshp->bclhp", scores * lmat, xc)

    # Chunk-final states: sum_s exp(sum_{k>s} a) B_s x_s
    a_cum = jnp.cumsum(ac, axis=2)  # (B,nc,L,H)
    decay_to_end = jnp.exp(a_cum[:, :, -1:, :] - a_cum)  # (B,nc,L,H)
    states = jnp.einsum("bclhn,bclh,bclhp->bchpn", bh, decay_to_end, xc)

    # Inter-chunk recurrence over chunk states.
    chunk_decay = jnp.exp(a_cum[:, :, -1, :])  # (B,nc,H)

    def scan_fn(h_prev, inp):
        dec, st = inp  # (B,H), (B,H,P,N)
        h_new = h_prev * dec[..., None, None] + st
        return h_new, h_prev  # emit the state *entering* the chunk

    h_init = (
        h0.astype(jnp.float32)
        if h0 is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )
    h_last, h_enter = jax.lax.scan(
        scan_fn,
        h_init,
        (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)),
    )
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    # Off-diagonal contribution: C_t  decay(t)  h_enter
    in_decay = jnp.exp(a_cum)  # (B,nc,L,H)
    y_off = jnp.einsum("bclhn,bclh,bchpn->bclhp", ch, in_decay, h_enter)

    y = (y_diag + y_off).reshape(bsz, nc * chunk, h, p)[:, :l]
    return y, h_last


def ssd_step(
    h_state: jax.Array,  # (B, H, P, N)
    x: jax.Array,  # (B, H, P)  dt-scaled input
    a: jax.Array,  # (B, H)     dt * A (negative)
    b_vec: jax.Array,  # (B, G, N)
    c_vec: jax.Array,  # (B, G, N)
) -> tuple[jax.Array, jax.Array]:
    """One recurrent step: h' = e^a h + x (x) B ; y = h' . C."""
    bsz, h, p, n = h_state.shape
    g = b_vec.shape[1]
    rep = h // g
    bh = jnp.repeat(b_vec, rep, axis=1)  # (B,H,N)
    ch = jnp.repeat(c_vec, rep, axis=1)
    h_new = h_state * jnp.exp(a)[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", x.astype(jnp.float32), bh.astype(jnp.float32)
    )
    y = jnp.einsum("bhpn,bhn->bhp", h_new, ch.astype(jnp.float32))
    return y, h_new


def mamba_apply(
    params: Params,
    x: jax.Array,  # (B, S, d_model)
    cfg: ModelConfig,
    state: Params | None = None,
    *,
    return_state: bool = False,
    rows: jax.Array | None = None,  # (Bsub,) survivor rows (decode only)
    use_kernels: bool = False,  # decode: dispatch to the Pallas ssd_update
) -> tuple[jax.Array, Params | None]:
    """state=None: chunked scan over the sequence (train/prefill).
    state given: S must be 1 (decode) — O(1) recurrent update.

    ``rows``: x is a compacted survivor sub-batch; row ``i`` updates row
    ``rows[i]`` of the full-batch recurrent state (other rows untouched).
    With ``s > 1`` (continuous-batching admission) x is a block of newly
    admitted prompts: the scan starts from a *fresh zero* state — exactly
    a solo prefill — and the resulting conv window / SSM state come back
    as the admitted rows' block, which the layer stack writes into rows
    ``rows`` of the resident state in place.

    ``use_kernels`` (decode only): the recurrent step runs in the Pallas
    ssd_update kernel, which reads the survivor rows of the full-batch
    resident SSM state through a scalar-prefetched row map (no gather
    copy) — the tiny conv window still gathers in jnp."""
    inner, h, p, n, g, conv_dim = _dims(cfg)
    bsz, s, _ = x.shape
    dtype = x.dtype
    w = cfg.ssm_conv_width

    full_state = state
    prefill_rows = rows is not None and s > 1
    if rows is not None and not prefill_rows:
        assert state is not None, "rows needs a resident state"
        state = {
            "conv": state["conv"][rows],
            # The kernel path reads its rows of the resident state in
            # place (scalar prefetch) — no gather; jnp gathers here.
            "ssm": state["ssm"] if use_kernels else state["ssm"][rows],
            "length": state["length"],
        }
    elif prefill_rows:
        # Row-targeted prompt prefill: the admitted rows' recurrence starts
        # from a fresh zero state (solo-prefill semantics); the final state
        # scatters into the resident rows below.
        assert state is not None, "rows needs a resident state"
        state = None

    z = dense(params["w_z"], x, dtype)
    xbc = dense(params["w_xbc"], x, dtype)
    dt_raw = dense(params["w_dt"], x, dtype)  # (B,S,H)
    raw_xbc = xbc  # pre-conv inputs, needed to seed the decode conv window

    new_state = None
    if (state is not None or prefill_rows) and s > 1:
        # Prefill with state write-through.
        return_state = True
    if state is None or s > 1:
        # Causal depthwise conv via explicit left padding.
        xbc_pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
        windows = jnp.stack(
            [xbc_pad[:, i : i + s, :] for i in range(w)], axis=2
        )  # (B,S,W,C)
        xbc = jnp.einsum("bswc,wc->bsc", windows, params["conv_w"].astype(dtype))
        xbc = jax.nn.silu(xbc + params["conv_b"].astype(dtype))
    else:
        assert s == 1
        conv_in = jnp.concatenate([state["conv"].astype(dtype), xbc], axis=1)
        xbc = jnp.einsum(
            "bwc,wc->bc", conv_in, params["conv_w"].astype(dtype)
        )[:, None, :]
        xbc = jax.nn.silu(xbc + params["conv_b"].astype(dtype))
        new_conv = conv_in[:, 1:, :]

    xs = xbc[..., :inner].reshape(bsz, s, h, p)
    b_mat = xbc[..., inner : inner + g * n].reshape(bsz, s, g, n)
    c_mat = xbc[..., inner + g * n :].reshape(bsz, s, g, n)

    dt = jax.nn.softplus(
        dt_raw.astype(jnp.float32) + params["dt_bias"]
    )  # (B,S,H)
    a_neg = -jnp.exp(params["A_log"])  # (H,)
    x_dt = xs.astype(jnp.float32) * dt[..., None]
    a_dt = dt * a_neg  # (B,S,H)

    if state is None or s > 1:
        h0 = state["ssm"] if state is not None else None
        y, h_last = ssd_chunked(x_dt, a_dt, b_mat, c_mat, cfg.ssm_chunk, h0=h0)
        if return_state:
            # Raw (pre-conv) xBC inputs of the last W-1 positions seed the
            # decode-time conv window; left-pad if the sequence was shorter.
            conv_tail = jnp.pad(
                raw_xbc, ((0, 0), (max(0, (w - 1) - s), 0), (0, 0))
            )[:, -(w - 1) :, :]
            if prefill_rows:
                # The admitted rows' block, which the layer stack writes
                # into the resident state in place (models.transformer
                # .run_stack); the resident step counter is untouched.
                new_state = {
                    "conv": conv_tail.astype(full_state["conv"].dtype),
                    "ssm": h_last.astype(full_state["ssm"].dtype),
                    "length": full_state["length"],
                }
            else:
                prev = (
                    state["length"] if state is not None
                    else jnp.asarray(0, jnp.int32)
                )
                new_state = {
                    "conv": conv_tail,
                    "ssm": h_last,
                    "length": prev + s,
                }
    else:
        if use_kernels:
            # Pallas single-step SSD update; with ``rows`` the full
            # resident state goes in and the kernel DMAs only those rows.
            y1, h_new = kernel_ops.ssd_update(
                state["ssm"], x_dt[:, 0], a_dt[:, 0],
                b_mat[:, 0], c_mat[:, 0], rows,
            )
        else:
            y1, h_new = ssd_step(
                state["ssm"], x_dt[:, 0], a_dt[:, 0], b_mat[:, 0], c_mat[:, 0]
            )
        y = y1[:, None]
        if rows is None:
            new_state = {
                "conv": new_conv,
                "ssm": h_new,
                "length": state["length"] + 1,
            }
        else:  # scatter the sub-batch update back into the full-batch state
            # (mode="drop": exited padding rows carry an OOB sentinel)
            new_state = {
                "conv": full_state["conv"].at[rows].set(
                    new_conv.astype(full_state["conv"].dtype), mode="drop"
                ),
                "ssm": full_state["ssm"].at[rows].set(h_new, mode="drop"),
                "length": full_state["length"] + 1,
            }

    y = y + xs.astype(jnp.float32) * params["D"][:, None]
    y = y.reshape(bsz, s, inner).astype(dtype)
    y = rmsnorm({"scale": params["norm_scale"]}, y * jax.nn.silu(z))
    return dense(params["out_proj"], y, dtype), new_state
