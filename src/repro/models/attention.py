"""Attention: GQA (+qk_norm), MLA, sliding-window, KV caches.

Shapes follow the convention
    q: (B, Sq, K, G, D)   — K kv-head groups, G = num_heads // num_kv_heads
    k/v: (B, Sk, K, D)

Full-sequence softmax is computed *blockwise* (online softmax over KV
chunks, a jnp flash attention) so prefill_32k / train_4k never materialize
an (S, S) score tensor.  This function is also the reference oracle for the
Pallas flash_decode kernel (kernels/ref.py reuses it).

KV cache layout (dict):
    k, v: (B, C, K, D)    — C slots (max_len for full, window for ring)
    pos:  (B, C) int32    — absolute position stored in each slot, -1 empty
    length: () int32      — tokens decoded so far (write index = length % C)

k and v may hold head_dim zero-padded to the TPU's 128 lanes (D' > D,
:func:`repro.models.model.pad_kv_lanes`), so that the TPU's default layout
of the cache is the one the decode kernel and the in-place slot writes
read; attention pads the new K/V and q to D' and slices its output back.

``pos`` is per *sequence*: in the survivor-compacted tier runtime an
early-exited sequence skips the downstream tiers for that step, so its
slot stays -1 (a hole) while survivors' slots go valid — attention then
masks holes per row instead of attending stale/zero K/V.  Decode entry
points accept ``rows`` (a device-resident survivor index vector): the
sub-batch reads/writes only those rows of the full-batch cache, which is
what lets compaction happen without any host round trip.

MLA (DeepSeek-V3) caches the 512-d latent + decoupled-RoPE key instead:
    ckv: (B, C, kv_rank), k_rope: (B, C, rope_dim), pos, length
and uses the absorbed-matrix formulation at decode time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models.layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init
from repro.sharding.ctx import constrain

__all__ = [
    "attn_init",
    "attn_apply",
    "mla_init",
    "mla_apply",
    "init_kv_cache",
    "init_mla_cache",
    "flash_attention",
    "NEG_INF",
]

NEG_INF = -1e30
Params = dict


# =============================================================== mask helpers
def _band_mask(q_pos: jax.Array, k_pos: jax.Array, window: int) -> jax.Array:
    """(..., Sq, Sk) bool: causal, optionally banded to `window`, k slot valid.

    ``k_pos`` may carry leading batch dims — (B, Sk) per-sequence slot
    validity — which broadcast against ``q_pos``'s (Sq,) to (B, Sq, Sk).
    """
    m = q_pos[..., :, None] >= k_pos[..., None, :]
    if window > 0:
        m &= q_pos[..., :, None] - k_pos[..., None, :] < window
    m &= k_pos[..., None, :] >= 0  # empty cache slots carry pos == -1
    return m


# ======================================================== flash attention (jnp)
def flash_attention(
    q: jax.Array,  # (B, Sq, K, G, D)
    k: jax.Array,  # (B, Sk, K, D)
    v: jax.Array,  # (B, Sk, K, D)
    q_pos: jax.Array,  # (Sq,)
    k_pos: jax.Array,  # (Sk,) shared, or (B, Sk) per-sequence slot validity
    *,
    window: int = 0,
    block_k: int = 1024,
    block_q: int = 1024,
    scale: float | None = None,
) -> jax.Array:
    """Online-softmax attention, scanning KV in chunks of ``block_k`` and
    (for long queries) scanning Q in chunks of ``block_q``.

    Memory is O(block_q * block_k) per score tile instead of O(Sq * Sk).
    fp32 accumulators.
    """
    b, sq, kh, g, d = q.shape
    if sq > block_q:
        # Outer sequential loop over query chunks (lax.map = memory-bound).
        pad_q = (-sq) % block_q
        qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(q_pos, (0, pad_q), constant_values=-(10**9))
        nq = qp.shape[1] // block_q
        qb = qp.reshape(b, nq, block_q, kh, g, d).transpose(1, 0, 2, 3, 4, 5)
        pb = qpos.reshape(nq, block_q)

        def one(args):
            qi, pi = args
            return flash_attention(
                qi, k, v, pi, k_pos,
                window=window, block_k=block_k, block_q=block_q, scale=scale,
            )

        out = jax.lax.map(one, (qb, pb))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * block_q, kh, g, -1)
        return out[:, :sq]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    return _flash_vjp(q, k, v, q_pos, k_pos, window, block_k, scale)


def _flash_blocks(k, v, k_pos, block_k):
    b = k.shape[0]
    sk = k.shape[1]
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(
            k_pos,
            ((0, 0), (0, pad)) if k_pos.ndim == 2 else (0, pad),
            constant_values=-1,
        )
    nblk = k.shape[1] // block_k
    kb = k.reshape(b, nblk, block_k, k.shape[2], k.shape[3]).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block_k, v.shape[2], v.shape[3]).transpose(1, 0, 2, 3, 4)
    if k_pos.ndim == 2:  # per-sequence slot validity: (B, Sk) -> (nblk, B, bk)
        pb = k_pos.reshape(b, nblk, block_k).transpose(1, 0, 2)
    else:
        pb = k_pos.reshape(nblk, block_k)
    return kb, vb, pb, pad


def _expand_mask(mask: jax.Array) -> jax.Array:
    """Broadcast a band mask to score rank (B, Sq, K, G, bk): the mask is
    (Sq, bk) for shared slot positions, (B, Sq, bk) for per-sequence ones."""
    if mask.ndim == 2:
        return mask[None, :, None, None, :]
    return mask[:, :, None, None, :]


def _flash_fwd_core(q, k, v, q_pos, k_pos, window, block_k, scale):
    """Returns (out, m, l) — softmax stats kept for the recompute backward."""
    b, sq, kh, g, d = q.shape
    dv = v.shape[-1]  # may differ from d (MLA: RoPE-extended keys)
    kb, vb, pb, _ = _flash_blocks(k, v, k_pos, block_k)
    qf = (q * scale).astype(jnp.float32)

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, posb = blk
        s = jnp.einsum("bqkgd,bskd->bqkgs", qf, kblk.astype(jnp.float32))
        mask = _band_mask(q_pos, posb, window)  # (Sq, bk) or (B, Sq, bk)
        s = jnp.where(_expand_mask(mask), s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqkgs,bskd->bqkgd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, sq, kh, g), jnp.float32)
    a0 = jnp.zeros((b, sq, kh, g, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype), m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_vjp(q, k, v, q_pos, k_pos, window, block_k, scale):
    return _flash_fwd_core(q, k, v, q_pos, k_pos, window, block_k, scale)[0]


def _flash_vjp_fwd(q, k, v, q_pos, k_pos, window, block_k, scale):
    out, m, l = _flash_fwd_core(q, k, v, q_pos, k_pos, window, block_k, scale)
    return out, (q, k, v, q_pos, k_pos, out, m, l)


def _flash_vjp_bwd(window, block_k, scale, res, dout):
    """Flash backward: recompute p blockwise; nothing O(Sq x Sk) is ever
    materialized and — crucially — nothing per-block is *saved* (the naive
    autodiff of the forward scan keeps every block's p matrix alive, which
    is what blew the train_4k dry-run memory; EXPERIMENTS §Perf)."""
    q, k, v, q_pos, k_pos, out, m, l = res
    b, sq, kh, g, d = q.shape
    dv = v.shape[-1]
    kb, vb, pb, pad = _flash_blocks(k, v, k_pos, block_k)

    qf = (q * scale).astype(jnp.float32)
    do = dout.astype(jnp.float32)
    lsafe = jnp.maximum(l, 1e-30)
    # delta = rowwise sum(dout * out) (the softmax Jacobian diagonal term).
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1)  # (B,Sq,K,G)

    def step(dq, blk):
        kblk, vblk, posb = blk
        s = jnp.einsum("bqkgd,bskd->bqkgs", qf, kblk.astype(jnp.float32))
        mask = _band_mask(q_pos, posb, window)
        s = jnp.where(_expand_mask(mask), s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / lsafe[..., None]  # (B,Sq,K,G,bk)
        dvb = jnp.einsum("bqkgs,bqkgd->bskd", p, do)  # (B,bk,K,Dv)
        dp = jnp.einsum("bqkgd,bskd->bqkgs", do, vblk.astype(jnp.float32))
        ds = p * (dp - delta[..., None])  # (B,Sq,K,G,bk)
        dq = dq + jnp.einsum("bqkgs,bskd->bqkgd", ds, kblk.astype(jnp.float32))
        dkb = jnp.einsum("bqkgs,bqkgd->bskd", ds, qf)
        return dq, (dkb, dvb)

    dq0 = jnp.zeros((b, sq, kh, g, d), jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, (kb, vb, pb))
    # (nblk, B, bk, K, D) -> (B, Sk(+pad), K, D), drop padding.
    sk_p = dkb.shape[0] * dkb.shape[2]
    dk = dkb.transpose(1, 0, 2, 3, 4).reshape(b, sk_p, kh, d)
    dvf = dvb.transpose(1, 0, 2, 3, 4).reshape(b, sk_p, kh, dv)
    if pad:
        dk = dk[:, :-pad]
        dvf = dvf[:, :-pad]
    # s = scale * q.k: dk used qf (scale already folded in); dq needs it.
    dq = dq * scale
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dvf.astype(v.dtype),
        None,  # q_pos (int)
        None,  # k_pos (int)
    )


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# =================================================================== KV cache
def init_kv_cache(
    batch: int, capacity: int, num_kv_heads: int, head_dim: int, dtype=jnp.bfloat16
) -> Params:
    return {
        "k": jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
        "length": jnp.zeros((), jnp.int32),
    }


def _cache_write(
    cache: Params,
    k_new: jax.Array,
    v_new: jax.Array,
    rows: jax.Array | None = None,
    positions: jax.Array | None = None,
) -> Params:
    """Write one decode step (Sq == 1) into the (ring) cache.

    ``rows=None`` writes every batch row (the masked full-batch path).
    ``rows`` (Bsub,) writes only those rows of the full-batch cache — the
    survivor-compacted path — leaving excluded rows' slots untouched (their
    per-sequence ``pos`` stays -1, so attention masks the hole).

    ``positions`` with a batch dim ((B|Bsub, 1), the continuous-batching
    runtime) makes the write *per sequence*: row i writes its own ring slot
    ``positions[i] % C`` and records its own absolute position — requests
    admitted at different times coexist in one cache.  A 1-D ``positions``
    (or None) keeps the historical lock-step write at ``length % C``.
    """
    c = cache["k"].shape[1]
    if positions is not None and positions.ndim == 2:
        pos_vec = positions[:, 0].astype(jnp.int32)
        idx = pos_vec % c
        br = (
            rows
            if rows is not None
            else jnp.arange(k_new.shape[0], dtype=jnp.int32)
        )
        k = cache["k"].at[br, idx].set(k_new[:, 0], mode="drop")
        v = cache["v"].at[br, idx].set(v_new[:, 0], mode="drop")
        pos = cache["pos"].at[br, idx].set(pos_vec, mode="drop")
        return {"k": k, "v": v, "pos": pos, "length": cache["length"] + 1}
    idx = cache["length"] % c
    if rows is None:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, idx, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, idx, axis=1)
        b = cache["pos"].shape[0]
        pos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.broadcast_to(cache["length"], (b, 1)), idx, axis=1
        )
    else:
        # mode="drop": a padding row that already exited carries an
        # out-of-bounds sentinel — its write is skipped, leaving a hole.
        k = cache["k"].at[rows, idx].set(k_new[:, 0], mode="drop")
        v = cache["v"].at[rows, idx].set(v_new[:, 0], mode="drop")
        pos = cache["pos"].at[rows, idx].set(cache["length"], mode="drop")
    return {"k": k, "v": v, "pos": pos, "length": cache["length"] + 1}


def _cache_prefill(cache: Params, k: jax.Array, v: jax.Array) -> Params:
    """Write a whole prompt (S tokens at positions 0..S-1) into the cache,
    honoring the ring invariant slot = position % capacity so subsequent
    decode steps continue seamlessly."""
    s = k.shape[1]
    b = k.shape[0]
    cap = cache["k"].shape[1]
    if s >= cap:
        tail_k, tail_v = k[:, s - cap :], v[:, s - cap :]
        tail_pos = jnp.broadcast_to(
            jnp.arange(s - cap, s, dtype=jnp.int32), (b, cap)
        )
        shift = s % cap
        new_k = jnp.roll(tail_k, shift, axis=1)
        new_v = jnp.roll(tail_v, shift, axis=1)
        new_pos = jnp.roll(tail_pos, shift, axis=1)
    else:
        new_k = jnp.concatenate([k, cache["k"][:, s:]], axis=1)
        new_v = jnp.concatenate([v, cache["v"][:, s:]], axis=1)
        new_pos = jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)),
             cache["pos"][:, s:]],
            axis=1,
        )
    return {
        "k": new_k.astype(cache["k"].dtype),
        "v": new_v.astype(cache["v"].dtype),
        "pos": new_pos,
        "length": jnp.asarray(s, jnp.int32),
    }


def _cache_prefill_rows(cache: Params, k: jax.Array, v: jax.Array) -> Params:
    """Row-targeted prompt prefill: the (n, C, ...) row block that the
    admitted rows of the resident cache take — each row exactly a
    *freshly initialized* cache that just prefilled its prompt, slots past
    the prompt empty (pos = -1), so no stale K/V from the row's previous
    occupant can ever look valid.  The layer stack writes the block into
    its rows in place (:func:`repro.models.transformer.write_row_blocks`;
    OOB sentinel rows of admission-group padding drop); the resident cache
    is neither read nor rewritten whole, and its step counter is kept."""
    fresh = _cache_prefill(
        init_kv_cache(k.shape[0], cache["k"].shape[1], k.shape[2], k.shape[3],
                      cache["k"].dtype),
        k, v,
    )
    return {**fresh, "length": cache["length"]}


def _pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis of ``x`` to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


# ============================================================== standard GQA
def attn_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], d, cfg.q_dim),
        "wk": dense_init(ks[1], d, cfg.kv_dim),
        "wv": dense_init(ks[2], d, cfg.kv_dim),
        "wo": dense_init(ks[3], cfg.q_dim, d),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim)
        p["k_norm"] = rmsnorm_init(cfg.head_dim)
    return p


def _project(params: Params, name: str, x: jax.Array, dtype) -> jax.Array:
    """``x`` through projection ``name``: read from its ``[out, in]``
    transpose ``name_t`` where the served tree holds one
    (:func:`repro.models.model.served_params`), else ``dense``."""
    w_t = params.get(name + "_t")
    if w_t is None:
        return dense(params[name], x, dtype)
    return jnp.einsum("...i,oi->...o", x.astype(dtype), w_t.astype(dtype))


def attn_apply(
    params: Params,
    x: jax.Array,  # (B, S, d_model)
    cfg: ModelConfig,
    positions: jax.Array,  # (S,) absolute positions of x's tokens
    cache: Params | None = None,
    *,
    use_rope: bool = True,
    window: int | None = None,
    kv_override: tuple[jax.Array, jax.Array] | None = None,  # cross-attention
    rows: jax.Array | None = None,  # (Bsub,) survivor rows of the full cache
    use_kernels: bool = False,  # decode: dispatch to the Pallas flash_decode
) -> tuple[jax.Array, Params | None]:
    """One attention op.  cache=None -> full (training/prefill) attention;
    cache given -> single-step decode against the cache.  ``kv_override``
    supplies precomputed encoder K/V for cross-attention (no cache write).

    ``rows``: x is a compacted survivor sub-batch (decode) or a block of
    newly admitted prompts (prefill, s > 1); row ``i`` of x reads/writes
    row ``rows[i]`` of the full-batch cache.

    ``positions`` may be per sequence at decode time — (B, 1) instead of
    the shared (1,) — so requests admitted at different steps decode at
    their own absolute positions (continuous batching): RoPE, the banded
    mask and the ring-slot write all follow the row's own position.

    ``use_kernels`` (decode only): the single-token attention runs in the
    Pallas flash_decode kernel, which streams the survivor rows straight
    out of the full-batch resident cache through a scalar-prefetched row
    map (zero gather copies) instead of the jnp ``cache[...][rows]``
    gather + flash_attention.  GQA head grouping, per-sequence ``pos``
    slot validity and sliding windows all ride through; prefill/train
    paths ignore the flag."""
    b, s, _ = x.shape
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // kh
    window = cfg.sliding_window if window is None else window
    dtype = x.dtype

    q = _project(params, "wq", x, dtype).reshape(b, s, kh * g, hd)
    if kv_override is None:
        k = _project(params, "wk", x, dtype).reshape(b, s, kh, hd)
        v = _project(params, "wv", x, dtype).reshape(b, s, kh, hd)
    else:
        k, v = kv_override

    if cfg.use_qk_norm:
        q = rmsnorm(params["q_norm"], q)
        if kv_override is None:
            k = rmsnorm(params["k_norm"], k)
    if use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_override is None:
            k = apply_rope(k, positions, cfg.rope_theta)

    qg = q.reshape(b, s, kh, g, hd)
    if cache is not None:
        # Lane-padded storage (module docstring): K/V written at the
        # cache's head_dim, the extra lanes zero.
        dp = cache["k"].shape[-1]
        kc, vc = (k, v) if dp == hd else (_pad_lanes(k, dp), _pad_lanes(v, dp))

    if cache is not None and s > 1:
        # -------- prefill with cache write-through: full-sequence attention
        # plus populating the (ring) cache for subsequent decode steps.
        # ``rows`` (continuous-batching admission) returns the admitted
        # rows' block for the layer stack to write in place; the
        # prompt's attention itself never reads the cache, so it is
        # identical to a fresh solo prefill by construction.
        new_cache = (
            _cache_prefill(cache, kc, vc) if rows is None
            else _cache_prefill_rows(cache, kc, vc)
        )
        out = flash_attention(
            qg, k, v, positions, positions, window=window, block_k=min(1024, s)
        )
    elif cache is not None:
        # -------- decode: write this step, attend over the whole cache.
        cache = _cache_write(cache, kc, vc, rows, positions)
        # A lane-padded cache: q gains zero lanes (the scores are those of
        # the true head_dim, which sets the scale) and the output loses
        # them.
        scale = None if dp == hd else 1.0 / np.sqrt(hd)
        if dp != hd:
            qg = _pad_lanes(qg, dp)
        if cfg.decode_qhd_shard:
            # Run attention in the cache's head-dim-sharded layout: scores
            # become partial sums (all-reduce) instead of resharding the
            # cache or q every layer (§Perf).
            qg = constrain(qg, "b...v")
        if use_kernels:
            # Pallas flash_decode: the survivor row map is a scalar-prefetch
            # operand, so the kernel DMAs only rows ``rows`` of the resident
            # cache — the compacted sub-batch attends in place, no gather.
            # Per-sequence query positions ((B, 1), continuous batching)
            # ride the same scalar-prefetch path as a (B,) vector.
            q_pos = positions[:, 0] if positions.ndim == 2 else positions[0]
            out = kernel_ops.flash_decode(
                qg.reshape(b, kh * g, dp),
                cache["k"], cache["v"], cache["pos"], q_pos,
                rows, window=window, scale=scale,
            ).reshape(b, 1, kh, g, dp)
        else:
            if rows is None:
                ck, cv, cp = cache["k"], cache["v"], cache["pos"]
            else:
                # jnp compacted path: gather the survivor rows and hope XLA
                # fuses the gather into the attention (the kernel path
                # above is the copy-free version of this).
                ck, cv, cp = (
                    cache["k"][rows], cache["v"][rows], cache["pos"][rows]
                )
            out = flash_attention(
                qg, ck, cv, positions, cp,
                window=window, block_k=min(1024, ck.shape[1]), scale=scale,
            )
        out = out[..., :hd]
        new_cache = cache
    elif kv_override is not None:
        # -------- cross-attention: bidirectional over encoder frames.
        enc_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        out = flash_attention(
            qg, k, v, jnp.full_like(positions, k.shape[1]), enc_pos,
            window=0, block_k=min(1024, k.shape[1]),
        )
        new_cache = None
    else:
        # -------- training / prefill: causal (optionally banded).
        out = flash_attention(
            qg, k, v, positions, positions, window=window,
            block_k=min(1024, s),
        )
        new_cache = None

    out = out.reshape(b, s, kh * g * hd)
    return dense(params["wo"], out, dtype), new_cache


# ==================================================================== MLA
def mla_init(key, cfg: ModelConfig) -> Params:
    """DeepSeek-V3 Multi-head Latent Attention [arXiv:2412.19437].

    Queries go through a low-rank bottleneck (q_rank); keys/values through a
    shared latent (kv_rank) plus a small decoupled-RoPE subspace shared by
    all heads.  Only (latent, k_rope) is cached.
    """
    ks = jax.random.split(key, 8)
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    r_q, r_kv, r_rope = cfg.mla_q_rank, cfg.mla_kv_rank, cfg.mla_rope_dim
    return {
        "wq_a": dense_init(ks[0], d, r_q),
        "q_norm": rmsnorm_init(r_q),
        "wq_b": dense_init(ks[1], r_q, h * (hd + r_rope)),
        "wkv_a": dense_init(ks[2], d, r_kv + r_rope),
        "kv_norm": rmsnorm_init(r_kv),
        "wk_b": dense_init(ks[3], r_kv, h * hd),  # latent -> per-head key
        "wv_b": dense_init(ks[4], r_kv, h * hd),  # latent -> per-head value
        "wo": dense_init(ks[5], h * hd, d),
    }


def init_mla_cache(batch: int, capacity: int, cfg: ModelConfig, dtype=jnp.bfloat16):
    return {
        "ckv": jnp.zeros((batch, capacity, cfg.mla_kv_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, cfg.mla_rope_dim), dtype),
        "pos": jnp.full((batch, capacity), -1, jnp.int32),
        "length": jnp.zeros((), jnp.int32),
    }


def _mla_prefill_cache(
    cache: Params, ckv: jax.Array, k_rope: jax.Array
) -> Params:
    """Prefill write-through of the MLA latent cache (ring invariant)."""
    b, s, _ = ckv.shape
    cap = cache["ckv"].shape[1]
    if s >= cap:
        shift = s % cap
        return {
            "ckv": jnp.roll(ckv[:, s - cap :], shift, axis=1).astype(
                cache["ckv"].dtype
            ),
            "k_rope": jnp.roll(k_rope[:, s - cap :], shift, axis=1).astype(
                cache["k_rope"].dtype
            ),
            "pos": jnp.roll(
                jnp.broadcast_to(
                    jnp.arange(s - cap, s, dtype=jnp.int32), (b, cap)
                ),
                shift,
                axis=1,
            ),
            "length": jnp.asarray(s, jnp.int32),
        }
    return {
        "ckv": jnp.concatenate([ckv, cache["ckv"][:, s:]], 1).astype(
            cache["ckv"].dtype
        ),
        "k_rope": jnp.concatenate([k_rope, cache["k_rope"][:, s:]], 1).astype(
            cache["k_rope"].dtype
        ),
        "pos": jnp.concatenate(
            [jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s)),
             cache["pos"][:, s:]],
            1,
        ),
        "length": jnp.asarray(s, jnp.int32),
    }


def _mla_prefill_rows(
    cache: Params, ckv: jax.Array, k_rope: jax.Array, cfg
) -> Params:
    """Row-targeted MLA prompt prefill (see :func:`_cache_prefill_rows`):
    the admitted rows' block, each row exactly a fresh solo prefill —
    tail slots empty — with the resident step counter kept."""
    fresh = _mla_prefill_cache(
        init_mla_cache(ckv.shape[0], cache["ckv"].shape[1], cfg,
                       cache["ckv"].dtype),
        ckv, k_rope,
    )
    return {**fresh, "length": cache["length"]}


def _mla_qkr(params, x, cfg, positions):
    """Shared query path: returns (q_nope, q_rope) with RoPE applied."""
    b, s, _ = x.shape
    h, hd, r_rope = cfg.num_heads, cfg.head_dim, cfg.mla_rope_dim
    dtype = x.dtype
    qa = rmsnorm(params["q_norm"], dense(params["wq_a"], x, dtype))
    qb = dense(params["wq_b"], qa, dtype).reshape(b, s, h, hd + r_rope)
    q_nope, q_rope = qb[..., :hd], qb[..., hd:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_apply(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    cache: Params | None = None,
    rows: jax.Array | None = None,  # (Bsub,) cache rows: decode survivors,
    #                                 or admission targets at prefill (s > 1)
) -> tuple[jax.Array, Params | None]:
    b, s, d = x.shape
    h, hd, r_rope = cfg.num_heads, cfg.head_dim, cfg.mla_rope_dim
    r_kv = cfg.mla_kv_rank
    dtype = x.dtype
    scale = 1.0 / np.sqrt(hd + r_rope)

    q_nope, q_rope = _mla_qkr(params, x, cfg, positions)

    kv = dense(params["wkv_a"], x, dtype)  # (B, S, r_kv + r_rope)
    ckv = rmsnorm(params["kv_norm"], kv[..., :r_kv])
    k_rope = apply_rope(kv[..., None, r_kv:], positions, cfg.rope_theta)[:, :, 0]

    if cache is None or s > 1:
        # Naive (train/prefill) form: expand latent to per-head K/V.
        k_nope = dense(params["wk_b"], ckv, dtype).reshape(b, s, h, hd)
        v = dense(params["wv_b"], ckv, dtype).reshape(b, s, h, hd)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, r_rope))],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = flash_attention(
            q_full.reshape(b, s, h, 1, hd + r_rope),
            k_full,
            v,
            positions,
            positions,
            window=cfg.sliding_window,
            block_k=min(1024, s),
            scale=scale,
        ).reshape(b, s, h, hd)
        new_cache = None
        if cache is not None:
            # Prefill write-through of the latent cache (ring invariant);
            # ``rows``: the admitted rows' block (see _cache_prefill_rows).
            new_cache = (
                _mla_prefill_cache(cache, ckv, k_rope) if rows is None
                else _mla_prefill_rows(cache, ckv, k_rope, cfg)
            )
    else:
        # Absorbed decode: score and read directly in the latent space.
        assert s == 1
        c = cache["ckv"].shape[1]
        per_seq = positions.ndim == 2  # continuous batching: (B|Bsub, 1)
        if per_seq:
            pos_vec = positions[:, 0].astype(jnp.int32)
            idx = pos_vec % c
            pos_val = pos_vec
        else:
            idx = cache["length"] % c
            pos_val = cache["length"]
        if rows is None and not per_seq:
            cache = {
                "ckv": jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, idx, 1),
                "k_rope": jax.lax.dynamic_update_slice_in_dim(
                    cache["k_rope"], k_rope, idx, 1
                ),
                "pos": jax.lax.dynamic_update_slice_in_dim(
                    cache["pos"],
                    jnp.broadcast_to(cache["length"], (cache["pos"].shape[0], 1)),
                    idx,
                    axis=1,
                ),
                "length": cache["length"] + 1,
            }
            ckv_r, rope_r, pos_r = cache["ckv"], cache["k_rope"], cache["pos"]
        else:
            br = rows if rows is not None else jnp.arange(b, dtype=jnp.int32)
            cache = {
                "ckv": cache["ckv"].at[br, idx].set(ckv[:, 0], mode="drop"),
                "k_rope": cache["k_rope"].at[br, idx].set(
                    k_rope[:, 0], mode="drop"
                ),
                "pos": cache["pos"].at[br, idx].set(pos_val, mode="drop"),
                "length": cache["length"] + 1,
            }
            if rows is None:
                ckv_r, rope_r, pos_r = (
                    cache["ckv"], cache["k_rope"], cache["pos"]
                )
            else:
                ckv_r = cache["ckv"][rows]
                rope_r = cache["k_rope"][rows]
                pos_r = cache["pos"][rows]
        wk_b = params["wk_b"].astype(dtype).reshape(r_kv, h, hd)
        # Absorb W_uk into q: (B,1,H,hd) x (r,H,hd) -> (B,1,H,r)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, wk_b)
        s_lat = jnp.einsum(
            "bshr,bcr->bshc", q_lat.astype(jnp.float32),
            ckv_r.astype(jnp.float32),
        )
        s_rope = jnp.einsum(
            "bshr,bcr->bshc", q_rope.astype(jnp.float32),
            rope_r.astype(jnp.float32),
        )
        logits = (s_lat + s_rope) * scale  # (B,1,H,C)
        mask = _band_mask(positions, pos_r, cfg.sliding_window)  # (B, 1, C)
        logits = jnp.where(mask[:, :, None, :], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bshc,bcr->bshr", p, ckv_r.astype(jnp.float32))
        wv_b = params["wv_b"].astype(dtype).reshape(r_kv, h, hd)
        out = jnp.einsum("bshr,rhd->bshd", o_lat.astype(dtype), wv_b)
        new_cache = cache

    out = out.reshape(b, s, h * hd)
    return dense(params["wo"], out, dtype), new_cache
