"""Block assembly and layer-stack execution.

A *block* is one residual trunk layer: a mixer (GQA / MLA / Mamba2 / none)
plus an MLP (dense / MoE / none), each behind a pre-norm.  Blocks of the
same *kind* are stacked along a leading layer axis and executed with
``jax.lax.scan`` so 61–80-layer models compile as one program regardless of
depth (critical for the 512-device dry-run).

The trunk is segmented at *stop points* (side-branch positions, hybrid
shared-attention sites, the partition layer): each segment is its own scan
over a static slice of the stacked params.  This is exactly the structure
the paper's partitioner needs — the edge runs a prefix of segments, ships
the residual stream, and the cloud runs the rest.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models.layers import mlp_apply, mlp_init, norm_apply, norm_init
from repro.sharding.ctx import constrain

__all__ = [
    "BlockKind",
    "block_init",
    "block_apply",
    "stack_init",
    "stack_slice",
    "run_stack",
    "init_block_cache",
    "write_row_blocks",
]

Params = dict


@dataclasses.dataclass(frozen=True)
class BlockKind:
    mixer: str  # "gqa" | "mla" | "mamba" | "none"
    mlp: str  # "dense" | "moe" | "none"
    cross_attention: bool = False  # whisper decoder
    causal: bool = True  # False for encoder blocks
    use_rope: bool = True


def block_init(key, cfg: ModelConfig, kind: BlockKind) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: Params = {}
    if kind.mixer == "gqa":
        p["norm1"] = norm_init(cfg.norm_type, d)
        p["attn"] = attn_mod.attn_init(ks[0], cfg)
    elif kind.mixer == "mla":
        p["norm1"] = norm_init(cfg.norm_type, d)
        p["attn"] = attn_mod.mla_init(ks[0], cfg)
    elif kind.mixer == "mamba":
        p["norm1"] = norm_init(cfg.norm_type, d)
        p["mamba"] = mamba_mod.mamba_init(ks[0], cfg)
    if kind.cross_attention:
        p["norm_x"] = norm_init(cfg.norm_type, d)
        p["xattn"] = attn_mod.attn_init(ks[1], cfg)
    if kind.mlp == "dense":
        p["norm2"] = norm_init(cfg.norm_type, d)
        p["mlp"] = mlp_init(ks[2], d, cfg.d_ff, cfg.mlp_type)
    elif kind.mlp == "moe":
        p["norm2"] = norm_init(cfg.norm_type, d)
        p["moe"] = moe_mod.moe_init(ks[2], cfg)
    return p


def init_block_cache(
    batch: int, capacity: int, cfg: ModelConfig, kind: BlockKind, dtype=jnp.bfloat16
):
    """Decode-time cache for one block (None if the block is stateless)."""
    cache: dict[str, Any] = {}
    if kind.mixer == "gqa":
        cache["self"] = attn_mod.init_kv_cache(
            batch, capacity, cfg.num_kv_heads, cfg.head_dim, dtype
        )
    elif kind.mixer == "mla":
        cache["self"] = attn_mod.init_mla_cache(batch, capacity, cfg, dtype)
    elif kind.mixer == "mamba":
        cache["self"] = mamba_mod.init_ssm_state(batch, cfg)
    return cache


def block_apply(
    params: Params,
    h: jax.Array,
    cfg: ModelConfig,
    kind: BlockKind,
    positions: jax.Array,
    cache: Params | None = None,
    cross_kv: tuple[jax.Array, jax.Array] | None = None,
    moe_dispatch: str = "einsum",
    rows: jax.Array | None = None,
    use_kernels: bool = False,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Returns (h, new_cache, aux_loss).

    ``rows`` (decode only): h is a compacted survivor sub-batch; stateful
    ops read/write rows ``rows`` of the full-batch cache/state.

    ``use_kernels`` (decode only): GQA attention and Mamba2 recurrent
    updates dispatch to the Pallas kernels (flash_decode / ssd_update);
    MLA's absorbed-latent decode and cross-attention stay on the jnp
    path (no kernel variant)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict[str, Any] = {}
    window = cfg.sliding_window

    if kind.mixer in ("gqa", "mla"):
        hn = norm_apply(cfg.norm_type, params["norm1"], h)
        sa_cache = cache.get("self") if cache else None
        if kind.mixer == "gqa":
            y, c = attn_mod.attn_apply(
                params["attn"], hn, cfg, positions, sa_cache,
                use_rope=kind.use_rope,
                window=window if kind.causal else 0,
                rows=rows if sa_cache is not None else None,
                use_kernels=use_kernels and sa_cache is not None,
            )
        else:
            y, c = attn_mod.mla_apply(
                params["attn"], hn, cfg, positions, sa_cache,
                rows=rows if sa_cache is not None else None,
            )
        h = h + y
        if c is not None:
            new_cache["self"] = c
    elif kind.mixer == "mamba":
        hn = norm_apply(cfg.norm_type, params["norm1"], h)
        y, c = mamba_mod.mamba_apply(
            params["mamba"], hn, cfg,
            state=cache.get("self") if cache else None,
            rows=rows if cache else None,
            use_kernels=use_kernels and cache is not None,
        )
        h = h + y
        if c is not None:
            new_cache["self"] = c

    if kind.cross_attention and cross_kv is not None:
        if rows is not None:
            # Compacted sub-batch: cross K/V rows follow the survivors.
            cross_kv = (cross_kv[0][rows], cross_kv[1][rows])
        hn = norm_apply(cfg.norm_type, params["norm_x"], h)
        y, _ = attn_mod.attn_apply(
            params["xattn"], hn, cfg, positions, None,
            use_rope=False, window=0, kv_override=cross_kv,
        )
        h = h + y

    if kind.mlp == "dense":
        hn = norm_apply(cfg.norm_type, params["norm2"], h)
        h = h + mlp_apply(params["mlp"], hn, cfg.mlp_type)
    elif kind.mlp == "moe":
        hn = norm_apply(cfg.norm_type, params["norm2"], h)
        y, aux_moe = moe_mod.moe_apply(
            params["moe"], hn, cfg, dispatch=moe_dispatch
        )
        h = h + y
        aux = aux + aux_moe

    return h, (new_cache if new_cache else None), aux


def write_row_blocks(
    full: Params, blocks: Params, layer, rows: jax.Array
) -> Params:
    """Write a row-targeted prompt prefill's blocks into the stacked cache
    ``full`` in place: ``blocks`` leaves are (Lb, n, ...) — layers
    ``[layer, layer + Lb)`` of the admitted rows — and row ``j`` of them
    lands in row ``rows[j]`` (an OOB sentinel row of admission-group
    padding writes back what it reads: a no-op).  The resident cache is
    never read or rewritten whole, and its step counters are kept."""

    def write(path, a, b):
        if path[-1].key == "length":
            return a
        b = b.astype(a.dtype)
        zeros = (0,) * (a.ndim - 2)
        for j in range(b.shape[1]):
            ok = (rows[j] >= 0) & (rows[j] < a.shape[1])
            start = (layer, jnp.where(ok, rows[j], 0), *zeros)
            old = jax.lax.dynamic_slice(a, start, (b.shape[0], 1, *a.shape[2:]))
            a = jax.lax.dynamic_update_slice(
                a, jnp.where(ok, b[:, j : j + 1], old), start
            )
        return a

    return jax.tree_util.tree_map_with_path(write, full, blocks)


def stack_init(key, cfg: ModelConfig, n_layers: int, kind: BlockKind) -> Params:
    """Stacked params: every leaf gains a leading (n_layers,) axis."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: block_init(k, cfg, kind))(keys)


def stack_slice(stacked: Params, lo: int, hi: int) -> Params:
    return jax.tree_util.tree_map(lambda a: a[lo:hi], stacked)


def run_stack(
    stacked_params: Params,
    h: jax.Array,
    cfg: ModelConfig,
    kind: BlockKind,
    positions: jax.Array,
    caches: Params | None = None,  # stacked along layer axis
    cross_kv: tuple[jax.Array, jax.Array] | None = None,  # stacked (L, B, S, K, D)
    *,
    layers: tuple[int, int] | None = None,  # [lo, hi) of the stack; None = all
    remat: bool = False,
    moe_dispatch: str = "einsum",
    rows: jax.Array | None = None,
    use_kernels: bool = False,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Scan blocks ``layers`` of a stack over the residual stream.

    Returns (h, new stacked caches, summed aux loss).  ``rows`` threads the
    survivor-compaction row map into every stateful block (decode only);
    ``use_kernels`` dispatches each stateful block's decode math to the
    Pallas kernels.
    """

    if caches is None:
        # Stateless (training / cache-free prefill): params (+cross KV) are
        # scan inputs; nothing is carried but the residual stream.
        if layers is not None:
            stacked_params = stack_slice(stacked_params, *layers)
            if cross_kv is not None:
                cross_kv = stack_slice(cross_kv, *layers)

        def body(carry, xs):
            h = carry
            lparams, lcross = xs
            h, _, aux = block_apply(
                lparams, h, cfg, kind, positions, None, lcross,
                moe_dispatch=moe_dispatch,
            )
            if cfg.seq_shard_activations:
                # The remat-saved per-layer carry is seq-sharded over the
                # model axis (sequence parallelism); compute re-gathers.
                h = constrain(h, "bv.")
            return h, aux

        if remat:
            body = jax.checkpoint(body, prevent_cse=False)
        # `None` is an empty pytree: scan broadcasts it per step unchanged.
        h, auxes = jax.lax.scan(body, h, (stacked_params, cross_kv))
        return h, None, jnp.sum(auxes)

    # Stateful (decode / cache-writing prefill): the FULL stacked cache is a
    # loop carry updated in place at the layer index — this lets XLA alias
    # the cache buffers instead of double-buffering a scan ys output (which
    # costs ~2x cache HBM at 32k contexts; see EXPERIMENTS §Perf).  Params,
    # cache and cross K/V are indexed per layer inside the body rather than
    # sliced to ``layers`` up front: a static slice of the stack is
    # materialized as a copy (16 of phi3-mini's 32 layers: 3.5 GB of
    # weights plus the cache slice), which a 16 GB chip cannot spare.
    lo, hi = layers or (0, jax.tree_util.tree_leaves(stacked_params)[0].shape[0])

    def pick(i):
        return lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    if rows is not None and h.shape[1] > 1:
        # Row-targeted prompt prefill (admission): the prompt's attention
        # reads no cache, so the scan carries only the residual stream and
        # emits each layer's block of the admitted rows, which lands in
        # the resident cache in place after the scan — the cache is never
        # carried through the loop, read, or rewritten whole.
        def body_rows(h, i):
            # Shapes and dtypes only: a layer's block never reads them.
            like = jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape[1:], a.dtype), caches
            )
            h, block, aux = block_apply(
                jax.tree_util.tree_map(pick(i), stacked_params), h, cfg,
                kind, positions, like, moe_dispatch=moe_dispatch, rows=rows,
            )
            return h, (block, aux)

        h, (blocks, auxes) = jax.lax.scan(
            body_rows, h, jnp.arange(lo, hi, dtype=jnp.int32)
        )
        return h, write_row_blocks(caches, blocks, lo, rows), jnp.sum(auxes)

    def body_cache(carry, i):
        h, cache_full = carry
        lcross = (
            None if cross_kv is None
            else jax.tree_util.tree_map(pick(i), cross_kv)
        )
        h, new_cache, aux = block_apply(
            jax.tree_util.tree_map(pick(i), stacked_params), h, cfg, kind,
            positions, jax.tree_util.tree_map(pick(i), cache_full), lcross,
            moe_dispatch=moe_dispatch, rows=rows, use_kernels=use_kernels,
        )
        cache_full = jax.tree_util.tree_map(
            lambda full, one: jax.lax.dynamic_update_index_in_dim(
                full, one.astype(full.dtype), i, 0
            ),
            cache_full, new_cache,
        )
        return (h, cache_full), aux

    if remat:
        body_cache = jax.checkpoint(body_cache, prevent_cse=False)
    (h, new_caches), auxes = jax.lax.scan(
        body_cache, (h, caches), jnp.arange(lo, hi, dtype=jnp.int32)
    )
    return h, new_caches, jnp.sum(auxes)
