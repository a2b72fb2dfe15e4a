"""BranchyModel: backbone + side branches, with train / prefill / decode
entry points for every assigned architecture family.

Trunk layers are numbered 1..L like the paper's ``v_i``; side branches sit
after the layers in ``cfg.branch_layers`` and are evaluated by
``run_trunk(collect=...)`` which segments the layer scan at those points.
Branch heads are *tied* to the main LM head (per-branch norm + shared
unembedding) — early-exit LMs at 100k+ vocabs cannot afford a private
unembedding per exit; DESIGN.md records this adaptation.

Caches pytree (decode):
    {"blocks": stacked, "dense_blocks": stacked (MoE first-k),
     "shared_attn": stacked per-site (hybrid),
     "cross_kv": (L, B, S_enc, K, D) (whisper, set at encode time),
     "length": ()}

Caches are always *full-batch resident*: the survivor-compacted tier
runtime passes a ``rows`` index vector down ``run_trunk`` so a dense
sub-batch reads/writes only its rows in place — the C-sized KV buffers
never move at a tier hop.  KV slot validity (``pos``) is per sequence, so
a row that skipped a step downstream leaves a hole that later attention
masks (see models/attention.py).
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.calibration import normalized_entropy
from repro.kernels.ops import resolve_use_kernels
from repro.sharding.ctx import constrain
from repro.models.layers import (
    dense,
    embed,
    embedding_init,
    norm_apply,
    norm_init,
    sinusoidal_embed,
    sinusoidal_positions,
)
from repro.models.transformer import (
    BlockKind,
    block_apply,
    block_init,
    init_block_cache,
    run_stack,
    stack_init,
    stack_slice,
    write_row_blocks,
)

__all__ = [
    "branch_logits_stacked",
    "init_params",
    "init_caches",
    "pad_kv_lanes",
    "served_params",
    "snapshot_decode_writes",
    "restore_decode_writes",
    "run_trunk",
    "forward_train",
    "prefill",
    "decode_step",
    "embed_decode",
    "trunk_layout",
    "softmax_xent",
    "compute_dtype",
]

Params = dict


def compute_dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------- layout
def trunk_layout(cfg: ModelConfig) -> list[tuple[str, BlockKind, int]]:
    """Ordered stacks composing the trunk: (param key, kind, n_layers)."""
    if cfg.arch_type in ("dense", "vlm"):
        return [("blocks", BlockKind("gqa", "dense"), cfg.num_layers)]
    if cfg.arch_type == "moe":
        mixer = "mla" if cfg.use_mla else "gqa"
        out = []
        if cfg.first_k_dense:
            out.append(("dense_blocks", BlockKind(mixer, "dense"), cfg.first_k_dense))
        out.append(("blocks", BlockKind(mixer, "moe"), cfg.num_layers - cfg.first_k_dense))
        return out
    if cfg.arch_type == "ssm":
        return [("blocks", BlockKind("mamba", "none"), cfg.num_layers)]
    if cfg.arch_type == "hybrid":
        return [("blocks", BlockKind("mamba", "none"), cfg.num_layers)]
    if cfg.arch_type == "audio":
        # decoder trunk only; the encoder is a separate stack in params.
        return [
            (
                "blocks",
                BlockKind("gqa", "dense", cross_attention=True, use_rope=False),
                cfg.num_layers,
            )
        ]
    raise ValueError(cfg.arch_type)


def hybrid_sites(cfg: ModelConfig) -> tuple[int, ...]:
    """Trunk layers after which the shared attention block runs (Zamba2)."""
    if cfg.arch_type != "hybrid" or not cfg.attn_every:
        return ()
    return tuple(
        i for i in range(cfg.attn_every, cfg.num_layers + 1, cfg.attn_every)
    )


_SHARED_ATTN_KIND = BlockKind("gqa", "dense")
_ENC_KIND = BlockKind("gqa", "dense", causal=False, use_rope=False)


# ---------------------------------------------------------------- init
def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 10)
    d = cfg.d_model
    p: Params = {"embed": embedding_init(ks[0], cfg.padded_vocab_size, d)}
    for i, (name, kind, n) in enumerate(trunk_layout(cfg)):
        p[name] = stack_init(ks[1 + i], cfg, n, kind)
    p["final_norm"] = norm_init(cfg.norm_type, d)
    if not cfg.tie_embeddings:
        p["lm_head"] = embedding_init(ks[4], cfg.padded_vocab_size, d).T
    if cfg.branch_layers:
        # Tied branch heads: per-branch norm only (see module docstring).
        p["branches"] = jax.vmap(lambda k: norm_init(cfg.norm_type, d))(
            jax.random.split(ks[5], len(cfg.branch_layers))
        ) if cfg.norm_type == "rmsnorm" else [
            norm_init(cfg.norm_type, d) for _ in cfg.branch_layers
        ]
    if cfg.arch_type == "hybrid":
        p["shared_attn"] = block_init(ks[6], cfg, _SHARED_ATTN_KIND)
    if cfg.arch_type == "audio":
        p["encoder"] = stack_init(ks[7], cfg, cfg.num_encoder_layers, _ENC_KIND)
        p["enc_norm"] = norm_init(cfg.norm_type, d)
    if cfg.use_mtp:
        p["mtp_block"] = block_init(ks[8], cfg, BlockKind(
            "mla" if cfg.use_mla else "gqa", "dense"))
        p["mtp_norm"] = norm_init(cfg.norm_type, d)
    if cfg.param_dtype == "bfloat16":
        # >100B configs: params live in bf16 (optimizer keeps factored fp32
        # statistics; see DESIGN.md Sec. 5 memory budget).
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
    return p


def cache_capacity(cfg: ModelConfig, seq_len: int) -> int:
    """KV slots needed to decode against a context of ``seq_len``."""
    if cfg.sliding_window:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_caches(
    cfg: ModelConfig, batch: int, seq_len: int, dtype=None
) -> Params:
    dtype = dtype or compute_dtype(cfg)
    cap = cache_capacity(cfg, seq_len)
    caches: Params = {"length": jnp.zeros((), jnp.int32)}
    for name, kind, n in trunk_layout(cfg):
        one = init_block_cache(batch, cap, cfg, kind, dtype)
        caches[name] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n, *a.shape)), one
        )
    if cfg.arch_type == "hybrid":
        sites = hybrid_sites(cfg)
        one = init_block_cache(batch, cap, cfg, _SHARED_ATTN_KIND, dtype)
        caches["shared_attn"] = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (len(sites), *a.shape)), one
        )
    if cfg.arch_type == "audio":
        kh, hd = cfg.num_kv_heads, cfg.head_dim
        caches["cross_kv"] = (
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq_len, kh, hd), dtype),
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq_len, kh, hd), dtype),
        )
    return caches


#: The TPU's lane width: the minor dimension of its (8, 128) memory tiles.
LANES = 128


def pad_kv_lanes(caches: Params) -> Params:
    """``caches`` with each attention cache's K/V head_dim zero-padded to
    a multiple of :data:`LANES` (unchanged where it already is one).

    The TPU lays a cache out by its shape: with a head_dim of 96 (phi3)
    its default layout puts the slots in the lanes, while the decode
    kernel and the in-place slot writes read head_dim there, so every
    program that takes the cache would re-lay it out whole on entry and
    exit.  Padded, the default layout is the one they read (the same
    bytes the TPU's tiling pads 96 to anyway).  Attention pads what it
    writes and slices what it reads (:mod:`repro.models.attention`)."""

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        if {"k", "v", "pos"} <= tree.keys():
            d = tree["k"].shape[-1]
            pad = [(0, 0)] * (tree["k"].ndim - 1) + [(0, -d % LANES)]
            return {**tree, "k": jnp.pad(tree["k"], pad),
                    "v": jnp.pad(tree["v"], pad)}
        return {k: walk(v) for k, v in tree.items()}

    return walk(caches)


#: Attention projections that :func:`served_params` stores transposed.
TRANSPOSED_PROJECTIONS = ("wq", "wk", "wv")


def _swap_io(w):
    """``w`` with its last two axes swapped (an abstract leaf, as a compile
    for a described chip hands over, keeps its sharding)."""
    if isinstance(w, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct((*w.shape[:-2], w.shape[-1], w.shape[-2]),
                                    w.dtype, sharding=w.sharding)
    return jnp.swapaxes(w, -1, -2)


def served_params(cfg: ModelConfig, params: Params) -> Params:
    """The tree a server computes from: where ``cfg.head_dim`` is not a
    multiple of :data:`LANES`, each trunk stack's ``attn/wq``, ``wk`` and
    ``wv`` (``[L, in, out]``) become ``wq_t``, ``wk_t`` and ``wv_t``
    (``[L, out, in]``); otherwise ``params`` itself.

    With a head width off the lanes (phi3: 96) XLA computes the
    projections from the weight transposed rather than re-lay out the
    activation for the head reshape, and hoists that transpose of the
    whole stack to the program's entry: every decode step would copy the
    three stacks.  Stored transposed, their default layout is the one the
    dots read.  The new names keep a canonical tree from being read as a
    served one.  Every dict of the result is new (the leaves not
    transposed are shared); a served tree comes back with its leaves."""
    if cfg.head_dim % LANES == 0:
        return params
    out = jax.tree_util.tree_map(lambda a: a, params)
    for stack, kind, _ in trunk_layout(cfg):
        if kind.mixer != "gqa":
            continue
        attn = out[stack]["attn"]
        for name in TRANSPOSED_PROJECTIONS:
            if name in attn:
                attn[name + "_t"] = _swap_io(attn.pop(name))
    return out


#: Leaves of a slotted cache (one that carries per-slot ``pos``) laid out
#: (L, B, C, ...): a decode step writes one slot of each row per layer.
_SLOT_LEAVES = ("k", "v", "pos", "ckv", "k_rope")


def snapshot_decode_writes(caches: Params, pos: jax.Array) -> Params:
    """Everything one decode step at ``pos`` can write, read before it
    writes: slot ``pos % C`` of every row in every layer of each slotted
    cache (slot ``length % C`` per layer for a shared () ``pos``), and
    whole every other leaf a step rewrites (``length``, the recurrent
    ``conv`` / ``ssm`` state).  Encoder cross K/V is never written and is
    left out.  About 4 MB for phi3-mini's 32 layers x 8 slots, against a
    2 GB cache; :func:`restore_decode_writes` puts it back."""

    def walk(tree):
        out = {}
        for key, val in tree.items():
            if key == "cross_kv":
                continue
            if isinstance(val, dict) and "pos" in val:
                n, b, c = val["pos"].shape
                slot = (
                    jnp.broadcast_to(val["length"][:, None] % c, (n, b))
                    if pos.ndim == 0
                    else jnp.broadcast_to(pos.astype(jnp.int32) % c, (n, b))
                )
                out[key] = {"slot": slot}
                for k, a in val.items():
                    if k in _SLOT_LEAVES:
                        idx = slot.reshape(n, b, 1, *([1] * (a.ndim - 3)))
                        out[key][k] = jnp.take_along_axis(a, idx, axis=2)
                    else:
                        out[key][k] = a
            elif isinstance(val, dict):
                out[key] = walk(val)
            else:
                out[key] = val
        return out

    return walk(caches)


def restore_decode_writes(caches: Params, snap: Params) -> Params:
    """``caches`` as they were when ``snap`` was taken from them
    (:func:`snapshot_decode_writes`), bitwise, provided only the one
    decode step ``snap`` was taken before has written to them since."""

    def walk(tree, saved):
        out = {}
        for key, val in tree.items():
            if key not in saved:
                out[key] = val
            elif isinstance(val, dict) and "slot" in saved[key]:
                slot = saved[key]["slot"]
                n, b = slot.shape
                li = jnp.arange(n)[:, None]
                bi = jnp.arange(b)[None, :]
                out[key] = {
                    k: (a.at[li, bi, slot].set(saved[key][k][:, :, 0])
                        if k in _SLOT_LEAVES else saved[key][k])
                    for k, a in val.items()
                }
            elif isinstance(val, dict):
                out[key] = walk(val, saved[key])
            else:
                out[key] = saved[key]
        return out

    return walk(caches, snap)


# ---------------------------------------------------------------- trunk
def _segments(breaks: list[int], lo: int, hi: int) -> list[tuple[int, int]]:
    pts = sorted({lo, hi, *[b for b in breaks if lo < b < hi]})
    return list(zip(pts[:-1], pts[1:]))


def run_trunk(
    params: Params,
    h: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    caches: Params | None = None,
    *,
    layer_range: tuple[int, int] | None = None,  # absolute, 0-based [lo, hi)
    collect: tuple[int, ...] = (),  # 1-based "after layer i" collection points
    remat: bool = False,
    moe_dispatch: str = "einsum",
    rows: jax.Array | None = None,  # (Bsub,) cache rows: compacted decode
    #                                 survivors, or admission-prefill targets
    use_kernels: bool = False,  # decode: Pallas flash_decode / ssd_update
) -> tuple[jax.Array, Params | None, jax.Array, dict[int, jax.Array]]:
    """Run trunk layers [lo, hi), segmenting at collect points and (hybrid)
    shared-attention sites.  Returns (h, new_caches, aux, {layer: hidden}).

    ``rows``: h is a dense survivor sub-batch; every stateful block reads
    and writes only those rows of the full-batch caches (per-sequence slot
    validity in the KV caches masks the skipped rows' holes later).

    ``use_kernels`` (decode only): every stateful block's single-token
    math dispatches to the Pallas kernel suite — flash_decode streams
    ``rows`` out of the resident KV cache, ssd_update does the same for
    the SSM state."""
    layout = trunk_layout(cfg)
    total = sum(n for _, _, n in layout)
    lo, hi = layer_range or (0, total)
    sites = hybrid_sites(cfg)
    breaks = [*collect, *sites]
    # Stack boundaries are natural breaks too.
    acc = 0
    stack_bounds = {}
    for name, kind, n in layout:
        stack_bounds[name] = (acc, acc + n)
        acc += n
        breaks.append(acc)

    new_caches = dict(caches) if caches is not None else None
    aux = jnp.zeros((), jnp.float32)
    collected: dict[int, jax.Array] = {}

    for seg_lo, seg_hi in _segments(breaks, lo, hi):
        # Locate the stack containing this segment (segments never straddle
        # stacks because stack bounds are break points).
        for name, kind, n in layout:
            s_lo, s_hi = stack_bounds[name]
            if s_lo <= seg_lo < s_hi:
                cross = (
                    caches["cross_kv"]
                    if kind.cross_attention and caches is not None else None
                )
                h, nc, a = run_stack(
                    params[name], h, cfg, kind, positions,
                    new_caches[name] if caches is not None else None, cross,
                    layers=(seg_lo - s_lo, seg_hi - s_lo),
                    remat=remat, moe_dispatch=moe_dispatch, rows=rows,
                    use_kernels=use_kernels,
                )
                h = constrain(h, "b..")
                aux = aux + a
                if nc is not None:
                    new_caches[name] = nc
                break
        else:
            raise AssertionError("segment outside all stacks")

        # Hybrid: the shared attention block runs with the layer it follows,
        # so a cut "after layer s" keeps site s on the edge side.
        if seg_hi in sites:
            site_idx = sites.index(seg_hi)
            site_cache = (
                jax.tree_util.tree_map(lambda a: a[site_idx], caches["shared_attn"])
                if caches is not None
                else None
            )
            h, nc, a = block_apply(
                params["shared_attn"], h, cfg, _SHARED_ATTN_KIND, positions,
                site_cache, rows=rows, use_kernels=use_kernels,
            )
            aux = aux + a
            if nc is not None and caches is not None:
                new_caches["shared_attn"] = (
                    write_row_blocks(
                        new_caches["shared_attn"],
                        jax.tree_util.tree_map(lambda a: a[None], nc),
                        site_idx, rows,
                    )
                    if rows is not None and h.shape[1] > 1
                    else jax.tree_util.tree_map(
                        lambda full, one: full.at[site_idx].set(one),
                        new_caches["shared_attn"], nc,
                    )
                )

        if seg_hi in collect:
            collected[seg_hi] = h

    return h, new_caches, aux, collected


# ---------------------------------------------------------------- heads
def _unembed(params: Params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = dense(w, h, h.dtype)
    if cfg.padded_vocab_size != cfg.vocab_size:
        # Vocab-padding rows never win a softmax (fused into the matmul).
        pad_mask = jnp.arange(cfg.padded_vocab_size) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))
    return logits


def _stacked_branch_norm(
    params: Params, hs: jax.Array, idx: Sequence[int], cfg: ModelConfig
) -> jax.Array:
    """Per-branch norm over stacked hiddens ``hs`` (K, ..., D); ``idx[k]``
    selects head k's row of the stacked ``params["branches"]`` tree.  Both
    norms reduce over the last axis only, so the stacked apply is bitwise
    the per-head apply."""
    if cfg.norm_type == "rmsnorm":
        scale = params["branches"]["scale"][np.asarray(idx)]  # (K, D)
        bcast = scale.reshape(scale.shape[0], *([1] * (hs.ndim - 2)), -1)
        return norm_apply(cfg.norm_type, {"scale": bcast}, hs)
    return norm_apply(cfg.norm_type, {}, hs)


def branch_logits_stacked(
    params: Params,
    collected: dict[int, jax.Array],
    cfg: ModelConfig,
    layers: Sequence[int] | None = None,
) -> tuple[tuple[int, ...], jax.Array | None]:
    """Batched tied exit heads: ONE stacked norm + ONE shared-unembedding
    einsum for every requested branch.

    The per-branch params are stored stacked ((K, D) scale tree for
    rmsnorm; parameter-free otherwise), so K heads price like one: the
    collected hiddens stack to (K, B, S, D), the norm applies over the
    stack, and the unembedding weight is read (and cast) once by a single
    (K*B*S, D) x (D, V) contraction instead of once per head.  Returns
    ``(layers, logits (K, B, S, V))`` in ``layers`` order — ``((), None)``
    when no requested layer was collected.  Per-head results are bitwise
    identical to the sequential per-branch path: the norm reductions and
    the contraction over D are row-independent."""
    want = cfg.branch_layers if layers is None else tuple(layers)
    present = tuple(l for l in want if l in collected)
    if not present:
        return (), None
    idx = [cfg.branch_layers.index(l) for l in present]
    hs = jnp.stack([collected[l] for l in present])  # (K, B, S, D)
    hn = _stacked_branch_norm(params, hs, idx, cfg)
    return present, _unembed(params, hn, cfg)


def _branch_logits(
    params: Params, collected: dict[int, jax.Array], cfg: ModelConfig
) -> dict[int, jax.Array]:
    """Tied early-exit heads: per-branch norm + shared unembedding,
    evaluated through the batched (K, B, S, V) path."""
    layers, stk = branch_logits_stacked(params, collected, cfg)
    return {layer: stk[k] for k, layer in enumerate(layers)}


def branch_logits_per_head(
    params: Params, collected: dict[int, jax.Array], cfg: ModelConfig
) -> dict[int, jax.Array]:
    """Sequential reference heads: one norm + one unembedding einsum PER
    branch (the pre-batching lowering).  The serving runtime keeps this as
    the parity baseline behind ``TierExecutor(batched_heads=False)`` —
    per-head outputs are bitwise identical to the stacked path."""
    out = {}
    for j, layer in enumerate(cfg.branch_layers):
        if layer not in collected:
            continue
        bn = jax.tree_util.tree_map(lambda a: a[j], params["branches"])
        hb = norm_apply(cfg.norm_type, bn, collected[layer])
        out[layer] = _unembed(params, hb, cfg)
    return out


def softmax_xent(
    logits: jax.Array, labels: jax.Array, mask: jax.Array | None = None
) -> jax.Array:
    """Mean masked token cross-entropy, fp32 reductions."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return nll.mean()
    mask = mask.astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------- embedding
def _embed_inputs(
    params: Params, inputs: dict, cfg: ModelConfig
) -> tuple[jax.Array, jax.Array]:
    """Returns (h (B, S, d), positions (S,)).  Modality frontends are stubs
    per spec: precomputed patch/frame embeddings arrive in ``inputs``."""
    dtype = compute_dtype(cfg)
    if cfg.frontend == "vision":
        tok = embed(params["embed"], inputs["tokens"], dtype)
        h = jnp.concatenate([inputs["patch_embeds"].astype(dtype), tok], axis=1)
    else:
        h = embed(params["embed"], inputs["tokens"], dtype)
    h = constrain(h, "b..")
    s = h.shape[1]
    positions = jnp.arange(s, dtype=jnp.int32)
    if cfg.arch_type == "audio":
        # Whisper decoder uses absolute positions added to embeddings.
        h = h + sinusoidal_positions(s, cfg.d_model).astype(dtype)[None]
    return h, positions


def encode_audio(params: Params, frame_embeds: jax.Array, cfg: ModelConfig):
    """Whisper encoder over (stubbed) conv-frontend frame embeddings."""
    dtype = compute_dtype(cfg)
    h = frame_embeds.astype(dtype)
    h = h + sinusoidal_positions(h.shape[1], cfg.d_model).astype(dtype)[None]
    pos = jnp.arange(h.shape[1], dtype=jnp.int32)
    h, _, _ = run_stack(params["encoder"], h, cfg, _ENC_KIND, pos)
    return norm_apply(cfg.norm_type, params["enc_norm"], h)


def compute_cross_kv(params: Params, enc_out: jax.Array, cfg: ModelConfig):
    """Per-decoder-layer cross K/V, stacked (L, B, S_enc, K, D)."""
    b, s, _ = enc_out.shape
    kh, hd = cfg.num_kv_heads, cfg.head_dim

    def per_layer(xattn):
        k = dense(xattn["wk"], enc_out, enc_out.dtype).reshape(b, s, kh, hd)
        v = dense(xattn["wv"], enc_out, enc_out.dtype).reshape(b, s, kh, hd)
        return k, v

    return jax.vmap(per_layer)(params["blocks"]["xattn"])


# ---------------------------------------------------------------- train
def forward_train(
    params: Params,
    batch: dict,
    cfg: ModelConfig,
    *,
    moe_dispatch: str = "einsum",
) -> dict[str, jax.Array]:
    """Joint BranchyNet training loss (paper Sec. III / BranchyNet [5]):
    main CE + branch_loss_weight * sum_k CE_k (+ MoE aux, + MTP)."""
    h, positions = _embed_inputs(params, batch, cfg)
    caches = None
    if cfg.arch_type == "audio":
        enc_out = encode_audio(params, batch["frame_embeds"], cfg)
        cross = compute_cross_kv(params, enc_out, cfg)
        caches = None  # training path passes cross_kv through run_stack xs
        h2, _, aux, collected = _run_trunk_with_cross(
            params, h, cfg, positions, cross,
            collect=cfg.branch_layers, remat=cfg.remat,
        )
    else:
        h2, _, aux, collected = run_trunk(
            params, h, cfg, positions, caches,
            collect=cfg.branch_layers, remat=cfg.remat,
            moe_dispatch=moe_dispatch,
        )

    labels = batch["labels"]
    mask = batch.get("mask")
    n_patch = cfg.num_patches if cfg.frontend == "vision" else 0

    # Each head's loss is checkpointed: the (B, S, V) logits would otherwise
    # be SAVED for backward per head (fp32!) — with 3 branches + main + MTP
    # on a 128k vocab that alone was ~17 GB/device in the train_4k dry-run.
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def head_loss(norm_params, h):
        hn = norm_apply(cfg.norm_type, norm_params, h)
        logits = constrain(_unembed(params, hn, cfg), "b.v")
        lt = logits[:, n_patch:] if n_patch else logits
        return softmax_xent(lt[:, :-1], labels[:, 1:],
                            None if mask is None else mask[:, 1:])

    main_loss = head_loss(params["final_norm"], h2)

    branch_losses = {}
    present = tuple(l for l in cfg.branch_layers if l in collected)
    if present:
        idx = [cfg.branch_layers.index(l) for l in present]

        # All K branch heads in one stacked norm + one unembedding einsum
        # (the serving runtime prices branches exactly this way, see
        # branch_logits_stacked).  Checkpointed like head_loss so no
        # (K, B, S, V) logits are saved for backward; the backward-pass
        # recompute does materialize all K heads' logits at once (vs one
        # head at a time sequentially) — the price of reading the
        # unembedding once instead of K times.
        @functools.partial(jax.checkpoint, prevent_cse=False)
        def branch_losses_fn(hs):
            hn = _stacked_branch_norm(params, hs, idx, cfg)
            logits = constrain(_unembed(params, hn, cfg), ".b.v")
            lt = logits[:, :, n_patch:] if n_patch else logits
            return jax.vmap(
                lambda lg: softmax_xent(
                    lg[:, :-1], labels[:, 1:],
                    None if mask is None else mask[:, 1:],
                )
            )(lt)

        bl = branch_losses_fn(jnp.stack([collected[l] for l in present]))
        for k, layer in enumerate(present):
            branch_losses[f"branch_{layer}"] = bl[k]

    loss = main_loss + cfg.branch_loss_weight * sum(branch_losses.values())
    loss = loss + cfg.router_aux_weight * aux

    if cfg.use_mtp:
        # DeepSeek-V3-style multi-token prediction: one extra block applied
        # to the trunk output predicts token t+2 (simplified single-depth
        # MTP).  Checkpointed for the same reason as head_loss.
        @functools.partial(jax.checkpoint, prevent_cse=False)
        def mtp_loss_fn(h):
            h_mtp, _, _ = block_apply(
                params["mtp_block"], h, cfg,
                BlockKind("mla" if cfg.use_mla else "gqa", "dense"), positions,
            )
            logits = constrain(_unembed(
                params, norm_apply(cfg.norm_type, params["mtp_norm"], h_mtp), cfg
            ), "b.v")
            lt = logits[:, n_patch:] if n_patch else logits
            return softmax_xent(lt[:, :-2], labels[:, 2:],
                                None if mask is None else mask[:, 2:])

        mtp_loss = mtp_loss_fn(h2)
        branch_losses["mtp"] = mtp_loss
        loss = loss + 0.3 * mtp_loss

    return {
        "loss": loss,
        "main_loss": main_loss,
        "aux_loss": aux,
        "branch_losses": branch_losses,
    }


def _run_trunk_with_cross(params, h, cfg, positions, cross_kv, *, collect, remat):
    """Training-mode trunk for enc-dec: cross_kv threaded through segments."""
    total = cfg.num_layers
    kind = trunk_layout(cfg)[0][1]
    aux = jnp.zeros((), jnp.float32)
    collected = {}
    lo = 0
    for stop in [*sorted(c for c in collect if 0 < c < total), total]:
        sp = stack_slice(params["blocks"], lo, stop)
        cr = jax.tree_util.tree_map(lambda a: a[lo:stop], cross_kv)
        h, _, a = run_stack(sp, h, cfg, kind, positions, None, cr, remat=remat)
        aux = aux + a
        if stop in collect:
            collected[stop] = h
        lo = stop
    return h, None, aux, collected


# ---------------------------------------------------------------- serving
def prefill(
    params: Params,
    inputs: dict,
    cfg: ModelConfig,
    caches: Params,
    *,
    moe_dispatch: str = "einsum",
    rows: jax.Array | None = None,
) -> tuple[jax.Array, Params]:
    """Process the full prompt; returns (last-position logits, caches).

    For attention caches, prefill runs the full-sequence path and then
    writes K/V into the cache tensors; SSM states come from the chunked
    scan's final state.  For the dry-run's prefill shape we lower exactly
    this function.

    ``rows`` (continuous-batching admission): ``inputs`` is a block of
    newly admitted prompts and ``caches`` the *resident full-batch* caches
    — prompt row ``i`` prefills into cache row ``rows[i]`` in place, ending
    exactly as a fresh solo prefill of that prompt (stale slots from the
    row's previous occupant reset to empty).  Other rows and the resident
    step counter are untouched; OOB sentinel rows drop their writes.
    """
    if rows is not None and cfg.arch_type == "audio":
        raise NotImplementedError(
            "row-targeted prefill does not cover encoder cross-KV caches"
        )
    h, positions = _embed_inputs(params, inputs, cfg)
    if cfg.arch_type == "audio":
        enc_out = encode_audio(params, inputs["frame_embeds"], cfg)
        caches = dict(caches)
        caches["cross_kv"] = compute_cross_kv(params, enc_out, cfg)
    h2, new_caches, _, _ = run_trunk(
        params, h, cfg, positions, caches, moe_dispatch=moe_dispatch,
        rows=rows,
    )
    if new_caches is not None and rows is None:
        new_caches["length"] = jnp.asarray(h.shape[1], jnp.int32)
    hF = norm_apply(cfg.norm_type, params["final_norm"], h2)
    logits = constrain(_unembed(params, hF[:, -1:], cfg), "b.v")
    return logits, new_caches


def embed_decode(
    params: Params, token: jax.Array, positions: jax.Array, cfg: ModelConfig
) -> jax.Array:
    """Embed one decode-step token (B, 1) — the entry point of whichever
    tier holds trunk layer 1 in a partitioned deployment.  ``positions``
    is the shared (1,) step position, or (B, 1) per-sequence positions
    under continuous batching."""
    dtype = compute_dtype(cfg)
    h = embed(params["embed"], token, dtype)
    if cfg.arch_type == "audio":
        # RoPE-free decoder: add the absolute sinusoidal embedding at `pos`.
        emb = sinusoidal_embed(positions, cfg.d_model).astype(dtype)
        h = h + (emb if positions.ndim == 2 else emb[None])
    return h


def decode_step(
    params: Params,
    token: jax.Array,  # (B, 1) int32
    pos: jax.Array,  # () int32 — absolute position of this token
    caches: Params,
    cfg: ModelConfig,
    *,
    moe_dispatch: str = "einsum",
    layer_range: tuple[int, int] | None = None,
    with_branches: bool = True,
    use_kernels: bool | None = None,  # None = cfg.use_kernels (auto on TPU)
) -> dict[str, Any]:
    """One decode step.  Returns logits, per-branch entropies/exit masks
    (the paper's confidence test at each side branch), and updated caches."""
    kernels = resolve_use_kernels(
        cfg.use_kernels if use_kernels is None else use_kernels
    )
    positions = pos[None].astype(jnp.int32)
    h = embed_decode(params, token, positions, cfg)

    collect = cfg.branch_layers if with_branches else ()
    h2, new_caches, _, collected = run_trunk(
        params, h, cfg, positions, caches,
        layer_range=layer_range, collect=collect, moe_dispatch=moe_dispatch,
        use_kernels=kernels,
    )
    out: dict[str, Any] = {}
    total = sum(n for _, _, n in trunk_layout(cfg))
    if layer_range is None or layer_range[1] == total:
        hF = norm_apply(cfg.norm_type, params["final_norm"], h2)
        out["logits"] = constrain(_unembed(params, hF, cfg), "b.v")[:, 0]
    else:
        out["hidden"] = h2  # partitioned execution: ship the residual stream

    if with_branches:
        bl = _branch_logits(params, collected, cfg)
        out["branch_logits"] = {k: v[:, 0] for k, v in bl.items()}
        out["branch_entropy"] = {
            k: normalized_entropy(v) for k, v in out["branch_logits"].items()
        }
        out["branch_exit"] = {
            k: e < cfg.exit_threshold for k, e in out["branch_entropy"].items()
        }
    if new_caches is not None:
        new_caches["length"] = caches["length"] + 1
    out["caches"] = new_caches
    return out
