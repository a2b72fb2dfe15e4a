"""Plain float32 reference of a dense decoder with tied early-exit heads.

The architecture of Phi-3 (arXiv:2404.14219) and Qwen3 (arXiv:2505.09388):
pre-norm blocks of grouped-query attention with rotary embeddings (the
``rotate_half`` convention of both published implementations, optional
per-head RMSNorm of q and k for Qwen3) and a SwiGLU MLP, a final RMSNorm
and an unembedding.  The exit heads are BranchyNet's side branches as the
served system builds them: after layer ``b``, an RMSNorm of its own and
the shared unembedding; their confidence is the entropy of the softmax
over the vocabulary divided by log(vocabulary).

Everything runs in float32 at the highest matmul precision, one sequence
at a time, over the whole sequence at once (no cache, no batching).  The
weights are the benchmark's seeded bf16 values, upcast where they are
used.  One departure serves the early exits: a token that left at a side
branch never ran the layers of the later tiers, so those layers hold no
key or value for its position.  ``depth`` gives, per position, how many
layers hold its key and value; later queries attend to it only there.

``quant=True`` is the control: every matmul's weights (per output column)
and inputs (per row) rounded to float8 e4m3, the step below the bf16 the
configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant:
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.einsum("...i,io->...o", x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(lp, h, l, depth, dims, quant):
    heads, kv, hd, theta, eps, qk_norm = dims[:6]
    s = h.shape[0]
    pos = jnp.arange(s)
    x = _rms(h, lp["norm1"]["scale"], eps)
    a = lp["attn"]
    q = _mm(x, a["wq"], quant).reshape(s, heads, hd)
    k = _mm(x, a["wk"], quant).reshape(s, kv, hd)
    v = _mm(x, a["wv"], quant).reshape(s, kv, hd)
    if qk_norm:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = heads // kv
    qg = q.reshape(s, kv, g, hd)
    sc = jnp.einsum("qkgd,pkd->kgqp", qg, k, precision=HIGHEST) / np.sqrt(hd)
    ok = (pos[:, None] >= pos[None, :]) & (l < depth)[None, :]
    sc = jnp.where(ok, sc, -jnp.inf)
    o = jnp.einsum("kgqp,pkd->qkgd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(s, heads * hd)
    h = h + _mm(o, a["wo"], quant)
    x = _rms(h, lp["norm2"]["scale"], eps)
    m = lp["mlp"]
    y = jax.nn.silu(_mm(x, m["w_gate"], quant)) * _mm(x, m["w_up"], quant)
    return h + _mm(y, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "branches", "quant",
                                             "chunk"))
def _score(params, tokens, depth, head_layer, targets, *, dims, branches,
           quant, chunk):
    """One sequence: per position, for the head that produced its output
    (``head_layer``: 0 = main head, b = the exit head after layer b), the
    argmax, the gap max logit - logit[target] of each target row, and the
    normalized entropy of every exit head in ``branches``."""
    eps = dims[4]
    blocks = params["blocks"]
    n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    h = params["embed"].astype(jnp.float32)[tokens]
    saved = jnp.zeros((len(branches), *h.shape), jnp.float32)

    def body(carry, l):
        h, saved = carry
        lp = jax.tree_util.tree_map(
            lambda w: jax.lax.dynamic_index_in_dim(w, l, 0, keepdims=False),
            blocks)
        h = _layer(lp, h, l, depth, dims, quant)
        for j, b in enumerate(branches):
            saved = saved.at[j].set(jnp.where(l + 1 == b, h, saved[j]))
        return (h, saved), None

    (h, saved), _ = jax.lax.scan(body, (h, saved), jnp.arange(n_layers))
    gains = params["branches"]["scale"]
    all_branches = dims[6]
    normed = [_rms(h, params["final_norm"]["scale"], eps)] + [
        _rms(saved[j], gains[all_branches.index(b)], eps)
        for j, b in enumerate(branches)
    ]
    layer_of = np.array([0, *branches])
    w = params["lm_head"]
    vocab = w.shape[-1]
    s = h.shape[0]

    def one_chunk(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk, 0)
        logits = jnp.stack([_mm(sl(x), w, quant) for x in normed])  # (H,c,V)
        lp = jax.nn.log_softmax(logits, -1)
        ent = -jnp.sum(jnp.exp(lp) * lp, -1) / np.log(vocab)  # (H, c)
        pick = jnp.argmax(layer_of[:, None] == sl(head_layer)[None], 0)
        used = jnp.take_along_axis(logits, pick[None, :, None], 0)[0]
        tgt = jax.vmap(sl)(targets)  # (T, c)
        at = jnp.take_along_axis(used[None], jnp.maximum(tgt, 0)[..., None],
                                 -1)[..., 0]
        top = used.max(-1)
        gap = jnp.where(tgt >= 0, top[None] - at, 0.0)
        return jnp.argmax(used, -1).astype(jnp.int32), gap, ent[1:]

    arg, gap, ent = jax.lax.map(one_chunk, jnp.arange(s // chunk))
    return {
        "argmax": arg.reshape(s),
        "gap": gap.transpose(1, 0, 2).reshape(targets.shape[0], s),
        "entropy": ent.transpose(1, 0, 2).reshape(len(branches), s),
    }


def score(params, spec: dict, tokens, depth, head_layer, targets,
          branches: tuple[int, ...], quant: bool = False) -> dict:
    """Reference readings for one sequence padded to a multiple of 128
    positions (see ``_score``); numpy arrays."""
    dims = (
        spec["num_attention_heads"], spec["num_key_value_heads"],
        spec.get("head_dim") or spec["hidden_size"]
        // spec["num_attention_heads"],
        float(spec["rope_theta"]), float(spec["rms_norm_eps"]),
        bool(spec.get("qk_norm", False)), tuple(spec["branch_layers"]),
    )
    out = _score(
        params, jnp.asarray(tokens, jnp.int32), jnp.asarray(depth, jnp.int32),
        jnp.asarray(head_layer, jnp.int32), jnp.asarray(targets, jnp.int32),
        dims=dims, branches=tuple(branches), quant=quant,
        chunk=min(128, len(tokens)),
    )
    return {k: np.asarray(v) for k, v in out.items()}
