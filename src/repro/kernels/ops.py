"""jit'd public wrappers for the Pallas kernels — the decode hot path's
kernel dispatch layer.

These are what the model/serving code calls when ``use_kernels`` is on:

  * :func:`flash_decode` — single-token GQA decode attention streaming
    survivor rows straight out of the full-batch resident KV cache via a
    scalar-prefetched row map (``models.attention.attn_apply`` decode);
  * :func:`entropy_exit_argmax` — the fused BranchyNet exit decision:
    normalized entropy, threshold flag and argmax token in ONE pass over
    the (B, V) branch logits (``serving.tiers.TierExecutor`` per-branch
    exit masking);
  * :func:`entropy_exit` — the entropy + flag pair without the token
    (calibration sweeps);
  * :func:`ssd_update` — one recurrent Mamba2/SSD decode step against the
    resident state, same ``rows`` plumbing (``models.mamba.mamba_apply``
    decode);
  * :func:`ssd_scan` — the chunked SSD prefill/train scan.

``use_kernels`` resolution (:func:`resolve_use_kernels`): ``None`` means
auto — kernels on TPU, pure-jnp elsewhere.  An explicit ``True`` off-TPU
runs the kernels in *interpret mode* (the kernel body executes as jax ops
on CPU): bit-for-bit the same dataflow the TPU lowering compiles, correct
but slow, which is exactly what the equivalence tests and the
``benchmarks/kernel_micro.py`` sweep exercise.  Each wrapper picks
interpret mode from :func:`on_tpu` each time it is traced (the wrappers
are not jitted themselves, so no cached trace outlives that decision);
pass ``interpret=`` explicitly to override.  The ``*_pallas`` kernels
they call are jitted, and are traced inside the tier runtime's
per-(spec, bucket) segment functions, so a new *bucket* shape compiles
once and a survivor-count change within a bucket never recompiles — the
same contract as the jnp path.
"""

from __future__ import annotations

import jax

from repro.kernels.entropy_exit import (
    entropy_exit_argmax_heads_pallas,
    entropy_exit_argmax_pallas,
    entropy_exit_pallas,
)
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas, ssd_update_pallas

__all__ = [
    "entropy_exit",
    "entropy_exit_argmax",
    "entropy_exit_argmax_heads",
    "flash_decode",
    "ssd_scan",
    "ssd_update",
    "on_tpu",
    "resolve_use_kernels",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_use_kernels(flag: bool | None, *, sharded: bool = False) -> bool:
    """The ``use_kernels`` tri-state: None = auto (kernels on TPU only),
    True/False force the kernel / pure-jnp path (True off-TPU runs the
    kernels in interpret mode).

    ``sharded=True`` (a mesh-sharded tier segment) always resolves to the
    jnp path: the Pallas kernels are single-device programs, and handing
    them a mesh-global batch under SPMD would either fail to partition or
    silently gather the full sharded KV cache to one device.  The jnp
    lowering partitions cleanly under ``NamedSharding``; a per-shard
    ``shard_map`` kernel dispatch is the documented follow-up."""
    if sharded:
        return False
    return on_tpu() if flag is None else bool(flag)


def _interpret(flag: bool | None) -> bool:
    return (not on_tpu()) if flag is None else flag


def entropy_exit(logits, threshold, *, interpret: bool | None = None):
    """(B, V) logits -> (normalized entropy (B,), exit flags (B,))."""
    return entropy_exit_pallas(logits, threshold,
                               interpret=_interpret(interpret))


def entropy_exit_argmax(logits, threshold, *, interpret: bool | None = None):
    """Fused exit decision: (B, V) logits -> (normalized entropy (B,),
    exit flags (B,), argmax token (B,) int32) in one streaming pass."""
    return entropy_exit_argmax_pallas(logits, threshold,
                                      interpret=_interpret(interpret))


def entropy_exit_argmax_heads(logits, thresholds, *,
                              interpret: bool | None = None):
    """Multi-head fused exit decision: (K, B, V) stacked branch-head
    logits -> (normalized entropy (K, B), exit flags (K, B), argmax token
    (K, B) int32) in ONE kernel launch — the batched-head counterpart of
    :func:`entropy_exit_argmax` (per-head slices are bitwise identical).
    ``thresholds`` is a scalar (every head) or (K,) per-head array.
    Sharded segments never reach this wrapper: ``resolve_use_kernels``
    routes them to the jnp fallback (see ``serving.tiers``)."""
    return entropy_exit_argmax_heads_pallas(logits, thresholds,
                                            interpret=_interpret(interpret))


def flash_decode(q, k, v, k_pos, q_pos, rows=None, *, window: int = 0,
                 scale: float | None = None, interpret: bool | None = None):
    """Single-token GQA decode attention against a (ring) KV cache.
    ``rows`` maps a compacted survivor sub-batch onto cache rows;
    ``scale`` overrides ``1 / sqrt(D)`` (a lane-padded head_dim)."""
    return flash_decode_pallas(q, k, v, k_pos, q_pos, rows, window=window,
                               scale=scale, interpret=_interpret(interpret))


def ssd_scan(x, a, b_mat, c_mat, *, chunk: int = 128,
             interpret: bool | None = None):
    """Mamba2 chunked SSD scan: (y, final_state)."""
    return ssd_scan_pallas(x, a, b_mat, c_mat, chunk=chunk,
                           interpret=_interpret(interpret))


def ssd_update(h_state, x, a, b_vec, c_vec, rows=None, *,
               interpret: bool | None = None):
    """One recurrent SSD decode step against the full-batch resident state;
    ``rows`` maps the sub-batch onto state rows (scalar-prefetch, no gather
    copy).  Returns (y (B,H,P), new state rows (B,H,P,N)), fp32."""
    return ssd_update_pallas(h_state, x, a, b_vec, c_vec, rows,
                             interpret=_interpret(interpret))
