"""Per-layer cost extraction: the partitioner's ``t_c`` / ``alpha`` inputs.

The paper measures ``t_i^c`` on Google Colab (K80) and sets
``t_i^e = gamma * t_i^c``.  We support two sources:

  * :func:`measure_layer_times` — wall-clock each layer callable on the local
    CPU device (paper-faithful for the B-AlexNet reproduction);
  * :func:`analyze_layer_costs` — derive roofline times from the compiled
    HLO of each layer (``cost_analysis()``): t = max(flops/peak, bytes/bw).
    This is the deployable path — no hardware in the loop (DESIGN.md Sec. 7).

Both return a :class:`repro.core.types.CostProfile`-ready pair of arrays.

:func:`profile_decode_layers` builds the serving-relevant inputs for
either source directly from a BranchyNet trunk: one decode-step callable
per trunk layer (its residual update *including* the resident-cache
read/write), dispatched through the same ``use_kernels`` tri-state as the
tier runtime — so ``compute_j`` can come from the Pallas kernel lowering
(interpret mode off-TPU) instead of only the jnp path, and the cost model
prices what the runtime actually executes.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "HardwareSpec",
    "TPU_V5E",
    "LayerCost",
    "analyze_layer_costs",
    "branch_head_cost",
    "decode_layer_fns",
    "measure_layer_times",
    "output_bytes",
    "profile_decode_layers",
]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Roofline constants for one accelerator tier."""

    name: str
    peak_flops: float  # FLOP/s (bf16 unless noted)
    hbm_bw: float  # bytes/s
    link_bw: float = 50e9  # bytes/s per ICI link
    hbm_bytes: float = 16e9

    def roofline_time(
        self, flops: float, bytes_: float, devices: int = 1
    ) -> float:
        """Execution time lower bound: max of compute and memory terms.

        ``devices > 1`` models a tensor-parallel shard of the layer: FLOPs
        and HBM traffic split across the shard width; the collective cost
        of re-assembling the activation is priced separately by
        :func:`collective_time` (the sum feeds ``TierSpec.devices``-aware
        profiles)."""
        d = max(int(devices), 1)
        return max(flops / d / self.peak_flops, bytes_ / d / self.hbm_bw)

    def collective_time(self, activation_bytes: float, devices: int) -> float:
        """Per-layer intra-tier collective term: a ring all-reduce of the
        layer's activation over the ICI link, twice per layer (attention-
        out + MLP-down partial sums) — the profiler-side mirror of
        ``repro.core.multitier._collective_seconds``."""
        d = max(int(devices), 1)
        if d <= 1 or activation_bytes <= 0.0:
            return 0.0
        return 2.0 * (2.0 * (d - 1) / d) * activation_bytes / self.link_bw


#: The target accelerator for this framework (system prompt constants).
TPU_V5E = HardwareSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9)


@dataclasses.dataclass(frozen=True)
class LayerCost:
    name: str
    flops: float
    bytes_accessed: float
    output_bytes: float
    time_s: float


def output_bytes(tree) -> float:
    """Total bytes of a pytree of abstract/concrete arrays (the paper's
    alpha_i for the tensor that crosses the cut)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0.0
    for leaf in leaves:
        total += float(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return total


def _cost_analysis(fn: Callable, *abstract_args) -> dict:
    lowered = jax.jit(fn).lower(*abstract_args)
    compiled = lowered.compile()
    return compiled.cost_analysis() or {}


def analyze_layer_costs(
    layer_fns: Sequence[tuple[str, Callable]],
    layer_inputs: Sequence,
    hardware: HardwareSpec = TPU_V5E,
    *,
    devices: int = 1,
) -> list[LayerCost]:
    """Roofline-cost every layer of a chain from its compiled HLO.

    ``layer_fns[i]`` maps layer i's input pytree to its output pytree;
    ``layer_inputs[i]`` is a pytree of ShapeDtypeStructs.  No device memory
    is allocated.

    ``devices > 1`` prices a mesh-sharded tier: each layer's roofline time
    divides by the shard width and gains the per-layer collective term
    (``HardwareSpec.collective_time`` on the layer's output activation) —
    the same two cost-model terms ``TierSpec(devices=, ici_bps=)`` carries
    into :func:`repro.core.multitier.solve_multitier`.
    """
    out: list[LayerCost] = []
    for (name, fn), args in zip(layer_fns, layer_inputs):
        ca = _cost_analysis(fn, args)
        flops = float(ca.get("flops", 0.0))
        bytes_accessed = float(ca.get("bytes accessed", 0.0))
        shape = jax.eval_shape(fn, args)
        ob = output_bytes(shape)
        t = hardware.roofline_time(flops, max(bytes_accessed, ob), devices)
        t += hardware.collective_time(ob, devices)
        out.append(LayerCost(name, flops, bytes_accessed, ob, t))
    return out


def measure_layer_times(
    layer_fns: Sequence[tuple[str, Callable]],
    layer_inputs: Sequence,
    iters: int = 10,
    warmup: int = 2,
) -> list[LayerCost]:
    """Wall-clock per-layer timing on the local device (paper Sec. VI mode).

    ``layer_inputs`` here are concrete arrays.  Used by the B-AlexNet
    reproduction where the paper measured Colab times; everything is jitted
    and block_until_ready'd so we time steady-state compute only.
    """
    out: list[LayerCost] = []
    for (name, fn), args in zip(layer_fns, layer_inputs):
        jf = jax.jit(fn)
        res = jf(args)
        jax.block_until_ready(res)
        for _ in range(warmup):
            jax.block_until_ready(jf(args))
        t0 = time.perf_counter()
        for _ in range(iters):
            res = jf(args)
        jax.block_until_ready(res)
        dt = (time.perf_counter() - t0) / iters
        ob = output_bytes(jax.eval_shape(fn, args))
        out.append(LayerCost(name, 0.0, 0.0, ob, dt))
    return out


# ------------------------------------------------- branch-head pricing
def branch_head_cost(
    cfg,
    batch: int,
    *,
    heads_batched: bool = True,
    hardware: HardwareSpec = TPU_V5E,
):
    """Roofline seconds to evaluate ``m`` tied exit heads in one decode
    step at the cloud-reference tier: per-branch norm + the shared
    (D, V) unembedding applied to a (batch, D) hidden per head.

    Returns a callable ``m -> seconds`` (``m = 0`` is free) — the
    ``head_cost=`` input of :func:`repro.core.multitier.solve_multitier` /
    ``expected_time_multitier`` and both servers' ``est_latency_s``.

    ``heads_batched=True`` prices the runtime's stacked evaluation
    (``TierExecutor(batched_heads=True)``, the default): FLOPs still scale
    with ``m``, but the dominant HBM term — streaming (and casting) the
    D x V unembedding weight — is paid ONCE for the whole stack, so ``m``
    heads cost about one head's bandwidth.  ``heads_batched=False`` prices
    the sequential per-head lowering: ``m`` independent projections, each
    re-reading the weight — what probe-step estimates used to charge
    unconditionally (K full head passes) even when the runtime batches.
    """
    d = float(cfg.d_model)
    v = float(cfg.padded_vocab_size)
    b = float(batch)
    itemsize = 2.0 if cfg.dtype == "bfloat16" else 4.0
    w_bytes = d * v * itemsize  # the shared unembedding read
    act_bytes = b * (d + v) * itemsize  # per-head hidden read + logits write
    flops_per_head = 2.0 * b * d * v

    def cost(m: int) -> float:
        m = int(m)
        if m <= 0:
            return 0.0
        if heads_batched:
            return hardware.roofline_time(
                m * flops_per_head, w_bytes + m * act_bytes
            )
        return m * hardware.roofline_time(flops_per_head, w_bytes + act_bytes)

    return cost


# ------------------------------------------------- serving decode profiles
def decode_layer_fns(
    cfg,
    params,
    batch: int,
    context_len: int,
    *,
    use_kernels: bool | None = None,
    pos: int | None = None,
) -> tuple[list[tuple[str, Callable]], list]:
    """Per-trunk-layer decode-step callables + their input pytrees.

    Layer ``i``'s callable maps ``(h (B, 1, d), caches)`` to the residual
    stream after layer ``i`` — including the layer's resident-cache
    read/write — through :func:`repro.models.model.run_trunk` with the
    SAME ``use_kernels`` dispatch the tier runtime uses (None = the
    config's tri-state: auto on TPU; True off-TPU runs the Pallas kernels
    in interpret mode).  Feed the pairs to :func:`analyze_layer_costs`
    (inputs become ShapeDtypeStructs automatically) or
    :func:`measure_layer_times` via :func:`profile_decode_layers`.

    ``output_bytes`` of each callable is the residual stream — the
    paper's per-layer ``alpha_i`` — because the cache stays resident and
    never crosses a cut.
    """
    # Deferred: core.profiler is imported by repro.core.__init__, and the
    # model stack imports repro.core submodules.
    from repro.kernels.ops import resolve_use_kernels
    from repro.models import model as M

    kernels = resolve_use_kernels(
        cfg.use_kernels if use_kernels is None else use_kernels
    )
    total = sum(n for _, _, n in M.trunk_layout(cfg))
    dtype = M.compute_dtype(cfg)
    # Mid-context query position: the cache is charged at its full
    # resident size either way (static shapes), the position only gates
    # the validity mask.
    positions = jnp.full((1,), pos if pos is not None else context_len // 2,
                         jnp.int32)

    def make_fn(i: int) -> Callable:
        def fn(args):
            h, caches = args
            h2, _, _, _ = M.run_trunk(
                params, h, cfg, positions, caches,
                layer_range=(i, i + 1), use_kernels=kernels,
            )
            return h2

        return fn

    fns = [(f"layer{i + 1}", make_fn(i)) for i in range(total)]
    h0 = jnp.zeros((batch, 1, cfg.d_model), dtype)
    caches = M.init_caches(cfg, batch, context_len)
    inputs = [(h0, caches)] * total
    return fns, inputs


def profile_decode_layers(
    cfg,
    params,
    batch: int,
    context_len: int,
    *,
    use_kernels: bool | None = None,
    mode: str = "analyze",
    hardware: HardwareSpec = TPU_V5E,
    iters: int = 10,
    warmup: int = 2,
    devices: int = 1,
) -> list[LayerCost]:
    """Per-layer decode-step costs of a BranchyNet trunk, kernel-aware.

    ``mode="analyze"`` rooflines each layer's compiled HLO (no device
    work beyond compilation); ``mode="measure"`` wall-clocks it.  Either
    way the lowered program is the tier runtime's own decode math —
    ``use_kernels=True`` prices the Pallas kernel lowering, ``False`` the
    jnp lowering, ``None`` the config/backend default — so the resulting
    ``t_c`` feeds :class:`~repro.core.types.CostProfile` with
    runtime-faithful ``compute_j`` terms.

    ``devices`` (analyze mode) prices the layers as a mesh-sharded tier
    would run them: roofline over the shard width plus the per-layer
    collective term — sharded segments resolve ``use_kernels`` to the jnp
    path, matching the runtime's sharded dispatch."""
    if mode not in ("analyze", "measure"):
        raise ValueError(f"unknown profiling mode: {mode!r}")
    from repro.kernels.ops import resolve_use_kernels

    if devices > 1:
        use_kernels = resolve_use_kernels(use_kernels, sharded=True)
    fns, inputs = decode_layer_fns(
        cfg, params, batch, context_len, use_kernels=use_kernels
    )
    if mode == "analyze":
        abstract = [
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
            )
            for args in inputs
        ]
        return analyze_layer_costs(fns, abstract, hardware, devices=devices)
    return measure_layer_times(fns, inputs, iters=iters, warmup=warmup)
