"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run must set XLA_FLAGS
before any jax initialization.

Target fleet: TPU v5e, 256 chips/pod (16x16), 2 pods = 512 chips.
Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
Every axis is ``AxisType.Auto``: shardings propagate through XLA's SPMD
partitioner from the placed params and the models' ``constrain`` calls.
(``jax.make_mesh`` defaults to explicit axes, which put shardings into
array types and refuse, e.g., a gather from a vocab-sharded embedding.)
"""

from __future__ import annotations

import numpy as np

import jax

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "mesh_axis_sizes",
    "mesh_devices",
]


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(*, data: int | None = None, model: int | None = None):
    """("data", "model") mesh over the local devices.

    Defaults put every device on the "model" axis (a (1, n) mesh — tensor
    parallelism across whatever is available, which is the sharded-tier
    serving shape).  ``data=`` / ``model=`` override either axis so tests
    can build e.g. a (2, 4) mesh on 8 virtual CPU devices; an unset axis
    absorbs the remaining devices.
    """
    n = len(jax.devices())
    if data is None and model is None:
        data, model = 1, n
    elif data is None:
        data = max(n // model, 1)
    elif model is None:
        model = max(n // data, 1)
    if data < 1 or model < 1:
        raise ValueError(f"mesh axes must be >= 1: data={data}, model={model}")
    if data * model > n:
        raise ValueError(
            f"requested mesh ({data}, {model}) = {data * model} devices, "
            f"but only {n} are available (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"jax initializes to virtualize more)"
        )
    return _auto_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def mesh_devices(mesh) -> int:
    """Shard width a tier running on ``mesh`` has: the total device count
    across every mesh axis.  This is the ``TierSpec.devices`` /
    ``TierSegment.devices`` term of the sharding-aware partition cost
    (compute scales 1/devices, plus the intra-tier collective term)."""
    if mesh is None:
        return 1
    return int(np.prod(list(mesh_axis_sizes(mesh).values()), dtype=np.int64))
