"""Model weights made from ``--seed``, on the device, in one jitted call.

The program under test only lends the layout (leaf paths, shapes, dtypes,
read with ``jax.eval_shape`` from its own initializer); the values come
from here, so neither the program nor a later change to it decides what
the reference is compared against.  Rules, by the leaf's last key:

  * ``scale`` (every RMSNorm gain, the exit heads' norms): 1 + 0.1 N(0, 1),
    so that a program which skips a gain differs from the reference;
  * ``embed``: 0.02 N(0, 1);
  * any other matrix (..., fan_in, fan_out): N(0, 1) / sqrt(fan_in);
  * any other vector: 0.02 N(0, 1).

Each leaf draws from its own key, ``fold_in(seed key, leaf index)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _draw(key, name: str, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        w = 1.0 + 0.1 * z
    elif name == "embed":
        w = 0.02 * z
    elif len(shape) >= 2:
        w = z / np.sqrt(shape[-2])
    else:
        w = 0.02 * z
    return w.astype(dtype)


def make_params(shapes, seed: int, shardings=None):
    """Values for the pytree of ``ShapeDtypeStruct`` ``shapes``, made by one
    jitted program straight into their (possibly sharded) buffers."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_leaf_name(p), tuple(s.shape), s.dtype) for p, s in flat]

    def build(key):
        leaves = [_draw(jax.random.fold_in(key, i), name, shape, dtype)
                  for i, (name, shape, dtype) in enumerate(specs)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    params = jax.jit(build, out_shardings=shardings)(seed_key(seed))
    jax.block_until_ready(params)
    return params
