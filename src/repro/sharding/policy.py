"""Sharding policy: param/activation/cache PartitionSpecs per architecture.

Axis conventions (DESIGN.md Sec. 5):

  * ``data`` (+ ``pod`` when present) — batch parallelism; also the FSDP
    axes for archs with ``cfg.fsdp`` (param shards are all-gathered per
    layer by XLA SPMD under the scan).
  * ``model`` — tensor parallelism: attention heads / FFN hidden / expert
    dim / vocab.

Every rule checks divisibility and falls back to replication — phi3-medium's
kv=10 heads or whisper's 51865 vocab must not crash the lowering.  KV caches
shard kv-heads over "model" when divisible, else head_dim (DESIGN.md).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig

__all__ = ["ShardingPolicy", "make_policy"]


def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    mesh: Mesh
    cfg: ModelConfig
    batch_axes: tuple[str, ...]  # ("pod","data") or ("data",)
    model_axis: str = "model"

    # ------------------------------------------------------------ helpers
    def _axis_size(self, name: str | tuple[str, ...]) -> int:
        if isinstance(name, tuple):
            return int(np.prod([self.mesh.shape[a] for a in name]))
        return self.mesh.shape[name]

    def _maybe(self, axis, dim: int):
        """axis if it divides dim else None (replicate)."""
        return axis if _div(dim, self._axis_size(axis)) else None

    def _fsdp_axes(self) -> tuple[str, ...] | None:
        if not self.cfg.fsdp:
            return None
        axes = tuple(a for a in self.cfg.fsdp_axes if a in self.mesh.shape)
        return axes or None

    # ------------------------------------------------------------ params
    def param_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """Rule table keyed on the param's tree path (joined with '/').

        Stacked trunk params carry a leading layer axis (never sharded).
        """
        cfg = self.cfg
        tp = self.model_axis
        fsdp = self._fsdp_axes()
        stacked = self._is_stacked(path, shape)

        def spec(*dims):
            """dims for the *unstacked* suffix of the shape."""
            lead = (None,) * (len(shape) - len(dims))
            return P(*lead, *dims)

        leaf = path.split("/")[-1]

        # ---- embeddings / heads
        if leaf == "embed":
            return P(self._maybe(tp, shape[0]), fsdp and self._maybe(fsdp, shape[1]))
        if leaf == "lm_head":
            return P(fsdp and self._maybe(fsdp, shape[0]), self._maybe(tp, shape[1]))

        # ---- attention
        if re.search(r"(attn|xattn)/(wq|wk|wv|wq_b|wk_b|wv_b|wq_a|wkv_a)$", path):
            din, dout = shape[-2], shape[-1]
            return spec(
                fsdp and self._maybe(fsdp, din), self._maybe(tp, dout)
            )
        if re.search(r"(attn|xattn)/(wq|wk|wv)_t$", path):
            # Served transposed, [out, in] (models.model.served_params).
            dout, din = shape[-2], shape[-1]
            return spec(
                self._maybe(tp, dout), fsdp and self._maybe(fsdp, din)
            )
        if re.search(r"(attn|xattn)/wo$", path):
            din, dout = shape[-2], shape[-1]
            return spec(self._maybe(tp, din), fsdp and self._maybe(fsdp, dout))

        # ---- dense MLP
        if re.search(r"mlp/(w_gate|w_up)$", path):
            return spec(fsdp and self._maybe(fsdp, shape[-2]), self._maybe(tp, shape[-1]))
        if re.search(r"mlp/w_down$", path):
            return spec(self._maybe(tp, shape[-2]), fsdp and self._maybe(fsdp, shape[-1]))

        # ---- MoE: expert axis on "model"; FSDP over the hidden dims.
        if re.search(r"moe/(w_gate|w_up|w_down)$", path):
            e = shape[-3]
            if cfg.expert_parallel:
                # Expert parallelism over the whole mesh: weights fully
                # local per expert group, no FSDP gathers (§Perf pair 1).
                # REFUTED at baseline dispatch: XLA SPMD reshards the token
                # activations instead of emitting all-to-alls (EXPERIMENTS
                # §Perf); kept for the shard_map dispatch follow-up.
                ep_axes = tuple(a for a in ("data", "model") if a in self.mesh.shape)
                if _div(e, self._axis_size(ep_axes)):
                    return spec(ep_axes, None, None)
            if cfg.moe_fsdp_dim == "ff" and fsdp:
                # FSDP over the expert-hidden dim: contraction partial-sums
                # all-reduce the activations instead of gathering weights.
                is_down = path.endswith("w_down")
                ff_idx = -2 if is_down else -1
                dims = [self._maybe(tp, e), None, None]
                dims[2 + ff_idx + 1] = self._maybe(fsdp, shape[ff_idx])
                return spec(*dims)
            return spec(
                self._maybe(tp, e),
                fsdp and self._maybe(fsdp, shape[-2]),
                None,
            )
        if re.search(r"moe/router$", path):
            return spec(fsdp and self._maybe(fsdp, shape[-2]), None)
        if re.search(r"moe/shared/(w_gate|w_up)$", path):
            return spec(fsdp and self._maybe(fsdp, shape[-2]), self._maybe(tp, shape[-1]))
        if re.search(r"moe/shared/w_down$", path):
            return spec(self._maybe(tp, shape[-2]), fsdp and self._maybe(fsdp, shape[-1]))

        # ---- Mamba2
        if re.search(r"mamba/(w_z|w_xbc)$", path):
            return spec(fsdp and self._maybe(fsdp, shape[-2]), self._maybe(tp, shape[-1]))
        if re.search(r"mamba/out_proj$", path):
            return spec(self._maybe(tp, shape[-2]), fsdp and self._maybe(fsdp, shape[-1]))
        if re.search(r"mamba/w_dt$", path):
            return spec(fsdp and self._maybe(fsdp, shape[-2]), None)
        if re.search(r"mamba/conv_w$", path):
            return spec(None, self._maybe(tp, shape[-1]))
        if re.search(r"mamba/(conv_b|norm_scale)$", path):
            return spec(self._maybe(tp, shape[-1]))

        # ---- everything else (norms, scalars): replicated.
        return P()

    @staticmethod
    def _is_stacked(path: str, shape) -> bool:
        return any(seg in path for seg in ("blocks/", "dense_blocks/"))

    def params_shardings(self, params_shapes) -> Any:
        """NamedShardings matching a pytree of ShapeDtypeStruct/arrays."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
        out = []
        for keypath, leaf in flat:
            path = "/".join(_key_str(k) for k in keypath)
            out.append(NamedSharding(self.mesh, self.param_spec(path, leaf.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------ data
    def batch_spec_axes(self, batch_size: int):
        """Largest prefix of the batch axes that divides batch_size
        (long_500k has global_batch == 1: replicate)."""
        axes = []
        size = 1
        for a in self.batch_axes:
            if batch_size % (size * self.mesh.shape[a]) == 0:
                axes.append(a)
                size *= self.mesh.shape[a]
        return tuple(axes) or None

    def data_spec(self, shape: tuple[int, ...]) -> P:
        """Token-like inputs: batch over (pod, data) when divisible."""
        return P(self.batch_spec_axes(shape[0]), *(None,) * (len(shape) - 1))

    def data_shardings(self, tree) -> Any:
        return jax.tree_util.tree_map(
            lambda leaf: NamedSharding(self.mesh, self.data_spec(leaf.shape)),
            tree,
        )

    # ------------------------------------------------------------ caches
    def cache_spec(self, path: str, shape: tuple[int, ...]) -> P:
        """KV / SSM / latent caches.  Leading axis is the stacked layer axis
        for trunk caches; batch comes next."""
        cfg = self.cfg
        tp = self.model_axis
        leaf = path.split("/")[-1]
        if leaf in ("length", "pos"):
            return P(*(None,) * len(shape))

        def bsp(batch_dim_from_end: int):
            return self.batch_spec_axes(shape[-batch_dim_from_end])

        if leaf in ("k", "v") or "cross_kv" in path:
            # (L, B, C, K, D): kv-heads on model if divisible, else head_dim.
            kh, hd = shape[-2], shape[-1]
            if _div(kh, self._axis_size(tp)):
                return P(*(None,) * (len(shape) - 4), bsp(4), None, tp, None)
            return P(
                *(None,) * (len(shape) - 4), bsp(4), None, None,
                self._maybe(tp, hd),
            )
        if leaf in ("ckv", "k_rope"):
            # MLA latent: batch + latent dim (61L x 128B x 32k x 576 is NOT
            # tiny — 295 GB at decode_32k; model-shard the latent dim).
            return P(
                *(None,) * (len(shape) - 3), bsp(3), None,
                self._maybe(tp, shape[-1]),
            )
        if leaf == "ssm":
            # (L, B, H, P, N): heads on model if divisible else P dim.
            h, pdim = shape[-3], shape[-2]
            if _div(h, self._axis_size(tp)):
                return P(*(None,) * (len(shape) - 4), bsp(4), tp, None, None)
            return P(
                *(None,) * (len(shape) - 4), bsp(4), None,
                self._maybe(tp, pdim), None,
            )
        if leaf == "conv":
            return P(
                *(None,) * (len(shape) - 3), bsp(3), None,
                self._maybe(tp, shape[-1]),
            )
        return P(*(None,) * len(shape))

    def cache_shardings(self, cache_shapes) -> Any:
        flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
        out = []
        for keypath, leaf in flat:
            path = "/".join(_key_str(k) for k in keypath)
            out.append(NamedSharding(self.mesh, self.cache_spec(path, leaf.shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------ placement
    def shard_params(self, params) -> Any:
        """Place a concrete param pytree per :meth:`param_spec` (the serving
        entry point: ``TierExecutor`` calls this once at construction)."""
        return jax.device_put(params, self.params_shardings(params))

    def shard_caches(self, caches) -> Any:
        """Place a concrete cache pytree per :meth:`cache_spec`.  Serving
        callers run it on freshly initialized caches; sharded decode steps
        then keep the layouts through XLA's propagation."""
        return jax.device_put(caches, self.cache_shardings(caches))

    # ------------------------------------------------------------ optimizer
    def opt_state_shardings(self, params_shapes, optimizer_name: str) -> Any:
        """Shardings for the optimizer state pytree.

        AdamW's m/v mirror the params; Adafactor's factored vr/vc drop the
        last / second-to-last param axis from the spec.
        """
        if optimizer_name == "adamw":
            ps = self.params_shardings(params_shapes)
            return {"m": ps, "v": ps}
        if optimizer_name != "adafactor":
            raise ValueError(optimizer_name)

        flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
        out = []
        for keypath, leaf in flat:
            path = "/".join(_key_str(k) for k in keypath)
            spec = tuple(self.param_spec(path, leaf.shape)) + (None,) * (
                len(leaf.shape) - len(self.param_spec(path, leaf.shape))
            )
            spec = spec[: len(leaf.shape)]
            if len(leaf.shape) >= 2:
                out.append(
                    {
                        "vr": NamedSharding(self.mesh, P(*spec[:-1])),
                        "vc": NamedSharding(self.mesh, P(*spec[:-2], spec[-1])),
                    }
                )
            else:
                out.append({"v": NamedSharding(self.mesh, P(*spec))})
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------ misc
    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def logits_spec(self) -> P:
        return P(self.batch_axes, None, self._maybe("model", self.cfg.vocab_size))


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def make_policy(mesh: Mesh, cfg: ModelConfig) -> ShardingPolicy:
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return ShardingPolicy(mesh=mesh, cfg=cfg, batch_axes=batch_axes)
