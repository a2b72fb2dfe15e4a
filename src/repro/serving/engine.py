"""Batched serving engine with BranchyNet early exits.

The engine owns the jitted prefill closure and a single-tier
:class:`~repro.serving.tiers.TierExecutor` (the K=1 configuration of the
unified runtime: one segment spanning the whole trunk, every side branch
evaluated in place).  It tracks positions and records per-branch exit
statistics — the live measurement that calibrates the partitioner's
``p_k`` (paper Sec. IV-C: "the probability that a sample is classified at
the side branch" is an input-data property, so a serving system must
estimate it online).

Exit masking runs device-resident inside the fused decode step; the loop
performs one host sync per decoded token (down from 3 per branch).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.calibration import calibrate_exit_probs
from repro.launch.mesh import mesh_devices
from repro.models import model as M
from repro.serving.scheduler import ServesRequests
from repro.serving.tiers import StepReport, TierExecutor, segments_for_cuts

__all__ = ["ServingEngine", "ExitStats"]


@dataclasses.dataclass
class ExitStats:
    """Counts of first-exit events per branch across decoded tokens."""

    branch_layers: tuple[int, ...]
    counts: np.ndarray  # (K+1,): per branch + the main head
    entropies: list[np.ndarray]  # per step: (K, B) normalized entropies

    @property
    def total(self) -> int:
        return int(self.counts.sum())

    def exit_fractions(self) -> np.ndarray:
        return self.counts / max(self.total, 1)

    def conditional_probs(self) -> np.ndarray:
        """Sequential conditional p_k (what CostProfile consumes)."""
        alive = float(self.total)
        out = []
        for c in self.counts[:-1]:
            out.append(float(c) / alive if alive > 0 else 0.0)
            alive -= float(c)
        return np.asarray(out)

    def calibrate(self, threshold: float):
        ents = np.concatenate(self.entropies, axis=1)  # (K, steps*B)
        return calibrate_exit_probs(ents, threshold)


@dataclasses.dataclass
class ServingEngine(ServesRequests):
    cfg: ModelConfig
    params: Any
    context_len: int = 4096
    # Decode hot path on the Pallas kernels; None = cfg.use_kernels
    # (still None = auto: kernels on TPU, jnp elsewhere).
    use_kernels: bool | None = None
    # Batched exit heads: one stacked projection + one multi-head fused
    # entropy-exit launch per step (serving.tiers "Batched exit heads").
    heads_batched: bool = True
    # Request-scheduler KV slots for the submit()/run()/drain() API.
    slots: int = 8
    # Device mesh (+ optional explicit ShardingPolicy): run the trunk
    # tensor/expert-parallel — see serving.tiers "Mesh-sharded tier
    # segments".  Params/caches are placed by the executor.
    mesh: Any = None
    sharding: Any = None

    def __post_init__(self):
        cfg = self.cfg
        self._exec = TierExecutor(
            cfg, self.params,
            segments_for_cuts(
                cfg, (), devices=(mesh_devices(self.mesh),) if self.mesh else None
            ),
            use_kernels=self.use_kernels,
            batched_heads=self.heads_batched,
            mesh=self.mesh, sharding=self.sharding,
        )
        # The executor owns the (possibly mesh-placed) param tree; prefill
        # must run on the same placement.
        self.params = self._exec.params
        self._prefill = self._exec._jit(
            lambda params, inputs, caches: M.prefill(params, inputs, cfg, caches),
            "prefill_batch", cache_arg=2,
        )

    @property
    def executor(self) -> TierExecutor:
        return self._exec

    def step(
        self, tok: jax.Array, pos, caches: Any, *, active=None
    ) -> tuple[StepReport, Any]:
        """One fused decode step (the K=1 tier configuration); ``pos`` may
        be per-sequence and ``active`` masks dead request slots — the
        entry points the request scheduler drives."""
        res, caches = self._exec.step(tok, pos, caches, active=active)
        return StepReport(
            res,
            overflow_retries=self._exec.overflow_retries,
            pipeline_fallbacks=self._exec.pipeline_fallbacks,
        ), caches

    def start(self, inputs: dict) -> dict:
        """Prefill a batch of prompts; returns mutable serve state."""
        batch = inputs["tokens"].shape[0]
        prompt_len = inputs["tokens"].shape[1]
        if self.cfg.frontend == "vision":
            prompt_len += self.cfg.num_patches
        caches = self._exec.new_caches(batch, self.context_len)
        logits, caches = self._prefill(self.params, inputs, caches)
        return {
            "caches": caches,
            "pos": prompt_len,
            "last_logits": logits[:, 0],
            "batch": batch,
        }

    def decode(
        self, state: dict, steps: int, *, greedy: bool = True, key=None
    ) -> tuple[np.ndarray, ExitStats]:
        """Decode ``steps`` tokens; returns (tokens (B, steps), exit stats).

        A sequence "exits" at the first branch whose normalized entropy
        clears cfg.exit_threshold; its emitted token comes from that branch
        head (BranchyNet inference, paper Sec. III).
        """
        cfg = self.cfg
        k = len(cfg.branch_layers)
        batch = state["batch"]
        counts = np.zeros(k + 1, dtype=np.int64)
        ents_log: list[np.ndarray] = []
        toks_out = []

        tok = jnp.argmax(state["last_logits"], -1).astype(jnp.int32)[:, None]
        caches = state["caches"]
        pos = state["pos"]
        for _ in range(steps):
            res, caches = self._exec.step(tok, pos, caches)
            pos += 1
            for j, layer in enumerate(cfg.branch_layers):
                counts[j] += int(res.branch_take[layer].sum())
            counts[k] += int((~res.exited).sum())
            ents_log.append(
                np.stack([res.branch_entropy[l] for l in cfg.branch_layers])
                if k else np.zeros((0, batch))
            )
            toks_out.append(res.tokens)
            tok = res.tokens_dev[:, None]

        state["caches"] = caches
        state["pos"] = pos
        state["last_logits"] = res.last_logits
        return np.stack(toks_out, axis=1), ExitStats(
            cfg.branch_layers, counts, ents_log
        )

    @property
    def host_syncs(self) -> int:
        """Device->host syncs performed by decode steps so far."""
        return self._exec.host_syncs
