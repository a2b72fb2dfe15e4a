"""Quickstart: the paper's full loop in one script.

1. Build a (reduced) BranchyNet LM — a phi3-family trunk with 1 side branch.
2. Serve a batch and MEASURE per-branch exit statistics (calibration).
3. Profile per-layer costs, build the cost model (Eq. 1-6).
4. Solve the partitioning as a shortest path (Sec. V, Dijkstra).
5. Deploy the plan on the two-tier PartitionedServer and decode.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import (
    Partitioner,
    build_cost_profile,
    LayerCost,
)
from repro.models import model as M
from repro.serving.engine import ServingEngine
from repro.serving.partitioned import PartitionedServer


def main() -> None:
    # --- 1. model -----------------------------------------------------------
    cfg = get_smoke_config("phi3_mini_3_8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    print(f"model: {cfg.name} (reduced) — {cfg.num_layers} layers, "
          f"branches after {cfg.branch_layers}")

    # --- 2. serve + calibrate -----------------------------------------------
    engine = ServingEngine(cfg, params, context_len=128)
    prompts = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                            cfg.vocab_size)}
    state = engine.start(prompts)
    tokens, stats = engine.decode(state, steps=12)
    print(f"decoded {tokens.shape} tokens; exit fractions per branch+final: "
          f"{np.round(stats.exit_fractions(), 3)}")
    p_k = stats.conditional_probs()
    print(f"calibrated conditional exit probs p_k = {np.round(p_k, 3)}")

    # --- 3. per-layer cost model ---------------------------------------------
    # For the quickstart we use uniform synthetic layer times; see
    # benchmarks/alexnet_profile.py for measured profiles.
    n = cfg.num_layers
    costs = [
        LayerCost(f"block{i}", 0.0, 0.0, cfg.d_model * 2.0, 2e-3)
        for i in range(1, n + 1)
    ]
    profile = build_cost_profile(
        costs,
        branch_positions=cfg.branch_layers,
        exit_probs=p_k,
        network="4g",
        gamma=50.0,
        raw_input_bytes=16 * 4,  # the token prompt
    )

    # --- 4. optimal split (the paper's contribution) -------------------------
    plan = Partitioner(profile).solve()
    print(plan.describe())
    for net in ("3g", "4g", "wifi"):
        alt = Partitioner(profile).with_network(net).solve()
        print(f"  under {net:4s}: split={alt.split_layer} "
              f"E[T]={alt.expected_time_s * 1e3:.2f} ms")

    # --- 5. partitioned serving ----------------------------------------------
    srv = PartitionedServer(cfg, params, plan.split_layer, cost_profile=profile)
    caches = M.init_caches(cfg, 8, 128)
    # re-prefill through the engine cache path for simplicity
    tok = jnp.asarray(tokens[:, -1:])
    pos = int(state["pos"])
    shipped_total = 0
    for i in range(8):
        rep, caches = srv.step(tok, pos + i, caches)
        tok = jnp.asarray(rep.tier_result.tokens[:, None])
        shipped_total += sum(rep.tier_result.shipped_per_hop)
    print(f"partitioned decode: {shipped_total}/64 token-steps crossed the "
          f"cut (the rest exited on the edge)")


if __name__ == "__main__":
    main()
