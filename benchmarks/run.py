"""Benchmark harness — one function per paper table/figure plus the
framework-level tables.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig4 fig6  # subset
"""

from __future__ import annotations

import sys
import traceback


def bench_fig4() -> list[str]:
    from benchmarks.fig4_inference_time import run

    return run()


def bench_fig5() -> list[str]:
    from benchmarks.fig5_partition_layer import run

    return run()


def bench_fig6() -> list[str]:
    from benchmarks.fig6_calibration import run

    return run()


def bench_solver() -> list[str]:
    """Partitioner solver throughput: Dijkstra vs closed-form vs vmapped."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        BranchSpec,
        CostProfile,
        NetworkProfile,
        brute_force_split,
        shortest_path_plan,
        solve_chain_jax,
    )

    rng = np.random.default_rng(0)
    n = 64  # a deep chain (e.g. an 80-layer trunk with branches)
    t_c = np.concatenate([[0.0], rng.uniform(1e-3, 1e-1, n)])
    alpha = rng.uniform(1e3, 1e6, n + 1)
    branches = tuple(BranchSpec(i, 0.3) for i in (8, 16, 32, 48))
    prof = CostProfile(
        t_c=t_c, alpha=alpha, branches=branches, gamma=100.0,
        network=NetworkProfile("bench", 5.85e6),
    )
    iters = 200
    t0 = time.perf_counter()
    for _ in range(iters):
        plan = shortest_path_plan(prof)
    dt_dij = (time.perf_counter() - t0) / iters * 1e6
    t0 = time.perf_counter()
    for _ in range(iters):
        brute_force_split(prof)
    dt_bf = (time.perf_counter() - t0) / iters * 1e6

    # vmapped solve over a 1000-point bandwidth grid.
    p = np.zeros(n + 1)
    for b in branches:
        p[b.after_layer] = b.exit_prob
    bws = jnp.logspace(5, 9, 1000)
    f = jax.jit(
        jax.vmap(
            lambda bw: solve_chain_jax(
                jnp.asarray(t_c), jnp.asarray(alpha), jnp.asarray(p),
                jnp.asarray(100.0), bw,
            )[1]
        )
    )
    jax.block_until_ready(f(bws))
    t0 = time.perf_counter()
    for _ in range(50):
        jax.block_until_ready(f(bws))
    dt_vmap = (time.perf_counter() - t0) / 50 / 1000 * 1e6

    return [
        f"solver/dijkstra_n64,{dt_dij:.1f},split={plan.split_layer}",
        f"solver/closed_form_n64,{dt_bf:.1f},oracle",
        f"solver/vmap_per_point_n64,{dt_vmap:.3f},grid=1000",
    ]


def bench_kernels() -> list[str]:
    from benchmarks.kernel_micro import run

    return run()


def bench_roofline() -> list[str]:
    from benchmarks.roofline import csv_rows

    rows = csv_rows()
    return rows or ["roofline/no_dryrun_results,0.0,run repro.launch.dryrun first"]


def bench_partitioned_serving() -> list[str]:
    """End-to-end partitioned decode on a smoke model: bytes shipped and
    expected latency per split (the paper's system, measured)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.core import BranchSpec, CostProfile, NetworkProfile
    from repro.models import model as M
    from repro.serving.partitioned import PartitionedServer

    cfg = get_smoke_config("phi3_mini_3_8b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    n = cfg.num_layers
    prof = CostProfile(
        t_c=np.concatenate([[0.0], np.full(n, 1e-3)]),
        alpha=np.concatenate([[224 * 224 * 3 * 4.0], np.full(n, cfg.d_model * 2.0)]),
        branches=tuple(BranchSpec(b, 0.5) for b in cfg.branch_layers),
        gamma=10.0,
        network=NetworkProfile("4g", 5.85e6),
    )
    rows = []
    for split in (0, 1, n):
        srv = PartitionedServer(cfg, params, split, cost_profile=prof)
        caches = M.init_caches(cfg, 8, 64)
        tok = jnp.zeros((8, 1), jnp.int32)
        rep, caches = srv.step(tok, 0, caches)  # warm
        t0 = time.perf_counter()
        for i in range(5):
            rep, caches = srv.step(tok, i + 1, caches)
        dt = (time.perf_counter() - t0) / 5 * 1e6
        res = rep.tier_result
        est = "-" if rep.est_latency_s is None else f"{rep.est_latency_s:.5f}"
        rows.append(
            f"serving/partitioned_split{split},{dt:.0f},"
            f"shipped={sum(res.shipped_per_hop)}/8;"
            f"bytes={sum(res.bytes_per_hop):.0f};estT={est}"
        )
    return rows


BENCHES = {
    "fig4": bench_fig4,
    "fig5": bench_fig5,
    "fig6": bench_fig6,
    "solver": bench_solver,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
    "serving": bench_partitioned_serving,
}


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    names = [a for a in sys.argv[1:] if a in BENCHES] or list(BENCHES)
    print("name,us_per_call,derived")
    failures = 0
    for name in names:
        try:
            for row in BENCHES[name]():
                print(row)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name}/FAILED,0.0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
