"""Kernel microbenchmarks: kernel-vs-jnp decode hot path sweep.

Two parts:

Part 1 (reference timings): wall-clock of the pure-jnp reference paths at
serving-scale shapes — what a CPU host actually executes, and the
baseline the Pallas kernels must beat on TPU.

Part 2 (kernel-vs-jnp decode-step sweep): for each decode hot spot the
``use_kernels`` plumbing swaps, time BOTH paths across batch x bucket
(survivor sub-batch width) x cache length, plus the end-to-end
``TierExecutor`` decode step with kernels on/off:

  * flash_decode: Pallas survivor-row streaming vs jnp gather +
    flash_attention (the attn_apply decode branch);
  * entropy_exit_argmax: the fused exit decision vs inline
    normalized_entropy + argmax (the TierExecutor branch masking);
  * ssd_update: the Pallas SSD step vs models.mamba.ssd_step;
  * tier_step: a full K=2 bucketed TierExecutor decode step.

On CPU the kernels run in *interpret mode*, so their absolute numbers are
meaningless (orders of magnitude slow) — the sweep's value off-TPU is (a)
CI proof that every kernel path executes end to end at serving shapes and
(b) the harness the profiler/cost layer will point at a real TPU to get
kernel-true ``compute_j`` timings for the lattice solver.  The jnp column
is the honest CPU cost either way.

Output rows: ``name,shape,us_kernel,us_jnp`` (Part 2) appended after the
Part 1 ``name,us,impl`` rows.

Run:  PYTHONPATH=src python benchmarks/kernel_micro.py
Fast CI smoke:  REPRO_BENCH_FAST=1 PYTHONPATH=src python benchmarks/kernel_micro.py
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.launch.compile_cache import use_compile_cache

FAST = bool(int(os.environ.get("REPRO_BENCH_FAST", "0")))


def _time(fn, *args, iters=20, warmup=3) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run_reference() -> list[str]:
    """Part 1: jnp reference paths at serving-scale shapes."""
    rows = []
    key = jax.random.PRNGKey(0)

    # Entropy-exit over a Qwen3-sized vocab (the per-branch confidence test).
    logits = jax.random.normal(key, (64, 151_936), jnp.float32)
    f = jax.jit(lambda x: ref.entropy_exit_ref(x, 0.5))
    rows.append(f"kernel/entropy_exit_b64_v152k,{_time(f, logits):.1f},jnp_ref")

    # Flash-decode against a 32k cache (decode_32k per-layer shape).
    q = jax.random.normal(key, (8, 32, 128), jnp.bfloat16)
    k = jax.random.normal(key, (8, 32_768, 8, 128), jnp.bfloat16)
    v = jax.random.normal(key, (8, 32_768, 8, 128), jnp.bfloat16)
    pos = jnp.arange(32_768, dtype=jnp.int32)
    qpos = jnp.asarray(32_768, jnp.int32)
    f = jax.jit(lambda *a: ref.flash_decode_ref(*a))
    rows.append(
        f"kernel/flash_decode_b8_c32k,{_time(f, q, k, v, pos, qpos):.1f},jnp_ref"
    )

    # SSD scan, mamba2-130m block shape, 4k tokens.
    from repro.models.mamba import ssd_chunked

    x = jax.random.normal(key, (2, 4096, 24, 64), jnp.float32) * 0.5
    a = -jnp.abs(jax.random.normal(key, (2, 4096, 24))) * 0.3
    bm = jax.random.normal(key, (2, 4096, 24, 128)) * 0.5
    cm = jax.random.normal(key, (2, 4096, 24, 128)) * 0.5
    f = jax.jit(lambda *args: ssd_chunked(*args, chunk=64))
    rows.append(f"kernel/ssd_chunked_4k,{_time(f, x, a, bm, cm, iters=5):.1f},jnp_chunked")

    return rows


# ------------------------------------------------------- part 2: the sweep
ITERS = 2 if FAST else 10
WARMUP = 1 if FAST else 3
# (full batch resident in the cache, survivor bucket, cache slots)
DECODE_CELLS = (
    [(8, 4, 256)] if FAST
    else [(8, 4, 256), (8, 8, 1024), (16, 4, 1024), (16, 16, 4096)]
)


def _pair(name: str, shape: str, t_kernel: float, t_jnp: float) -> str:
    return f"{name},{shape},{t_kernel:.1f},{t_jnp:.1f}"


def sweep_flash_decode() -> list[str]:
    rows = []
    kh, g, d = 2, 4, 64
    for batch, bucket, cache in DECODE_CELLS:
        ks = jax.random.split(jax.random.PRNGKey(cache + bucket), 3)
        q = jax.random.normal(ks[0], (bucket, kh * g, d), jnp.float32)
        k = jax.random.normal(ks[1], (batch, cache, kh, d), jnp.float32)
        v = jax.random.normal(ks[2], (batch, cache, kh, d), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(cache, dtype=jnp.int32), (batch, cache))
        qpos = jnp.asarray(cache, jnp.int32)
        rows_map = jnp.arange(bucket, dtype=jnp.int32)  # survivors-first order

        t_k = _time(
            lambda: ops.flash_decode(q, k, v, pos, qpos, rows_map),
            iters=ITERS, warmup=WARMUP,
        )
        jf = jax.jit(
            lambda q, k, v, pos, qpos, r: ref.flash_decode_ref(
                q, k, v, pos, qpos, r
            )
        )
        t_j = _time(lambda: jf(q, k, v, pos, qpos, rows_map),
                    iters=ITERS, warmup=WARMUP)
        rows.append(_pair(
            "sweep/flash_decode", f"b{batch}_rows{bucket}_c{cache}", t_k, t_j
        ))
    return rows


def sweep_entropy_exit() -> list[str]:
    rows = []
    from repro.core.calibration import normalized_entropy

    vocab = 2048 if FAST else 32_064
    for batch, bucket, _ in DECODE_CELLS:
        logits = jax.random.normal(
            jax.random.PRNGKey(bucket), (bucket, vocab), jnp.float32
        ) * 4
        t_k = _time(lambda: ops.entropy_exit_argmax(logits, 0.5),
                    iters=ITERS, warmup=WARMUP)
        jf = jax.jit(lambda l: (
            normalized_entropy(l),
            normalized_entropy(l) < 0.5,
            jnp.argmax(l, -1).astype(jnp.int32),
        ))
        t_j = _time(lambda: jf(logits), iters=ITERS, warmup=WARMUP)
        rows.append(_pair(
            "sweep/entropy_exit_argmax", f"rows{bucket}_v{vocab}", t_k, t_j
        ))
    return rows


def sweep_ssd_update() -> list[str]:
    rows = []
    from repro.models.mamba import ssd_step

    h, p, n, g = (4, 64, 32, 1) if FAST else (24, 64, 128, 1)
    for batch, bucket, _ in DECODE_CELLS:
        ks = jax.random.split(jax.random.PRNGKey(batch * bucket), 5)
        hs = jax.random.normal(ks[0], (batch, h, p, n), jnp.float32)
        x = jax.random.normal(ks[1], (bucket, h, p)) * 0.5
        a = -jnp.abs(jax.random.normal(ks[2], (bucket, h))) * 0.3
        bv = jax.random.normal(ks[3], (bucket, g, n)) * 0.5
        cv = jax.random.normal(ks[4], (bucket, g, n)) * 0.5
        rows_map = jnp.arange(bucket, dtype=jnp.int32)
        t_k = _time(lambda: ops.ssd_update(hs, x, a, bv, cv, rows_map),
                    iters=ITERS, warmup=WARMUP)
        jf = jax.jit(lambda hs, x, a, bv, cv, r: ssd_step(hs[r], x, a, bv, cv))
        t_j = _time(lambda: jf(hs, x, a, bv, cv, rows_map),
                    iters=ITERS, warmup=WARMUP)
        rows.append(_pair(
            "sweep/ssd_update", f"b{batch}_rows{bucket}", t_k, t_j
        ))
    return rows


def sweep_entropy_heads() -> list[str]:
    """Multi-head fused exit decision: ONE (K, B, V) kernel launch vs K
    single-head launches over the same stacked logits (per-head outputs
    are bitwise identical by construction — asserted here)."""
    rows = []
    vocab = 2048 if FAST else 32_064
    ks = (3,) if FAST else (2, 3, 5)
    for k in ks:
        for batch, bucket, _ in DECODE_CELLS[:1]:
            logits = jax.random.normal(
                jax.random.PRNGKey(k * 7 + bucket), (k, bucket, vocab),
                jnp.float32
            ) * 4
            th = jnp.linspace(0.3, 0.7, k)
            multi = jax.jit(lambda l: ops.entropy_exit_argmax_heads(l, th))
            single = jax.jit(lambda l: [
                ops.entropy_exit_argmax(l[j], th[j]) for j in range(k)
            ])
            e, f, t = multi(logits)
            for j, (ej, fj, tj) in enumerate(single(logits)):
                np.testing.assert_array_equal(np.asarray(e[j]), np.asarray(ej))
                np.testing.assert_array_equal(np.asarray(f[j]), np.asarray(fj))
                np.testing.assert_array_equal(np.asarray(t[j]), np.asarray(tj))
            t_multi = _time(lambda: multi(logits), iters=ITERS, warmup=WARMUP)
            t_single = _time(lambda: single(logits), iters=ITERS, warmup=WARMUP)
            rows.append(_pair(
                "heads/entropy_exit_argmax_heads",
                f"k{k}_rows{bucket}_v{vocab}", t_multi, t_single,
            ))
    return rows


def sweep_heads_batched() -> list[str]:
    """End-to-end probe-step (all-heads) TierExecutor decode: batched exit
    heads (one stacked projection + one multi-head exit decision) vs the
    sequential per-head path.  Shapes are chosen so the K=5 exit heads
    carry the head-bandwidth term the batching amortizes (d_model 1024,
    16k vocab: each sequential head re-streams the unembedding).  The
    trajectories and exit masks must be bitwise identical; the full run
    asserts the >=1.2x probe-step speedup the batching is for (FAST keeps
    a loose >=1.0 sanity floor — 2 timed steps are too noisy to gate on).
    """
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving import TierExecutor, segments_for_cuts

    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=6,
        branch_layers=(1, 2, 3, 4, 5), d_model=1024, vocab_size=16_384,
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = 8
    steps = 2 if FAST else 8
    times = {}
    trajs = {}
    masks = {}
    for batched in (True, False):
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (5,)),
            batched_heads=batched,
        )
        caches = M.init_caches(cfg, batch, 64)
        tok = jax.random.randint(
            jax.random.PRNGKey(2), (batch, 1), 0, cfg.vocab_size
        )
        ex.probe_next = True
        res, caches = ex.step(tok, 0, caches)  # compile + warm hints
        ex.probe_next = True  # warm the probe-step compile as well
        res, caches = ex.step(res.tokens_dev[:, None], 1, caches)
        t0 = time.perf_counter()
        traj, msk = [], []
        for i in range(steps):
            ex.probe_next = True  # every timed step evaluates all K heads
            res, caches = ex.step(res.tokens_dev[:, None], i + 2, caches)
            traj.append(res.tokens)
            msk.append(res.exited)
        times[batched] = (time.perf_counter() - t0) / steps * 1e6
        trajs[batched], masks[batched] = traj, msk
        assert ex.host_syncs == steps + 2 + ex.overflow_retries
    for a, b in zip(trajs[True], trajs[False]):
        np.testing.assert_array_equal(a, b)  # identical trajectory
    for a, b in zip(masks[True], masks[False]):
        np.testing.assert_array_equal(a, b)  # identical exit masks
    speedup = times[False] / times[True]
    floor = 1.0 if FAST else 1.2
    assert speedup >= floor, (
        f"batched exit heads {speedup:.2f}x vs sequential (floor {floor}x)"
    )
    return [_pair(
        "heads/probe_step_k5", f"b{batch}_steps{steps}",
        times[True], times[False],
    )]


def sweep_tier_step() -> list[str]:
    """End-to-end TierExecutor decode step, kernels on vs off (K=2,
    bucketed compaction, mixed exits on the fixed seed)."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.serving import TierExecutor, segments_for_cuts

    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = 8
    steps = 2 if FAST else 8
    rows = []
    times = {}
    trajs = {}
    for use_kernels in (True, False):
        ex = TierExecutor(
            cfg, params, segments_for_cuts(cfg, (2,)),
            use_kernels=use_kernels,
        )
        caches = M.init_caches(cfg, batch, 64)
        tok = jax.random.randint(
            jax.random.PRNGKey(2), (batch, 1), 0, cfg.vocab_size
        )
        res, caches = ex.step(tok, 0, caches)  # compile + warm hints
        t0 = time.perf_counter()
        traj = []
        for i in range(steps):
            res, caches = ex.step(res.tokens_dev[:, None], i + 1, caches)
            traj.append(res.tokens)
        times[use_kernels] = (time.perf_counter() - t0) / steps * 1e6
        trajs[use_kernels] = traj
        # The contract the sweep certifies: one sync per step either way.
        assert ex.host_syncs == steps + 1 + ex.overflow_retries
    for a, b in zip(trajs[True], trajs[False]):
        np.testing.assert_array_equal(a, b)  # identical trajectory
    rows.append(_pair(
        "sweep/tier_step_k2", f"b{batch}_steps{steps}",
        times[True], times[False],
    ))
    return rows


def run() -> list[str]:
    rows = [] if FAST else run_reference()
    backend = jax.default_backend()
    mode = "compiled" if backend == "tpu" else "interpret"
    rows.append(f"# kernel-vs-jnp decode sweep: backend={backend}, "
                f"kernel mode={mode} (columns: name,shape,us_kernel,us_jnp)")
    rows += sweep_flash_decode()
    rows += sweep_entropy_exit()
    rows += sweep_ssd_update()
    rows.append("# heads/* rows compare batched vs sequential exit heads "
                "(columns: name,shape,us_batched,us_sequential)")
    rows += sweep_entropy_heads()
    rows += sweep_heads_batched()
    rows += sweep_tier_step()
    return rows


if __name__ == "__main__":
    use_compile_cache()
    for r in run():
        print(r)
