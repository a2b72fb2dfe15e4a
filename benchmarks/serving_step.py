"""Serving decode-step benchmark: host syncs, wall time, a roofline-style
masked-vs-compacted sweep, and a serial-vs-pipelined overlap cell.

Part 1 (legacy vs fused): before the unified tier runtime, every decode
step crossed the device boundary once per side branch *twice* (entropy
fetch + exit-count fetch) plus once for the survivor count and once for
the tokens — the legacy loop below reproduces that pattern.  The fused
runtime keeps exit masking device-resident and performs exactly ONE
device->host sync per step.

Part 2 (roofline sweep): across batch size x split point x exit regime,
compare the masked runtime (every tier computes the full batch) against
the survivor-compacted runtime (downstream tiers compute a dense
sub-batch padded to the bucket ladder).  Reported downstream FLOPs/step
are analytic (2 * active params per layer per row * rows), so the sweep
shows the *shape* win even on CPU where wall time is noisy; syncs/step
and retry counts come from the executor's own counters.

Part 3 (overlap pipeline): under ``simulate_network=True`` with a
transfer-dominated K=3 profile, the serial runtime pays the chain sum
``compute + sum_j(transfer_j)`` per decode step while
``overlap="pipelined"`` pays the bottleneck stage
``max_j(compute_j, transfer_j)``; the cell asserts pipelined <= serial,
that the pipelined wall time agrees with
``expected_time_multitier(..., overlap=True)``, and that the cost model's
optimal cut *moves* when solved under overlap (the plan flip that
motivates re-solving on pipelined deployments).

Part 4 (continuous batching): a Poisson-arrival stream of mixed prompt
lengths and token budgets with early exits enabled, served twice through
the SAME warmed server — once with gang (lock-step wave) admission, once
with continuous admission into recycled KV slots.  Continuous batching
retires finished/early-exited requests mid-flight and prefill-admits the
queue into the freed rows, so the same useful tokens take fewer decode
steps: the cell reports tokens/sec and p50/p95 TTFT per policy and
asserts continuous > lock-step throughput at one host sync per decode
step.

Part 5 (faults): a scripted mid-run link flap on hop 1 of a K=3 serving
stack with the fault plane armed (seeded LinkFaultModel + HopPolicy) and
a RepartitionController ingesting hop health.  Retries exhaust, the
breaker opens, survivors finalize from the deepest exit head below the
broken hop (degraded tokens — still real tokens), and the controller
re-solves to cuts that ship zero bytes on the sick link.  The cell
reports tokens/sec, the degraded-token fraction, and the fault re-solve
count, and asserts every request completes with no leaked KV slots.

Run:  PYTHONPATH=src python benchmarks/serving_step.py
Fast CI smoke:  REPRO_BENCH_FAST=1 PYTHONPATH=src python benchmarks/serving_step.py
Overlap cell only:  REPRO_BENCH_ONLY=overlap PYTHONPATH=src python benchmarks/serving_step.py
Request cell only:  REPRO_BENCH_ONLY=requests PYTHONPATH=src python benchmarks/serving_step.py
Fault cell only:  REPRO_BENCH_ONLY=faults PYTHONPATH=src python benchmarks/serving_step.py
"""

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench_io import BenchBundle
from repro.configs import get_smoke_config
from repro.core import LayerCost, build_cost_profile
from repro.core.multitier import TierSpec, expected_time_multitier, solve_multitier
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.serving import (
    FlapWindow,
    HopPolicy,
    LinkFaultModel,
    MultiTierServer,
    PartitionedServer,
    RepartitionController,
    RequestScheduler,
)

FAST = bool(int(os.environ.get("REPRO_BENCH_FAST", "0")))
ONLY = os.environ.get("REPRO_BENCH_ONLY", "")

CONTEXT = 128
STEPS = 8 if FAST else 32
WARMUP = 2 if FAST else 4
BATCH = 8  # part-1 batch
SWEEP_BATCHES = (8,) if FAST else (8, 16)
SWEEP_SPLITS = (2,) if FAST else (1, 2, 3)
#: exit regimes: threshold -> expected exit-rate band
REGIMES = (
    (("all-exit", 1.5),) if FAST
    else (("no-exit", 0.0), ("all-exit", 1.5))
)


class SyncCounter:
    """Counts device->host fetches the way the legacy loop caused them."""

    def __init__(self):
        self.count = 0

    def __call__(self, x):
        self.count += 1
        return np.asarray(x)


def legacy_step(decode, params, cfg, tok, pos, caches, sync):
    """The pre-refactor decode step: monolithic jitted forward, then
    per-branch host round trips for entropy logging, exit counting, and
    selection."""
    out = decode(params, tok, jnp.asarray(pos, jnp.int32), caches)
    chosen = jnp.argmax(out["logits"], -1).astype(jnp.int32)
    exited = jnp.zeros(chosen.shape, bool)
    for layer in cfg.branch_layers:
        sync(out["branch_entropy"][layer])  # stats logging fetch
        b_tok = jnp.argmax(out["branch_logits"][layer], -1).astype(jnp.int32)
        take = out["branch_exit"][layer] & ~exited
        int(sync(take).sum())  # per-branch exit count fetch
        chosen = jnp.where(take, b_tok, chosen)
        exited = exited | out["branch_exit"][layer]
    int(sync(~exited).sum())  # survivor count fetch
    toks = sync(chosen)  # token fetch
    return toks, out["caches"]


def run_legacy(cfg, params):
    decode = jax.jit(
        lambda params, tok, pos, caches: M.decode_step(params, tok, pos,
                                                       caches, cfg)
    )
    sync = SyncCounter()
    caches = M.init_caches(cfg, BATCH, CONTEXT)
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    for i in range(WARMUP):
        toks, caches = legacy_step(decode, params, cfg, tok, i, caches,
                                   SyncCounter())
        tok = jnp.asarray(toks[:, None])
    t0 = time.perf_counter()
    for i in range(WARMUP, WARMUP + STEPS):
        toks, caches = legacy_step(decode, params, cfg, tok, i, caches, sync)
        tok = jnp.asarray(toks[:, None])
    dt = time.perf_counter() - t0
    return dt / STEPS, sync.count / STEPS


def run_fused(cfg, params, split, *, batch=BATCH, compaction="bucketed",
              steps=STEPS, warmup=WARMUP):
    """Returns (ms/step, syncs/step, retries, mean survivors, mean bucket,
    mean exit rate) over the measured steps."""
    srv = PartitionedServer(cfg, params, split, compaction=compaction)
    caches = M.init_caches(cfg, batch, CONTEXT)
    tok = jnp.zeros((batch, 1), jnp.int32)
    for i in range(warmup):
        rep, caches = srv.step(tok, i, caches)
        tok = jnp.asarray(rep.tokens[:, None])
    start_syncs = srv.executor.host_syncs
    start_retries = srv.executor.overflow_retries
    surv, buck, exit_rate = [], [], []
    t0 = time.perf_counter()
    for i in range(warmup, warmup + steps):
        rep, caches = srv.step(tok, i, caches)
        tok = jnp.asarray(rep.tokens[:, None])
        exit_rate.append(float(rep.exited_on_edge.mean()))
        if rep.compaction:
            surv.append(rep.compaction[0].survivors)
            buck.append(rep.compaction[0].bucket)
    dt = time.perf_counter() - t0
    return (
        dt / steps * 1e3,
        (srv.executor.host_syncs - start_syncs) / steps,
        srv.executor.overflow_retries - start_retries,
        float(np.mean(surv)) if surv else 0.0,
        float(np.mean(buck)) if buck else 0.0,
        float(np.mean(exit_rate)),
    )


def downstream_flops_per_row(cfg, split):
    """Analytic decode FLOPs per sequence-row for the layers after the
    split (2 FLOPs per MAC on the active matmul params, shared with
    ModelConfig's parameter accounting)."""
    assert cfg.arch_type in ("dense", "vlm"), (
        "per-row FLOPs formula only covers dense trunks; extend via "
        "ModelConfig helpers before sweeping other arch types"
    )
    per_layer = cfg.attn_matmul_params() + cfg.dense_mlp_matmul_params()
    layers_dn = cfg.num_layers - split
    head = cfg.d_model * cfg.padded_vocab_size if layers_dn > 0 else 0
    return 2.0 * (layers_dn * per_layer + head)


def part1_legacy_vs_fused(cfg, params, bundle):
    total = cfg.num_layers
    t_old, s_old = run_legacy(cfg, params)
    # Like-for-like wall-time comparison: edge-only (split == L) evaluates
    # the same branch set + final head as the legacy monolithic loop, so
    # the delta is sync elimination, not skipped branch compute.
    t_new, s_new, r_new, *_ = run_fused(cfg, params, total)
    # The shipped configuration: a mid split (the cloud tier evaluates no
    # branches, so its compute differs from legacy — sync count is the
    # comparable number here, not wall time).
    t_mid, s_mid, r_mid, *_ = run_fused(cfg, params, 2)

    print(f"\n{'path':<30}{'ms/step':>10}{'host syncs/step':>18}")
    print(f"{'legacy per-branch loop':<30}{t_old * 1e3:>10.3f}{s_old:>18.1f}")
    print(f"{'fused runtime (edge-only)':<30}{t_new:>10.3f}{s_new:>18.1f}")
    print(f"{'fused runtime (split=2)':<30}{t_mid:>10.3f}{s_mid:>18.1f}")
    print(f"\nlike-for-like speedup {t_old * 1e3 / t_new:.2f}x, "
          f"syncs {s_old:.0f} -> {s_new:.0f}")

    # The invariant the serving tests and ROADMAP claim: one sync per
    # decode step.  Overflow-retry steps legitimately pay one extra
    # (counted) sync, so the assertion is exact accounting, not a flake:
    # syncs == steps + retries, with retries == 0 in the steady state here.
    assert s_new == 1.0 + r_new / STEPS, (
        f"edge-only: {s_new} syncs/step with {r_new} retries")
    assert s_mid == 1.0 + r_mid / STEPS, (
        f"split=2: {s_mid} syncs/step with {r_mid} retries")
    assert s_old >= 2 + 2 * len(cfg.branch_layers) - 1e-9
    print(f"OK: fused partitioned decode performs exactly 1 host sync/step "
          f"(+{r_new + r_mid} overflow retries)")
    bundle.cell(
        "legacy_vs_fused",
        config=dict(batch=BATCH, steps=STEPS, fast=FAST),
        strict=dict(
            legacy_syncs_per_step=s_old,
            fused_edge_syncs_per_step=s_new,
            fused_split2_syncs_per_step=s_mid,
            overflow_retries=r_new + r_mid,
        ),
        timing=dict(
            legacy_ms_step=t_old * 1e3,
            fused_edge_ms_step=t_new,
            fused_split2_ms_step=t_mid,
        ),
    )


def part2_roofline_sweep(cfg0, params, bundle):
    print("\n== roofline sweep: masked vs survivor-compacted downstream "
          "FLOPs/step ==")
    hdr = (f"{'B':>3} {'split':>5} {'regime':>9} {'exit%':>6} "
           f"{'surv':>5} {'bucket':>6} "
           f"{'GF/step masked':>15} {'GF/step compact':>16} {'save':>6} "
           f"{'ms mask':>8} {'ms comp':>8} {'syncs':>6} {'retry':>6}")
    print(hdr)
    checked_50 = False
    for batch in SWEEP_BATCHES:
        for split in SWEEP_SPLITS:
            for name, thr in REGIMES:
                cfg = dataclasses.replace(cfg0, exit_threshold=thr)
                t_m, s_m, _, _, _, _ = run_fused(
                    cfg, params, split, batch=batch, compaction="off",
                    steps=max(4, STEPS // 2), warmup=WARMUP,
                )
                (t_c, s_c, retries, surv, buck, exit_rate) = run_fused(
                    cfg, params, split, batch=batch,
                    steps=max(4, STEPS // 2), warmup=WARMUP,
                )
                fpr = downstream_flops_per_row(cfg, split)
                gf_masked = fpr * batch / 1e9
                gf_comp = fpr * (buck if buck else batch) / 1e9
                save = 1.0 - gf_comp / gf_masked if gf_masked else 0.0
                print(f"{batch:>3} {split:>5} {name:>9} "
                      f"{exit_rate * 100:>5.0f}% {surv:>5.1f} {buck:>6.1f} "
                      f"{gf_masked:>15.3f} {gf_comp:>16.3f} "
                      f"{save * 100:>5.0f}% {t_m:>8.2f} {t_c:>8.2f} "
                      f"{s_c:>6.2f} {retries:>6}")
                bundle.cell(
                    f"roofline_b{batch}_s{split}_{name}",
                    config=dict(batch=batch, split=split, regime=name,
                                fast=FAST),
                    strict=dict(
                        exit_rate=round(exit_rate, 6),
                        survivors=surv, bucket=buck,
                        gf_step_masked=round(gf_masked, 6),
                        gf_step_compact=round(gf_comp, 6),
                        syncs_per_step=s_c, overflow_retries=retries,
                    ),
                    timing=dict(ms_step_masked=t_m, ms_step_compact=t_c),
                )
                assert s_m == 1.0, "masked path must stay at 1 sync/step"
                # Acceptance: at exit rates >= 0.5 the downstream tier's
                # FLOPs scale with the padded survivor count, not with B.
                if exit_rate >= 0.5 and split < cfg.num_layers:
                    assert gf_comp <= gf_masked / 2 + 1e-9, (
                        f"expected >=2x downstream FLOPs saving at exit rate "
                        f"{exit_rate:.2f}: masked {gf_masked}, compacted {gf_comp}"
                    )
                    checked_50 = True
    if checked_50:
        print("OK: downstream FLOPs scale with padded survivors "
              "(>=2x saving at exit rate >= 0.5)")


def _plan_flip_cell() -> dict:
    """Cost-model cell (no wall clock): on a profile whose transfers shrink
    with depth, the serial optimum hides on the edge (ship nothing) while
    the overlap optimum moves the cut forward — transfers below the
    bottleneck stage are free when pipelined."""
    t_c = np.array([0.0, 0.01, 0.01, 0.01, 0.01])
    alpha = np.array([80e3, 40e3, 20e3, 10e3, 5e3])
    p = np.zeros(5)
    tiers = [TierSpec("edge", 2.0, 4e6), TierSpec("cloud", 1.0)]
    print(f"\n{'cut':>4} {'serial ms':>10} {'pipelined ms':>13}")
    for s in range(len(t_c)):
        ser = expected_time_multitier(t_c, alpha, p, tiers, (s,))
        ovl = expected_time_multitier(t_c, alpha, p, tiers, (s,), overlap=True)
        print(f"{s:>4} {ser * 1e3:>10.1f} {ovl * 1e3:>13.1f}")
    plan_s = solve_multitier(t_c, alpha, p, tiers)
    plan_o = solve_multitier(t_c, alpha, p, tiers, overlap=True)
    print(f"serial plan: cut {plan_s.cut_after} "
          f"(E[T] {plan_s.expected_time_s * 1e3:.1f} ms) -> "
          f"pipelined plan: cut {plan_o.cut_after} "
          f"(E[T]/step {plan_o.expected_time_s * 1e3:.1f} ms)")
    assert plan_o.cut_after != plan_s.cut_after, (
        "expected the optimal cut to move under overlap on this profile"
    )
    assert plan_o.expected_time_s <= plan_s.expected_time_s + 1e-12
    print("OK: the optimal cut moves when transfers overlap compute")
    return dict(
        serial_cut=list(plan_s.cut_after),
        pipelined_cut=list(plan_o.cut_after),
        serial_est_ms=round(plan_s.expected_time_s * 1e3, 6),
        pipelined_est_ms=round(plan_o.expected_time_s * 1e3, 6),
    )


def _run_overlap(cfg, params, tiers, cuts, overlap, *, batch, steps, warmup):
    """Measured ms/step of a simulated-uplink K=3 server; the pipelined
    variant's trailing transfers are drained inside the timed region so
    both modes account for identical total work."""
    srv = MultiTierServer(
        cfg, params, tiers, cuts, simulate_network=True, overlap=overlap
    )
    caches = M.init_caches(cfg, batch, CONTEXT)
    tok = jnp.zeros((batch, 1), jnp.int32)
    for i in range(warmup):
        rep, caches = srv.step(tok, i, caches)
        tok = jnp.asarray(rep.tokens[:, None])
    srv.executor.drain()
    t0 = time.perf_counter()
    for i in range(warmup, warmup + steps):
        rep, caches = srv.step(tok, i, caches)
        tok = jnp.asarray(rep.tokens[:, None])
    srv.executor.drain()
    dt = time.perf_counter() - t0
    return dt / steps * 1e3, rep.sim_transfer_s


def part3_overlap_pipeline(cfg0, params, bundle):
    print("\n== overlap cell: serial vs pipelined tier runtime "
          "(simulate_network=True) ==")
    flip = _plan_flip_cell()

    # Transfer-dominated K=3 smoke: no exits, so every sequence crosses
    # both hops and the transfer sizes are deterministic.
    cfg = dataclasses.replace(cfg0, exit_threshold=0.0)
    batch = BATCH
    steps = 6 if FAST else 12
    per_seq = cfg.d_model * 2.0
    hop_s = (0.09, 0.05)  # target per-hop transfer seconds at full batch
    tiers = [
        TierSpec("device", 1.0, per_seq * batch * 8.0 / hop_s[0]),
        TierSpec("edge", 1.0, per_seq * batch * 8.0 / hop_s[1]),
        TierSpec("cloud", 1.0),
    ]
    cuts = (2, 3)
    t_serial, sim = _run_overlap(
        cfg, params, tiers, cuts, "serial",
        batch=batch, steps=steps, warmup=WARMUP,
    )
    t_pipe, _ = _run_overlap(
        cfg, params, tiers, cuts, "pipelined",
        batch=batch, steps=steps, warmup=WARMUP,
    )
    # Compute-only baseline calibrates the cost model's t_c (uniform
    # per-layer split of the measured masked step on this host).
    srv = MultiTierServer(cfg, params, tiers, cuts)
    caches = M.init_caches(cfg, batch, CONTEXT)
    tok = jnp.zeros((batch, 1), jnp.int32)
    for i in range(WARMUP):
        rep, caches = srv.step(tok, i, caches)
        tok = jnp.asarray(rep.tokens[:, None])
    t0 = time.perf_counter()
    for i in range(WARMUP, WARMUP + steps):
        rep, caches = srv.step(tok, i, caches)
        tok = jnp.asarray(rep.tokens[:, None])
    t_comp = (time.perf_counter() - t0) / steps

    n = cfg.num_layers
    t_c = np.concatenate([[0.0], np.full(n, t_comp / n)])
    alpha = np.full(n + 1, per_seq * batch)  # full batch crosses every hop
    p = np.zeros(n + 1)
    est_serial = expected_time_multitier(t_c, alpha, p, tiers, cuts)
    est_pipe = expected_time_multitier(t_c, alpha, p, tiers, cuts,
                                       overlap=True)
    print(f"\n{'mode':<12} {'ms/step':>9} {'est ms/step':>12} "
          f"(hop transfers {tuple(round(s * 1e3) for s in sim)} ms)")
    print(f"{'serial':<12} {t_serial:>9.1f} {est_serial * 1e3:>12.1f}")
    print(f"{'pipelined':<12} {t_pipe:>9.1f} {est_pipe * 1e3:>12.1f}")

    assert t_pipe <= t_serial, (
        f"pipelined steady-state step ({t_pipe:.1f} ms) must not exceed "
        f"serial ({t_serial:.1f} ms)"
    )
    # The pipelined wall time tracks the bottleneck stage, not the serial
    # sum: agreement with the overlap cost model within a pipeline-fill
    # tolerance (compute overhead + the non-bottleneck hop's tail).
    slack = 1e3 * (t_comp + min(hop_s)) + 0.25 * est_pipe * 1e3
    assert abs(t_pipe - est_pipe * 1e3) <= slack, (
        f"pipelined {t_pipe:.1f} ms/step vs overlap estimate "
        f"{est_pipe * 1e3:.1f} ms/step (slack {slack:.1f})"
    )
    assert t_serial >= est_pipe * 1e3  # serial pays at least the bottleneck
    print(f"OK: pipelined step tracks max_j(compute_j, transfer_j) "
          f"({t_pipe:.1f} ms vs est {est_pipe * 1e3:.1f} ms; serial pays "
          f"{t_serial:.1f} ms)")
    bundle.cell(
        "overlap_pipeline",
        config=dict(batch=batch, steps=steps, cuts=list(cuts),
                    hop_s=list(hop_s), fast=FAST),
        strict=flip,
        timing=dict(serial_ms_step=t_serial, pipelined_ms_step=t_pipe,
                    est_pipelined_ms_step=est_pipe * 1e3),
    )


def _mixed_threshold(cfg, params, batch=8):
    """Threshold between observed branch entropies -> deterministic mixed
    exits on the fixed seed (some tokens exit early, some don't)."""
    srv = PartitionedServer(cfg, params, cfg.num_layers)
    caches = M.init_caches(cfg, batch, CONTEXT)
    tok = jnp.zeros((batch, 1), jnp.int32)
    rep, _ = srv.step(tok, 0, caches)
    ents = np.concatenate(
        [rep.tier_result.branch_entropy[l] for l in cfg.branch_layers]
    )
    return float((ents.min() + ents.max()) / 2)


def _request_workload(cfg, n, seed=0):
    """Poisson arrivals (1 per step on average), mixed prompt lengths and
    budgets, half the requests retiring at their first early exit."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0, size=n)).astype(int)
    work = []
    for i in range(n):
        plen = int(rng.choice((4, 8)))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        work.append(dict(
            prompt=prompt,
            max_new_tokens=int(rng.integers(2, 9)),
            stop_on_exit=bool(i % 2),
            arrival_step=int(arrivals[i]),
        ))
    return work


def _run_requests(srv, slots, work, policy):
    """Serve the workload through a fresh scheduler on the (shared, warm)
    server; returns (steps, wall_s, tokens, ttft list, sync delta, retry
    delta)."""
    sched = RequestScheduler(srv, slots, CONTEXT, policy=policy)
    syncs0 = srv.executor.host_syncs
    retries0 = srv.executor.overflow_retries
    t0 = time.perf_counter()
    for w in work:
        sched.submit(w["prompt"], w["max_new_tokens"],
                     stop_on_exit=w["stop_on_exit"],
                     arrival_step=w["arrival_step"])
    results = sched.drain()
    dt = time.perf_counter() - t0
    ttfts = [r.ttft_s for r in results]
    return (
        sched.decode_steps, dt, sched.total_tokens, ttfts,
        srv.executor.host_syncs - syncs0,
        srv.executor.overflow_retries - retries0,
    )


def part4_continuous_batching(cfg0, params, bundle):
    print("\n== continuous batching: lock-step (gang) waves vs request "
          "admission into recycled KV slots ==")
    cfg = dataclasses.replace(
        cfg0, exit_threshold=_mixed_threshold(cfg0, params)
    )
    slots = 4 if FAST else 8
    n_req = 10 if FAST else 32
    srv = PartitionedServer(cfg, params, 2, slots=slots, context_len=CONTEXT)
    work = _request_workload(cfg, n_req)
    # Warm every (prompt-len, group) prefill shape and decode bucket once:
    # both policies then run all-compiled on the shared executor cache.
    for policy in ("gang", "continuous"):
        _run_requests(srv, slots, work, policy)

    rows = {}
    for policy in ("gang", "continuous"):
        # Best-of-2 timed passes: the step-count win is deterministic,
        # the wall-clock one shouldn't flake on a noisy CI runner.
        best = None
        for _ in range(2):
            r = _run_requests(srv, slots, work, policy)
            if best is None or r[1] < best[1]:
                best = r
        rows[policy] = best
    print(f"\n{'policy':<12} {'steps':>6} {'tokens':>7} {'tok/s':>8} "
          f"{'p50 TTFT ms':>12} {'p95 TTFT ms':>12} {'syncs/step':>11}")
    for policy, (steps, dt, toks, ttfts, syncs, retries) in rows.items():
        print(f"{policy:<12} {steps:>6} {toks:>7} {toks / dt:>8.1f} "
              f"{np.percentile(ttfts, 50) * 1e3:>12.1f} "
              f"{np.percentile(ttfts, 95) * 1e3:>12.1f} "
              f"{syncs / max(steps, 1):>11.2f}")

    g_steps, g_dt, g_toks, _, g_syncs, g_retries = rows["gang"]
    c_steps, c_dt, c_toks, _, c_syncs, c_retries = rows["continuous"]
    assert g_toks == c_toks, "both policies decode the same useful tokens"
    assert c_steps < g_steps, (
        f"continuous admission must need fewer decode steps "
        f"({c_steps} vs {g_steps})"
    )
    assert c_toks / c_dt > g_toks / g_dt, (
        f"continuous batching must beat lock-step throughput "
        f"({c_toks / c_dt:.1f} vs {g_toks / g_dt:.1f} tok/s)"
    )
    # The decode loop's contract survives admission/retirement churn:
    # exactly one device->host sync per decode step (+ counted retries).
    assert c_syncs == c_steps + c_retries, (
        f"continuous loop: {c_syncs} syncs over {c_steps} steps "
        f"({c_retries} retries)"
    )
    print(f"OK: continuous admission decodes the same {c_toks} tokens in "
          f"{c_steps} steps vs lock-step's {g_steps} "
          f"({c_toks / c_dt / (g_toks / g_dt):.2f}x tokens/sec) at 1 "
          f"sync/step")
    for policy, (steps, dt, toks, ttfts, syncs, retries) in rows.items():
        bundle.cell(
            f"requests_{policy}",
            config=dict(slots=slots, requests=n_req, fast=FAST),
            strict=dict(
                decode_steps=steps, tokens=toks,
                syncs_per_step=round(syncs / max(steps, 1), 6),
                overflow_retries=retries,
            ),
            timing=dict(
                tokens_per_s=toks / dt,
                ttft_p50_ms=float(np.percentile(ttfts, 50)) * 1e3,
                ttft_p95_ms=float(np.percentile(ttfts, 95)) * 1e3,
            ),
        )


def part5_faults(cfg0, params, bundle):
    print("\n== fault plane: scripted link flap -> degraded tokens + "
          "availability re-solve ==")
    cfg = dataclasses.replace(
        cfg0, exit_threshold=_mixed_threshold(cfg0, params)
    )
    slots = 4
    n_req = 8 if FAST else 24
    tiers = [
        TierSpec("edge", 4.0, 1e9),
        TierSpec("mid", 2.0, 1e9),
        TierSpec("cloud", 1.0),
    ]
    fault_model = LinkFaultModel(
        seed=0, flaps=(FlapWindow(hop=1, start_step=6, end_step=10_000),)
    )
    policy = HopPolicy(
        timeout_s=0.02, max_retries=1, backoff_s=0.002,
        breaker_threshold=2, breaker_cooldown_steps=3,
    )
    srv = MultiTierServer(
        cfg, params, tiers, (1, 3), simulate_network=True,
        slots=slots, context_len=CONTEXT,
        fault_model=fault_model, hop_policy=policy,
    )
    costs = [LayerCost(f"l{i}", 0, 0, cfg.d_model * 2.0, 1e-3)
             for i in range(cfg.num_layers)]
    profile = build_cost_profile(
        costs, cfg.branch_layers, np.array([0.2, 0.2]), "3g", 50.0, 64.0
    )
    ctl = RepartitionController(srv, profile, tiers=list(tiers))
    work = _request_workload(cfg, n_req, seed=3)

    sched = RequestScheduler(srv, slots, CONTEXT, on_step=[ctl.observe])
    t0 = time.perf_counter()
    for w in work:
        sched.submit(w["prompt"], w["max_new_tokens"],
                     stop_on_exit=w["stop_on_exit"],
                     arrival_step=w["arrival_step"])
    results = sched.drain()
    dt = time.perf_counter() - t0

    tokens = sched.total_tokens
    degraded_tokens = sum(r.degraded_tokens for r in results)
    deg_frac = degraded_tokens / max(tokens, 1)
    ex = srv.executor
    print(f"requests {len(results)}  tokens {tokens}  "
          f"tok/s {tokens / dt:.1f}")
    print(f"degraded tokens {degraded_tokens} ({deg_frac:.1%})  "
          f"degraded steps {ex.degraded_steps}  retries {ex.fault_retries}")
    print(f"fault re-solves {ctl.fault_resolves}  cuts now {srv.cuts}  "
          f"hop health {ctl.hop_health()}")
    assert len(results) == n_req and all(r.done for r in results), \
        "every request must complete despite the dead link"
    assert {r.status for r in results} <= {"ok", "degraded"}
    assert degraded_tokens > 0, "the flap must force degraded tokens"
    assert ctl.fault_resolves >= 1, "breaker-open must trigger a re-solve"
    assert srv.cuts[1] == cfg.num_layers, \
        "the re-solved plan must ship nothing on the sick hop"
    assert sched.active.sum() == 0 and all(
        r is None for r in sched._slot_req
    ), "no leaked KV slots"
    print(f"OK: {n_req} requests survived a hop-1 kill — {deg_frac:.1%} of "
          f"tokens finalized from the fallback head, "
          f"{ctl.fault_resolves} availability re-solve(s)")
    bundle.cell(
        "faults",
        config=dict(slots=slots, requests=n_req, flap_hop=1,
                    flap_start=6, fast=FAST),
        strict=dict(
            requests_done=len(results),
            failed_requests=sum(r.status == "failed" for r in results),
            fault_resolves=ctl.fault_resolves,
            sick_hop_bytes_after_resolve=0.0,
        ),
        timing=dict(
            tokens_per_s=tokens / dt,
            degraded_token_frac=deg_frac,
            degraded_steps=ex.degraded_steps,
            fault_retries=ex.fault_retries,
        ),
    )


def main() -> None:
    use_compile_cache()
    cfg = dataclasses.replace(
        get_smoke_config("qwen3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    print(f"{cfg.name} (reduced): {cfg.num_layers} layers, "
          f"branches {cfg.branch_layers}, batch {BATCH}"
          f"{' [fast mode]' if FAST else ''}")

    bundle = BenchBundle("serving")
    try:
        if ONLY == "overlap":
            part3_overlap_pipeline(cfg, params, bundle)
            return
        if ONLY == "requests":
            part4_continuous_batching(cfg, params, bundle)
            return
        if ONLY == "faults":
            part5_faults(cfg, params, bundle)
            return
        part1_legacy_vs_fused(cfg, params, bundle)
        part2_roofline_sweep(cfg, params, bundle)
        part3_overlap_pipeline(cfg, params, bundle)
        part4_continuous_batching(cfg, params, bundle)
        part5_faults(cfg, params, bundle)
    finally:
        print(f"\nwrote {bundle.write()}")


if __name__ == "__main__":
    main()
