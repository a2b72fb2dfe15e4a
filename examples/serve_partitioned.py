"""End-to-end driver (deliverable b): serve a small BranchyNet LM with
batched requests across simulated tier splits, re-optimizing the partition
as network conditions change.

This is the paper's deployment story: the cost model + Dijkstra run in the
control plane at admission time and whenever bandwidth drifts; the data
plane executes the currently-installed split.  Beyond the paper, the same
unified runtime executes a K=3 lattice plan (device -> edge -> cloud) with
per-hop byte accounting, and repartitioning hot-swaps the cuts without
re-jitting unchanged tier segments.

Run:  PYTHONPATH=src python examples/serve_partitioned.py
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core import LayerCost, Partitioner, build_cost_profile
from repro.core.multitier import TierSpec, solve_multitier
from repro.core.types import NetworkProfile
from repro.models import model as M
from repro.serving import (
    PartitionedServer,
    RepartitionController,
    ServingEngine,
)
from repro.serving.tiers import bytes_per_sequence

BATCH = 16
PROMPT = 24
CONTEXT = 256
DECODE_STEPS = 16

#: The paper's regime: the raw input sample (an image) dwarfs any layer's
#: output, so cuts past the first layers pay off on slow uplinks.  For the
#: LM stand-in we model a vision-style 32 KiB admission payload.
RAW_INPUT_BYTES = 32 * 1024.0

#: Bandwidth schedule the "deployment" experiences (bits/s).
NETWORK_SCHEDULE = [
    ("wifi", 18.8e6),
    ("4g", 5.85e6),
    ("degraded-3g", 0.4e6),
]


def main() -> None:
    key = jax.random.PRNGKey(0)
    cfg = dataclasses.replace(
        get_smoke_config("qwen3_8b"), num_layers=4, branch_layers=(1, 3)
    )
    params = M.init_params(key, cfg)
    n = cfg.num_layers
    print(f"serving {cfg.name} (reduced): {n} layers, branches {cfg.branch_layers}")

    # ---- calibration pass on the unpartitioned engine (K=1 runtime).
    engine = ServingEngine(cfg, params, context_len=CONTEXT)
    prompts = {
        "tokens": jax.random.randint(key, (BATCH, PROMPT), 0, cfg.vocab_size)
    }
    state = engine.start(prompts)
    _, stats = engine.decode(state, steps=8)
    p_k = stats.conditional_probs()
    print(f"calibrated p_k = {np.round(p_k, 3)} "
          f"(fractions {np.round(stats.exit_fractions(), 3)}), "
          f"{engine.host_syncs} host syncs for 8 decode steps")

    # ---- measured per-layer costs (uniform stub; a real deployment uses
    # core.profiler.measure_layer_times on the edge and cloud tiers).
    costs = [LayerCost(f"block{i}", 0, 0, cfg.d_model * 2.0, 1.5e-3)
             for i in range(1, n + 1)]

    # ---- paper system: 2 tiers, repartitioned as bandwidth drifts.  The
    # server is created once; the controller re-solves on each link change
    # and hot-swaps the cut, re-using the compiled segment functions of any
    # previously-installed split.
    profile = build_cost_profile(
        costs, cfg.branch_layers, p_k,
        network=NetworkProfile(*NETWORK_SCHEDULE[0]),
        gamma=25.0, raw_input_bytes=RAW_INPUT_BYTES,
    )
    srv = PartitionedServer(cfg, params, 0, cost_profile=profile)
    ctl = RepartitionController(srv, profile)
    ctl.update(stats)
    for net_name, bw in NETWORK_SCHEDULE:
        (cut,) = ctl.update_network(NetworkProfile(net_name, bw))
        plan = Partitioner(ctl.profile).solve()
        assert plan.split_layer == cut
        print(f"\n== network {net_name} ({bw / 1e6:.2f} Mbps) -> {plan.describe()}")

        caches = M.init_caches(cfg, BATCH, CONTEXT)
        tok = jnp.zeros((BATCH, 1), jnp.int32)
        shipped = 0
        edge_exits = 0
        t0 = time.perf_counter()
        for i in range(DECODE_STEPS):
            rep, caches = srv.step(tok, PROMPT + i, caches)
            res = rep.tier_result
            tok = jnp.asarray(res.tokens[:, None])
            shipped += sum(res.shipped_per_hop)
            edge_exits += int(res.exited.sum())
        dt = time.perf_counter() - t0
        total = BATCH * DECODE_STEPS
        print(
            f"   decoded {total} token-steps in {dt:.2f}s: "
            f"{edge_exits} exited on edge, {shipped} crossed the cut "
            f"({(1 - shipped / total) * 100:.0f}% transfer saved), "
            f"model-estimated E[T]={0.0 if rep.est_latency_s is None else rep.est_latency_s * 1e3:.2f} ms/sample"
        )

    # ---- beyond the paper: K=3 lattice plan on the same unified runtime.
    tiers = [
        TierSpec("device", 60.0, uplink_bps=18.8e6),  # wifi to the edge box
        TierSpec("edge", 12.0, uplink_bps=1.10e6),  # 3g backhaul to the cloud
        TierSpec("cloud", 1.0),
    ]
    profile = build_cost_profile(
        costs, cfg.branch_layers, p_k, "3g", 25.0, RAW_INPUT_BYTES
    )
    plan3 = solve_multitier(
        profile.t_c, profile.alpha, profile.branch_exit_probs(), tiers
    )
    print(f"\n== K=3 lattice plan: cuts after {plan3.cut_after}, "
          f"tier_of_layer {plan3.tier_of_layer}, "
          f"E[T]={plan3.expected_time_s * 1e3:.2f} ms")

    srv3 = PartitionedServer(
        cfg, params, plan3.cut_after, tiers=tiers, cost_profile=profile
    )
    caches = M.init_caches(cfg, BATCH, CONTEXT)
    tok = jnp.zeros((BATCH, 1), jnp.int32)
    hop_bytes = np.zeros(len(tiers) - 1)
    hop_shipped = np.zeros(len(tiers) - 1, int)
    for i in range(DECODE_STEPS):
        rep3, caches = srv3.step(tok, PROMPT + i, caches)
        res3 = rep3.tier_result
        tok = jnp.asarray(res3.tokens[:, None])
        for j in range(len(res3.bytes_per_hop)):
            hop_bytes[j] += res3.bytes_per_hop[j]
            hop_shipped[j] += res3.shipped_per_hop[j]

    # Per-hop byte accounting must match the installed lattice plan: every
    # survivor crossing hop j carries the residual stream of the plan's cut
    # layer (alpha_{c_j}; a cut before layer 1 ships the 4-byte token id).
    for j, cut in enumerate(srv3.cuts[: len(res3.bytes_per_hop)]):
        per_seq = bytes_per_sequence(cfg, cut)
        assert hop_bytes[j] == hop_shipped[j] * per_seq
        if cut > 0:
            assert per_seq == profile.alpha[cut]
        print(f"   hop {tiers[j].name}->{tiers[j + 1].name} (cut after v_{cut}): "
              f"{hop_shipped[j]} survivors, {hop_bytes[j] / 1024:.1f} KiB "
              f"over {tiers[j].uplink_bps / 1e6:.2f} Mbps "
              f"(matches plan alpha)")
    print(f"   last step est E[T]={rep3.est_latency_s * 1e3:.2f} ms/sample, "
          f"exit tiers {np.bincount(res3.exit_tier + 1, minlength=len(tiers) + 1)}")
    # Survivor compaction: each downstream tier ran a dense sub-batch
    # padded to the bucket ladder, not the masked full batch.
    for j, hop in enumerate(res3.compaction):
        print(f"   hop {j}: {hop.survivors} survivors -> bucket {hop.bucket} "
              f"({hop.padded_waste} padding rows), "
              f"{srv3.executor.overflow_retries} overflow retries total")

    # ---- pipelined overlap: the serial runtime pays compute + every hop's
    # transfer per step; overlap="pipelined" overlaps transfers with the
    # next step's compute, so the steady-state step cost is the bottleneck
    # stage max_j(compute_j, transfer_j).  The optimal cut can MOVE under
    # overlap — re-solve with overlap=True before installing.
    plan3o = solve_multitier(
        profile.t_c, profile.alpha, profile.branch_exit_probs(), tiers,
        overlap=True,
    )
    print(f"\n== pipelined K=3: serial plan cuts {plan3.cut_after} "
          f"(E[T] {plan3.expected_time_s * 1e3:.2f} ms) vs overlap plan "
          f"cuts {plan3o.cut_after} "
          f"(E[T]/step {plan3o.expected_time_s * 1e3:.2f} ms)")
    per_seq = bytes_per_sequence(cfg, 2)
    sim_tiers = [  # ~35 ms / ~20 ms per-hop transfers at full batch
        TierSpec("device", 60.0, per_seq * BATCH * 8.0 / 0.035),
        TierSpec("edge", 12.0, per_seq * BATCH * 8.0 / 0.020),
        TierSpec("cloud", 1.0),
    ]
    for overlap in ("serial", "pipelined"):
        srvp = PartitionedServer(
            cfg, params, (2, 3), tiers=sim_tiers, cost_profile=profile,
            simulate_network=True, overlap=overlap,
        )
        caches = M.init_caches(cfg, BATCH, CONTEXT)
        tok = jnp.zeros((BATCH, 1), jnp.int32)
        repp, caches = srvp.step(tok, PROMPT, caches)  # warm the jit
        tok = jnp.asarray(repp.tier_result.tokens[:, None])
        srvp.executor.drain()  # don't time the warmup step's transfers
        t0 = time.perf_counter()
        for i in range(1, DECODE_STEPS):
            repp, caches = srvp.step(tok, PROMPT + i, caches)
            tok = jnp.asarray(repp.tier_result.tokens[:, None])
        srvp.executor.drain()  # account the trailing in-flight transfers
        dt = (time.perf_counter() - t0) / (DECODE_STEPS - 1)
        print(f"   {overlap:<9} {dt * 1e3:7.1f} ms/step "
              f"(sim transfers {tuple(round(s * 1e3) for s in repp.tier_result.sim_transfer_s)} ms, "
              f"est E[T]/step {repp.est_latency_s * 1e3:.2f} ms)")

    # ---- continuous batching on the K=3 plan: a stream of requests with
    # staggered arrivals, mixed prompt lengths and budgets flows through
    # submit()/drain() — finished/early-exited requests retire mid-flight
    # and waiting prompts prefill into the freed KV rows, so nobody waits
    # for a lock-step wave to drain.
    srvr = PartitionedServer(
        cfg, params, plan3.cut_after, tiers=tiers, cost_profile=profile,
        slots=6, context_len=CONTEXT,
    )
    rng = np.random.default_rng(0)
    rids = []
    for i in range(10):
        plen = int(rng.choice((8, 16)))
        prompt = rng.integers(0, cfg.vocab_size, size=plen)
        rids.append(srvr.submit(
            prompt, int(rng.integers(3, 10)),
            stop_on_exit=bool(i % 2), arrival_step=i,
        ))
    results = srvr.drain()
    sched = srvr.scheduler
    print(f"\n== continuous batching on the K=3 plan: {len(results)} "
          f"requests over {sched.decode_steps} decode steps "
          f"({sched.executor.host_syncs} host syncs), 6 slots")
    for r in results:
        print(f"   req {r.rid}: slot {r.slot}, admitted step "
              f"{r.admitted_step}, {len(r.tokens)} tokens, "
              f"exits {sum(r.exited)}, TTFT {r.ttft_s * 1e3:.0f} ms, "
              f"latency {r.latency_s * 1e3:.0f} ms")
    # Per-request accounting sanity: every request finished, decoded at
    # least one token within budget, and latency dominates its TTFT.
    assert len(results) == len(rids)
    for rid in rids:
        r = sched.results[rid]
        assert r.done and 1 <= len(r.tokens)
        assert r.ttft_s is not None and 0 < r.ttft_s <= r.latency_s
        assert r.retired_step > r.admitted_step >= 0
    # 10 requests over 6 slots: at least one KV row served two occupants.
    slot_uses = np.bincount([r.slot for r in results], minlength=6)
    assert slot_uses.max() >= 2, "expected a recycled slot"
    print(f"   slot reuse histogram {slot_uses.tolist()} — recycled rows "
          f"served later arrivals with bitwise-solo trajectories "
          f"(tests/test_scheduler.py pins the invariant)")

    # ---- fault plane: mid-run link kill with graceful degradation.  A
    # scripted flap takes the edge->cloud hop down; retries exhaust, the
    # circuit breaker opens, and survivors finalize from the deepest exit
    # head below the broken hop (tokens still emit, flagged degraded).
    # The controller ingests the breaker event and re-solves with the
    # hop's availability at 0 — the new cuts ship nothing across it.
    from repro.serving import (
        FlapWindow, HopPolicy, LinkFaultModel, RequestScheduler,
    )
    fault_tiers = [
        TierSpec("edge", 12.0, uplink_bps=18.8e6),
        TierSpec("mid", 4.0, uplink_bps=5.85e6),
        TierSpec("cloud", 1.0),
    ]
    srvf = PartitionedServer(
        cfg, params, (1, 3), tiers=fault_tiers, simulate_network=True,
        slots=6, context_len=CONTEXT,
        fault_model=LinkFaultModel(
            seed=0, flaps=(FlapWindow(hop=1, start_step=6, end_step=10_000),)
        ),
        hop_policy=HopPolicy(timeout_s=0.02, max_retries=1,
                             backoff_s=0.002, breaker_threshold=2),
    )
    ctl = RepartitionController(srvf, profile, tiers=list(fault_tiers))
    schedf = RequestScheduler(srvf, 6, CONTEXT, on_step=[ctl.observe])
    for i in range(10):
        plen = int(rng.choice((8, 16)))
        schedf.submit(rng.integers(0, cfg.vocab_size, size=plen),
                      int(rng.integers(3, 10)), arrival_step=i)
    resultsf = schedf.drain()
    deg = sum(r.degraded_tokens for r in resultsf)
    print(f"\n== fault plane: hop mid->cloud killed at step 6 — "
          f"{len(resultsf)} requests still completed "
          f"({deg}/{schedf.total_tokens} tokens degraded via the fallback "
          f"head, {srvf.executor.fault_retries} retries)")
    print(f"   controller: {ctl.fault_resolves} availability re-solve(s), "
          f"cuts now {srvf.cuts}, hop health {ctl.hop_health()}")
    assert all(r.done for r in resultsf)
    assert ctl.fault_resolves >= 1 and srvf.cuts[1] == cfg.num_layers
    print("   every request completed despite the dead link — "
          "tests/test_faults.py pins the degraded-step contract")


if __name__ == "__main__":
    main()
