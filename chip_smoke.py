"""Bring-up check of the BranchyNet serving path on TPU, end to end.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips (a 2x2 host), and nothing else

One chip: phi3-mini at its published widths and all 32 layers, bf16
parameters made from ``--seed`` under ``jit`` straight into device memory,
served through RequestScheduler -> PartitionedServer -> TierExecutor with a
K=2 split at layer 16 and ``use_kernels`` left on auto.  It checks that

  * the Pallas kernels are compiled into the tier segments (Mosaic
    ``tpu_custom_call`` in the lowered segment programs);
  * decode logits of the kernel path agree with the jnp path and with a
    float32 reference within ``LOGIT_ATOL``, and greedy tokens agree
    wherever the reference's top-2 margin exceeds twice that;
  * in two traffic regimes — no early exits (the default threshold) and a
    threshold at which the layer-8 branch head fires — every request
    completes, a decode step costs one host sync plus the overflow
    re-runs it reports, and nothing is traced after warm-up.

Four chips (``--chips 4``): qwen3-8b at full width and all 36 layers on a
(1, 4) mesh, parameters created already sharded, serves a few requests;
then a depth-cut qwen3-8b (18 layers, branch at 9) runs on one chip
(Pallas kernels) and on the mesh (jnp SPMD): one decode step from the
same prompts and token must agree within ``LOGIT_ATOL`` with equal exit
masks, and how many served greedy trajectories stay identical is
reported (a bf16 near-tie may flip a token; see the README).

The script stops with a non-zero exit and prints no result unless JAX's
first device is a TPU.  No phase's failure is caught.  The last line of
standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serving import PartitionedServer  # noqa: E402
from repro.sharding.policy import make_policy  # noqa: E402

#: Per-chip peaks by ``jax.Device.device_kind`` (Google Cloud TPU docs,
#: "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM).  An unknown kind is an error.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}

#: Decode-logit agreement bound (absolute, in logits).  Random-init heads
#: put logits at |l| <= ~6, where one bf16 ulp is 1/32.  On a TPU v5e the
#: largest differences seen were 0.066 (phi3-mini kernel vs jnp path),
#: 0.075 (either vs float32) and 0.098 (qwen3-8b x18, one chip vs a (1, 4)
#: mesh).  Greedy tokens must agree wherever the top-2 margin exceeds
#: 2 * LOGIT_ATOL (two logits each off by the bound can swap no closer
#: pair).
LOGIT_ATOL = 0.15

#: (prompt tokens, new tokens) per request: mixed lengths and budgets; 12
#: requests over 8 slots, so later ones are admitted into freed slots.
REQUESTS = (
    (64, 16), (192, 32), (448, 24), (64, 28), (192, 20), (448, 32),
    (64, 24), (192, 16), (448, 20), (64, 32), (192, 24), (448, 16),
)
SLOTS = 8
SPLIT = 16  # K=2: edge runs layers [0, 16) with the layer-8 branch head

#: Live copies of the KV cache while a decode step runs: the step's entry
#: caches (kept for an overflow re-run), the edge segment's output (the
#: cloud segment's input), the cloud segment's output, plus XLA temporaries
#: of up to about one more (v5e compile rehearsal of the segments).
LIVE_CACHE_COPIES = 4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(*parts) -> None:
    print(*parts, flush=True)


# ----------------------------------------------------------------- sizing
def tree_bytes(tree) -> int:
    return sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        for a in jax.tree_util.tree_leaves(tree)
    )


def kv_bytes_per_token(cfg) -> int:
    """One slot's K and V for one token across every layer (bf16)."""
    return cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 2 * 2


def size_context(cfg, slots: int, param_bytes: int, hbm_bytes: int,
                 need: int) -> int:
    """Largest context (a multiple of 128, at most 1024) whose caches fit
    next to the parameters: params + LIVE_CACHE_COPIES x caches within 90%
    of the device's memory.  Fails if that is shorter than ``need``."""
    room = 0.9 * hbm_bytes - param_bytes
    fit = int(room / (LIVE_CACHE_COPIES * slots * kv_bytes_per_token(cfg)))
    context = min(1024, fit // 128 * 128)
    check(context >= need,
          f"{slots} slots x {need} tokens do not fit: {fit} tokens per slot "
          f"left next to {param_bytes / 1e9:.2f} GB of parameters")
    return context


# ----------------------------------------------------------------- phases
def init_params(cfg, seed: int, shardings):
    """Parameters from ``seed``, made by one jitted program straight into
    their (possibly sharded) device buffers in ``cfg.param_dtype``."""
    fn = jax.jit(M.init_params, static_argnums=1, out_shardings=shardings)
    params = fn(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    return params


def prompts_for(cfg, lengths, seed: int) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
            for n in lengths]


def kernel_calls(server) -> list[int]:
    """Mosaic kernels in each lowered tier segment program."""
    ex = server.executor
    b = SLOTS
    caches = jax.eval_shape(lambda: M.init_caches(server.cfg, b, 128))
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    exited = jax.ShapeDtypeStruct((b,), jnp.bool_)
    x0 = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    xh = jax.ShapeDtypeStruct((b, 1, server.cfg.d_model), jnp.bfloat16)
    return [
        ex.segment_fn(i).lower(server.params, x0 if i == 0 else xh, pos,
                               exited, pos, caches)
        .as_text().count("tpu_custom_call")
        for i in range(len(ex.segments))
    ]


def reference_logits(params, cfg, prompts, tok) -> np.ndarray:
    """Float32 decode logits: the same prompts prefilled and one token
    decoded by the monolithic model in float32 activations and caches at
    the highest matmul precision (bf16 parameters upcast at use)."""
    cfg32 = dataclasses.replace(cfg, dtype="float32", use_kernels=False)
    b, plen = prompts.shape

    def run(params, prompts, tok):
        caches = M.init_caches(cfg32, b, plen + 1)
        _, caches = M.prefill(params, {"tokens": prompts}, cfg32, caches)
        out = M.decode_step(params, tok, jnp.asarray(plen, jnp.int32),
                            caches, cfg32, with_branches=False)
        return out["logits"]

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(run)(params, prompts, tok), np.float32)


def agreement(got: np.ndarray, ref: np.ndarray) -> dict:
    """Max |got - ref|, and greedy-token agreement on the rows whose
    reference top-2 margin exceeds 2 * LOGIT_ATOL."""
    top2 = np.sort(ref, axis=-1)[:, -2:]
    decided = (top2[:, 1] - top2[:, 0]) > 2 * LOGIT_ATOL
    same = got.argmax(-1) == ref.argmax(-1)
    return {
        "max_abs_err": float(np.abs(got - ref).max()),
        "ref_max_abs": float(np.abs(ref).max()),
        "decided_rows": int(decided.sum()),
        "tokens_agree_decided": bool(same[decided].all()),
        "tokens_agree_all": int(same.sum()),
        "rows": int(same.size),
    }


def step_logits(server, prompts: np.ndarray, context: int, tok=None):
    """Admit ``prompts`` (B, P) into fresh slot caches and decode one step
    of ``tok`` (B, 1) (default: the prefill's greedy token).  Returns
    (tok, main-head logits (B, V) float32, {branch layer: entropy (B,)})."""
    ex = server.executor
    b, plen = prompts.shape
    caches = ex.new_caches(b, context)
    caches, tok0 = ex.prefill_rows(caches, prompts, np.arange(b))
    if tok is None:
        tok = np.asarray(tok0)[:, None]
    rep, _ = server.step(tok, np.full(b, plen, np.int32), caches,
                         active=np.ones(b, bool))
    res = rep.tier_result
    return (tok, np.asarray(res.last_logits, np.float32),
            dict(res.branch_entropy))


def check_agreement(results: dict) -> None:
    for name, a in results.items():
        log(f"[logits] {name}: max |dlogit| {a['max_abs_err']:.5f} "
            f"(|ref| <= {a['ref_max_abs']:.3f}, bound {LOGIT_ATOL}); "
            f"greedy tokens {a['tokens_agree_all']}/{a['rows']} equal, "
            f"{a['decided_rows']} rows with margin > {2 * LOGIT_ATOL}")
        check(a["max_abs_err"] <= LOGIT_ATOL,
              f"{name}: max |dlogit| {a['max_abs_err']:.4f} > {LOGIT_ATOL}")
        check(a["tokens_agree_decided"],
              f"{name}: greedy tokens differ where the margin is > "
              f"{2 * LOGIT_ATOL}")


def compare_paths(server, jnp_server, prompts: np.ndarray,
                  context: int) -> None:
    """One decode step after admitting ``prompts``, on the kernel path
    (``server``) and the jnp path (``jnp_server``), against the float32
    reference."""
    tok, kernel, _ = step_logits(server, prompts, context)
    _, jnp_, _ = step_logits(jnp_server, prompts, context, tok)
    ref = reference_logits(server.params, server.cfg, prompts, tok)
    check_agreement({
        "kernel_vs_jnp": agreement(kernel, jnp_),
        "kernel_vs_f32": agreement(kernel, ref),
        "jnp_vs_f32": agreement(jnp_, ref),
    })


def submit_all(server, prompts, budgets) -> list[int]:
    return [server.submit(p, n) for p, n in zip(prompts, budgets)]


def serve_twice(server, prompts, budgets) -> dict:
    """Serve the workload once to compile every shape it meets (warm-up),
    then again as the measured window, and check the window's contract."""
    ex, sched = server.executor, server.scheduler
    t0 = time.perf_counter()
    warm = submit_all(server, prompts, budgets)
    sched.run()
    warm_s = time.perf_counter() - t0

    # Fresh bucket hints: the window replays the warm-up's bucket choices.
    ex.install(ex.segments)
    traces = dict(ex.trace_counts)
    syncs, steps = ex.host_syncs, sched.decode_steps
    retries = ex.overflow_retries
    t0 = time.perf_counter()
    rids = submit_all(server, prompts, budgets)
    reports = sched.run()
    window_s = time.perf_counter() - t0

    results = [sched.results[r] for r in rids]
    for r, n in zip(results, budgets):
        check(r.done and r.status == "ok" and len(r.tokens) == n,
              f"request {r.rid} ended {r.status} with {len(r.tokens)}/{n} "
              "tokens")
    new = {k: v - traces.get(k, 0) for k, v in ex.trace_counts.items()
           if v != traces.get(k, 0)}
    check(not new, f"traced inside the decode window: {new}")
    n_steps = sched.decode_steps - steps
    n_retries = ex.overflow_retries - retries
    n_syncs = ex.host_syncs - syncs
    check(n_syncs == n_steps + n_retries,
          f"{n_syncs} host syncs for {n_steps} decode steps and "
          f"{n_retries} overflow re-runs")
    check([r.tokens for r in results]
          == [sched.results[r].tokens for r in warm],
          "window trajectories differ from the warm-up's")
    tokens = sum(len(r.tokens) for r in results)
    exited = sum(sum(r.exited) for r in results)
    return {
        "warmup_s": warm_s,
        "window_s": window_s,
        "decode_steps": n_steps,
        "host_syncs": n_syncs,
        "overflow_retries": n_retries,
        "tokens": tokens,
        "exited_tokens": exited,
        "tokens_per_s": tokens / window_s,
        "reports": reports,
        "rids": rids,
    }


def branch_entropies(reports, layer: int) -> np.ndarray:
    """Entropies of the live rows at branch ``layer`` over a window."""
    out = []
    for rep in reports:
        res = rep.server_report.tier_result
        out.append(res.branch_entropy[layer][res.active])
    return np.concatenate(out)


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def summary(self) -> str:
        return (f"{self.seconds:.1f} s backend compile over {self.compiles} "
                f"programs, {self.cache_hits} persistent-cache hits")


def report_regime(name: str, stats: dict) -> None:
    log(f"[{name}] window: {stats['tokens']} tokens "
        f"({stats['exited_tokens']} early exits) in "
        f"{stats['decode_steps']} decode steps, {stats['host_syncs']} host "
        f"syncs, {stats['overflow_retries']} overflow re-runs, "
        f"{stats['window_s']:.3f} s ({stats['tokens_per_s']:.1f} tok/s host "
        f"clock, admissions included); warm-up {stats['warmup_s']:.1f} s")


def serve_regime(name, cfg, params, context, prompts, budgets, **server_kw):
    server = PartitionedServer(cfg, params, SPLIT, slots=SLOTS,
                               context_len=context, **server_kw)
    stats = serve_twice(server, prompts, budgets)
    report_regime(name, stats)
    return server, stats


def one_chip(cfg, seed: int, hbm_bytes: int) -> None:
    t0 = time.perf_counter()
    params = init_params(cfg, seed, SingleDeviceSharding(jax.devices()[0]))
    param_bytes = tree_bytes(params)
    log(f"[init] {cfg.name}: {cfg.num_layers} layers, d_model "
        f"{cfg.d_model}, {param_bytes / 1e9:.3f} GB {cfg.param_dtype} "
        f"params in {time.perf_counter() - t0:.1f} s")

    need = max(p for p, _ in REQUESTS) + max(n for _, n in REQUESTS)
    context = size_context(cfg, SLOTS, param_bytes, hbm_bytes, need)
    log(f"[sizing] {SLOTS} slots x {context} context: "
        f"{SLOTS * context * kv_bytes_per_token(cfg) / 1e9:.3f} GB of KV "
        f"per cache copy, {LIVE_CACHE_COPIES} copies budgeted")

    server = PartitionedServer(cfg, params, SPLIT, slots=SLOTS,
                               context_len=context)
    check(server.executor.use_kernels, "use_kernels=None resolved to jnp")
    # Later servers take the served tree: its leaves are shared, not
    # transposed again, and the canonical q/k/v stacks are freed.
    params = server.params
    calls = kernel_calls(server)
    log(f"[kernels] tpu_custom_call per lowered segment: {calls}; served "
        f"transposed: {server.executor.served_transposed}")
    check(all(c >= 1 for c in calls) and calls[0] >= 2,
          "a tier segment lowered without its Pallas kernels")

    jnp_server = PartitionedServer(cfg, params, SPLIT, use_kernels=False)
    prompts = np.stack(prompts_for(cfg, [128] * SLOTS, seed + 1))
    compare_paths(server, jnp_server, prompts, context)
    del jnp_server, server

    prompts = prompts_for(cfg, [p for p, _ in REQUESTS], seed + 2)
    budgets = [n for _, n in REQUESTS]
    server, base = serve_regime("no-exit", cfg, params, context, prompts,
                                budgets)
    check(base["exited_tokens"] == 0,
          "tokens exited at the default threshold on random weights")
    ent = branch_entropies(base["reports"], cfg.branch_layers[0])
    threshold = float(np.median(ent))
    log(f"[no-exit] layer-{cfg.branch_layers[0]} entropy over the window: "
        f"min {ent.min():.5f} median {threshold:.5f} max {ent.max():.5f}")
    del server, base

    exit_cfg = dataclasses.replace(cfg, exit_threshold=threshold)
    server, ex = serve_regime("exits", exit_cfg, params, context, prompts,
                              budgets)
    check(0 < ex["exited_tokens"] < ex["tokens"],
          f"threshold {threshold:.5f} gave {ex['exited_tokens']} exits of "
          f"{ex['tokens']} tokens")
    calls = kernel_calls(server)
    check(calls[0] >= 2, "exit-regime edge segment lost its kernels")
    del server


def four_chip(seed: int) -> None:
    mesh = make_local_mesh()
    check(mesh.devices.size == 4, f"mesh {dict(mesh.shape)} is not 4 chips")
    full = get_config("qwen3_8b")
    check(full.param_dtype == "bfloat16", "qwen3_8b params are not bf16")

    def sharded_params(cfg):
        shapes = jax.eval_shape(lambda k: M.init_params(k, cfg),
                                jax.random.PRNGKey(seed))
        return init_params(
            cfg, seed, make_policy(mesh, cfg).params_shardings(shapes)
        )

    t0 = time.perf_counter()
    params = sharded_params(full)
    total = tree_bytes(params)
    held = {d: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    log(f"[init] {full.name}: {full.num_layers} layers, "
        f"{total / 1e9:.3f} GB bf16 params created sharded in "
        f"{time.perf_counter() - t0:.1f} s; param bytes per chip: "
        + ", ".join(f"{u / 1e9:.3f} GB" for u in held.values()))
    check(max(held.values()) < 0.5 * total,
          "parameters landed whole on one chip")

    budgets = [16, 16, 16, 16]
    prompts = prompts_for(full, [64] * 4, seed + 2)
    srv = PartitionedServer(full, params, 18, slots=4, context_len=256,
                            mesh=mesh)
    stats = serve_twice(srv, prompts, budgets)
    report_regime("qwen3-8b x36 on (1, 4)", stats)
    del srv, params

    # Depth-cut qwen3-8b on one chip (Pallas kernels) vs the mesh (jnp):
    # one decode step from the same prompts and token, then the served
    # trajectories.
    cut = dataclasses.replace(full, num_layers=18, branch_layers=(9,))
    step_prompts = np.stack(prompts_for(cut, [64] * 4, seed + 1))
    runs, tok = {}, None
    for name in ("one-chip", "mesh"):
        if name == "one-chip":
            params = init_params(cut, seed,
                                 SingleDeviceSharding(jax.devices()[0]))
            kw = {}
        else:
            params = sharded_params(cut)
            kw = {"mesh": mesh}
        srv = PartitionedServer(cut, params, 12, slots=4, context_len=256,
                                **kw)
        tok, logits, ents = step_logits(srv, step_prompts, 256, tok)
        stats = serve_twice(srv, prompts, budgets)
        report_regime(f"qwen3-8b x18 {name}", stats)
        results = [srv.scheduler.results[r] for r in stats["rids"]]
        runs[name] = {
            "logits": logits,
            "entropy": ents[9],
            "tokens": [r.tokens for r in results],
            "exited": [r.exited for r in results],
        }
        del srv, params
    a, b = runs["one-chip"], runs["mesh"]
    check_agreement({"one-chip_vs_mesh": agreement(a["logits"],
                                                   b["logits"])})
    same = sum(x == y for x, y in zip(a["tokens"], b["tokens"]))
    log(f"[1 vs 4 chips] layer-9 entropy max |diff| "
        f"{float(np.abs(a['entropy'] - b['entropy']).max()):.2e}; served "
        f"greedy trajectories identical for {same}/{len(budgets)} requests; "
        f"exit masks identical: {a['exited'] == b['exited']}")
    check(a["exited"] == b["exited"], "one-chip and mesh exit masks differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform})",
              file=sys.stderr)
        return 2
    if dev.device_kind not in DEVICE_PEAKS:
        raise SmokeFailure(f"unknown device kind {dev.device_kind!r}")
    if len(devices) < args.chips:
        raise SmokeFailure(f"--chips {args.chips} but {len(devices)} found")

    log(f"[device] {len(devices)} x {dev.device_kind}; compile cache: "
        f"{use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip(args.seed)
    else:
        cfg = dataclasses.replace(get_config("phi3_mini_3_8b"),
                                  param_dtype="bfloat16")
        one_chip(cfg, args.seed, dev.memory_stats()["bytes_limit"])
    for d in devices[: args.chips]:
        stats = d.memory_stats()
        log(f"[memory] device {d.id}: peak {stats['peak_bytes_in_use']} "
            f"bytes of {stats['bytes_limit']}")
    log(f"[compile] {clock.summary()}; total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
