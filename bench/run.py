"""Run one cell of ``BENCHMARK.json`` and print its result line.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it exits 2 before any work unless JAX's first device is a TPU
whose kind is in the peaks table and the cell's chips are there.  Set-up
(``setup_s``, from process start) makes the weights from the seed, builds
the served path, runs every shape the cell's traffic can produce and the
mix's regime set-up; the window then serves the traffic for ``--seconds``;
afterwards the served tokens of a sample of finished requests are compared
with the float32 reference.  Standard output ends with one JSON line; the
numbers compared and their limits are the last lines of standard error.
``--trace 1`` records the last seconds of the window with the profiler and
reports the per-layer metrics instead of the end-to-end ones.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from bench import harness as H  # noqa: E402
from bench.accounting import itl_ms  # noqa: E402
from bench import traffic as traffic_mod  # noqa: E402
from bench.clock import CompileClock  # noqa: E402
from bench.flops import Dims  # noqa: E402
from bench.peaks import DEVICE_PEAKS  # noqa: E402

#: Where traced runs leave the profiler's files (inside the checkout).
TRACE_ROOT = ROOT / ".bench_trace"


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def pct(values, q: float) -> float | None:
    return float(np.percentile(values, q)) if len(values) else None


# ------------------------------------------------------ end-to-end metrics
def ttft_ms(run) -> list[float]:
    """Scheduled send to first token of every request sent in the window of
    an open loop (the run steps on after the window until each has its
    first token)."""
    t0, t1 = run.window
    return [1e3 * (r.emits[0] - r.send) for r in run.reqs.values()
            if t0 <= r.send <= t1 and r.emits]


def window_tokens(run) -> int:
    t0, t1 = run.window
    return sum(1 for r in run.reqs.values() for t in r.emits if t0 < t <= t1)


END_TO_END = {
    "itl_p90_ms": lambda run: pct(itl_ms(run), 90),
    "itl_p95_ms": lambda run: pct(itl_ms(run), 95),
    "tokens_per_s": lambda run: window_tokens(run)
    / (run.window[1] - run.window[0]),
    "setup_s": lambda run: run.setup_s,
}


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` ("end_to_end" or "per_layer") this cell
    reports: those that list it, or, for a metric that lists no cells,
    every cell (a per-layer one: every cell of the metric it moves)."""
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def in_cell(m):
        if "workloads" in m:
            return cell in m["workloads"]
        return "moves" not in m or in_cell(e2e[m["moves"]])

    return [m for m in bench[kind] if in_cell(m)]


def attempted_failed(run, results) -> tuple[int, int]:
    """Requests sent by the window's close, and those of them that never
    had a first token (an open loop waits ``DRAIN_SECONDS`` for it) or
    ended in another status than "ok"."""
    t1 = run.window[1]
    sent = [r for r in run.reqs.values() if r.send <= t1]
    failed = 0
    for r in sent:
        res = results.get(r.rid)
        never = run.traffic["arrivals"] != "backlog" and not r.emits
        if never or (res is not None and res.done and res.status != "ok"):
            failed += 1
    return len(sent), failed


# ------------------------------------------------------------ one run
def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             traced: bool, *, system: H.System | None = None,
             control: bool = False, root: Path = ROOT,
             t_start: float | None = None) -> tuple[dict, H.System]:
    """Set up, serve the window, check the outputs; returns the result
    object (not printed) and the system.  ``system`` reuses a built
    system on a new seed (new weights, fresh scheduler, the same compiled
    programs); ``control`` adds the float8 control's readings."""
    t_start = _T_START if t_start is None else t_start
    wl = next(w for w in bench["workloads"] if w["name"] == workload)
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    spec = H.load_json(root / entry["file"])
    traffic = traffic_mod.load(root / "bench" / "traffic", wl["traffic"])
    limits = H.load_limits(workload, root / "bench" / "limits")
    clock = CompileClock()
    if system is None:
        system = H.build_system(spec, traffic, seed)
        log(f"[setup] {system.cfg.name}: {system.cfg.num_layers} layers, "
            f"cut {spec['cuts']}, exit heads {system.branches}, threshold "
            f"{system.cfg.exit_threshold}, {spec['slots']} slots x "
            f"{spec['context']}, kernels {system.executor.use_kernels}")
        warm_steps = H.warm_up(system)
    else:
        H.reseed(system, seed)
        warm_steps = 0
    cal = H.calibrate_exit(system, seed)
    if cal:
        log(f"[setup] exit-head entropy medians {cal['medians']}, gains "
            f"{cal['gains']} (threshold {system.cfg.exit_threshold})")
    requests = traffic_mod.generate(traffic, seed, system.cfg.vocab_size,
                                    spec["context"], seconds)
    log(f"[setup] {warm_steps} warm-up steps; {clock.summary()}")

    compiles = clock.snapshot()
    trace_dir = None
    if traced:
        TRACE_ROOT.mkdir(exist_ok=True)
        trace_dir = tempfile.mkdtemp(dir=TRACE_ROOT)
    opened = {}
    window, steps, reqs, tr_steps = H.serve(
        system, requests, seconds, trace_dir=trace_dir,
        on_open=lambda: opened.setdefault("t", time.perf_counter()))
    setup_s = opened["t"] - t_start
    in_window = clock.since(compiles)
    late = max([r.sent - r.send for r in reqs.values()
                if r.send >= window[0]], default=0.0)
    log(f"[window] {len(steps)} steps, {len(reqs)} requests sent, the "
        f"generator at most {1e3 * late:.1f} ms late")
    log(f"[window] programs built in the window and its drain: "
        f"{in_window['compiles']} ({in_window['cache_hits']} of them from "
        f"the persistent cache) {in_window['names']}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in system.devices)
    dev = jax.devices()[0]
    results = system.sched.results
    run = H.Run(
        spec=spec, traffic=traffic, dims=Dims.from_spec(spec),
        peaks=DEVICE_PEAKS.get(dev.device_kind, {}), chips=wl["chips"],
        branches=system.branches, depth_cut=spec["cuts"][0], window=window,
        steps=steps, reqs=reqs, setup_s=setup_s,
        tokens=H.token_records(system, steps, reqs))
    attempted, failed = attempted_failed(run, results)
    if traffic["arrivals"] != "backlog":
        log(f"[window] ttft p50 {pct(ttft_ms(run), 50)} p90 "
            f"{pct(ttft_ms(run), 90)} ms; itl p90 {pct(itl_ms(run), 90)} p95 "
            f"{pct(itl_ms(run), 95)} ms")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
    metrics = {}
    if traced:
        run.trace = H.reduce_trace(trace_dir, wl["chips"], tr_steps) \
            if tr_steps else {}
        for m in cell_metrics(bench, workload, "per_layer"):
            value = H.metric_reader(m["name"], root / "bench" / "metrics")(
                run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if run.trace:
            device["busy_s"] = float(
                np.mean(list(run.trace["busy_ns"].values())) / 1e9)
            device["window_s"] = run.trace["window_ns"] / 1e9
    else:
        for m in cell_metrics(bench, workload, "end_to_end"):
            value = END_TO_END[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    rids = H.sample_finished(system, reqs, int(traffic["check_requests"]),
                             seed)
    tok0 = H.first_tokens(system, rids)
    seqs = H.served_sequences(system, reqs, rids, tok0)
    H.free_program_state(system, keep_programs=control)
    t = time.perf_counter()
    readings = H.check_outputs(system, seqs, control=control)
    log(f"[check] {len(rids)} requests, {readings['tokens']} served tokens "
        f"against the float32 reference in {time.perf_counter() - t:.1f} s")

    log("[check] readings: " + ", ".join(
        f"{k} {readings[k]!r}" for k in H.READINGS))
    ok, checks = judge(readings, limits, "")
    ok = ok and bool(rids) and failed == 0
    checks["failed"] = {"value": failed, "limit": 0}
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced and run.trace:
        result["breakdown"] = run.trace["breakdown"]
    if control:
        result["readings"] = readings
        result["control_correct"], result["control_checks"] = judge(
            readings, limits, "control_")
        result["exit_share"] = (
            100.0 * sum(t[2] for t in run.tokens) / len(run.tokens)
            if run.tokens else None)
    result["checks"] = checks
    return result, system


def judge(readings: dict, limits: dict, prefix: str) -> tuple[bool, dict]:
    """Whether the readings under ``prefix`` ("" for the program's,
    "control_" for the control's) keep to every limit of the cell, and
    each number beside its limit.  No limits: not correct."""
    checks = {name: {"value": readings[prefix + name], "limit": lim["limit"]}
              for name, lim in limits.items()}
    ok = bool(limits) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks


def chips_missing(chips: int) -> str | None:
    """Why this machine cannot run the cell, or None."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return f"JAX found no TPU (first device: {dev.platform})"
    if dev.device_kind not in DEVICE_PEAKS:
        return f"no peaks for device kind {dev.device_kind!r}"
    if len(devices) < chips:
        return f"the cell needs {chips} chips; {len(devices)} found"
    return None


def setup_compile_cache() -> str:
    """The program's placement of JAX's persistent cache, every program
    kept in it."""
    from repro.launch.compile_cache import use_compile_cache

    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def report(result: dict) -> None:
    """The numbers compared beside their limits as the last lines of
    standard error, then the result line as the last of standard output."""
    for name, c in result["checks"].items():
        log(f"[correct] {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = H.load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"unknown workload {args.workload!r}; cells: {sorted(cells)}")
        return 2
    why = chips_missing(cells[args.workload]["chips"])
    if why:
        log(f"bench.run: {why}")
        return 2
    log(f"[device] {len(jax.devices())} x {jax.devices()[0].device_kind}; "
        f"compile cache {setup_compile_cache()}")
    result, _ = run_cell(bench, args.workload, args.seed, args.seconds,
                         bool(args.trace))
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
