"""Find the knee of an open-loop cell: the highest offered rate whose backlog
does not grow over a window.

    python3 -m bench.sweep --workload <name> --rates 1.5,2,2.5 --seconds 30

One process on the chip: it sets the cell up once (weights from ``--seed``,
warm-up, the mix's regime set-up), then serves the mix at each rate in
turn, draining the scheduler between rates.  Per rate it prints one JSON
line: requests sent, the queue left at the close, the wait from scheduled
send to admission in the first and second half of the window, and the
time-to-first-token percentiles.  A rate whose second-half wait is well
above its first half's, or that leaves a queue, is past the knee.  The
chosen rate is written into the mix's file by hand, with the sweep's
numbers in ``PERF.md``: the benchmark never searches for a rate itself.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from bench import harness as H
from bench import run as R
from bench import traffic as traffic_mod


def sweep_rate(system: H.System, rate: float, seconds: float, seed: int
               ) -> dict:
    traffic = dict(system.traffic, rate_per_s=rate)
    system.traffic = traffic
    requests = traffic_mod.generate(traffic, seed, system.cfg.vocab_size,
                                    system.spec["context"], seconds)
    (t0, t1), steps, reqs, _ = H.serve(system, requests, seconds)
    queued = len(system.sched.queue)
    half = t0 + seconds / 2
    waits = {0: [], 1: []}
    for r in reqs.values():
        if r.admit_step is not None and r.send <= t1:
            waits[int(r.send >= half)].append(1e3 * (r.admit_step - r.send))
    ttft = [1e3 * (r.emits[0] - r.send) for r in reqs.values() if r.emits]
    system.sched.run()  # drain before the next rate
    system.executor.install(system.executor.segments)
    return {
        "rate_per_s": rate, "sent": len(reqs), "steps": len(steps),
        "queued_at_close": queued,
        "wait_ms_first_half": float(np.mean(waits[0])) if waits[0] else None,
        "wait_ms_second_half": float(np.mean(waits[1])) if waits[1]
        else None,
        "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
        "ttft_p90_ms": float(np.percentile(ttft, 90)) if ttft else None,
        "tokens_per_s": sum(len(r.emits) for r in reqs.values()) / seconds,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    bench = H.load_json(R.ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    why = R.chips_missing(wl["chips"])
    if why:
        R.log(f"bench.sweep: {why}")
        return 2
    R.setup_compile_cache()
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    spec = H.load_json(R.ROOT / entry["file"])
    traffic = traffic_mod.load(H.BENCH / "traffic", wl["traffic"])
    t = time.perf_counter()
    system = H.build_system(spec, traffic, args.seed)
    H.warm_up(system)
    R.log(f"[sweep] exit calibration {H.calibrate_exit(system, args.seed)}; "
          f"set-up {time.perf_counter() - t:.1f} s")
    for rate in (float(x) for x in args.rates.split(",")):
        print(json.dumps(sweep_rate(system, rate, args.seconds, args.seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
