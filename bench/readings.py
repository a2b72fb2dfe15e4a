"""Readings that the limits of ``correct`` are set from, many seeds in one
process.

    python3 -m bench.readings --workload <name> --seeds 1,2,3 --seconds 20

The cell is set up once; each seed then gets new weights and a fresh
scheduler on the same compiled programs, its regime set-up, a window of
``--seconds`` at the cell's own load, and the comparison with the float32
reference, of the program's served tokens and of the float8 control put
in the program's place.  One JSON line per seed: both sets of readings,
and ``correct`` and ``control_correct``, each set judged against the
cell's limits by the run's own comparison.  The benchmark's own runs never
run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench import harness as H
from bench import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    bench = H.load_json(R.ROOT / "BENCHMARK.json")
    wl = next(w for w in bench["workloads"] if w["name"] == args.workload)
    why = R.chips_missing(wl["chips"])
    if why:
        R.log(f"bench.readings: {why}")
        return 2
    R.setup_compile_cache()
    system = None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        result, system = R.run_cell(bench, args.workload, seed, args.seconds,
                                    False, system=system, control=True,
                                    t_start=t)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "control_correct": result["control_correct"],
                          "exit_share": result["exit_share"],
                          **result["readings"],
                          "metrics": result["metrics"],
                          "memory_peak_bytes":
                          result["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
