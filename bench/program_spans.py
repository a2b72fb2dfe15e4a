"""The served program's own trace marks, as the benchmark reads them.

The program writes host spans (``jax.profiler.TraceAnnotation``) around
each phase of a scheduler step and of a tier step, and names each jitted
program after its cache key, so its module on a device's "XLA Modules"
line reads ``jit_decode_l{lo}_{hi}_b{bucket}``, ``jit_prefill_p{plen}_n{n}``
or ``jit_reset_rows_n{n}``.  The names are listed here, not imported from
the program: they are part of the yardstick.

The harness's trace reduction (``harness.reduce_trace``) keeps the slice's
device ops and program events in ``run.trace`` but not the program's host
spans, so ``host_spans`` reads them from the run's own ``.xplane.pb``: the
newest under ``.bench_trace/``, taken only if its program events are the
ones in ``run.trace``.  A program without these spans or names (an older
commit) reads as nothing, and each reader then returns None.

The reductions below work on plain event lists (``bench.trace.Event``):

  * ``program_of``: a program event's name without its bucket;
  * ``module_ns``: device time of the programs a predicate picks;
  * ``host_idle_ns``: device idle time with no program executing, inside
    one set of host spans and outside another;
  * ``retry_ns``: device time of decode programs that start after the
    first ``tier.sync`` of their ``tier.step`` has ended (overflow
    re-runs), and of all decode programs;
  * ``attribute_idle``: idle time charged to the program executing around
    it (``program:<name>``), else to the innermost host span.
"""

from __future__ import annotations

import bisect
import os
import re
from pathlib import Path

from bench import trace as T

#: Host spans the program writes (serving/spans.py in the program).
PROGRAM_SPANS = frozenset({
    "sched.step", "sched.admit", "sched.emit", "sched.retire",
    "tier.step", "tier.plan", "tier.stage", "tier.dispatch", "tier.sync",
    "tier.retry", "tier.account", "tier.prefill", "tier.reset",
})
#: Host spans the harness writes around the program (``harness.SPANS``).
HARNESS_SPANS = frozenset({"submit", "wait", "scheduler.step",
                           "PartitionedServer.step", "prefill_rows"})
TIER, SCHED = "tier.", "sched."

DECODE = re.compile(r"decode_l(\d+)_(\d+)_b(?:full|\d+)((?:_probe)?"
                    r"(?:_degrade\d+)?)")
PREFILL = re.compile(r"prefill_p\d+_n\d+")
RESET = re.compile(r"reset_rows_n\d+")
#: The run id XLA appends to a program event's name: ``jit_fn(123)``.
MODULE_ID = re.compile(r"\(\d+\)$")

#: Where ``bench.run`` leaves a traced run's profiler files.
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".bench_trace"


def program_of(event) -> str | None:
    """``decode_l{lo}_{hi}`` (with ``_probe`` / ``_degrade{L}`` where the
    program has them), ``prefill`` or ``reset_rows`` for a program event
    of the served path; None for any other program."""
    m = DECODE.search(event.name)
    if m:
        return f"decode_l{m.group(1)}_{m.group(2)}{m.group(3)}"
    if PREFILL.search(event.name):
        return "prefill"
    if RESET.search(event.name):
        return "reset_rows"
    return None


def is_edge(event) -> bool:
    """A decode program of the entry tier (its layers start at 0)."""
    m = DECODE.search(event.name)
    return bool(m) and m.group(1) == "0"


def is_cloud(event) -> bool:
    m = DECODE.search(event.name)
    return bool(m) and m.group(1) != "0"


def is_decode(event) -> bool:
    return bool(DECODE.search(event.name))


def is_prefill(event) -> bool:
    return bool(PREFILL.search(event.name))


def module_ns(modules, pick, lo: float, hi: float) -> float | None:
    """Device time of the program events ``pick`` accepts, cut to
    [lo, hi); None where it accepts none there."""
    sel = [e for e in modules if pick(e)]
    cut = T.clip(sel, lo, hi)
    if not cut:
        return None
    return sum(b - a for a, b in T.merged(cut))


# ------------------------------------------------------------ intervals
def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[float, float]]:
    """``a`` less ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, t = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if hi > t:
            out.append((t, hi))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _union(events, lo, hi):
    return T.merged(T.clip(events, lo, hi))


# ------------------------------------------------------------ reductions
def idle_outside_programs(ops, modules, lo: float, hi: float):
    """Device idle intervals (no op running) in which no program executes
    either: the device waits on the host."""
    return subtract(T.idle_gaps(ops, lo, hi), _union(modules, lo, hi))


def host_idle_ns(ops, modules, spans, inside: str, outside: str | None,
                 lo: float, hi: float) -> float | None:
    """Device idle time with no program executing, while the host is in a
    span whose name starts with ``inside`` and in none whose name starts
    with ``outside``; None where there is no ``inside`` span."""
    ins = [s for s in spans if s.name.startswith(inside)]
    if not ins:
        return None
    idle = intersect(idle_outside_programs(ops, modules, lo, hi),
                     _union(ins, lo, hi))
    if outside:
        idle = subtract(idle, _union(
            [s for s in spans if s.name.startswith(outside)], lo, hi))
    return length(idle)


def retry_ns(modules, spans, lo: float, hi: float):
    """(device time of decode programs that start after the first
    ``tier.sync`` of the ``tier.step`` they run in has ended, device time
    of all decode programs), cut to [lo, hi); None where the trace holds
    no ``tier.step`` or no decode program."""
    steps = sorted((s for s in spans if s.name == "tier.step"),
                   key=lambda s: s.start)
    syncs = sorted((s for s in spans if s.name == "tier.sync"),
                   key=lambda s: s.start)
    decode = [e for e in modules if is_decode(e)]
    if not steps or not decode:
        return None
    starts = [s.start for s in steps]
    sync_starts = [s.start for s in syncs]
    retried = total = 0.0
    for e in decode:
        a, b = max(e.start, lo), min(e.end, hi)
        if b <= a:
            continue
        total += b - a
        k = bisect.bisect_right(starts, e.start) - 1
        if k < 0 or e.start >= steps[k].end:
            continue
        j = bisect.bisect_left(sync_starts, steps[k].start)
        if j < len(syncs) and syncs[j].end <= steps[k].end \
                and e.start >= syncs[j].end:
            retried += b - a
    return retried, total


def attribute_idle(ops, modules, spans, lo: float, hi: float, n: int = 12):
    """[(activity, idle seconds)]: device idle time (no op running) inside
    an executing program charged to ``program:<name>`` (its name without
    bucket), the rest to the innermost host span around it, "no span"
    otherwise."""
    gaps = T.idle_gaps(ops, lo, hi)
    ends = [g1 for _, g1 in gaps]
    acc: dict[str, float] = {}
    for e in modules:
        a, b = max(e.start, lo), min(e.end, hi)
        got, k = 0.0, bisect.bisect_right(ends, a)
        while k < len(gaps) and gaps[k][0] < b:
            got += min(b, gaps[k][1]) - max(a, gaps[k][0])
            k += 1
        if got > 0:
            key = "program:" + (program_of(e) or MODULE_ID.sub("", e.name))
            acc[key] = acc.get(key, 0.0) + got / 1e9
    for name, s in T.attribute_gaps(
            subtract(gaps, _union(modules, lo, hi)), spans, n=10 ** 6):
        acc[name] = acc.get(name, 0.0) + s
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])][:n]


# ------------------------------------------------------------ loading
def load_host(paths, names=PROGRAM_SPANS | HARNESS_SPANS):
    """The named host spans, and the first chip's program events, of the
    ``.xplane.pb`` files of one traced run."""
    from jax.profiler import ProfileData

    host, modules = [], {}
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            m = T.DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    if line.name == T.MODULES_LINE:
                        modules.setdefault(int(m.group(1)), []).extend(
                            T.Event(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                            for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in names:
                            host.append(T.Event(e.name, float(e.start_ns),
                                                float(e.duration_ns)))
    host.sort(key=lambda e: e.start)
    return host, modules


def _newest_run(root: Path) -> list[str]:
    try:
        found = T.find_xplanes(str(root))
    except FileNotFoundError:
        return []
    newest = os.path.dirname(max(found, key=os.path.getmtime))
    return [p for p in found if os.path.dirname(p) == newest]


def host_spans(run, root: Path = TRACE_ROOT) -> list:
    """The host spans (program's and harness's) of the run's traced slice,
    read once per run and kept in ``run.trace["host_spans"]``; [] where
    the run's trace files are not found."""
    tr = run.trace
    if not tr:
        return []
    if "host_spans" not in tr:
        tr["host_spans"] = _load_matching(tr, root)
    return tr["host_spans"]


def _load_matching(tr: dict, root: Path) -> list:
    paths = _newest_run(root)
    if not paths:
        return []
    host, modules = load_host(paths)
    chip = tr["chips"][0]
    want = [(e.name, e.start) for e in tr["modules"].get(chip, [])]
    got = [(e.name, e.start) for e in modules.get(chip, [])]
    if not want or want != got:
        return []
    return [e for e in host if e.start < tr["hi"] and e.end > tr["lo"]]


def traced_step_count(run) -> int:
    if not run.trace:
        return 0
    i0, i1 = run.trace["steps"]
    return i1 - i0


# ------------------------------------------------------------ command line
def slice_of(host) -> tuple[float, float]:
    """The harness's slice: from the first harness span to the end of the
    last ``scheduler.step``; for a trace taken outside the benchmark, the
    first to the last ``sched.step``."""
    harness = [e for e in host if e.name in HARNESS_SPANS]
    steps = [e for e in harness if e.name == "scheduler.step"]
    if not steps:
        harness = steps = [e for e in host if e.name == "sched.step"]
    return min(e.start for e in harness), max(e.end for e in steps)


def excerpt(paths, out: str, steps: int = 1) -> None:
    """Write ``steps`` consecutive ``scheduler.step`` spans of the first
    chip as JSON (op events by short name, program events, every host
    span), starting at the first step that holds a ``tier.retry`` (else the
    third): a recorded trace small enough to keep with the tests."""
    import json

    tr = T.load(paths, PROGRAM_SPANS | HARNESS_SPANS)
    sched = sorted((e for e in tr.host if e.name == "scheduler.step"),
                   key=lambda e: e.start)
    retry = [e for e in tr.host if e.name == "tier.retry"]
    first = next((k for k, s in enumerate(sched)
                  if any(s.start <= r.start < s.end for r in retry)), 2)
    lo = sched[first].start
    hi = sched[min(first + steps, len(sched)) - 1].end
    chip = sorted(tr.ops)[0]
    def keep(evs, name=lambda e: e.name):
        return [[name(e), e.start - lo, e.dur, ""] for e in evs
                if e.end > lo and e.start < hi]

    with open(out, "w") as f:
        json.dump({"lo": 0.0, "hi": hi - lo,
                   "ops": keep(tr.ops[chip], lambda e: T.short_name(e.name)),
                   "modules": keep(tr.modules[chip]), "host": keep(tr.host)},
                  f, separators=(",", ":"))


def main(argv) -> None:
    """``python -m bench.program_spans <trace dir> [excerpt.json]``: the
    idle breakdown by program and host span, the six program metrics'
    numerators over the recorded slice, and optionally an excerpt."""
    paths = T.find_xplanes(argv[0])
    tr = T.load(paths, PROGRAM_SPANS | HARNESS_SPANS)
    chip = sorted(tr.ops)[0]
    ops, mods, host = tr.ops[chip], tr.modules.get(chip, []), tr.host
    lo, hi = slice_of(host)
    n = sum(1 for e in host if e.name == "tier.step" and lo <= e.start < hi)
    print(f"slice {(hi - lo) / 1e9:.3f} s, {n} tier steps, busy "
          f"{T.union_ns(ops, lo, hi) / 1e9:.3f} s")
    for name, s in attribute_idle(ops, mods, host, lo, hi, n=30):
        print(f"  idle {s:.6f} s  {name}")
    for what, pick in (("edge", is_edge), ("cloud", is_cloud),
                       ("prefill", is_prefill)):
        ns = module_ns(mods, pick, lo, hi) or 0.0
        print(f"  {what} device s {ns / 1e9}")
    print(f"  tier host idle s "
          f"{(host_idle_ns(ops, mods, host, TIER, None, lo, hi) or 0) / 1e9}")
    print(f"  sched host idle s "
          f"{(host_idle_ns(ops, mods, host, SCHED, TIER, lo, hi) or 0) / 1e9}")
    print(f"  retry / decode ns {retry_ns(mods, host, lo, hi)}")
    if len(argv) > 1:
        excerpt(paths, argv[1])


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
