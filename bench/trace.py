"""Reduction of a profiler trace (``.xplane.pb``) to device time.

``load`` reads the trace with nothing but JAX's ``ProfileData`` and keeps,
per chip, the op events of its "XLA Ops" line and the program events of
its "XLA Modules" line, plus the host spans the harness annotated.  The
functions below it work on plain event lists, so a small recorded excerpt
checks them without a chip:

  * ``union_ns``: busy time, the union of the intervals in which an op ran;
  * ``idle_gaps``: the complement of that union inside a window;
  * ``matching_ns``: summed device time of the ops whose name or labels
    contain a pattern (a kernel, a collective);
  * ``top_ops``: device time per op name;
  * ``attribute_gaps``: idle time by the innermost host span around it.

Times are nanoseconds on the trace's clock, which the profiler shares
between host and device planes.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

#: Collective operations, by the names XLA gives them.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "allreduce", "allgather")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    dur: float  # ns
    labels: str = ""  # the event's string stats, joined

    @property
    def end(self) -> float:
        return self.start + self.dur

    def mentions(self, pattern: str) -> bool:
        return pattern in self.name or pattern in self.labels


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]  # chip id -> op events
    modules: dict[int, list[Event]]  # chip id -> program events
    host: list[Event]  # annotated host spans


#: Ops that contain other ops of the same line (a scan's loop, a branch):
#: their time is their body's, so they are left out of the top ops.
CONTAINERS = frozenset({"while", "conditional", "call"})
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def find_xplanes(directory: str) -> list[str]:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found


def opcode(name: str) -> str:
    """The HLO opcode of a TPU op event named by its instruction text
    (``%copy.45 = bf16[...]{...} copy(...)``); "" if there is none."""
    _, eq, rhs = name.partition(" = ")
    m = _OPCODE.search(" " + rhs) if eq else None
    return m.group(1) if m else ""


def short_name(name: str) -> str:
    """``copy.45 copy bf16[32,8,512,32,96]`` for an HLO instruction text;
    other names unchanged."""
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    shape = "" if rhs.startswith("(") else rhs.split("{")[0].split(" ")[0]
    return f"{lhs.lstrip('%')} {opcode(name)} {shape}".strip()[:120]


def _labels(ev) -> str:
    out = []
    for item in ev.stats:
        value = item[1] if isinstance(item, tuple) else item
        if isinstance(value, str):
            out.append(value)
    return " ".join(out)


def _planes(paths):
    from jax.profiler import ProfileData

    for path in paths:
        yield from ProfileData.from_file(path).planes


def load(paths: list[str], host_names: frozenset[str]) -> Trace:
    """Device op and program events per chip, and the named host spans,
    from every ``.xplane.pb`` of one traced run."""
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in _planes(paths):
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                             _labels(e)) for e in line.events]
                (ops if line.name == OPS_LINE else modules).setdefault(
                    chip, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.duration_ns)))
    return Trace(ops, modules, host)


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of ``events`` cut to [lo, hi), sorted by start."""
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    out.sort()
    return out


def merged(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def union_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(clip(events, lo, hi)))


def idle_gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    gaps, t = [], lo
    for a, b in merged(clip(events, lo, hi)):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def matching_ns(events, patterns, lo: float, hi: float) -> float:
    """Summed (not unioned) device time of the ops that mention any of
    ``patterns``, cut to the window."""
    sel = [e for e in events if any(e.mentions(p) for p in patterns)]
    return sum(b - a for a, b in clip(sel, lo, hi))


def top_ops(events, lo: float, hi: float, n: int = 10):
    """[(op, seconds)] of the ``n`` ops that took most device time, by
    short name, containers left out."""
    acc: dict[str, float] = collections.defaultdict(float)
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a and opcode(e.name) not in CONTAINERS:
            acc[short_name(e.name)] += b - a
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def attribute_gaps(gaps, spans, n: int = 10):
    """[(host activity, idle seconds)] summed over ``gaps``, each gap split
    at the boundaries of the host spans around it and each piece charged
    to the innermost (shortest) span covering it, "no span" otherwise."""
    bounds = sorted({t for s in spans for t in (s.start, s.end)})
    acc: dict[str, float] = collections.defaultdict(float)
    for g0, g1 in gaps:
        cuts = [g0] + [t for t in bounds if g0 < t < g1] + [g1]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = [s for s in spans if s.start <= mid < s.end]
            name = min(cover, key=lambda s: s.dur).name if cover \
                else "no span"
            acc[name] += b - a
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def describe(paths: list[str], n: int = 60) -> None:
    """Print the planes and lines of a trace and, for the first device, the
    ``n`` op names with most device time and their labels: what the
    reduction's patterns are written against."""
    first = None
    for plane in _planes(paths):
        lines = [(line.name, sum(1 for _ in line.events))
                 for line in plane.lines]
        print(f"plane {plane.name}: {lines}")
        if first is None and DEVICE_PLANE.match(plane.name):
            first = plane
    if first is None:
        return
    acc: dict[str, list] = {}
    for line in first.lines:
        if line.name != OPS_LINE:
            continue
        for e in line.events:
            entry = acc.setdefault(e.name, [0.0, 0, _labels(e)[:400]])
            entry[0] += e.duration_ns
            entry[1] += 1
    for name, (ns, count, labels) in sorted(acc.items(),
                                            key=lambda kv: -kv[1][0])[:n]:
        print(f"{ns / 1e6:10.3f} ms {count:6d}x {name} | {labels}")


def excerpt(paths: list[str], out: str, step: int = 3) -> None:
    """Write the first chip's op events and the harness's host spans over
    the ``step``-th recorded ``scheduler.step`` span as JSON: a recorded
    trace small enough to keep with the tests."""
    import json

    tr = load(paths, frozenset({"submit", "wait", "scheduler.step",
                               "PartitionedServer.step", "prefill_rows"}))
    steps = sorted((e for e in tr.host if e.name == "scheduler.step"),
                   key=lambda e: e.start)
    lo, hi = steps[step].start, steps[step].end
    chip = sorted(tr.ops)[0]
    keep = lambda evs: [[e.name, e.start - lo, e.dur, e.labels[:160]]
                        for e in evs if e.end > lo and e.start < hi]
    with open(out, "w") as f:
        json.dump({"lo": 0.0, "hi": hi - lo, "ops": keep(tr.ops[chip]),
                   "host": keep(tr.host)}, f)


if __name__ == "__main__":
    import sys

    if len(sys.argv) > 2:
        excerpt(find_xplanes(sys.argv[1]), sys.argv[2])
    else:
        describe(find_xplanes(sys.argv[1]))
