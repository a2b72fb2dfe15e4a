"""The reduction from trace events to device time, on small event lists
whose answers are counted by hand, and on an excerpt recorded on a v5e."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace as T  # noqa: E402

E = T.Event
OPS = [
    E("fusion.1", 0, 10),
    E("fusion.2", 5, 10),  # overlaps fusion.1: busy 0-15
    E("custom-call.3", 20, 5, "jit(flash_decode_pallas)/pallas_call"),
    E("all-reduce.4", 30, 4),
    E("fusion.1", 38, 4),  # runs past the window's end at 40
]
HOST = [E("scheduler.step", 0, 40), E("PartitionedServer.step", 18, 16),
        E("prefill_rows", 14, 3)]


def test_union_counts_overlap_once_and_clips_to_the_window():
    assert T.union_ns(OPS, 0, 40) == 15 + 5 + 4 + 2
    assert T.union_ns(OPS, 6, 22) == 9 + 2
    assert T.union_ns([], 0, 40) == 0


def test_idle_gaps_are_the_complement():
    gaps = T.idle_gaps(OPS, 0, 40)
    assert gaps == [(15, 20), (25, 30), (34, 38)]
    assert sum(b - a for a, b in gaps) + T.union_ns(OPS, 0, 40) == 40
    assert T.idle_gaps([], 0, 10) == [(0, 10)]


def test_kernel_and_collective_sums():
    assert T.matching_ns(OPS, ("flash_decode",), 0, 40) == 5
    assert T.matching_ns(OPS, T.COLLECTIVES, 0, 40) == 4
    # Summed, not unioned: two overlapping matches both count.
    both = OPS + [E("all-reduce.9", 31, 2)]
    assert T.matching_ns(both, T.COLLECTIVES, 0, 40) == 6
    assert T.matching_ns(OPS, ("entropy_exit",), 0, 40) == 0


def test_top_ops_sums_by_name():
    top = T.top_ops(OPS, 0, 40, n=2)
    assert top == [["fusion.1", 12e-9], ["fusion.2", 10e-9]]


def test_gaps_charged_to_the_innermost_host_span():
    acc = dict(T.attribute_gaps(T.idle_gaps(OPS, 0, 40), HOST))
    # (15, 20): 15-17 inside prefill_rows, 17-18 only scheduler.step,
    # 18-20 PartitionedServer.step; (25, 30) PartitionedServer.step;
    # (34, 38) scheduler.step.
    assert acc["prefill_rows"] == pytest.approx(2e-9)
    assert acc["PartitionedServer.step"] == pytest.approx(2e-9 + 5e-9)
    assert acc["scheduler.step"] == pytest.approx(1e-9 + 4e-9)
    assert sum(acc.values()) == pytest.approx(14e-9)


EXCERPT = Path(__file__).parent / "data" / "v5e_trace_excerpt.json"


def test_recorded_v5e_excerpt():
    """One decode step as a v5e recorded it (the first chip's device ops
    and the harness's host spans): busy and idle time, kernel time and the
    top op, each counted here by brute force."""
    data = json.loads(EXCERPT.read_text())
    ops = [E(*e) for e in data["ops"]]
    host = [E(*e) for e in data["host"]]
    lo, hi = data["lo"], data["hi"]
    assert ops and host
    # Busy: a 1 ns grid would be exact but slow; sweep the sorted ends.
    cut = sorted((max(e.start, lo), min(e.end, hi)) for e in ops
                 if e.end > lo and e.start < hi)
    busy, end = 0.0, lo
    for a, b in cut:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert T.union_ns(ops, lo, hi) == pytest.approx(busy)
    gaps = T.idle_gaps(ops, lo, hi)
    assert sum(b - a for a, b in gaps) == pytest.approx(hi - lo - busy)
    charged = sum(s for _, s in T.attribute_gaps(gaps, host, n=100))
    assert charged == pytest.approx((hi - lo - busy) * 1e-9)
    for kernel in ("flash_decode", "entropy_exit"):
        want = sum(min(e.end, hi) - max(e.start, lo) for e in ops
                   if (kernel in e.name or kernel in e.labels)
                   and e.end > lo and e.start < hi)
        assert T.matching_ns(ops, (kernel,), lo, hi) == pytest.approx(want)
    per_name = {}
    for e in ops:
        per_name[e.name] = per_name.get(e.name, 0.0) + \
            max(0.0, min(e.end, hi) - max(e.start, lo))
    assert T.top_ops(ops, lo, hi, 1)[0][0] == max(per_name,
                                                   key=per_name.get)
