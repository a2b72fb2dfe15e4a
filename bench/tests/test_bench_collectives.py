"""Collectives inside the decode programs (``bench.collectives``) and the
two readers built on it, on event lists counted by hand."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import collectives as C  # noqa: E402
from bench import harness as H  # noqa: E402
from bench import trace as T  # noqa: E402

E = T.Event
AR = "%all-reduce.12 = bf16[32,1,4096]{2,0,1} all-reduce(%fusion.342), " \
     "channel_id=3"
A2A = "%all-to-all.14 = bf16[4,2,4096]{2,1,0} all-to-all(%reshape.857)"
AG_START = "%all-gather-start.1 = (s32[1,32,4]) all-gather-start(%b.7)"
FUSION = "%fusion.3 = bf16[32,4096]{1,0} fusion(%a), kind=kLoop"
COPY_OF_AR = "%copy.1 = bf16[32,1,4096]{2,1,0} copy(%all-reduce.3)"

MODULES = [
    E("jit_prefill_p256_n1(1)", 0, 10),
    E("jit_decode_l0_18_bfull(2)", 10, 20),
    E("jit_decode_l18_36_b32(3)", 30, 30),
]
OPS = [
    E(AR, 2, 4),  # inside the prefill: not counted
    E(FUSION, 10, 5), E(AR, 15, 2), E(A2A, 17, 1), E(COPY_OF_AR, 18, 2),
    E(AR, 30, 3), E(AG_START, 40, 1), E(FUSION, 41, 10),
]


@pytest.mark.parametrize("name,want", [
    (AR, True), (A2A, True), (AG_START, True),
    ("%reduce-scatter.2 = f32[8] reduce-scatter(%x)", True),
    ("%all-reduce-scatter-fusion.1 = bf16[8] fusion(%x), kind=kOutput",
     True),
    (FUSION, False), (COPY_OF_AR, False),
])
def test_is_collective(name, want):
    assert C.is_collective(name) is want


def test_in_decode_counts_collectives_inside_decode_programs():
    count, ns, decode = C.in_decode(OPS, MODULES, 0, 100)
    assert (count, ns, decode) == (4, 2 + 1 + 3 + 1, 50)
    # cut to a slice: the edge program's part of it and the collectives
    # whose midpoint lies there, their time cut to the slice
    assert C.in_decode(OPS, MODULES, 0, 20) == (2, 3.0, 10)
    assert C.in_decode(OPS, MODULES, 0, 16.5) == (1, 1.5, 6.5)


def test_no_decode_program_reads_as_nothing():
    assert C.in_decode(OPS, MODULES[:1], 0, 100) is None


def fake_run(steps=2):
    other = [E(FUSION, 10, 20), E(AR, 30, 6)]  # a second chip
    trace = {"lo": 0.0, "hi": 100.0, "steps": (0, steps), "chips": [0, 1],
             "ops": {0: OPS, 1: other}, "modules": {0: MODULES, 1: MODULES}}
    return SimpleNamespace(trace=trace)


def test_readers():
    run = fake_run()
    share = H.metric_reader("step.collective_share",
                            ROOT / "bench" / "metrics")(run)
    # chip 0: 7 of 50 ns; chip 1: 6 of 50 ns
    assert share == pytest.approx(100 * (7 / 50 + 6 / 50) / 2)
    ops = H.metric_reader("step.collective_ops",
                          ROOT / "bench" / "metrics")(run)
    assert ops == 4 / 2  # the first chip's, per traced step
    empty = SimpleNamespace(trace={})
    for name in ("step.collective_share", "step.collective_ops"):
        assert H.metric_reader(name, ROOT / "bench" / "metrics")(empty) \
            is None
