"""The readers of the program's own spans and program names, on small event
lists whose answers are counted by hand, on an excerpt recorded on a v5e,
and beside the readers that were there before them."""

import dataclasses
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import program_spans as P  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.flops import Dims  # noqa: E402
from bench.peaks import DEVICE_PEAKS  # noqa: E402

E = T.Event
NEW = ("tier.host_idle_ms", "sched.host_idle_ms", "tier.edge_device_ms",
       "tier.cloud_device_ms", "tier.prefill_device_ms",
       "tier.retry_device_share")

# One scheduler step, in ns: an admission prefill, the two segments, a
# sync, one overflow re-run of both segments with its own sync, and the
# bookkeeping after it.
HOST = [
    E("scheduler.step", 0, 100), E("sched.step", 0, 99),
    E("sched.admit", 0, 10), E("prefill_rows", 1, 8), E("tier.prefill", 2, 6),
    E("PartitionedServer.step", 11, 80), E("tier.step", 12, 78),
    E("tier.plan", 12, 3), E("tier.stage", 15, 3),
    E("tier.dispatch", 18, 2), E("tier.dispatch", 20, 2),
    E("tier.sync", 22, 38),
    E("tier.retry", 62, 23), E("tier.stage", 62, 1),
    E("tier.dispatch", 63, 2), E("tier.sync", 66, 18),
    E("tier.account", 85, 5), E("sched.emit", 90, 8),
]
MODULES = [
    E("jit_prefill_p64_n1(1)", 5, 7),
    E("jit_decode_l0_16_bfull(2)", 19, 21),
    E("jit_decode_l16_32_b4(3)", 41, 17),
    E("jit_decode_l0_16_bfull(2)", 64, 8),  # the re-run
    E("jit_decode_l16_32_b8(4)", 72, 11),
]
OPS = [
    E("fusion.1", 5, 6),  # the prefill; 11-12 idle inside its program
    E("fusion.2", 19, 11), E("copy.3", 32, 8),  # 30-32 idle inside edge
    E("fusion.4", 41, 17),
    E("fusion.2", 64, 8), E("fusion.4", 72, 11),
]


def fake_run(ops=OPS, modules=MODULES, host=HOST, steps=1, admitted=(7,)):
    trace = {"lo": 0.0, "hi": 100.0, "steps": (0, steps), "chips": [0],
             "ops": {0: ops}, "modules": {0: modules},
             "busy_ns": {0: T.union_ns(ops, 0, 100)}, "window_ns": 100.0,
             "host_spans": host}
    return SimpleNamespace(trace=trace, steps=[
        SimpleNamespace(admitted=admitted if i == 0 else ())
        for i in range(steps)])


def read(name, run):
    return H.metric_reader(name)(run)


def test_host_idle_by_layer_of_spans():
    run = fake_run()
    # Idle with no program running: 0-5, 12-19, 40-41, 58-64, 83-100.
    # In a tier.* span: 2-5 (tier.prefill), 12-19, 40-41, 58-64, 83-90.
    assert read("tier.host_idle_ms", run) == pytest.approx(24e-6)
    # In a sched.* span and no tier.* span: 0-2, 90-99.
    assert read("sched.host_idle_ms", run) == pytest.approx(11e-6)
    run2 = fake_run(steps=2)
    assert read("tier.host_idle_ms", run2) == pytest.approx(12e-6)


def test_device_time_by_program():
    run = fake_run()
    assert read("tier.edge_device_ms", run) == pytest.approx(29e-6)
    assert read("tier.cloud_device_ms", run) == pytest.approx(28e-6)
    assert read("tier.prefill_device_ms", run) == pytest.approx(7e-6)
    two = fake_run(admitted=(7, 8))
    assert read("tier.prefill_device_ms", two) == pytest.approx(3.5e-6)


def test_retry_share_counts_programs_started_after_the_first_sync():
    # Decode programs: 21 + 17 before the first sync ends (60), 8 + 11
    # after it.
    assert read("tier.retry_device_share", fake_run()) == pytest.approx(
        100 * 19 / 57)
    plain = [e for e in HOST if e.name not in ("tier.retry",)
             and not (e.start >= 62 and e.name.startswith("tier.")
                      and e.name != "tier.account")]
    assert read("tier.retry_device_share",
                fake_run(modules=MODULES[:3], host=plain)) == 0.0


def test_gap_inside_a_running_program_is_the_programs():
    acc = dict(P.attribute_idle(OPS, MODULES, HOST, 0, 100, n=100))
    # 11-12 inside the prefill program, 30-32 inside the edge program:
    # charged to the programs, not to sched.admit or tier.sync around them.
    assert acc["program:prefill"] == pytest.approx(1e-9)
    assert acc["program:decode_l0_16"] == pytest.approx(2e-9)
    assert acc["sched.admit"] == pytest.approx(1e-9)
    assert acc["prefill_rows"] == pytest.approx(1e-9)
    assert acc["tier.prefill"] == pytest.approx(3e-9)
    assert acc["tier.plan"] == pytest.approx(3e-9)
    assert acc["tier.stage"] == pytest.approx(4e-9)
    assert acc["tier.dispatch"] == pytest.approx(2e-9)
    assert acc["tier.sync"] == pytest.approx(4e-9)
    assert acc["tier.step"] == pytest.approx(2e-9)
    assert acc["tier.retry"] == pytest.approx(1e-9)
    assert acc["tier.account"] == pytest.approx(5e-9)
    assert acc["sched.emit"] == pytest.approx(8e-9)
    assert acc["sched.step"] == pytest.approx(1e-9)
    assert acc["scheduler.step"] == pytest.approx(1e-9)
    idle = sum(b - a for a, b in T.idle_gaps(OPS, 0, 100))
    assert sum(acc.values()) == pytest.approx(idle * 1e-9)


def test_interval_algebra():
    a = [(0, 10), (20, 30)]
    b = [(5, 25)]
    assert P.intersect(a, b) == [(5, 10), (20, 25)]
    assert P.subtract(a, b) == [(0, 5), (25, 30)]
    assert P.subtract(a, []) == a and P.intersect(a, []) == []
    assert P.subtract([(0, 10)], [(2, 3), (4, 5), (9, 12)]) == [
        (0, 2), (3, 4), (5, 9)]


def test_program_names():
    name = lambda s: P.program_of(E(s, 0, 1))  # noqa: E731
    assert name("jit_decode_l0_16_bfull(12)") == "decode_l0_16"
    assert name("jit_decode_l16_32_b4(3)") == "decode_l16_32"
    assert name("jit_decode_l0_16_b8_probe_degrade8(3)") == \
        "decode_l0_16_probe_degrade8"
    assert name("jit_prefill_p256_n2(4)") == "prefill"
    assert name("jit_reset_rows_n1(5)") == "reset_rows"
    # An older program's names: nothing of the served path is recognized.
    for old in ("jit_fn(1)", "jit_prefill_fn(2)", "jit_reset_fn(3)"):
        assert name(old) is None


def test_an_older_program_reads_as_nothing():
    """Without the program's spans and names (the parent of the change
    that added them) every new reader returns None and raises nothing."""
    old = [E("jit_prefill_fn(1)", 5, 7), E("jit_fn(2)", 19, 21),
           E("jit_fn(3)", 41, 17)]
    harness = [e for e in HOST if e.name in P.HARNESS_SPANS]
    for host in (harness, []):
        run = fake_run(modules=old, host=host)
        for name in NEW:
            assert read(name, run) is None, name
    empty = SimpleNamespace(trace={}, steps=[])
    for name in NEW:
        assert read(name, empty) is None, name


def test_trace_files_not_found_or_not_this_runs(tmp_path):
    run = fake_run()
    del run.trace["host_spans"]
    assert P.host_spans(run, tmp_path / "absent") == []
    assert run.trace["host_spans"] == []
    for name in NEW[:2] + NEW[-1:]:
        assert read(name, run) is None
    # A trace whose program events are not the run's is not read.
    import jax

    root = tmp_path / "traces"
    with jax.profiler.trace(str(root / "run")):
        with jax.profiler.TraceAnnotation("tier.step"):
            jax.numpy.ones(3).block_until_ready()
    run = fake_run()
    del run.trace["host_spans"]
    assert P.host_spans(run, root) == []


EXCERPT = Path(__file__).parent / "data" / "v5e_program_trace_excerpt.json"


def _excerpt():
    data = json.loads(EXCERPT.read_text())
    ev = lambda key: [E(*e) for e in data[key]]  # noqa: E731
    return ev("ops"), ev("modules"), ev("host"), data["lo"], data["hi"]


def _cover(bounds, events):
    """Per elementary interval of ``bounds``: whether any event covers it."""
    diff = np.zeros(len(bounds) + 1, int)
    for e in events:
        diff[np.searchsorted(bounds, e.start)] += 1
        diff[np.searchsorted(bounds, e.end)] -= 1
    return np.cumsum(diff)[:-1][:-1] > 0


def test_recorded_v5e_program_excerpt():
    """One scheduler step of the exit cell as a v5e recorded it, one with
    an overflow re-run (seed 2147485101): the program's spans and program
    events, and the readers' sums against a brute-force sweep over every
    event boundary."""
    ops, mods, host, lo, hi = _excerpt()
    names = {e.name for e in host}
    assert P.PROGRAM_SPANS - {"sched.retire", "tier.reset",
                              "tier.prefill"} <= names
    assert any(e.name == "tier.retry" for e in host)
    assert all(P.program_of(m) for m in mods if "decode" in m.name
               or "prefill" in m.name)
    ends = [t for e in ops + mods + host for t in (e.start, e.end)]
    bounds = np.unique(np.clip(np.array(ends + [lo, hi]), lo, hi))
    width = np.diff(bounds)
    busy, running = _cover(bounds, ops), _cover(bounds, mods)
    tier = _cover(bounds, [e for e in host if e.name.startswith("tier.")])
    sched = _cover(bounds, [e for e in host if e.name.startswith("sched.")])
    free = ~busy & ~running
    assert P.host_idle_ns(ops, mods, host, P.TIER, None, lo, hi) == \
        pytest.approx(width[free & tier].sum())
    assert P.host_idle_ns(ops, mods, host, P.SCHED, P.TIER, lo, hi) == \
        pytest.approx(width[free & sched & ~tier].sum())
    acc = P.attribute_idle(ops, mods, host, lo, hi, n=1000)
    assert sum(s for _, s in acc) == pytest.approx(width[~busy].sum() * 1e-9)
    inside = sum(s for k, s in acc if k.startswith("program:"))
    assert inside == pytest.approx(width[~busy & running].sum() * 1e-9)
    for pick in (P.is_edge, P.is_cloud, P.is_prefill):
        want = sum(min(m.end, hi) - max(m.start, lo) for m in mods
                   if pick(m) and m.end > lo and m.start < hi)
        assert (P.module_ns(mods, pick, lo, hi) or 0.0) == \
            pytest.approx(want)
    retried, total = P.retry_ns(mods, host, lo, hi)
    assert 0 < retried < total
    want = 0.0
    for m in filter(P.is_decode, mods):
        step = next((t for t in host if t.name == "tier.step"
                     and t.start <= m.start < t.end), None)
        syncs = [s.end for s in host if s.name == "tier.sync" and step
                 and step.start <= s.start and s.end <= step.end]
        if syncs and m.start >= min(syncs):
            want += min(m.end, hi) - max(m.start, lo)
    assert retried == pytest.approx(want)


def _readers_run(host):
    """A run over the excerpt's step, counted as two for the readers that
    were there before the program's spans: eight live rows per step, none
    exited."""
    ops, mods, _, lo, hi = _excerpt()
    spec = H.load_json(ROOT / "bench" / "configs" / "phi3-mini-3.8b-k2.json")
    steps = [H.Step(lo, hi, 8, ((8, 8),), 1, 0, (), 0.05)] * 2
    tokens = [(i, 200 + r, False, 201 + r, r) for i in range(2)
              for r in range(8)]
    run = H.Run(spec=spec, traffic={}, dims=Dims.from_spec(spec),
                peaks=DEVICE_PEAKS["TPU v5 lite"], chips=1, branches=(8,),
                depth_cut=16, window=(lo, hi), steps=steps, reqs={},
                tokens=tokens)
    run.trace = {"lo": lo, "hi": hi, "steps": (0, 2), "chips": [0],
                 "ops": {0: ops}, "modules": {0: mods},
                 "busy_ns": {0: T.union_ns(ops, lo, hi)},
                 "window_ns": hi - lo, "breakdown": {}}
    if host is not None:
        run.trace["host_spans"] = host
    return run


BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
OLD = [m["name"] for m in BENCH["per_layer"] if m["name"] not in NEW]


def test_existing_readers_read_the_same_with_the_programs_spans():
    """Loading the program's spans (as the new readers do, into
    ``run.trace``) moves no reading of the metrics that were there."""
    _, _, host, _, _ = _excerpt()
    bare, loaded = _readers_run(None), _readers_run(host)
    for name in NEW:  # the excerpt's step admits no request
        got = read(name, loaded)
        assert (got is None) == (name == "tier.prefill_device_ms"), name
    for name in OLD:
        a, b = read(name, bare), read(name, loaded)
        assert a == b, name
    assert dataclasses.asdict(bare.steps[0]) == dataclasses.asdict(
        loaded.steps[0])


def test_every_new_metric_is_declared_for_both_cells():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for name in NEW:
        m = declared[name]
        assert m["moves"] == "itl_p90_ms" and m["workloads"] == cells
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == list(NEW)
