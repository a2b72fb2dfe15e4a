"""The served path's host spans and program names, as a profiler trace
records them (CPU).

A tiny K=2 server runs a few scheduler steps under ``jax.profiler.trace``,
one of them with its downstream bucket hint pinned too low so that it
re-runs once; the ``.xplane.pb`` is read back with ``ProfileData``.  The
benchmark reads these names from its own list (``bench/program_spans.py``),
so the two lists are checked against each other here too.
"""

import dataclasses
import glob
import os
import sys
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serving import PartitionedServer, RequestScheduler, spans

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import program_spans as bench_spans  # noqa: E402

SLOTS = 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Host events and op events of a traced run of four steps: two plain
    steps, one forced re-run that also retires a request (and resets its
    row), and one that admits a new request into the freed row.  No row
    exits (threshold 0), so both rows reach the cloud tier."""
    cfg = dataclasses.replace(
        get_smoke_config("phi3_mini_3_8b"), num_layers=4,
        branch_layers=(1, 3), exit_threshold=0.0,
    )
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    server = PartitionedServer(cfg, params, 2, slots=SLOTS, context_len=64)
    sched = RequestScheduler(server, SLOTS, 64, reset_on_retire=True)
    ex = server.executor
    rng = np.random.default_rng(3)
    for budget in (5, 8):
        sched.submit(rng.integers(0, cfg.vocab_size, 6), budget)
    for _ in range(2):  # compile outside the trace
        sched.step()
    out = tmp_path_factory.mktemp("trace")
    forced = None
    with jax.profiler.trace(str(out)):
        for k in range(4):
            if k == 2:
                ex._hints = {1: 1}  # two live rows overflow a 1-row bucket
                forced = sched.step_count
                retries = ex.overflow_retries
            if k == 3:
                sched.submit(rng.integers(0, cfg.vocab_size, 6), 2)
            rep = sched.step()
            if k == 2:
                assert ex.overflow_retries == retries + 1
                assert len(rep.retired) == 1
            if k == 3:
                assert len(rep.admitted) == 1
    assert ex.overflow_retries >= 1
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    host, ops = [], []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        ev = (e.name, e.start_ns, e.start_ns + e.duration_ns,
                              _stats(e))
                        (ops if "hlo_module" in ev[3] else host).append(ev)
    return {"host": host, "ops": ops, "forced": forced, "executor": ex}


def _stats(event) -> dict:
    # Reading event stats warns once per event on this jaxlib
    # (DeprecationWarning: builtin type event_stats has no __module__).
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def _named(events, name):
    return sorted((e for e in events if e[0] == name), key=lambda e: e[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_the_program_and_the_benchmark_list_the_same_spans():
    assert set(spans.ALL) == bench_spans.PROGRAM_SPANS
    assert len(spans.ALL) == len(set(spans.ALL))


def test_every_span_is_on_the_host_plane_by_its_plain_name(traced):
    names = {e[0] for e in traced["host"]}
    for name in bench_spans.PROGRAM_SPANS:
        assert name in names, name
    assert not any("#" in n for n in names if n.startswith(("sched.",
                                                             "tier.")))
    steps = _named(traced["host"], "sched.step")
    assert [e[3]["step_num"] for e in steps] == list(
        range(steps[0][3]["step_num"], steps[0][3]["step_num"] + 4))
    dispatch = _named(traced["host"], "tier.dispatch")
    assert {e[3]["seg"] for e in dispatch} == {0, 1}
    assert all(1 <= e[3]["bucket"] <= SLOTS for e in dispatch)


def test_spans_nest_sync_in_tier_step_in_sched_step(traced):
    host = traced["host"]
    sched_steps = _named(host, "sched.step")
    tier_steps = _named(host, "tier.step")
    assert len(tier_steps) == len(sched_steps) == 4
    for sync in _named(host, "tier.sync"):
        outer = [t for t in tier_steps if _inside(sync, t)]
        assert len(outer) == 1
        assert any(_inside(outer[0], s) for s in sched_steps)
    for name in ("tier.plan", "tier.stage", "tier.dispatch",
                 "tier.account", "tier.retry"):
        for e in _named(host, name):
            assert any(_inside(e, t) for t in tier_steps), name
    for name in ("sched.admit", "sched.emit", "sched.retire"):
        for e in _named(host, name):
            assert any(_inside(e, s) for s in sched_steps), name
    for e in _named(host, "tier.prefill"):
        assert any(_inside(e, a) for a in _named(host, "sched.admit"))
    for e in _named(host, "tier.reset"):
        assert any(_inside(e, r) for r in _named(host, "sched.retire"))


def test_one_sync_per_step_and_two_with_one_retry_when_forced(traced):
    host = traced["host"]
    syncs, retries = _named(host, "tier.sync"), _named(host, "tier.retry")
    for step in _named(host, "sched.step"):
        n_sync = sum(_inside(s, step) for s in syncs)
        n_retry = sum(_inside(r, step) for r in retries)
        if step[3]["step_num"] == traced["forced"]:
            assert (n_sync, n_retry) == (2, 1)
            retry = next(r for r in retries if _inside(r, step))
            assert sum(_inside(s, retry) for s in syncs) == 1
            assert sum(_inside(d, retry)
                       for d in _named(host, "tier.dispatch")) == 2
        else:
            assert (n_sync, n_retry) == (1, 0)


def test_programs_carry_their_cache_key_names(traced):
    modules = {e[3]["hlo_module"] for e in traced["ops"]}
    assert "jit_decode_l0_2_bfull" in modules
    assert any(m.startswith("jit_decode_l2_4_b") for m in modules)
    assert "jit_decode_l2_4_b1" in modules  # the forced 1-row bucket
    assert any(m.startswith("jit_prefill_p6_n") for m in modules)
    assert any(m.startswith("jit_reset_rows_n") for m in modules)
    served = [m for m in modules if "decode" in m or "prefill" in m
              or "reset" in m]
    assert not [m for m in served if m in ("jit_fn", "jit_prefill_fn",
                                           "jit_reset_fn")]
    assert [m for m in modules if "prefill" in m] == [
        m for m in modules if m.startswith("jit_prefill_p")]


def test_cache_keys_keep_their_shape(traced):
    ex = traced["executor"]
    for key in ex._fn_cache:
        if key[0] == "prefill":
            assert len(key) == 3
        elif key[0] == "reset":
            assert len(key) == 2
        else:
            spec, bucket = key
            assert len(spec) == 8 and (bucket is None or bucket >= 1)
    assert set(ex.trace_counts) <= set(ex._fn_cache)


def test_variant_names(traced):
    ex = traced["executor"]
    edge, cloud = ex.segments
    probe = ex._segment_fn(edge, False, None, probe=(3,))
    degrade = ex._segment_fn(edge, False, None, degrade=1)
    both = ex._segment_fn(cloud, True, 2, probe=(3,), degrade=3)
    assert probe.__name__ == "decode_l0_2_bfull_probe"
    assert degrade.__name__ == "decode_l0_2_bfull_degrade1"
    assert both.__name__ == "decode_l2_4_b2_probe_degrade3"
    assert bench_spans.program_of(type("E", (), {
        "name": "jit_decode_l2_4_b2_probe_degrade3(7)"})()) == \
        "decode_l2_4_probe_degrade3"
