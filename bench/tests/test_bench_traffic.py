"""The traffic generator: seed-determinism, clips, context, steadiness."""

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import traffic as T  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [(w["name"], w["traffic"], w["config"]) for w in BENCH["workloads"]]
BIG_SEED = 2 ** 31 + 12345


def cell(traffic, config):
    mix = T.load(ROOT / "bench" / "traffic", traffic)
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    spec = json.loads((ROOT / entry["file"]).read_text())
    return mix, spec


def gen(mix, spec, seed, seconds=51.0):
    return T.generate(mix, seed, spec["vocab_size"], spec["context"],
                      seconds)


@pytest.mark.parametrize("name,traffic,config", CELLS)
def test_same_seed_same_requests(name, traffic, config):
    mix, spec = cell(traffic, config)
    a, b = gen(mix, spec, BIG_SEED), gen(mix, spec, BIG_SEED)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.arrival_s == y.arrival_s and x.budget == y.budget
        assert np.array_equal(x.prompt, y.prompt)
    c = gen(mix, spec, BIG_SEED + 1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("name,traffic,config", CELLS)
def test_sizes_inside_clips_and_context(name, traffic, config):
    mix, spec = cell(traffic, config)
    reqs = gen(mix, spec, 7)
    p, o = mix["prompt"], mix["output"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert len(r.prompt) % p.get("multiple", 1) == 0
        assert o["min"] <= r.budget <= o["max"]
        assert len(r.prompt) + r.budget <= spec["context"]
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < spec["vocab_size"]
    assert {len(r.prompt) for r in reqs} <= set(T.prompt_lengths(mix))


@pytest.mark.parametrize("name,traffic,config", CELLS)
def test_every_seed_offers_the_same_work(name, traffic, config):
    """Seeds change the order and the tokens, not the sizes: a run holds the
    same lengths whatever the seed, and an open loop's gaps are the same
    set, in an order drawn over the whole run (so the load of a stretch of
    requests differs from seed to seed, as a Poisson stream's does)."""
    mix, spec = cell(traffic, config)
    a, b = gen(mix, spec, 1), gen(mix, spec, 99)
    sizes = lambda reqs: [(len(r.prompt), r.budget) for r in reqs]
    assert Counter(len(r.prompt) for r in a) == Counter(len(r.prompt) for r in b)
    assert Counter(r.budget for r in a) == Counter(r.budget for r in b)
    assert Counter(sizes(a)) != Counter(sizes(b))  # pairings reshuffled
    if mix["arrivals"] == "poisson":
        gaps = lambda reqs: np.diff([0.0] + [r.arrival_s for r in reqs])
        np.testing.assert_allclose(sorted(gaps(a)), sorted(gaps(b)))
        assert a[-1].arrival_s == pytest.approx(b[-1].arrival_s)
        block = 16
        loads = lambda reqs: np.add.reduceat(gaps(reqs), range(0, len(reqs),
                                                               block))
        assert np.ptp(loads(a)) > 0.1 * np.mean(loads(a))  # no even blocks
        assert not np.allclose(loads(a), loads(b))
        assert 0.9 * 51.0 <= a[-1].arrival_s <= 1.05 * 51.0
        assert len(a) == round(mix["rate_per_s"] * 51.0)
    else:
        assert all(r.arrival_s == 0.0 for r in a)


def test_lognormal_quantiles_are_stratified():
    dist = {"median": 128, "sigma": 0.6, "min": 64, "max": 384}
    q = T.lengths(dist, 16, 64)
    assert list(q) == sorted(q)
    assert q[8] == 128 or q[7] == 128  # the median stratum
    assert q.min() == 64 and q.max() == 384
