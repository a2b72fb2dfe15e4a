"""The one traffic generator; each mix is a JSON file of its parameters.

A mix file (``bench/traffic/<name>.json``) holds:

  * ``arrivals``: ``"poisson"`` (an open loop at ``rate_per_s``) or
    ``"backlog"`` (``backlog_requests`` queued at time 0);
  * ``prompt`` and ``output``: lognormal lengths, ``{"median", "sigma",
    "min", "max"}``, prompts rounded up to a multiple of ``multiple``;
  * ``stop_on_exit``; ``exit``, the exit regime (absent: the model's
    default threshold, no calibration): ``threshold``, the head's
    ``layer`` and the ``calibrate`` decode of ``harness.calibrate_exit``;
  * ``check_requests``, the finished requests the output check samples.

Sizes and gaps are stratified over the whole run: a run of ``n`` requests
takes the ``n`` quantiles (i + 0.5) / n of each length distribution and of
the exponential inter-arrival gap, each in its own order drawn from the
seed over the whole run.  So every seed offers the same work and the same
set of gaps, and the prompt tokens, drawn uniformly from the vocabulary,
are what the seed changes most; the arrivals clump and thin out by chance
as a Poisson stream's do (a Poisson stream given its count and its gaps),
with no block that evens the load out.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Request:
    arrival_s: float  # scheduled send time after the window opens
    prompt: np.ndarray  # (P,) int32
    budget: int  # new tokens to decode


def load(directory: Path, name: str) -> dict:
    with open(directory / f"{name}.json") as f:
        return json.load(f)


def lengths(dist: dict, n: int, multiple: int = 1) -> np.ndarray:
    """The ``n`` stratified lognormal quantiles of ``dist``, rounded up to
    ``multiple`` and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    raw = dist["median"] * np.exp(dist["sigma"] * z)
    rounded = np.ceil(raw / multiple) * multiple
    return np.clip(rounded, dist["min"], dist["max"]).astype(np.int64)


def count(spec: dict, seconds: float) -> int:
    if spec["arrivals"] == "poisson":
        return max(1, round(spec["rate_per_s"] * seconds))
    if spec["arrivals"] == "backlog":
        return int(spec["backlog_requests"])
    raise ValueError(f"unknown arrivals {spec['arrivals']!r}")


def arrivals(spec: dict, n: int, rng) -> np.ndarray:
    """Send times: the ``n`` quantiles of the exponential inter-arrival gap
    at ``rate_per_s``, in an order drawn from the seed over the whole
    run."""
    if spec["arrivals"] == "backlog":
        return np.zeros(n)
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / spec["rate_per_s"]
    return np.cumsum(rng.permutation(gaps))


def generate(spec: dict, seed: int, vocab: int, context: int,
             seconds: float) -> list[Request]:
    """The mix's requests for one run, in send order."""
    rng = np.random.default_rng(int(seed))
    n = count(spec, seconds)
    plen = rng.permutation(lengths(spec["prompt"], n,
                                   spec["prompt"].get("multiple", 1)))
    budget = rng.permutation(lengths(spec["output"], n))
    longest = int((plen + budget).max())
    if longest > context:
        raise ValueError(f"a request needs {longest} positions; the "
                         f"configuration holds {context}")
    times = arrivals(spec, n, rng)
    return [
        Request(float(t), rng.integers(0, vocab, int(p), dtype=np.int32),
                int(b))
        for t, p, b in zip(times, plen, budget)
    ]


def prompt_lengths(spec: dict) -> list[int]:
    """Every prompt length the mix can produce (the prefill shapes): each
    multiple of ``multiple`` from the minimum up, and the maximum."""
    p = spec["prompt"]
    m, hi = int(p.get("multiple", 1)), int(p["max"])
    lo = -(-int(p["min"]) // m) * m
    return list(range(lo, hi, m)) + [hi]
