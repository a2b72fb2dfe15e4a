"""``BENCHMARK.json`` and the files it names: the allowed characters, every
name found by discovery, and each per-layer metric's end-to-end metric
reported in each of its cells.  Also: adding a configuration, a mix or a
metric takes only new files and entries."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness as H  # noqa: E402
from bench import traffic as T  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def cell_metrics(cell, kind):
    # The rule the harness applies (bench.run.cell_metrics), without
    # importing the harness's JAX side.
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}

    def in_cell(m):
        if "workloads" in m:
            return cell in m["workloads"]
        return "moves" not in m or in_cell(e2e[m["moves"]])

    return [m["name"] for m in BENCH[kind] if in_cell(m)]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "-m", "bench.run"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + [
        x["name"] for x in BENCH["configs"] + BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for name in names:
        assert NAME.match(name), name
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [x["name"] for x in BENCH[group]]
        assert len(got) == len(set(got)), group
    for text in [c["source"] for c in BENCH["configs"]] + [
            x["why"] for x in BENCH["configs"] + BENCH["workloads"]] + [
            m["layer"] for m in BENCH["per_layer"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_end_to_end_bounds_and_sources():
    names = [m["name"] for m in BENCH["end_to_end"]]
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_enough(cell):
    e2e = cell_metrics(cell, "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell_metrics(cell, "per_layer")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_an_end_to_end_metric_of_its_cells(metric):
    cells = metric.get("workloads") or [w["name"] for w in
                                        BENCH["workloads"]]
    for cell in cells:
        assert metric["moves"] in cell_metrics(cell, "end_to_end"), cell
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").exists()
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_layers_are_named_alike():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers == {"server and tier runtime", "exit heads and compaction",
                      "model step", "kernels", "device"}
    mfu = [m for m in BENCH["per_layer"] if "mfu" in m["name"].split("_")]
    roof = [m for m in BENCH["per_layer"] if m["name"].endswith("_roofline")]
    for r in roof:
        assert any(m["moves"] == r["moves"] for m in mfu)


@pytest.mark.parametrize("wl", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_cells_name_files_that_exist(wl):
    entry = next(c for c in BENCH["configs"] if c["name"] == wl["config"])
    assert entry["file"].startswith("bench/configs/")
    spec = json.loads((ROOT / entry["file"]).read_text())
    assert spec["chips"] == wl["chips"] in (1, 4)
    assert spec["reduced"] == entry["reduced"]
    mix = T.load(ROOT / "bench" / "traffic", wl["traffic"])
    assert mix["arrivals"] in ("poisson", "backlog")
    limits = H.load_limits(wl["name"])
    assert "gap_mean" in limits and set(limits) <= set(H.READINGS)
    for lim in limits.values():
        if lim.get("exact"):
            assert lim["limit"] == lim["lower"] == 0
        else:
            # Between the program's readings and the control's, the more
            # room above the program's.
            lo, up = lim["lower"], lim["upper"]
            assert up >= 3 * lo
            assert lim["limit"] - lo > up - lim["limit"] > 0
    assert (ROOT / "bench" / "references"
            / f"{spec['reference']}.py").exists()
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_are_at_most_half():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_discovery_finds_an_added_metric_config_and_mix(tmp_path):
    """A new per-layer metric, configuration and mix are files found by
    name; nothing that is already there changes."""
    metrics = tmp_path / "metrics"
    shutil.copytree(ROOT / "bench" / "metrics", metrics)
    (metrics / "dummy.count.py").write_text(
        "def read(run):\n    return len(run.steps) or None\n")
    read = H.metric_reader("dummy.count", metrics)
    assert read(type("Run", (), {"steps": [1, 2, 3]})()) == 3
    assert read(type("Run", (), {"steps": []})()) is None
    # The readers that ship are found the same way.
    for m in BENCH["per_layer"]:
        assert callable(H.metric_reader(m["name"], metrics))

    traffic = tmp_path / "traffic"
    traffic.mkdir()
    mix = dict(T.load(ROOT / "bench" / "traffic", "exit-online"),
               rate_per_s=0.5)
    (traffic / "slow-online.json").write_text(json.dumps(mix))
    assert T.load(traffic, "slow-online")["rate_per_s"] == 0.5

    limits = tmp_path / "limits"
    limits.mkdir()
    (limits / "new-cell.json").write_text(json.dumps(
        {"gap_max": {"limit": 1.0}}))
    assert H.load_limits("new-cell", limits)["gap_max"]["limit"] == 1.0
    assert H.load_limits("absent", limits) == {}

    assert H.reference_module("dense_decoder").score


def test_run_refuses_without_a_tpu():
    """On a machine whose JAX finds no TPU the run exits non-zero before
    any work and prints no result line."""
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os

    env.update({k: v for k, v in os.environ.items()
                if k in ("HOME", "PYTHONPATH", "VIRTUAL_ENV")})
    out = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr
