"""The comparison that decides ``correct``, driven end to end on the CPU.

A tiny copy of the phi3 configuration (4 layers, 128 wide, the same
K=2 plan shape) serves each phi3 cell's mix through the whole run, with
the look for a chip skipped.  A sound run is correct under the cell's
limits; the float8 control, and each fault the cell can have planted
where the timed path produces it, are not:

  * a token altered where the decode step produces it;
  * an exit decision altered where the exit head makes it (a token that
    ran on reported as exited at the layer-8 head, and the reverse).
"""

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

CELLS = ("phi3mini-k2-exit-online", "phi3mini-k2-noexit-offline")
SECONDS = 3.0
SEEDS = (2 ** 31 + 5, 77)


@pytest.fixture(scope="module", params=CELLS)
def tiny(request, tmp_path_factory):
    """A checkout-like root holding a tiny copy of a cell, with its mix's
    shape and its limits; and the built system after a first sound run."""
    from bench import run as R

    CELL = request.param
    root = tmp_path_factory.mktemp("tinybench")
    (root / "bench").mkdir()
    for d in ("metrics", "limits", "traffic"):
        shutil.copytree(ROOT / "bench" / d, root / "bench" / d)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    spec = json.loads((ROOT / entry["file"]).read_text())
    sizes = dict(hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=32, intermediate_size=256,
                 vocab_size=512)
    spec.update(sizes, branch_layers=[1, 3], cuts=[2], slots=4, context=128)
    spec["program"] = dict(
        param_dtype="bfloat16", num_layers=4, d_model=128, num_heads=4,
        num_kv_heads=2, head_dim=32, d_ff=256, vocab_size=512,
        branch_layers=[1, 3])
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(spec))
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{wl['traffic']}.json").read_text())
    mix.update(prompt={"median": 16, "sigma": 0.6, "min": 8, "max": 48,
                       "multiple": 8},
               output={"median": 8, "sigma": 0.6, "min": 4, "max": 24})
    if mix["arrivals"] == "poisson":
        mix["rate_per_s"] = 4.0
    if "exit" in mix:
        mix["exit"] = dict(mix["exit"], layer=1)
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    shutil.copy(root / "bench" / "limits" / f"{CELL}.json",
                root / "bench" / "limits" / "tiny.json")
    tiny_bench = dict(bench, workloads=[dict(wl, name="tiny", config="tiny",
                                             traffic="tiny")],
                      configs=[dict(entry, name="tiny",
                                    file="bench/configs/tiny.json")])
    for m in tiny_bench["end_to_end"] + tiny_bench["per_layer"]:
        m.pop("workloads", None)

    def run(seed, system=None, control=False):
        return R.run_cell(tiny_bench, "tiny", seed, SECONDS, False,
                          system=system, control=control, root=root,
                          t_start=time.perf_counter())

    result, system = run(SEEDS[0], control=True)
    return {"run": run, "system": system, "first": result, "root": root}


def plant(system, monkeypatch, fault):
    """Wrap the served step so that ``fault`` alters every live row's
    result from the third step that decodes two or more live rows on (the
    check samples requests, so every request carries the fault)."""
    inner = system.server.step
    seen = []

    def step(tok, pos, caches, *, active=None):
        rep, caches = inner(tok, pos, caches, active=active)
        live = np.flatnonzero(active)
        if len(live) >= 2:
            seen.append(1)
            if len(seen) >= 3:
                for slot in live:
                    fault(rep.tier_result, slot)
        return rep, caches

    monkeypatch.setattr(system.server, "step", step)


def alter_token(res, slot):
    res.tokens = np.array(res.tokens)
    res.tokens[slot] = (int(res.tokens[slot]) + 1) % 512


def flip_exit(res, slot):
    res.exited, res.exit_tier = np.array(res.exited), np.array(res.exit_tier)
    res.exited[slot] = not res.exited[slot]
    res.exit_tier[slot] = 0 if res.exited[slot] else -1


def test_sound_runs_are_correct(tiny):
    first = tiny["first"]
    assert first["failed"] == 0 and first["attempted"] > 0
    assert first["correct"], first["checks"]
    result, _ = tiny["run"](SEEDS[1], tiny["system"])
    assert result["correct"], result["checks"]
    assert 0 < result["metrics"]["itl_p90_ms"]["value"] \
        <= result["metrics"]["itl_p95_ms"]["value"]


def test_the_float8_control_fails(tiny):
    """The reference computed in float8 in the program's place is judged
    not correct by the run's own comparison, while the bf16 program is
    correct, under limits set as the cell's are: between the program's
    reading and the control's, on the numbers the control reads at least
    three times higher, at the geometric mean of the two (the program's
    taken as at least a hundredth of the control's, since it may read 0).
    At this size the readings sit lower than at the cell's and swing more
    between seeds, so the limits come from a first seed here and judge a
    second."""
    first, root = tiny["first"], tiny["root"]
    readings = first["readings"]
    compared = [name for name in first["checks"] if name != "failed"]
    apart = [name for name in compared
             if readings[f"control_{name}"] > 0
             and readings[f"control_{name}"] >= 3 * readings[name]]
    assert apart, readings
    path = root / "bench" / "limits" / "tiny.json"
    kept = path.read_text()
    try:
        path.write_text(json.dumps({name: {"limit": float(np.sqrt(
            max(readings[name], readings[f"control_{name}"] / 100)
            * readings[f"control_{name}"]))} for name in apart}))
        result, _ = tiny["run"](SEEDS[1], tiny["system"], control=True)
    finally:
        path.write_text(kept)
    assert result["correct"], (result["checks"], readings)
    assert result["control_correct"] is False, (result["control_checks"],
                                                readings)
    assert set(result["control_checks"]) == set(apart)


@pytest.mark.parametrize("fault", [alter_token, flip_exit],
                         ids=["token_altered", "exit_altered"])
def test_a_planted_fault_is_not_correct(tiny, monkeypatch, fault):
    system = tiny["system"]
    plant(system, monkeypatch, fault)
    result, _ = tiny["run"](SEEDS[1], system)
    assert result["failed"] == 0
    assert not result["correct"], result["checks"]
