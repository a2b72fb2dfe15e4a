"""The four-chip cell's comparison, driven end to end on four virtual CPU
devices.

A tiny copy of ``qwen3-8b-k2-tp4`` (QK-norm, GQA 4:1, 4 layers cut at 2,
an exit head on each tier) serves the shape of the cell's mix through
``bench.run.run_cell`` on a (1, 4) mesh, so the segments, the cache and
the exit heads are sharded as on the chips and the float32 reference is
computed on the mesh after the program's state is freed.  A sound run is
correct under the cell's limits; a token altered where the decode step
produces it is not, and neither is the float8 control under limits set
between the two readings, as the cell's are.

The virtual devices must exist before JAX starts, so the cases run in one
child process (this file run as a script) and the tests read its report.
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen3-8b-k2-tp4-offline"
SECONDS = 3.0
SEEDS = (2 ** 31 + 7, 91)


def tiny_root(root: Path) -> dict:
    """A checkout-like ``root`` holding a tiny copy of the cell (its mix's
    shape, its limits, the benchmark's readers); returns the benchmark
    entries that name it."""
    (root / "bench").mkdir()
    for d in ("metrics", "limits", "traffic", "references"):
        shutil.copytree(ROOT / "bench" / d, root / "bench" / d)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = next(w for w in bench["workloads"] if w["name"] == CELL)
    entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    spec = json.loads((ROOT / entry["file"]).read_text())
    sizes = dict(hidden_size=256, num_hidden_layers=4, num_attention_heads=16,
                 num_key_value_heads=4, head_dim=16, intermediate_size=512,
                 vocab_size=512)
    spec.update(sizes, branch_layers=[1, 3], cuts=[2], slots=8, context=128)
    spec["program"] = dict(
        param_dtype="bfloat16", num_layers=4, d_model=256, num_heads=16,
        num_kv_heads=4, head_dim=16, d_ff=512, vocab_size=512,
        branch_layers=[1, 3])
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(spec))
    mix = json.loads((ROOT / "bench" / "traffic"
                      / f"{wl['traffic']}.json").read_text())
    mix.update(backlog_requests=60,
               prompt={"median": 40, "sigma": 0.6, "min": 16, "max": 64,
                       "multiple": 16},
               output={"median": 8, "sigma": 0.6, "min": 2, "max": 32})
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    shutil.copy(root / "bench" / "limits" / f"{CELL}.json",
                root / "bench" / "limits" / "tiny.json")
    tiny = dict(bench, workloads=[dict(wl, name="tiny", config="tiny",
                                       traffic="tiny")],
                configs=[dict(entry, name="tiny",
                              file="bench/configs/tiny.json")])
    for m in tiny["end_to_end"] + tiny["per_layer"]:
        m.pop("workloads", None)
    return tiny


def alter_tokens(system):
    """Wrap the served step so that every live row's token is altered from
    the third step that decodes two or more live rows on."""
    import numpy as np

    inner, seen = system.server.step, []

    def step(tok, pos, caches, *, active=None):
        rep, caches = inner(tok, pos, caches, active=active)
        live = np.flatnonzero(active)
        if len(live) >= 2:
            seen.append(1)
            if len(seen) >= 3:
                res = rep.tier_result
                res.tokens = np.array(res.tokens)
                res.tokens[live] = (res.tokens[live] + 1) % 512
        return rep, caches

    system.server.step = step
    return inner


def child() -> dict:
    """The cases, in one process on four virtual CPU devices."""
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np

    from bench import run as R

    assert len(jax.devices()) >= 4, jax.devices()
    root = Path(tempfile.mkdtemp(prefix="tinytp4"))
    tiny = tiny_root(root)

    def run(seed, system=None, control=False):
        return R.run_cell(tiny, "tiny", seed, SECONDS, False, system=system,
                          control=control, root=root,
                          t_start=time.perf_counter())

    out = {}
    first, system = run(SEEDS[0], control=True)
    ex = system.executor
    k = ex.new_caches(8, 128)["blocks"]["self"]["k"]
    out["mesh"] = {
        "sharded": ex.sharded, "use_kernels": ex.use_kernels,
        "devices": len(system.devices),
        "k_spec": [None if a is None else str(a) for a in k.sharding.spec],
        "k_shard": list(k.sharding.shard_shape(k.shape)),
    }
    del k
    out["first"] = {key: first[key] for key in
                    ("correct", "failed", "attempted", "checks", "readings")}
    second, system = run(SEEDS[1], system)
    out["second"] = {key: second[key] for key in
                     ("correct", "failed", "checks", "metrics")}

    # The float8 control under limits set between the first seed's two
    # readings (the geometric mean, the program's taken as at least a
    # hundredth of the control's), judged on the second seed.
    readings = first["readings"]
    compared = [n for n in first["checks"] if n != "failed"]
    apart = [n for n in compared if readings[f"control_{n}"] > 0
             and readings[f"control_{n}"] >= 3 * readings[n]]
    path = root / "bench" / "limits" / "tiny.json"
    kept = path.read_text()
    path.write_text(json.dumps({n: {"limit": float(np.sqrt(
        max(readings[n], readings[f"control_{n}"] / 100)
        * readings[f"control_{n}"]))} for n in apart}))
    control, system = run(SEEDS[1], system, control=True)
    path.write_text(kept)
    out["control"] = {"apart": apart, "correct": control["correct"],
                      "control_correct": control["control_correct"],
                      "control_checks": sorted(control["control_checks"])}

    inner = alter_tokens(system)
    altered, system = run(SEEDS[1], system)
    system.server.step = inner
    out["altered"] = {key: altered[key] for key in
                      ("correct", "failed", "checks")}
    return out


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_served_on_a_sharded_mesh(report):
    mesh = report["mesh"]
    assert mesh["sharded"] and mesh["devices"] == 4
    assert not mesh["use_kernels"]  # sharded segments take the jnp path
    # K/V sharded by KV head over "model": one of the 4 heads per device.
    assert mesh["k_spec"][3] == "model" and mesh["k_shard"][3] == 1


def test_sound_runs_are_correct(report):
    for key in ("first", "second"):
        run = report[key]
        assert run["failed"] == 0 and run["correct"], run["checks"]
    metrics = report["second"]["metrics"]
    assert metrics["tokens_per_s"]["value"] > 0
    assert 0 < metrics["itl_p90_ms"]["value"]


def test_a_token_altered_in_the_decode_step_is_not_correct(report):
    run = report["altered"]
    assert run["failed"] == 0
    assert not run["correct"], run["checks"]


def test_the_float8_control_fails(report):
    control = report["control"]
    assert control["apart"], report["first"]["readings"]
    assert control["correct"] is True
    assert control["control_correct"] is False
    assert control["control_checks"] == sorted(control["apart"])


if __name__ == "__main__":
    print(json.dumps(child()), flush=True)
