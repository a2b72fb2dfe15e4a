"""The multi-device lane inside the single-device suite.

``tests/test_sharded_tiers.py`` needs virtual devices before JAX starts,
so under a plain run its mesh cases skip.  This test runs that file in a
child process on eight virtual CPU devices and asserts that every case
ran and passed: the sharded segments, the donated sharded cache and the
mesh's program names stay guarded by the suite itself.
"""

import os
import subprocess
import sys
import xml.etree.ElementTree as ET
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_sharded_tiers_pass_on_eight_virtual_devices(tmp_path):
    report = tmp_path / "sharded.xml"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src")]
                   + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", f"--junitxml={report}",
         str(ROOT / "tests" / "test_sharded_tiers.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    suite = ET.parse(report).getroot()
    suite = suite if suite.tag == "testsuite" else suite.find("testsuite")
    counts = {k: int(suite.get(k)) for k in
              ("tests", "failures", "errors", "skipped")}
    assert counts["tests"] >= 28, counts
    assert counts["failures"] == counts["errors"] == counts["skipped"] == 0, \
        counts
