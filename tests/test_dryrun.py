"""Dry-run smoke: one real (arch × shape × mesh) cell compiles end-to-end.

Runs in a subprocess because the dry-run must own jax's device-count
initialization (512 forced host devices) — the test process has 1 device.
The full 68-cell sweep is exercised by `repro.launch.dryrun --all`
(artifacts in experiments/dryrun/); this keeps one representative cell in
the always-on test suite.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_single_cell_compiles(tmp_path, mesh):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "qwen2-0.5b", "--shape", "decode_32k",
         "--mesh", mesh, "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "all cells OK" in out.stdout
    tag = f"qwen2-0.5b_decode_32k_{mesh}.json"
    with open(tmp_path / tag) as f:
        res = json.load(f)
    assert res["chips"] == (512 if mesh == "multi" else 256)
    assert res["memory"]["peak_bytes_per_device"] < 16 * 2**30
    r = res["roofline"]
    assert r["compute_s"] > 0 and r["memory_s"] > 0
    assert r["dominant"] in ("compute", "memory", "collective")
