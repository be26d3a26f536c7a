"""Each cell of BENCHMARK.json through its own job at smoke size, and the
command without a chip.  The shape of the result line is checked, and that
a sound run is correct under the smoke limits; never its times."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys

import pytest

from bench_tpu_smoke import BENCH, ROOT, manifest, run, smoke_cell

WORKLOADS = [w["name"] for w in manifest()["workloads"]]


def test_manifest_files_exist_by_name():
    man = manifest()
    for c in man["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in man["workloads"]:
        assert (BENCH / "jobs" / f"{w['traffic']}.json").is_file()
        limits = json.loads(
            (BENCH / "limits" / f"{w['name']}.json").read_text())
        assert set(limits) == {"limits", "set_from"}
    from benchmarks.tpu import harness
    for m in man["end_to_end"] + man["per_layer"]:
        assert harness.reader_path(m["name"]).is_file(), m["name"]


def test_command_without_a_chip_exits_nonzero_and_prints_nothing():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, *manifest()["command"][1:], "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_at_smoke_size(workload):
    cell = smoke_cell(workload)
    res = run(cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in cell.end_to_end}
    assert set(res["metrics"]) == want
    for m in cell.end_to_end:
        v = res["metrics"][m["name"]]
        assert v["unit"] == m["unit"] and math.isfinite(v["value"])
        assert v["value"] > 0
    compared = {k for k, v in cell.limits.items() if v is not None}
    assert compared <= set(res["checks"])
    if cell.job["kind"] == "train":
        assert res["checks"]["hbm_gib"]["limit"] == cell.job["hbm_budget_gib"]
    for c in res["checks"].values():
        assert list(c) == ["value", "limit"]
        assert isinstance(c["value"], float)
    json.dumps(res, allow_nan=False)
