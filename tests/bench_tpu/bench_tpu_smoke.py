"""Smoke-size cells for the chip benchmark's CPU tests.

The harness runs its jobs here with ``device=None``, which skips only the
look for a chip: the set-up, window, check and result line are the ones
that a chip run takes.

The limits of the smoke cells are set as the chip's are, by
``calibrate.set_limits`` over the program's, the control's and the planted
faults' readings at smoke size on the CPU.  To make them again:

  JAX_PLATFORMS=cpu PYTHONPATH=src:. python3 tests/bench_tpu/bench_tpu_smoke.py
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "tpu"

# Widths far below any configuration's, two layers; the QKV bias is on so
# that the smoke covers Qwen2's attention.
SMOKE_CONF = {"program_arch": "qwen2-0.5b", "hidden_size": 64,
              "intermediate_size": 256, "num_attention_heads": 4,
              "num_key_value_heads": 2, "num_hidden_layers": 2,
              "vocab_size": 128, "rms_norm_eps": 1e-6,
              "rope_theta": 10000.0, "tie_word_embeddings": True,
              "qkv_bias": True, "initializer_range": 0.02}

# calibrate.set_limits over SMOKE_SEEDS (the first three also with the
# control and the faults), CPU.  Readings, largest sound / smallest upper:
# train loss_gap 1.3e-4 / 7.5e-4 (control), grad_gap 2.4e-3 / 7.9e-3
# (control), update_gap 6.0e-3 / 7.9e-2 (half batch); eager grad_gap
# 6.2e-4 / 6.4e-3 (control).
SMOKE_LIMITS = {
    "train": {"loss_gap": 0.00037, "grad_gap": 0.0049, "update_gap": 0.028},
    "eager": {"grad_gap": 0.0025},
}
SMOKE_SEEDS = [5, 7, 2**31 + 3, 2**33 + 1, 12345, 2**40 + 9]


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def smoke_job(traffic: str) -> dict:
    job = json.loads((BENCH / "jobs" / f"{traffic}.json").read_text())
    if job["kind"] == "train":
        job.update(batch=2, seq=16, ref_rows=1, ref_head_tokens=16)
    else:
        job.update(tokens=256, check_steps=2)
    return job


def smoke_cell(workload: str, *, seed: int = 2**31 + 11,
               trace: bool = False):
    """The workload's own job and metrics at smoke size, with the smoke
    limits of its kind."""
    from benchmarks.tpu import harness
    cell = harness.load_cell(workload, seed=seed, seconds=0.05,
                             trace=trace)
    w = {x["name"]: x for x in manifest()["workloads"]}[workload]
    cell.conf = dict(SMOKE_CONF)
    cell.job = smoke_job(w["traffic"])
    cell.limits = dict(SMOKE_LIMITS[cell.job["kind"]])
    return cell


def run(cell) -> dict:
    from benchmarks.tpu import harness
    return harness.run_cell(cell, t_start=time.perf_counter(), device=None)


def calibrate_smoke(workload: str, seeds=SMOKE_SEEDS, control_seeds=3):
    """``set_limits`` over smoke-size readings of the workload's job."""
    from benchmarks.tpu import calibrate
    from benchmarks.tpu.kinds import eager, train
    recs = []
    for n, seed in enumerate(seeds):
        cell = smoke_cell(workload, seed=seed)
        kind = train if cell.job["kind"] == "train" else eager
        job = kind.Job(cell)
        job.span = lambda name: contextlib.nullcontext()
        read = (calibrate._train_seed if kind is train
                else calibrate._eager_seed)
        recs.append({"seed": seed, **read(job, n < control_seeds)})
    return calibrate.set_limits(recs)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for kind, w in (("train", "train.smollm-135m.16x2048"),
                    ("eager", "eager.smollm-135m.ffn30.16x2048.b050")):
        print(kind, json.dumps(calibrate_smoke(w), indent=1))
