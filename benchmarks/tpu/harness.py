"""Run one cell of ``BENCHMARK.json`` once and build its result line.

A cell names a configuration, a traffic (here: a job) and a chip count.
Everything that belongs to one of them sits in a file of its own, found by
name:

- ``configs[].file`` in ``BENCHMARK.json``: the configuration's sizes;
- ``jobs/<traffic>.json``: the job's parameters; its ``kind`` names the
  module ``kinds/<kind>.py`` that drives the program;
- ``limits/<workload>.json``: ``limits``, the limit of each number that
  decides ``correct`` (``null``: the number is not compared), and
  ``set_from``, the readings each was set from;
- ``metrics/<metric>.py``: one reader per quantity, ``read(r) -> float |
  None`` over the run's ``Readings``; ``None`` leaves the metric out.  A
  metric ``<part>.<quantity>`` without a file of its own is read by
  ``metrics/<quantity>.py``, so that ``train.step_mfu`` and
  ``eager.step_mfu`` share one reader; ``BENCHMARK.json``'s
  ``workloads`` decide which cells report each.

So a later cell, job or metric is new files and new entries, and no file
here changes.  The harness owns the clock and the profiler; a kind owns the
work: ``setup()``, ``step(i) -> work units``, ``memory_peak_bytes()`` and
``check() -> {name: (value, limit)}``.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import math
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path

from . import peaks, trace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# The traced window is short: traces are large and tracing slows the host.
TRACE_SECONDS = 3.0


@dataclass
class Cell:
    """One workload with its files read."""
    name: str
    chips: int
    conf: dict
    job: dict
    limits: dict
    end_to_end: list
    per_layer: list
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    out_dir: Path = field(default_factory=lambda: ROOT / "bench_out")


@dataclass
class Readings:
    """What the metric readers see.  ``work`` is tokens for training and
    steps for the eager job; ``counters`` are the program's own counts
    summed over the window."""
    setup_s: float
    window_s: float
    steps: int
    work: float
    flops_per_step: float
    peak: dict
    counters: dict = field(default_factory=dict)
    hbm_bytes: float | None = None
    trace: trace.TraceSummary | None = None


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, *, root: Path = ROOT, **run) -> Cell:
    """Read the cell's configuration, job and limits by name."""
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[workload]
    confs = {c["name"]: c for c in man["configs"]}
    conf = json.loads((root / confs[w["config"]]["file"]).read_text())
    job = json.loads((HERE / "jobs" / f"{w['traffic']}.json").read_text())
    lim_file = HERE / "limits" / f"{workload}.json"
    limits = (json.loads(lim_file.read_text())["limits"]
              if lim_file.exists() else {})
    return Cell(name=workload, chips=w["chips"], conf=conf, job=job,
                limits=limits,
                end_to_end=[m for m in man["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in man["per_layer"]
                           if _applies(m, workload)], **run)


def reader_path(name: str) -> Path:
    """``metrics/<name>.py``, else ``metrics/<name after its first
    dot>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = HERE / "metrics" / f"{name.split('.', 1)[1]}.py"
    return path


def read_metric(name: str, r: Readings):
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.tpu.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(r)


def _span(on: bool):
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


def _window(job, seconds: float, first: int):
    """Steps until ``seconds`` have passed; returns (steps, seconds,
    work).  The step that crosses the deadline counts whole."""
    steps, work = 0, 0.0
    t0 = time.perf_counter()
    while True:
        work += job.step(first + steps)
        steps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return steps, elapsed, work


def traced_window(job, seconds: float, first: int, out: Path):
    """Steps for ``seconds`` under the profiler, between the two marker
    programs that ``trace`` reads the window from; returns (steps,
    TraceSummary).  The trace is written under ``out``, replacing the
    last one there."""
    import jax
    import jax.numpy as jnp
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    mark = jax.jit(trace.bench_window_marker)
    zero = jnp.zeros((), jnp.float32)
    mark(zero).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    job.span = _span(True)
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.MARKER_SPAN):
            mark(zero).block_until_ready()
        steps, _, _ = _window(job, seconds, first)
        with jax.profiler.TraceAnnotation(trace.MARKER_SPAN):
            mark(zero).block_until_ready()
    finally:
        jax.profiler.stop_trace()
        job.span = _span(False)
    [path] = out.glob("plugins/profile/*/*.xplane.pb")
    return steps, trace.read_trace(path)


def run_cell(cell: Cell, *, t_start: float, device) -> dict:
    """Set up, measure, check; return the result object."""
    kind = importlib.import_module(f"{__package__}.kinds.{cell.job['kind']}")
    peak = peaks.peak_for(device.device_kind) if device is not None else {}
    job = kind.Job(cell)
    job.span = _span(False)
    job.setup()
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    steps, window_s, work = _window(job, cell.seconds, 0)
    counters = dict(job.counters)
    summary = None
    if cell.trace:
        _, summary = traced_window(
            job, min(cell.seconds, TRACE_SECONDS), steps,
            cell.out_dir / "trace" / cell.name)
    mem = job.memory_peak_bytes()
    r = Readings(setup_s=setup_s, window_s=window_s,
                 steps=steps, work=work, flops_per_step=job.flops_per_step,
                 peak=peak, counters=counters,
                 hbm_bytes=getattr(job, "hbm_bytes", None), trace=summary)
    checks = job.check()
    # A number whose limit was never set (None) fails.
    correct = all(lim is not None and math.isfinite(v) and v <= lim
                  for v, lim in checks.values())
    metrics = {}
    for m in (cell.per_layer if cell.trace else cell.end_to_end):
        v = read_metric(m["name"], r)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    missing = [m["name"] for m in cell.end_to_end
               if not cell.trace and m["name"] not in metrics]
    if missing:
        raise RuntimeError(f"end-to-end metrics without a reading: "
                           f"{missing}")
    dev = {"platform": getattr(device, "platform", None),
           "kind": getattr(device, "device_kind", None),
           "count": job.device_count, "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": job.attempted,
              "failed": job.failed,
              "metrics": metrics, "device": dev}
    if summary is not None:
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    # A non-finite reading has failed; JSON has no spelling for it.
    result["checks"] = {k: {"value": v if math.isfinite(v) else None,
                            "limit": lim} for k, (v, lim) in checks.items()}
    return result
