"""Reduction of a profiler trace to device busy time, idle gaps and the
device operations that took most time.

The benchmark writes host spans (``jax.profiler.TraceAnnotation``) from its
own files: ``bench.*`` / ``dtr.*`` spans around each call into the
program.  The traced window opens and closes with a tiny jitted marker
program, each run inside a ``bench.marker`` span, because the device's
clock in the trace is offset from the host's by milliseconds:

- the window, on each device, runs from the end of the first marker
  program to the start of the second;
- busy: the union of the device's ``XLA Ops`` intervals in the window,
  averaged over the devices that ran the markers;
- idle gaps: the rest of the window on the first device.  Each gap is
  moved onto the host clock by the offset between the first marker's
  program and its host span, and named by the innermost host span open at
  its midpoint ("host" where none is); gaps are summed per name;
- device operations: summed durations per operation (the HLO instruction
  name before `` = ``), averaged over devices.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

MARKER_SPAN = "bench.marker"
MARKER_MODULE = "jit_bench_window_marker"
SPAN_PREFIXES = ("bench.", "dtr.")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def bench_window_marker(x):
    """The marker program (jit it; its module is ``MARKER_MODULE``)."""
    return x + 1


@dataclass
class TraceSummary:
    busy_s: float
    window_s: float
    devices: int
    device_ops: list = field(default_factory=list)   # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)    # [[name, seconds]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union_length(intervals) -> tuple[float, list]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _gaps(merged, lo, hi):
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _innermost(spans, t):
    """Name of the shortest span containing time ``t``; "host" if none."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host"


def _top(totals: dict, div: float) -> list:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, ns * 1e-9 / div] for name, ns in ranked]


def reduce_events(host_spans, device_ops, device_modules) -> TraceSummary:
    """``host_spans``: ``[(start_ns, end_ns, name)]`` on the host clock;
    ``device_ops`` and ``device_modules``: ``{device: [(start_ns, end_ns,
    name)]}`` on the device clock."""
    marks = sorted(sp for sp in host_spans if sp[2] == MARKER_SPAN)
    if len(marks) != 2:
        raise ValueError(f"expected two {MARKER_SPAN!r} spans, found "
                         f"{len(marks)}")
    spans = [sp for sp in host_spans if sp[2] != MARKER_SPAN]
    busy, per_op, first = [], defaultdict(float), None
    for dev in sorted(device_modules):
        mods = sorted(m for m in device_modules[dev]
                      if m[2].startswith(MARKER_MODULE))
        if len(mods) != 2:
            continue
        lo, hi = mods[0][1], mods[1][0]
        ops = [(max(s, lo), min(e, hi), n) for s, e, n in
               device_ops.get(dev, []) if e > lo and s < hi]
        total, merged = union_length([(s, e) for s, e, _ in ops])
        busy.append(total)
        for s, e, n in ops:
            per_op[n.split(" = ", 1)[0].lstrip("%")] += e - s
        if first is None:
            first = (lo, hi, mods[0][0] - marks[0][0], merged)
    if first is None:
        raise ValueError("no device ran both window markers")
    lo, hi, offset, merged = first
    gaps = defaultdict(float)
    for s, e in _gaps(merged, lo, hi):
        gaps[_innermost(spans, (s + e) / 2 - offset)] += e - s
    n = len(busy)
    return TraceSummary(busy_s=sum(busy) / n * 1e-9,
                        window_s=(hi - lo) * 1e-9, devices=n,
                        device_ops=_top(per_op, n), idle_gaps=_top(gaps, 1))


def load_events(path):
    """``(host_spans, device_ops, device_modules)`` of one ``.xplane.pb``
    file written by ``jax.profiler``, as ``reduce_events`` takes them."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    host, ops, mods = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: mods}.get(line.name)
                if dst is not None:
                    dst[plane.name] = [(e.start_ns, e.end_ns, e.name)
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.start_ns, e.end_ns, e.name)
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIXES))
    return host, ops, mods


def read_trace(path) -> TraceSummary:
    """Reduce one ``.xplane.pb`` file written by ``jax.profiler``."""
    return reduce_events(*load_events(path))
