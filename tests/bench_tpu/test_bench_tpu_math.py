"""The chip benchmark's own arithmetic: operation counts, the peak table
and the reduction of a profiler trace."""
from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from benchmarks.tpu import flops, peaks, trace

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "tpu"
FIXTURE = Path(__file__).resolve().parent / "data" / "fixture.xplane.pb"


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,seq,params,attn", [
    # SmolLM-135M: 30 x (576*576 + 2*576*192 + 576*576 + 3*576*1536)
    # + 49152*576 = 134,479,872 weights; 9 heads x 64 = 576.
    ("smollm-135m", 2048, 134_479_872, 6 * 576 * 2049 * 30),
    # Qwen2-0.5B: 24 x (896*896 + 2*896*128 + 896*896 + 3*896*4864)
    # + 151936*896 = 493,961,216 weights; 14 heads x 64 = 896.
    ("qwen2-0.5b", 4096, 493_961_216, 6 * 896 * 4097 * 24),
])
def test_lm_flops_per_token_match_hand_counts(name, seq, params, attn):
    c = conf(name)
    assert flops.lm_matmul_params(c) == params
    assert flops.lm_train_flops_per_token(c, seq) == 6 * params + attn


def test_ffn_stack_flops_match_hand_count():
    # Nine 32768 x 576 x 1536 products per block, 30 blocks.
    assert flops.ffn_stack_flops(30, 576, 1536, 32768) == (
        9 * 2 * 32768 * 576 * 1536 * 30)
    assert math.isclose(flops.ffn_stack_flops(30, 576, 1536, 32768),
                        15.65e12, rel_tol=1e-3)


def test_peak_table_refuses_unknown_device():
    assert peaks.peak_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("cpu")


def test_union_length_merges_overlaps():
    total, merged = trace.union_length([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6
    assert merged == [[0, 3], [5, 8]]


def test_reduce_events_by_hand():
    # Host clock: markers at 0 and 100; the device clock runs 1000 ahead.
    host = [(0, 1, trace.MARKER_SPAN), (100, 101, trace.MARKER_SPAN),
            (2, 40, "bench.step"), (40, 60, "bench.feed"),
            (60, 99, "bench.step")]
    mods = {"/device:TPU:0": [(1000, 1002, trace.MARKER_MODULE + "(1)"),
                              (1100, 1101, trace.MARKER_MODULE + "(1)"),
                              (1003, 1030, "jit_step(2)")]}
    ops = {"/device:TPU:0": [(1001, 1020, "%fusion.1 = f32[] ..."),
                             (1015, 1030, "%dot.2 = f32[] ..."),
                             (1070, 1090, "%fusion.1 = f32[] ...")]}
    s = trace.reduce_events(host, ops, mods)
    assert s.devices == 1
    assert s.window_s == pytest.approx(98e-9)          # 1002 .. 1100
    assert s.busy_s == pytest.approx(48e-9)            # 1002..1030, 1070..1090
    assert s.idle_share == pytest.approx(1 - 48 / 98)
    # Gaps 1030..1070 (midpoint 1050 -> host 50: feed) and 1090..1100
    # (midpoint 1095 -> host 95: step).
    assert dict((n, v) for n, v in s.idle_gaps) == pytest.approx(
        {"bench.feed": 40e-9, "bench.step": 10e-9})
    assert dict((n, v) for n, v in s.device_ops) == pytest.approx(
        {"fusion.1": 38e-9, "dot.2": 15e-9})


def test_reduce_events_needs_both_markers():
    with pytest.raises(ValueError):
        trace.reduce_events([(0, 1, trace.MARKER_SPAN)], {}, {})


def test_recorded_trace_reduces():
    """A trace recorded on a v5e (``record_trace_fixture.py`` before the
    window markers existed): three steps of a jitted matmul chain in
    ``bench.step`` spans, each followed by a 5 ms host sleep in a
    ``bench.sleep`` span.  The first and last steps stand in for the window
    markers, so the window holds the middle step and two sleeps."""
    host, ops, mods = trace.load_events(FIXTURE)
    assert list(ops) == list(mods) == ["/device:TPU:0"]
    steps = sorted(sp for sp in host if sp[2] == "bench.step")
    assert len(steps) == 3
    assert sum(sp[2] == "bench.sleep" for sp in host) == 3
    dev = sorted(mods["/device:TPU:0"])
    assert len(dev) == 3 and all(m[2].startswith("jit_") for m in dev)
    mark = lambda ev: (ev[0], ev[1], trace.MARKER_SPAN)  # noqa: E731
    host = host + [mark(steps[0]), mark(steps[-1])]
    name = trace.MARKER_MODULE + "(0)"
    mods = {"/device:TPU:0": [(dev[0][0], dev[0][1], name), dev[1],
                              (dev[2][0], dev[2][1], name)]}
    s = trace.reduce_events(host, ops, mods)
    assert s.devices == 1
    # Window: end of the first program to start of the third, two ~6.5 ms
    # step periods in which one program of ~0.19 ms ran.
    assert 10e-3 < s.window_s < 15e-3
    assert 1e-4 < s.busy_s < 3e-4
    # The device clock is offset from the host's by ~2 ms; aligned on the
    # first program, the longest idle cause is the host's sleep.
    assert s.idle_gaps[0][0] == "bench.sleep"
    assert s.idle_gaps[0][1] > 0.6 * (s.window_s - s.busy_s)
    assert {n for n, _ in s.device_ops} >= {"fusion",
                                             "convolution_tanh_fusion"}


def test_limits_follow_the_rule():
    from benchmarks.tpu.calibrate import set_limits
    recs = [
        {"seed": 1, "sound": {"a": 1e-3, "b": 1e-2, "update_gap": 1e-3},
         "control": {"a": 1e-1, "b": 2e-2, "update_gap": 1e-3},
         "half_batch": {"a": 5e-3, "b": 0.2, "update_gap": 0.5}},
        {"seed": 2, "sound": {"a": 2e-3, "b": 1e-2, "update_gap": 2e-3}},
    ]
    out = set_limits(recs)
    lim = out["limits"]
    # a: lower 2e-3, control 0.1 (>= 3x) is the upper; half_batch 5e-3 is
    # under 10x and does not count.
    assert lim["a"] == float(f"{2e-3 * (0.1 / 2e-3) ** 0.6:.2g}")
    assert 2e-3 < lim["a"] < 0.1
    # b: control 2e-2 is under 3x the lower 1e-2; the fault 0.2 is 20x.
    assert lim["b"] == float(f"{1e-2 * (0.2 / 1e-2) ** 0.6:.2g}")
    # update_gap: a state left unchanged reads 1 and bounds it.
    assert lim["update_gap"] == float(f"{2e-3 * (0.5 / 2e-3) ** 0.6:.2g}")
    assert out["set_from"]["a"]["seeds"] == [1, 2]
    # No upper reading: not compared, where another number is.
    assert set_limits([{"seed": 1, "sound": {"c": 0.1, "d": 1e-3},
                        "control": {"c": 0.1, "d": 1e-2}}])["limits"][
        "c"] is None
    # No number with an upper reading: no limits at all.
    with pytest.raises(ValueError):
        set_limits([{"seed": 1, "sound": {"c": 0.1},
                     "control": {"c": 0.1}}])
