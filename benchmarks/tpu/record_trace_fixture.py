"""Record the small profiler trace that the trace-reduction tests read.

  python3 benchmarks/tpu/record_trace_fixture.py --out <dir>

Needs the chip.  Runs three steps of a jitted matmul chain through the
harness's own traced window, each step followed by a 5 ms host sleep in a
``bench.sleep`` span, so that the trace has idle gaps of a known cause.
Writes ``<dir>/fixture.xplane.pb`` (the tests keep theirs in
``tests/bench_tpu/data/``) and prints what ``trace.read_trace`` makes of it.
"""
from __future__ import annotations

import argparse
import dataclasses
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


class SleepJob:
    """A job whose step is one device program and a host sleep."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self.f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
        self.x = jnp.ones((2048, 2048), jnp.bfloat16)
        self.f(self.x).block_until_ready()

    def step(self, i: int) -> int:
        with self.span("bench.step"):
            self.x = self.f(self.x)
            self.x.block_until_ready()
        with self.span("bench.sleep"):
            time.sleep(0.005)
        return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT)]
    import jax
    from benchmarks.tpu import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU; nothing recorded", file=sys.stderr)
        return 2
    out = Path(args.out)
    steps, summary = harness.traced_window(SleepJob(), 0.015, 0,
                                           out / "fixture_trace")
    [src] = (out / "fixture_trace").glob("plugins/profile/*/*.xplane.pb")
    shutil.copy(src, out / "fixture.xplane.pb")
    shutil.rmtree(out / "fixture_trace")
    print(steps, dataclasses.asdict(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
