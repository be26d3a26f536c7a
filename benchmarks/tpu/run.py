"""Run one benchmark cell once on the chip and print its result line.

  python3 benchmarks/tpu/run.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``checks``: each number compared for ``correct``
with its limit.  The same numbers are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.  JAX's persistent compilation cache lives at
``<checkout>/.jax_cache``, a fixed path, and keeps every compiled program,
however quick, so that only a checkout's first run compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.tpu import harness
    cell = harness.load_cell(args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). No result.",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = harness.run_cell(cell, t_start=T_START, device=devices[0])
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
