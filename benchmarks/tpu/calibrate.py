"""Readings that the limits in ``limits/<workload>.json`` are set from.

  python3 benchmarks/tpu/calibrate.py --workload <name> --seeds 1 2 ... \
      --control-seeds 3 [--out <dir>] [--write-limits]

Needs the chip; runs the cell at its own size, all seeds in one process.
For every seed: the program's readings (a train job's first steps, or two
window steps of an eager job) against the float32 reference, which is the
lower reading of each number.  For the first ``--control-seeds`` seeds
also the control, the reference computed in float8 (``reference.py``), and
for a train job the planted faults, each read against the sound
reference: ``half_batch`` (every step on half of the batch, planted in the
reference put in the program's place) and ``grad_doubled`` (one leaf's
gradient doubled inside the program's compiled step).  A
state left unchanged reads 1 by construction and is not run.

Writes one JSON line per seed to ``<out>/<workload>.jsonl``, and the
limits that ``set_limits`` makes of them, with the readings they were set
from, to ``<out>/<workload>.limits.json`` (and, with ``--write-limits``,
to the cell's ``limits/<workload>.json``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


# The leaf whose gradient the ``grad_doubled`` fault doubles.
FFN_WO = "['groups']['slot0']['ffn']['wo']"


def doubling_adamw(adamw, leaf: str = FFN_WO):
    """``adamw`` whose optimizers double one leaf's gradient where they
    take it, inside the compiled step."""
    import jax

    def build(*a, **k):
        opt = adamw(*a, **k)

        def update(grads, state, params):
            grads = jax.tree_util.tree_map_with_path(
                lambda p, g: 2 * g if jax.tree_util.keystr(p) == leaf
                else g, grads)
            return opt.update(grads, state, params)
        return type(opt)(opt.init, update, opt.name)
    return build


@contextlib.contextmanager
def grad_doubled():
    """Plant a fault in the program: while open, ``repro.optim.adamw`` is
    ``doubling_adamw``."""
    import repro.optim
    real = repro.optim.adamw
    repro.optim.adamw = doubling_adamw(real)
    try:
        yield
    finally:
        repro.optim.adamw = real


def _program_readings(job) -> dict:
    job.setup()
    prog = job.prog
    job.free_program()
    return prog


def _train_seed(job, control: bool) -> dict:
    from benchmarks.tpu.kinds import train
    prog = _program_readings(job)
    ref = job.reference_readings()
    rec = {"prog": prog, "ref": ref, "sound": train.compare(prog, ref)}
    if control:
        rec["control"] = train.compare(job.reference_readings("fp8"), ref)
        rec["half_batch"] = train.compare(
            job.reference_readings(fault="half_batch"), ref)
        faulty = train.Job(job.cell)
        faulty.span = job.span
        with grad_doubled():
            rec["grad_doubled"] = train.compare(
                _program_readings(faulty), ref)
    return rec


def _eager_seed(job, control: bool) -> dict:
    import numpy as np
    from benchmarks.tpu.kinds import eager
    job.setup()
    job.step(0)
    job.step(1)
    prog = [np.asarray(n) for n in job.norms]
    ref = [job.reference_norms(i) for i in range(2)]
    rec = {"sound": {"grad_gap": max(eager.compare_grads(p, r)
                                     for p, r in zip(prog, ref))},
           "remat_runs": job.counters["remat_runs"],
           "evictions": job.counters["evictions"],
           "budget": job.budget if job.budget != float("inf") else None}
    if control:
        rec["control"] = {"grad_gap": eager.compare_grads(
            job.reference_norms(0, "fp8"), ref[0])}
    return rec


# An upper reading counts where it is this many times the lower one.
CONTROL_FACTOR, FAULT_FACTOR = 3.0, 10.0


def set_limits(recs: list) -> dict:
    """Limits from the readings, by one rule fixed before any reading.

    For each number: lower = the largest sound reading over all seeds;
    upper = the smallest of the control's reading (if at least 3x lower),
    each planted fault's (if at least 10x lower) and, for ``update_gap``,
    1.0 for a state left unchanged (if at least 3x lower); limit = lower *
    (upper / lower) ** 0.6, to two digits: more room above the lower
    reading than below the upper.  A number with no upper reading gets
    ``null``: it is not compared.  Where that leaves no number at all,
    the readings set no limits and this raises.
    """
    limits, set_from = {}, {}
    for n in recs[0]["sound"]:
        lower = max(r["sound"][n] for r in recs)
        uppers = {k: min(r[k][n] for r in recs if k in r)
                  for k in ("control", "half_batch", "grad_doubled")
                  if any(k in r for r in recs)}
        if n == "update_gap":
            uppers["state_unchanged"] = 1.0
        need = {"control": CONTROL_FACTOR, "state_unchanged": CONTROL_FACTOR}
        valid = [v for k, v in uppers.items()
                 if v >= need.get(k, FAULT_FACTOR) * lower]
        set_from[n] = {"lower": lower, "seeds": [r["seed"] for r in recs],
                       **{f"upper_{k}": v for k, v in uppers.items()}}
        limits[n] = None
        if valid:
            lo, up = max(lower, 1e-12), min(valid)
            limits[n] = float(f"{lo * (up / lo) ** 0.6:.2g}")
    if all(v is None for v in limits.values()):
        raise ValueError(f"no number has an upper reading, so none could "
                         f"be compared: {set_from}")
    return {"limits": limits, "set_from": set_from}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=str(ROOT / "bench_out" / "calib"))
    ap.add_argument("--write-limits", action="store_true",
                    help="also write benchmarks/tpu/limits/<workload>.json")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import gc

    import jax
    from benchmarks.tpu import harness
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; nothing run", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    recs = []
    with open(out / f"{args.workload}.jsonl", "w") as fh:
        for n, seed in enumerate(args.seeds):
            cell = harness.load_cell(args.workload, seed=seed)
            kind = cell.job["kind"]
            mod = __import__(f"benchmarks.tpu.kinds.{kind}",
                             fromlist=["Job"])
            job = mod.Job(cell)
            job.span = lambda name: contextlib.nullcontext()
            run = _train_seed if kind == "train" else _eager_seed
            rec = {"seed": seed, **run(job, n < args.control_seeds)}
            recs.append(rec)
            fh.write(json.dumps(rec, allow_nan=False) + "\n")
            fh.flush()
            print(json.dumps({k: v for k, v in rec.items()
                              if k not in ("prog", "ref")}, allow_nan=False),
                  flush=True)
            del job
            gc.collect()
    limits = set_limits(recs)
    (out / f"{args.workload}.limits.json").write_text(
        json.dumps(limits, indent=1, allow_nan=False) + "\n")
    if args.write_limits:
        (ROOT / "benchmarks" / "tpu" / "limits" /
         f"{args.workload}.json").write_text(
            json.dumps(limits, indent=1, allow_nan=False) + "\n")
    print(json.dumps({"workload": args.workload, **limits},
                     allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
