"""Device time per named scope of the program, from a profiler trace and
the compiled step's HLO text.

  python3 benchmarks/tpu/scopes.py --workload <train cell> --seed <n> \
      [--seconds 3]

Needs the chip.  Sets up the cell's train job, keeps its compiled step's
text, runs the harness's traced window and prints, as one JSON line, each
scope's share of the window's busy device time with its largest ops.

The program marks regions with ``jax.named_scope`` (``models/model.py``:
``attn``, ``ffn``, ``lm_head``).  XLA keeps the scope in the ``op_name``
metadata of every instruction made inside it: in the forward pass, in its
transpose (``transpose(jvp(...))/.../attn/...``) and in the recompute under
``jax.checkpoint`` (``checkpoint/rematted_computation/attn/...``).  A trace
names a device op by its instruction only, so the text is read beside it:

- an instruction is in a scope when the scope is a component of its
  ``op_name`` path, once wrappers such as ``jvp(...)`` are taken off;
- a fusion with no metadata of its own takes that of the named
  instruction nearest its fused computation's root;
- control-flow ops (``while``, ``conditional``, ``call``) span their
  bodies' ops in the trace and count nothing, so no time counts twice;
- a share is the scope's device time over the busy time (the union of all
  op intervals) in the window between the harness's two marker programs.

The harness's result line does not carry these shares: for that its train
kind has to keep the compiled step's text and its trace reduction take it.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SCOPES = ("lm_head", "attn", "ffn")
CONTROL_FLOW = frozenset({"while", "conditional", "call"})
TOP = 5

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r" ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"[\w\-]+\((.*)\)")


def instructions(hlo_text: str) -> dict:
    """``{instruction name: (opcode, op_name or None)}`` of every
    instruction in an HLO module's text (``compiled.as_text()``)."""
    out, last_named, calls = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name, rest = m.groups()
        opcode = _OPCODE.search(rest)
        op_name = _OP_NAME.search(rest)
        out[name] = (opcode.group(1) if opcode else None,
                     op_name.group(1) if op_name else None)
        if op_name:
            last_named[comp] = op_name.group(1)
        called = _CALLS.search(rest)
        if called:
            calls[name] = called.group(1)
    # Text order puts operands first, so the root's nearest named
    # instruction comes last (a fused root is often an unnamed bitcast).
    for name, comp in calls.items():
        opcode, op_name = out[name]
        if op_name is None:
            out[name] = (opcode, last_named.get(comp))
    return out


def in_scope(op_name: str | None, scope: str) -> bool:
    """Whether ``scope`` is a component of the ``op_name`` path, each
    component taken out of wrappers such as ``transpose(jvp(...))``."""
    for part in (op_name or "").split("/"):
        while (m := _WRAPPED.fullmatch(part)) is not None:
            part = m.group(1)
        if part == scope:
            return True
    return False


def scope_shares(host_spans, device_ops, device_modules, instrs: dict,
                 scopes=SCOPES) -> dict:
    """Each scope's device time over the busy time of the traced window, in
    %, averaged over the devices that ran both window markers; the
    arguments are ``trace.load_events``'s and ``instructions``'s."""
    from benchmarks.tpu import trace
    summary = trace.reduce_events(host_spans, device_ops, device_modules)
    seconds = {s: 0.0 for s in scopes}
    per_op = {s: defaultdict(float) for s in scopes}
    for dev, mods in device_modules.items():
        marks = sorted(m for m in mods
                       if m[2].startswith(trace.MARKER_MODULE))
        if len(marks) != 2:
            continue
        lo, hi = marks[0][1], marks[1][0]
        for s, e, event in device_ops.get(dev, []):
            name = event.split(" = ", 1)[0].lstrip("%")
            opcode, op_name = instrs.get(name, (None, None))
            if e <= lo or s >= hi or opcode in CONTROL_FLOW:
                continue
            dt = (min(e, hi) - max(s, lo)) * 1e-9 / summary.devices
            for scope in scopes:
                if in_scope(op_name, scope):
                    seconds[scope] += dt
                    per_op[scope][name] += dt
    return {"busy_s": summary.busy_s, "window_s": summary.window_s,
            "shares": {s: 100.0 * v / summary.busy_s
                       for s, v in seconds.items()},
            "top_ops": {s: sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
                        for s, ops in per_op.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from benchmarks.tpu import harness, trace
    from benchmarks.tpu.kinds import train
    if jax.devices()[0].platform != "tpu":
        print("scopes: no TPU; nothing run", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload, seed=args.seed)
    if cell.job["kind"] != "train":
        raise SystemExit(f"{args.workload} is no train cell")
    job = train.Job(cell)
    job.span = lambda name: contextlib.nullcontext()
    job.setup()
    instrs = instructions(job.step_fn.as_text())
    out = cell.out_dir / "trace" / f"{cell.name}.scopes"
    harness.traced_window(job, args.seconds, 0, out)
    [path] = out.glob("plugins/profile/*/*.xplane.pb")
    res = scope_shares(*trace.load_events(path), instrs)
    print(json.dumps({"workload": args.workload, "seed": args.seed, **res},
                     allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
