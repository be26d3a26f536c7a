"""DTR as a trace-time rematerialization planner (the TPU-native form).

JAX retraces per input shape, so the paper's *online* algorithm can run at
trace time — the "just in time" static planning the paper describes in Sec. 6
(possible exactly because DTR's greedy heuristic costs milliseconds, unlike
Checkmate's ILP).  Pipeline:

  1. ``trace_to_log``: jaxpr of (usually) a value_and_grad step → DTR op log,
     with tensor sizes from avals and an analytic FLOPs cost model (the
     deterministic cost model Appendix E.3 recommends).
  2. ``plan``: replay the log through the DTR engine under a per-device
     activation-byte budget; tensors tagged via
     ``jax.ad_checkpoint.checkpoint_name`` that were *never evicted* form the
     save-set.
  3. ``Plan.policy``: the save-set becomes
     ``jax.checkpoint_policies.save_only_these_names(...)``, enforced by XLA
     remat — the runtime never sees the evicted activations at all
     (``dtr_checkpoint`` wraps a function in it).

Also provides ``plan_layer_blocks``: the remat block size for a scanned
layer stack under a byte budget (the √N pattern of Thm 3.1 emerges as the
planned block size).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .graph import Log, LogBuilder, replay
from .heuristics import by_name
from .runtime import DTRRuntime, OOMError


# ---------------------------------------------------------------------------
# Cost model over jaxpr equations
# ---------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    # Abstract tokens / effect avals lack shape/dtype (AttributeError);
    # extended dtypes without an itemsize raise TypeError.  Anything else
    # (a malformed shape, a numpy overflow) is a real bug and propagates.
    try:
        return int(np.prod(aval.shape, dtype=np.int64)
                   * jnp.dtype(aval.dtype).itemsize)
    except (AttributeError, TypeError):
        return 0


def _aval_elems(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64))
    except (AttributeError, TypeError):
        return 0


def eqn_flops(eqn) -> float:
    """Analytic FLOPs estimate for one jaxpr equation."""
    prim = eqn.primitive.name
    if prim == "dot_general":
        dims = eqn.params["dimension_numbers"]
        (lc, rc), (lb, rb) = dims
        lhs = eqn.invars[0].aval
        batch = 1
        for d in lb:
            batch *= lhs.shape[d]
        k = 1
        for d in lc:
            k *= lhs.shape[d]
        m = _aval_elems(lhs) // max(batch * k, 1)
        rhs = eqn.invars[1].aval
        rk = 1
        for d in rc:
            rk *= rhs.shape[d]
        n = _aval_elems(rhs) // max(batch * rk, 1)
        return 2.0 * batch * m * n * k
    if prim in ("conv_general_dilated",):
        out = eqn.outvars[0].aval
        lhs = eqn.invars[1].aval  # kernel
        return 2.0 * _aval_elems(out) * _aval_elems(lhs) / max(
            out.shape[-1] if out.shape else 1, 1)
    if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                "argmax", "argmin", "cumsum", "cumlogsumexp", "cummax"):
        return float(_aval_elems(eqn.invars[0].aval))
    if prim in ("exp", "log", "tanh", "logistic", "erf", "rsqrt", "sqrt",
                "sin", "cos", "pow", "integer_pow"):
        return 4.0 * _aval_elems(eqn.outvars[0].aval)
    # Metadata-only ops.
    if prim in ("reshape", "transpose", "broadcast_in_dim", "squeeze",
                "convert_element_type", "slice", "dynamic_slice",
                "dynamic_update_slice", "concatenate", "gather", "name",
                "stop_gradient", "copy", "rev", "iota", "pad",
                "scatter", "scatter-add", "select_n", "split"):
        return float(_aval_elems(eqn.outvars[0].aval)) * 0.1
    # Default: one flop per output element.
    return float(sum(_aval_elems(o.aval) for o in eqn.outvars))


# ---------------------------------------------------------------------------
# jaxpr -> DTR log
# ---------------------------------------------------------------------------

@dataclass
class TracedGraph:
    log: Log
    named: dict[str, str]            # checkpoint_name -> log tensor name
    outputs: list[str]               # log tensor names of jaxpr outputs
    total_bytes: int = 0
    total_flops: float = 0.0


def _eqn_cost(eqn, scale) -> float:
    """Cost contribution of one flattened (eqn, scale) pair.

    Opaque sub-jaxprs (nested pjit / scan inside a scanned body) carry their
    pre-summed total in the scale tuple; multiplying eqn_flops by it would be
    meaningless (and breaks on the tuple).
    """
    if isinstance(scale, tuple):
        return float(scale[1])
    return eqn_flops(eqn) * scale


def _flatten_eqns(jaxpr, depth: int = 0):
    """Yield (eqn, scale) with nested jaxprs inlined; scan bodies scaled."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in ("pjit", "closed_call", "core_call", "custom_jvp_call",
                    "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
                    "checkpoint", "custom_lin"):
            inner = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params:
                    inner = eqn.params[key]
                    break
            if inner is not None:
                ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                # Treat as opaque op (cost summed) to keep the DAG aligned
                # with data deps at this level.
                total = sum(_eqn_cost(e, s)
                            for e, s in _flatten_eqns(ij, depth + 1))
                yield eqn, ("opaque", total)
                continue
        if prim == "scan":
            length = eqn.params.get("length", 1)
            inner = eqn.params["jaxpr"]
            ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
            total = sum(_eqn_cost(e, s)
                        for e, s in _flatten_eqns(ij, depth + 1)) * length
            yield eqn, ("opaque", total)
            continue
        if prim in ("while", "cond"):
            yield eqn, ("opaque", float(
                sum(_aval_elems(o.aval) for o in eqn.outvars)))
            continue
        yield eqn, 1.0


def trace_to_log(fn: Callable, *example_args, name: str = "traced",
                 unroll_scans: bool = False, unroll_limit: int = 256,
                 **example_kwargs) -> TracedGraph:
    """Trace ``fn`` and convert its jaxpr into a DTR operator log.

    ``unroll_scans=True`` inlines ``lax.scan`` bodies per iteration (up to
    ``unroll_limit`` trips) instead of treating the scan as one opaque op.
    Scanned layer stacks then appear as per-layer operator chains — without
    this, the whole stack is a single op whose inputs/outputs lock nearly the
    entire peak and DTR has nothing to evict (the ``repro.trace`` captures of
    real train steps need the unrolled form).
    """
    closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
    jaxpr = closed.jaxpr
    b = LogBuilder(name=name)
    env: dict[Any, str] = {}
    named: dict[str, str] = {}
    totals = {"bytes": 0, "flops": 0.0}

    def lookup(v, env) -> str:
        # Literals become fresh constants (builder-unique names: per-scope
        # env sizes repeat across unrolled scan iterations).
        if not hasattr(v, "count") and hasattr(v, "val"):
            return b.constant(_aval_bytes(v.aval), name=b.fresh("lit"))
        return env[v]

    def emit_call(eqn, cost: float, env, op: str | None = None) -> None:
        cost = max(cost, 1.0)
        ins = [lookup(v, env) for v in eqn.invars]
        sizes = [_aval_bytes(o.aval) for o in eqn.outvars]
        prim = eqn.primitive.name
        # View-like ops share their input's storage (paper alias semantics);
        # `name` in particular must alias so that evicting the producer
        # registers against the checkpoint_name tag.  optimization_barrier
        # is identity on every operand — without the alias each scanned
        # layer's parameters would count as a fresh activation-sized copy.
        aliases = None
        if prim == "optimization_barrier" and len(ins) == len(sizes):
            aliases = list(ins)
        elif prim in ("name", "reshape", "transpose", "squeeze") and ins:
            aliases = [ins[0]] * len(sizes)
        outs = b.call(ins, sizes, cost, op or prim, aliases=aliases)
        for o, t in zip(eqn.outvars, outs):
            env[o] = t
            totals["bytes"] += _aval_bytes(o.aval)
        totals["flops"] += cost
        if prim == "name":
            named[eqn.params["name"]] = outs[0]

    # stack-output log tensor -> its per-iteration parts.  A later unrolled
    # scan consuming a stacked output as xs reads the parts directly instead
    # of slicing the monolithic storage — the fwd-residuals -> bwd-scan path
    # would otherwise make every backward step depend on the whole stacked
    # array, which locks ~the entire activation peak during remat.
    stacked: dict[str, list[str]] = {}

    def unroll_scan(eqn, env, depth: int) -> None:
        length = max(int(eqn.params.get("length", 1)), 1)
        reverse = bool(eqn.params.get("reverse", False))
        inner = eqn.params["jaxpr"]
        ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
        nc = int(eqn.params.get("num_consts", 0))
        nk = int(eqn.params.get("num_carry", 0))
        invals = [lookup(v, env) for v in eqn.invars]
        cvals, carry, xs = invals[:nc], invals[nc:nc + nk], invals[nc + nk:]
        const_env: dict[Any, str] = {}
        for v, cv in zip(ij.constvars, getattr(inner, "consts", ())):
            const_env[v] = b.constant(
                int(getattr(cv, "nbytes", _aval_bytes(v.aval))),
                name=f"scanconst{depth}_{len(const_env)}")
        n_ys = len(eqn.outvars) - nk
        ys_parts: list[list[str]] = [[] for _ in range(n_ys)]
        for it in range(length):
            benv: dict[Any, str] = dict(const_env)
            xe: list[str] = []
            for xi, xv in enumerate(xs):
                var = ij.invars[nc + nk + xi]
                parts = stacked.get(xv)
                if parts is not None and len(parts) == length:
                    xe.append(parts[length - 1 - it if reverse else it])
                    continue
                sz = _aval_bytes(var.aval)
                # A per-iteration slice is a view of the stacked operand
                # (XLA reads it in place); a fresh storage per layer would
                # double-count every scanned parameter stack as activation
                # memory.
                (t,) = b.call([xv], [sz],
                              max(0.1 * _aval_elems(var.aval), 1.0),
                              "scan_slice", aliases=[xv])
                xe.append(t)
            for var, val in zip(ij.invars, cvals + carry + xe):
                benv[var] = val
            emit(ij, benv, depth + 1)
            outs = [lookup(v, benv) for v in ij.outvars]
            carry = outs[:nk]
            for yi in range(n_ys):
                ys_parts[yi].append(outs[nk + yi])
        if reverse:
            ys_parts = [list(reversed(p)) for p in ys_parts]
        for var, val in zip(eqn.outvars[:nk], carry):
            env[var] = val
        for yi, var in enumerate(eqn.outvars[nk:]):
            sz = _aval_bytes(var.aval)
            (t,) = b.call(ys_parts[yi], [sz],
                          max(0.1 * _aval_elems(var.aval), 1.0),
                          "scan_stack")
            env[var] = t
            stacked[t] = ys_parts[yi]
            totals["bytes"] += sz

    def emit(jx, env, depth: int = 0) -> None:
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            if prim in ("pjit", "closed_call", "core_call",
                        "custom_jvp_call", "custom_vjp_call",
                        "custom_vjp_call_jaxpr", "remat", "checkpoint",
                        "custom_lin"):
                inner = None
                for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    if key in eqn.params:
                        inner = eqn.params[key]
                        break
                if inner is not None:
                    ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                    if unroll_scans and not getattr(inner, "consts", ()):
                        # Inline the call body: sub-eqns bind directly.
                        benv: dict[Any, str] = {}
                        for var, v in zip(ij.invars, eqn.invars):
                            benv[var] = lookup(v, env)
                        emit(ij, benv, depth + 1)
                        for var, v in zip(eqn.outvars, ij.outvars):
                            env[var] = lookup(v, benv)
                        continue
                    total = sum(_eqn_cost(e, s)
                                for e, s in _flatten_eqns(ij, depth + 1))
                    emit_call(eqn, total, env)
                    continue
            if prim == "scan":
                length = eqn.params.get("length", 1)
                if unroll_scans and length <= unroll_limit:
                    unroll_scan(eqn, env, depth)
                    continue
                inner = eqn.params["jaxpr"]
                ij = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                total = sum(_eqn_cost(e, s)
                            for e, s in _flatten_eqns(ij, depth + 1)
                            ) * length
                emit_call(eqn, total, env)
                continue
            if prim in ("while", "cond"):
                emit_call(eqn, float(sum(_aval_elems(o.aval)
                                         for o in eqn.outvars)), env)
                continue
            emit_call(eqn, eqn_flops(eqn), env)

    for v, cv in zip(jaxpr.constvars, closed.consts):
        env[v] = b.constant(
            int(getattr(cv, "nbytes", _aval_bytes(v.aval))), name=str(v))
    for v in jaxpr.invars:
        env[v] = b.constant(_aval_bytes(v.aval), name=f"in_{v}")

    emit(jaxpr, env)

    outputs = [env[v] if hasattr(v, "count") or v in env else lookup(v, env)
               for v in jaxpr.outvars]
    log = b.auto_release(keep=outputs)
    return TracedGraph(log=log, named=named, outputs=outputs,
                       total_bytes=totals["bytes"],
                       total_flops=totals["flops"])


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------

@dataclass
class Plan:
    budget_bytes: float
    feasible: bool
    save_names: list[str] = field(default_factory=list)
    remat_names: list[str] = field(default_factory=list)
    est_slowdown: float = 1.0
    est_peak_bytes: float = 0.0
    evictions: int = 0

    def policy(self):
        """A jax.checkpoint policy saving exactly the planned names."""
        if not self.remat_names:
            return jax.checkpoint_policies.everything_saveable
        if not self.save_names:
            return jax.checkpoint_policies.nothing_saveable
        return jax.checkpoint_policies.save_only_these_names(
            *self.save_names)


def plan(fn: Callable, *example_args, budget_bytes: float,
         heuristic: str = "h_dtr_eq", **example_kwargs) -> Plan:
    """Run the DTR greedy simulation over ``fn``'s graph under a budget.

    Returns the save/remat split over ``checkpoint_name``-tagged tensors.
    ``fn`` should be the *differentiated* step (e.g. value_and_grad) so the
    simulation sees the true fwd+bwd tensor lifetime structure.
    """
    tg = trace_to_log(fn, *example_args, name="plan", **example_kwargs)
    rt = DTRRuntime(budget=float(budget_bytes),
                    heuristic=by_name(heuristic), dealloc="eager")
    evicted_names: set[str] = set()

    orig_evict = rt._evict

    def traced_evict(s):
        # Only *pressure* evictions of still-live tensors are remat
        # decisions; eager evictions at refcount zero are ordinary frees.
        if s.refs > 0:
            for tid in s.tensor_tids:
                evicted_names.add(rt.tensors[tid].name)
        orig_evict(s)

    rt._evict = traced_evict
    try:
        env = replay(tg.log, rt)
    except OOMError:
        return Plan(budget_bytes=budget_bytes, feasible=False,
                    remat_names=sorted(tg.named))
    # env maps log tensor names -> tids; evicted_names recorded runtime names
    # — map through: runtime tensors were created with out_names = log names.
    save, remat = [], []
    for cname, log_t in tg.named.items():
        if log_t in evicted_names:
            remat.append(cname)
        else:
            save.append(cname)
    return Plan(budget_bytes=budget_bytes, feasible=True,
                save_names=sorted(save), remat_names=sorted(remat),
                est_slowdown=rt.slowdown(), est_peak_bytes=rt.peak_memory,
                evictions=rt.evictions)


def dtr_checkpoint(fn: Callable, *example_args, budget_bytes: float,
                   grad_fn: Callable | None = None,
                   heuristic: str = "h_dtr_eq", **example_kwargs):
    """Wrap ``fn`` in jax.checkpoint with a DTR-planned policy.

    ``grad_fn`` (default: grad of sum(fn)) is traced for planning so the
    simulation sees backward lifetimes; the returned callable is
    ``jax.checkpoint(fn, policy=planned)``.
    """
    if grad_fn is None:
        def grad_fn(*a, **k):
            return jax.grad(
                lambda *aa: jnp.sum(fn(*aa, **k)).astype(jnp.float32)
            )(*a)
    p = plan(grad_fn, *example_args, budget_bytes=budget_bytes,
             heuristic=heuristic, **example_kwargs)
    return jax.checkpoint(fn, policy=p.policy()), p


# ---------------------------------------------------------------------------
# Segment-level planning for scanned layer stacks
# ---------------------------------------------------------------------------

def plan_layer_blocks(n_layers: int, layer_act_bytes: float,
                      budget_bytes: float) -> int:
    """Pick the remat block size for a scanned stack of ``n_layers``.

    DTR's even-spacing behaviour (Lemma A.1) on a homogeneous chain puts
    checkpoints every L/B layers; with a byte budget this is
    ceil(n_layers * layer_act_bytes / budget) layers per block, clamped to
    [1, n_layers].  Block size √L falls out when the budget equals
    √L·layer_act_bytes — the Thm 3.1 regime.
    """
    if budget_bytes <= 0 or n_layers <= 1:
        return 1
    blocks = max(int(budget_bytes // max(layer_act_bytes, 1)), 1)
    size = math.ceil(n_layers / blocks)
    return max(1, min(size, n_layers))


def sqrt_block_size(n_layers: int) -> int:
    return max(1, int(round(math.sqrt(n_layers))))
