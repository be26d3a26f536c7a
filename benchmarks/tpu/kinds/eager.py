"""Eager job: the gated-FFN stack through the DTR executor, step by step.

Each step is one forward and manual backward of a residual gated-FFN stack
(the MLP blocks of the configuration, at its widths) over ``tokens`` rows,
every op dispatched through ``repro.eager.DTRContext.call``: the paper's
online DTR, with real eviction and replay when the budget binds.  A step
builds a fresh context at the job's budget, wraps the weights and that
step's input, and ends with the weight gradients on the device.

``budget_frac`` null runs unbounded.  Otherwise set-up runs one
unconstrained step to read the runtime's peak and pinned bytes, and the
budget is ``pinned + budget_frac * (peak - pinned)``.  Set-up also runs one
step at the budget, which compiles every op shape the window uses.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops, reference, weights
from .train import relative_gaps


def _act(g, h):
    return jax.nn.silu(g) * h


def _act_bwd(da, g, h):
    s = jax.nn.sigmoid(g)
    return da * g * s, da * h * s * (1 + g * (1 - s))


def ffn_stack_grads(ctx, ws, x0, span):
    """Forward and manual backward of the stack through ``ctx``; the loss
    is the token mean of ``|x_L|^2 / 2``.  ``ws`` are ``(wi, wg, wo)``
    DTRArrays per block and ``x0`` the wrapped input.  Returns the weight
    gradients as device arrays, block by block, and releases every handle
    it made."""
    def op(name, fn, args):
        with span("dtr.call." + name):
            return ctx.call(name, fn, args)

    xs, saved = [x0], []
    for wi, wg, wo in ws:
        x = xs[-1]
        h = op("ffn_in", jnp.matmul, [x, wi])[0]
        g = op("ffn_gate", jnp.matmul, [x, wg])[0]
        a = op("ffn_act", _act, [g, h])[0]
        y = op("ffn_out", jnp.matmul, [a, wo])[0]
        xs.append(op("residual", jnp.add, [x, y])[0])
        y.release()
        saved.append((h, g, a))

    tokens = x0.shape[0]
    dx = op("d_loss", lambda x: x / tokens, [xs[-1]])[0]
    xs.pop().release()
    grads = []
    for (wi, wg, wo), (h, g, a) in zip(reversed(ws), reversed(saved)):
        x = xs.pop()
        dwo = op("d_wo", lambda a_, d: a_.T @ d, [a, dx])[0]
        da = op("d_a", lambda d, w: d @ w.T, [dx, wo])[0]
        dh, dg = op("d_act", _act_bwd, [da, g, h])
        dwi = op("d_wi", lambda x_, d: x_.T @ d, [x, dh])[0]
        dwg = op("d_wg", lambda x_, d: x_.T @ d, [x, dg])[0]
        dx_new = op("d_x", lambda d, dh_, dg_, wi_, wg_:
                    d + dh_ @ wi_.T + dg_ @ wg_.T, [dx, dh, dg, wi, wg])[0]
        for t in (dx, da, dh, dg, h, g, a):
            t.release()
        if xs:                    # x0 is the caller's
            x.release()
        dx = dx_new
        grads.append((dwi, dwg, dwo))
    dx.release()
    out = [tuple(t.value for t in blk) for blk in reversed(grads)]
    for blk in grads:
        for t in blk:
            t.release()
    return out


@jax.jit
def _grad_norms(grads):
    """``[blocks, 3]`` float32 norms of (d_wi, d_wg, d_wo)."""
    return jnp.stack([jnp.stack([jnp.linalg.norm(
        t.astype(jnp.float32).reshape(-1)) for t in blk]) for blk in grads])


def _leaves(norms) -> dict:
    return {f"{b}.{w}": float(norms[b, j]) for b in range(norms.shape[0])
            for j, w in enumerate(("wi", "wg", "wo"))}


def compare_grads(prog_norms, ref_norms) -> float:
    """Worst leaf's gap of gradient norms, as in the train cells."""
    prog, ref = _leaves(prog_norms), _leaves(ref_norms)
    return max(relative_gaps(prog, ref, list(ref)).values())


class Job:
    def __init__(self, cell):
        self.cell, self.conf, self.job = cell, cell.conf, cell.job
        c = self.conf
        self.blocks = c["num_hidden_layers"]
        self.d, self.f = c["hidden_size"], c["intermediate_size"]
        self.tokens = self.job["tokens"]
        self.dtype = jnp.dtype(self.job["dtype"])
        self.flops_per_step = flops.ffn_stack_flops(self.blocks, self.d,
                                                    self.f, self.tokens)
        self.counters = {"remat_runs": 0, "evictions": 0}
        self.attempted = self.failed = 0
        self.device_count = 1

    def setup(self):
        from repro.eager import DTRContext
        self.DTRContext = DTRContext
        key = weights.seed_key(self.cell.seed)
        self.key = key
        self.ws = jax.jit(partial(weights.ffn_weights, self.blocks, self.d,
                                  self.f, dtype=self.dtype))(key)
        self.input = jax.jit(partial(weights.ffn_input, self.tokens, self.d,
                                     dtype=self.dtype))
        self.budget = math.inf
        frac = self.job["budget_frac"]
        if frac is not None:
            ctx = self._run(math.inf, -2)[1]
            act = ctx.rt.peak_memory - self.pinned
            self.budget = self.pinned + frac * act
        self._run(self.budget, -1)
        self.norms = []
        self.counters = {"remat_runs": 0, "evictions": 0}

    def _run(self, budget: float, i: int):
        """One step on input ``i`` (negative: set-up); returns the
        gradients' norms and the context."""
        ctx = self.DTRContext(budget_bytes=budget,
                              heuristic=self.job["heuristic"])
        ws = [tuple(ctx.wrap(w, name=n) for w, n in zip(blk, "io_"))
              for blk in self.ws]
        x0 = ctx.wrap(self.input(self.key, i + 2), name="x0")
        self.pinned = ctx.rt.memory
        grads = ffn_stack_grads(ctx, ws, x0, self.span)
        return _grad_norms(grads), ctx

    def step(self, i: int) -> int:
        with self.span("bench.eager_step"):
            norms, ctx = self._run(self.budget, i)
        self.norms.append(norms)
        self.counters["remat_runs"] += ctx.remat_runs
        self.counters["evictions"] += ctx.rt.evictions
        self.attempted += 1
        return 1

    def memory_peak_bytes(self) -> int:
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def sampled_steps(self) -> list:
        """Window steps the check compares, drawn from the seed."""
        rng = np.random.default_rng(np.random.SeedSequence(
            [self.cell.seed, 2]))
        n = len(self.norms)
        return sorted(int(i) for i in rng.choice(
            n, size=min(self.job["check_steps"], n), replace=False))

    def reference_norms(self, i: int, precision: str = "float32"):
        """``[blocks, 3]`` norms of the reference's gradients of step i."""
        g = reference.FFNReference(precision).grads(
            self.ws, self.input(self.key, i + 2))
        return np.asarray(_grad_norms(g))

    def check(self) -> dict:
        gap = max(compare_grads(np.asarray(self.norms[i]),
                                self.reference_norms(i))
                  for i in self.sampled_steps())
        return {"grad_gap": (gap, self.cell.limits.get("grad_gap"))}
