"""Op-interposition layer: DTR over concrete JAX arrays in eager mode.

Mirrors the paper's PyTorch prototype (Sec. 5):

  * every operator call is dispatched through :meth:`DTRContext.call`, which
    registers the op + its cost with the DTR runtime, stores a replay
    closure, and returns :class:`DTRArray` handles;
  * under memory pressure the runtime picks victims via ``h_DTR^eq`` (or any
    heuristic) and the context *actually drops the buffers*;
  * accessing an evicted array triggers recursive rematerialization through
    the stored closures.

Costs are wall-clock seconds measured once per op signature (the op's name
and the shapes and dtypes of its DTRArray inputs) in each context: the first call
with a signature waits for the device, then times the op alone; later calls
reuse that cost.  With ``use_wallclock_cost=False`` every cost is 1.0 and no
call waits to time anything.

Dispatch is asynchronous within a bounded window: the outputs of at most
``_MAX_INFLIGHT`` dispatched ops (first runs and replays alike) may still be
computing when :meth:`DTRContext.call` or :meth:`DTRContext.fetch` returns;
one more waits for the oldest.  The host's bookkeeping for the next op thus
overlaps the device's work on the last ones, and the caller need not sync.
The window refers to outputs weakly, so it keeps no buffer alive that the
runtime has freed, nor any after its context is dropped.

Like the prototype, the accounted budget may be exceeded by exactly one
allocation (op outputs are computed before the eviction pass — Appendix E.1
notes the same slack).  Real device memory may exceed it further by what the
in-flight ops hold until they finish: their outputs, the temporaries inside
their closures, and inputs evicted after they were dispatched.  The
allocator's ``peak_bytes_in_use`` counts these but not the temporaries of
compiled programs.
"""
from __future__ import annotations

import time
import weakref
from collections import deque
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.heuristics import by_name
from ..core.runtime import DTRRuntime, Operator

# Ops whose outputs may still be computing when the host moves on (chosen on
# one TPU v5e, PERF.md section 6).
_MAX_INFLIGHT = 8


class DTRArray:
    """Handle to a (possibly evicted) tensor managed by a DTRContext."""

    __slots__ = ("ctx", "tid", "shape", "dtype")

    def __init__(self, ctx: "DTRContext", tid: int, shape, dtype):
        self.ctx = ctx
        self.tid = tid
        self.shape = tuple(shape)
        self.dtype = dtype

    @property
    def value(self) -> jax.Array:
        """Materialize (rematerializing if evicted) and return the buffer."""
        return self.ctx.fetch(self)

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)
                   * jnp.dtype(self.dtype).itemsize)

    def release(self) -> None:
        self.ctx.release_tid(self.tid)

    # Convenience arithmetic (sugar over ctx.call).
    def __add__(self, other):
        return self.ctx.call("add", jnp.add, [self, other])[0]

    def __mul__(self, other):
        return self.ctx.call("mul", jnp.multiply, [self, other])[0]

    def __matmul__(self, other):
        return self.ctx.call("matmul", jnp.matmul, [self, other])[0]

    def __repr__(self):
        s = self.ctx.rt.storages[self.ctx.rt.tensors[self.tid].sid]
        state = "resident" if s.resident else "evicted"
        return f"DTRArray(shape={self.shape}, dtype={self.dtype}, {state})"


class DTRContext:
    """Owns the runtime, the buffers, and the replay closures."""

    def __init__(self, budget_bytes: float, heuristic: str = "h_dtr_eq",
                 dealloc: str = "eager", use_wallclock_cost: bool = True,
                 seed: int = 0, alloc_mode: str | None = None,
                 placement: str = "best_fit", recorder=None,
                 offload=None, faults=None, recovery=None):
        # alloc_mode="pool" maps the real JAX buffers onto simulated pool
        # accounting: every resident storage occupies a contiguous block and
        # memory pressure evicts contiguous windows (repro.alloc), so eager
        # runs report the fragmentation a real device allocator would see.
        #
        # ``offload`` (an enabled repro.offload.OffloadConfig, budgets and
        # bandwidths in bytes / bytes-per-second) adds the host tier: under
        # pressure, storages whose modeled round-trip transfer undercuts
        # their recompute cost have their *actual buffers* moved to host
        # memory (numpy) and brought back on access — contents preserved,
        # no replay.
        from ..core.simulator import make_allocator
        h = by_name(heuristic, seed)
        engine = None
        if offload is not None and offload.enabled:
            from ..offload import OffloadEngine, wrap_heuristic
            engine = OffloadEngine(offload)
            h = wrap_heuristic(h, engine)
        self.rt = DTRRuntime(
            budget=float(budget_bytes), heuristic=h,
            dealloc=dealloc,
            materialize_fn=self._on_perform, free_fn=self._on_free,
            allocator=make_allocator(alloc_mode, placement),
            offload=engine, offload_fn=self._on_offload,
            fetch_fn=self._on_fetch,
            # repro.faults: injected faults perturb the *simulated* memory
            # pressure and clock only — the replay closures still produce
            # exact buffers, so a recovered run's numerics match a
            # fault-free one bit-for-bit (the differential tests pin this).
            faults=faults, recovery=recovery)
        self.buffers: dict[int, jax.Array] = {}     # tid -> concrete array
        self.host_buffers: dict[int, np.ndarray] = {}  # tid -> offloaded copy
        self.closures: dict[int, Callable] = {}     # op_id -> replay fn
        self.use_wallclock_cost = use_wallclock_cost
        self._pending_outputs: list[jax.Array] | None = None
        self._costs: dict[tuple, float] = {}     # op signature -> seconds
        self._inflight: deque[list] = deque()    # weakrefs to op outputs
        self.remat_runs = 0
        self.timed_calls = 0       # calls that waited to time a new signature
        self.inflight_waits = 0    # dispatches that waited on a full window
        # Optional repro.trace.TraceRecorder: mirrors every wrap/call/release
        # into a core.graph.Log (first executions only — rematerializations
        # are the runtime's own doing, not part of the operator stream).
        self.recorder = recorder

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def wrap(self, x, constant: bool = True, name: str = "const") -> DTRArray:
        """Lift a concrete array into DTR management ("checkpoint()")."""
        x = jnp.asarray(x)
        tid = self.rt.constant(x.nbytes, name=name)
        self.buffers[tid] = x
        if self.recorder is not None:
            self.recorder.on_constant(tid, name, int(x.nbytes),
                                      shape=tuple(x.shape),
                                      dtype=str(x.dtype))
        return DTRArray(self, tid, x.shape, x.dtype)

    def fetch(self, a: DTRArray) -> jax.Array:
        """"decheckpoint()": rematerialize if needed and return the value."""
        self.rt.get(a.tid)
        return self.buffers[a.tid]

    def call(self, name: str, fn: Callable, args: Sequence,
             n_outputs: int | None = None) -> list[DTRArray]:
        """Dispatch ``fn(*args)`` through DTR.

        ``args`` may mix DTRArrays and plain arrays/scalars; plain values are
        captured in the closure (treated as op attributes, not tensors).

        The op's cost is the time measured at the first call with its
        signature in this context (name, input shapes and dtypes), or 1.0
        without wall-clock costs.  Only that first call waits for the device;
        otherwise the op is dispatched asynchronously and joins the in-flight
        window (see the module docstring).
        """
        dtr_args = [a for a in args if isinstance(a, DTRArray)]
        in_tids = [a.tid for a in dtr_args]

        def replay(*concrete):
            it = iter(concrete)
            full = [next(it) if isinstance(a, DTRArray) else a for a in args]
            out = fn(*full)
            return out if isinstance(out, tuple) else (out,)

        concrete_in = [self.fetch(a) for a in dtr_args]
        cost = 1.0
        if self.use_wallclock_cost:
            key = (name, *((a.shape, a.dtype) for a in dtr_args))
            cost = self._costs.get(key)
        if cost is None:
            # A new signature: let queued work finish, then time the op alone.
            jax.block_until_ready(([_live(e) for e in self._inflight],
                                   concrete_in))
            self._inflight.clear()
            t0 = time.perf_counter()
            outs = replay(*concrete_in)
            jax.block_until_ready(outs)
            cost = self._costs[key] = max(time.perf_counter() - t0, 1e-7)
            self.timed_calls += 1
        else:
            outs = replay(*concrete_in)
            self._dispatched(outs)

        self._pending_outputs = list(outs)
        oid = self.rt._next_oid
        self.closures[oid] = replay
        out_sizes = [int(o.nbytes) for o in outs]
        tids = self.rt.call(name, cost, in_tids, out_sizes)
        self._pending_outputs = None
        if self.recorder is not None:
            self.recorder.on_call(name, cost, in_tids, tids, out_sizes,
                                  shapes=[tuple(o.shape) for o in outs])
        return [DTRArray(self, tid, o.shape, o.dtype)
                for tid, o in zip(tids, outs)]

    def release_tid(self, tid: int) -> None:
        """Drop one external reference (recorded when tracing)."""
        if self.recorder is not None:
            self.recorder.on_release(tid)
        self.rt.release(tid)

    def fragmentation(self):
        """Pool telemetry (``repro.alloc.FragStats``); None in counter mode."""
        return self.rt.fragmentation()

    def live_bytes(self) -> int:
        """Actual bytes held in resident buffers (for budget verification)."""
        total = 0
        for tid, buf in self.buffers.items():
            t = self.rt.tensors[tid]
            if t.defined and not t.is_alias:
                total += int(buf.nbytes)
        return total

    # ------------------------------------------------------------------
    # Runtime hooks
    # ------------------------------------------------------------------
    def _on_perform(self, op: Operator, first: bool) -> None:
        if first:
            outs = self._pending_outputs
            assert outs is not None, "first perform without pending outputs"
        else:
            # Rematerialization: replay closure with input buffers (the
            # runtime guarantees inputs are defined here).
            self.remat_runs += 1
            ins = [self.buffers[tid] for tid in op.input_tids]
            outs = list(self.closures[op.op_id](*ins))
            self._dispatched(outs)
        for tid, buf in zip(op.output_tids, outs):
            if self.rt.tensors[tid].defined:
                self.buffers[tid] = buf

    def _dispatched(self, outs) -> None:
        """Add an op's outputs to the in-flight window; past
        ``_MAX_INFLIGHT`` ops, wait for the oldest op whose outputs still
        exist.  The device runs ops in the order they were dispatched, so
        that op's being done implies the dropped ones before it are done."""
        self._inflight.append([weakref.ref(o) for o in outs
                               if isinstance(o, jax.Array)])
        if len(self._inflight) <= _MAX_INFLIGHT:
            return
        while self._inflight:   # ``outs``, the newest entry, is live
            live = _live(self._inflight.popleft())
            if live:
                if not all(o.is_ready() for o in live):
                    self.inflight_waits += 1
                    jax.block_until_ready(live)
                return

    def _on_free(self, storage) -> None:
        for tid in storage.tensor_tids:
            self.buffers.pop(tid, None)
            self.host_buffers.pop(tid, None)

    def _on_offload(self, storage, defined_tids) -> None:
        """Move the storage's defined buffers to host memory (numpy)."""
        for tid in defined_tids:
            buf = self.buffers.pop(tid, None)
            if buf is not None:
                self.host_buffers[tid] = np.asarray(buf)
        for tid in storage.tensor_tids:   # undefined views hold no bytes
            self.buffers.pop(tid, None)

    def _on_fetch(self, storage, defined_tids) -> None:
        """Bring host copies back as device arrays (contents preserved)."""
        for tid in defined_tids:
            host = self.host_buffers.pop(tid, None)
            if host is not None:
                self.buffers[tid] = jnp.asarray(host)

    def host_bytes(self) -> int:
        """Actual bytes currently parked in host copies."""
        return sum(int(b.nbytes) for b in self.host_buffers.values())


def _live(refs) -> list:
    """The outputs that an in-flight window entry's weakrefs still reach."""
    return [o for o in (r() for r in refs) if o is not None]


def op(ctx: DTRContext, name: str, fn: Callable) -> Callable:
    """Decorator-style helper:  f = op(ctx, "gelu", jax.nn.gelu)."""
    def wrapped(*args):
        outs = ctx.call(name, fn, list(args))
        return outs[0] if len(outs) == 1 else tuple(outs)
    return wrapped
