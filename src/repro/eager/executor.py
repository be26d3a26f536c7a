"""Op-interposition layer: DTR over concrete JAX arrays in eager mode.

Mirrors the paper's PyTorch prototype (Sec. 5):

  * every operator call is dispatched through :meth:`DTRContext.call`, which
    registers the op + its cost with the DTR runtime, stores a replay
    closure, and returns :class:`DTRArray` handles;
  * under memory pressure the runtime picks victims via ``h_DTR^eq`` (or any
    heuristic) and the context *actually drops the buffers*;
  * accessing an evicted array triggers recursive rematerialization through
    the stored closures.

Each op runs as one compiled XLA program, so the primitives inside its
closure fuse instead of each making its own pass over memory.  Programs are
kept in a process-wide cache (at most ``_MAX_PROGRAMS``, least recently used
out), keyed by what determines them: the function (for a plain Python
function its code object, defaults and the values of its closure cells, so a
lambda made afresh in a loop finds the same program; otherwise the callable
itself, if it is its module's attribute of its own name), where the DTRArrays
sit among the arguments and the values of the plain ones, and the shapes,
dtypes and weak types of the DTRArray inputs.  The op's name is no part of
the key.  The program is compiled before its first run, and that run and
every replay of the op call the same executable.  An op falls back to running
``fn`` primitive by primitive when its key cannot be built (a closure value or
plain argument that is unhashable or hashed by identity, a callable that is
no module attribute such as a ``functools.partial`` or a bound method) or
when ``fn`` fails to trace (it branches on values or converts its input to
NumPy); a key that failed to trace is remembered and never traced again.
It also falls back when one function over one input layout already holds
``_MAX_VARIANTS`` programs: a value it captures (a step's loss, say) keeps
changing, and each compile would serve one call.
``ctx.compiled_calls`` and ``ctx.uncompiled_calls`` count calls and replays
on each path; ``_PROGRAMS.compiles`` counts compiles in the process.

Costs are wall-clock seconds measured once per op signature (the op's name
and the shapes and dtypes of its DTRArray inputs) in each context: the first call
with a signature waits for the device, then times the op alone; later calls
reuse that cost.  Compiling comes before the timing, so the cost is the
compiled program's run time.  With ``use_wallclock_cost=False`` every cost is
1.0 and no call waits to time anything.

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

import sys
import time
import types
import weakref
from collections import OrderedDict, deque
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.heuristics import by_name
from ..core.runtime import DTRRuntime, Operator

# Ops whose outputs may still be computing when the host moves on (chosen on
# one TPU v5e, PERF.md section 6).
_MAX_INFLIGHT = 8

# Compiled op programs kept across contexts; a step of the eager benchmark
# job uses eleven.  One function over one input layout may hold at most
# _MAX_VARIANTS of them, which differ in captured or plain values.
_MAX_PROGRAMS = 256
_MAX_VARIANTS = 4


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
        self.compiled_calls = 0    # calls and replays of a cached program
        self.uncompiled_calls = 0  # calls and replays run primitive by primitive
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

        ``fn`` runs as one compiled program, cached across contexts (see the
        module docstring), so it must be pure: it may read nothing but its
        arguments and the values it closes over, and those values must not
        change once it has been called.  An ``fn`` whose key cannot be built
        or that fails to trace runs primitive by primitive instead.

        The op's cost is the time measured at the first call with its
        signature in this context (name, input shapes and dtypes), or 1.0
        without wall-clock costs.  Only that first call waits for the device;
        otherwise the op is dispatched asynchronously and joins the in-flight
        window (see the module docstring).
        """
        dtr_args = [a for a in args if isinstance(a, DTRArray)]
        in_tids = [a.tid for a in dtr_args]
        concrete_in = [self.fetch(a) for a in dtr_args]
        replay = (_PROGRAMS.get(fn, args, concrete_in)
                  or _bind(fn, args))   # uncompiled: primitive by primitive

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
            outs = self._run(replay, concrete_in)
            jax.block_until_ready(outs)
            cost = self._costs[key] = max(time.perf_counter() - t0, 1e-7)
            self.timed_calls += 1
        else:
            outs = self._run(replay, concrete_in)
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
            outs = list(self._run(self.closures[op.op_id], ins))
            self._dispatched(outs)
        for tid, buf in zip(op.output_tids, outs):
            if self.rt.tensors[tid].defined:
                self.buffers[tid] = buf

    def _run(self, replay: Callable, concrete) -> tuple:
        """Run an op's replay fn on its input buffers, counting the path."""
        if isinstance(replay, jax.stages.Compiled):
            self.compiled_calls += 1
        else:
            self.uncompiled_calls += 1
        return replay(*concrete)

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


def _bind(fn: Callable, args: Sequence) -> Callable:
    """``fn`` as a function of the buffers of the DTRArrays among ``args``,
    returning its outputs as a tuple.  Keeps no DTRArray (nor so its
    context) alive."""
    buffer_at = [isinstance(a, DTRArray) for a in args]
    plain = [None if b else a for a, b in zip(args, buffer_at)]

    def program(*buffers):
        it = iter(buffers)
        out = fn(*[next(it) if b else a for a, b in zip(plain, buffer_at)])
        return out if isinstance(out, tuple) else (out,)
    return program


class _Uncacheable(Exception):
    """A value that cannot be part of a program's key."""


# Values keyed by themselves: equal values give the same program.
_PLAIN = (int, bool, str, type(None))
_KEY_DEPTH = 8     # nesting of functions and tuples a key may follow


def _value_key(v, depth: int = 0):
    """Key of a plain argument or closure value.  Raises ``_Uncacheable``
    for one whose equal copies could give different programs, or that is
    unhashable or hashed by identity (a fresh one would compile anew)."""
    if depth > _KEY_DEPTH:
        raise _Uncacheable
    t = type(v)
    if t in _PLAIN:
        return t, v
    if t in (float, complex) or isinstance(v, np.generic):
        return t, repr(v)             # 0.0 == -0.0, 1 == 1.0 == True
    if t is tuple:
        return t, tuple(_value_key(e, depth + 1) for e in v)
    if t is types.FunctionType:
        return _function_key(v, depth + 1)
    if t is types.ModuleType or _is_module_attribute(v):
        return v
    if callable(v) or t.__hash__ is None or t.__hash__ is object.__hash__:
        raise _Uncacheable            # a partial, a bound method, an array
    return t, v


def _function_key(fn: types.FunctionType, depth: int):
    try:
        cells = tuple(c.cell_contents for c in fn.__closure__ or ())
    except ValueError:                # a cell not yet assigned
        raise _Uncacheable from None
    kwdefaults = tuple(sorted((fn.__kwdefaults__ or {}).items()))
    return fn.__code__, (_value_key(fn.__defaults__, depth),
                         _value_key(kwdefaults, depth),
                         _value_key(cells, depth))


def _is_module_attribute(fn) -> bool:
    """Whether ``fn`` is its module's attribute of its own name, as
    ``jnp.matmul`` or ``jax.nn.relu`` is: the same object on every call."""
    module = sys.modules.get(getattr(fn, "__module__", None) or "")
    name = getattr(fn, "__name__", None)
    return (module is not None and isinstance(name, str)
            and getattr(module, name, None) is fn)


class _Programs:
    """Compiled op programs shared by every context in the process, least
    recently used first out.  A key whose function failed to trace maps to
    None, so it is never traced again.  Keys of one function over one input
    layout hold at most ``_MAX_VARIANTS`` entries: past that, its captured or
    plain values keep changing, and a compile would serve one call."""

    def __init__(self):
        self.entries: OrderedDict = OrderedDict()
        self.variants: dict = {}      # (function, layout) -> entries held
        self.compiles = 0

    def get(self, fn: Callable, args: Sequence, concrete: list):
        """The compiled program of ``fn`` over ``args`` (DTRArrays standing
        for the buffers ``concrete``), or None to run it uncompiled."""
        try:
            if type(fn) is types.FunctionType:
                ident, values = _function_key(fn, 0)
            elif _is_module_attribute(fn):
                ident, values = fn, ()
            else:
                return None
            avals = iter([(x.shape, x.dtype, x.weak_type) for x in concrete])
            layout = tuple(next(avals) if isinstance(a, DTRArray) else None
                           for a in args)
            values = (values, tuple(_value_key(a) for a in args
                                    if not isinstance(a, DTRArray)))
            key = ((ident, layout), values)
            hash(key)
        except (_Uncacheable, TypeError):
            return None
        try:
            program = self.entries[key]
        except KeyError:
            group = key[0]
            held = self.variants.get(group, 0)
            if held >= _MAX_VARIANTS:
                return None
            program = self.entries[key] = self._compile(fn, args, concrete)
            self.variants[group] = held + 1
            if len(self.entries) > _MAX_PROGRAMS:
                (group, _), _ = self.entries.popitem(last=False)
                self.variants[group] -= 1
                if not self.variants[group]:
                    del self.variants[group]
        else:
            self.entries.move_to_end(key)
        return program

    def _compile(self, fn: Callable, args: Sequence, concrete: list):
        try:
            lowered = jax.jit(_bind(fn, args)).lower(*concrete)
        except (jax.errors.JAXTypeError, jax.errors.JAXIndexError):
            return None               # depends on values: run uncompiled
        compiled = lowered.compile()
        self.compiles += 1
        return compiled


_PROGRAMS = _Programs()


def op(ctx: DTRContext, name: str, fn: Callable) -> Callable:
    """Decorator-style helper:  f = op(ctx, "gelu", jax.nn.gelu)."""
    def wrapped(*args):
        outs = ctx.call(name, fn, list(args))
        return outs[0] if len(outs) == 1 else tuple(outs)
    return wrapped
