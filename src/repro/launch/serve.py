"""Serving driver: continuous-batching decode loop on a host mesh.

A request queue feeds a fixed-width decode batch; finished slots are
immediately refilled from the queue (continuous batching).  The step function
is ``launch.steps.make_serve_step``, run over this host's devices.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 12 --slots 4 --gen 16

``--capture PATH`` additionally records the executed per-request/slot
operator stream as a DTR log (``repro.trace``): every admission, decode
step, and retirement the loop actually performs is mirrored into the trace,
so budget sweeps replay *this* serving run, not a synthetic stand-in.
"""
from __future__ import annotations

import argparse
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.distributed.sharding import mesh_context
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_serve_step
from repro.models import model as M


@dataclass
class ServeSummary:
    """What one ``main`` run served: the prompt and the generated token ids
    per request id (completed requests only in ``completed``), the batched
    decode steps taken and their wall time (first step's compilation
    included)."""
    requests: int
    prompts: dict[int, np.ndarray] = field(default_factory=dict)
    completed: dict[int, list[int]] = field(default_factory=dict)
    steps: int = 0
    seconds: float = 0.0


def main(argv=None) -> ServeSummary:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (continuous batching slots)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens to generate per request")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--capture", default=None, metavar="PATH",
                    help="record the executed operator stream as a DTR "
                         "trace log (repro.trace)")
    ap.add_argument("--kv-budget", type=float, default=None, metavar="FRAC",
                    help="admission control: cap the projected KV footprint "
                         "of admitted requests at FRAC x the full cache "
                         "size; overflow preempts the cheapest-to-"
                         "rematerialize slot and requeues it with bounded "
                         "retries + backoff (default: off)")
    ap.add_argument("--admit-retries", type=int, default=3,
                    help="max requeues per request before rejection")
    ap.add_argument("--admit-backoff", type=int, default=8,
                    help="base requeue backoff in decode steps (doubles "
                         "per retry, capped)")
    ap.add_argument("--chaos-shrink", type=float, default=0.0,
                    help="repro.faults: periodically shrink the admission "
                         "KV budget to this fraction (a co-tenant stealing "
                         "device memory); 0 = off")
    ap.add_argument("--chaos-period", type=int, default=64,
                    help="squeeze period in decode steps")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--offload-sweep", action="store_true",
                    help="after capture, replay the captured trace through "
                         "the hybrid remat-or-offload tier (repro.offload): "
                         "the per-slot KV chunks and activations become "
                         "offload candidates (weights stay pinned)")
    ap.add_argument("--device-fracs", nargs="+", type=float,
                    default=[0.5, 0.3],
                    help="device budgets, as fractions of the activation "
                         "range (offload sweep)")
    ap.add_argument("--host-fracs", nargs="+", type=float,
                    default=[0.0, 0.5, 1.0],
                    help="host-tier budgets, as fractions of the activation "
                         "range; 0 = DTR-only baseline (offload sweep)")
    ap.add_argument("--offload-bw", type=float, default=2.0,
                    help="transfer bandwidth relative to the trace's "
                         "characteristic bandwidth (peak bytes per unit "
                         "baseline compute)")
    args = ap.parse_args(argv)
    if args.offload_sweep and not args.capture:
        ap.error("--offload-sweep needs --capture (it replays the "
                 "captured trace)")

    tracer = None
    if args.capture:
        from repro.trace.capture import (WorkloadTrace,
                                         step_model_from_config)
        tracer = WorkloadTrace(
            step_model_from_config(args.arch, smoke=args.smoke),
            name=f"serve_{args.arch}_s{args.slots}",
            meta={"source": "launch.serve", "arch": args.arch,
                  "slots": args.slots, "requests": args.requests,
                  "gen": args.gen, "smoke": bool(args.smoke)})

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    mesh = make_host_mesh()

    rng = np.random.default_rng(0)
    queue = deque(
        (i, rng.integers(0, cfg.vocab, (int(rng.integers(4, 12)),))
         .astype(np.int32)) for i in range(args.requests))
    prompts = dict(queue)

    with mesh_context(mesh):
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        serve = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        cache = M.init_cache(cfg, args.slots, args.max_len)

        # Optional admission control + preemption-with-requeue
        # (repro.launch.admission): requests are priced at their projected
        # KV footprint against a fraction of the full cache size; a
        # request that cannot fit preempts the cheapest-to-rematerialize
        # slot instead of the loop dying or the request silently queueing
        # forever.  Default off — the loop below is bit-identical without
        # --kv-budget.
        admit = None
        tickets = {}
        if args.kv_budget is not None:
            from repro.launch.admission import (ADMIT, REJECT,
                                                AdmissionController, Ticket)
            cache_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(cache))
            per_tok = cache_bytes / (args.slots * args.max_len)
            chaos = None
            if args.chaos_shrink > 0:
                from repro.faults import FaultConfig, FaultSchedule
                chaos = FaultSchedule(FaultConfig(
                    seed=args.chaos_seed, budget_shrink=args.chaos_shrink,
                    budget_period=args.chaos_period))
            admit = AdmissionController(
                args.kv_budget * cache_bytes, per_tok,
                max_retries=args.admit_retries,
                backoff_steps=args.admit_backoff, faults=chaos)
            tickets = {rid: Ticket(rid, len(prompt), args.gen)
                       for rid, prompt in queue}

        # True continuous batching: each slot carries its own position
        # clock (decode_step accepts a [slots] pos vector — per-slot cache
        # scatter + per-slot masks/rope), so a finished slot is refilled
        # on the very next global step while its neighbors keep decoding.
        slots = [None] * args.slots
        tok = np.zeros((args.slots, 1), np.int32)
        pos = np.zeros(args.slots, np.int32)
        completed = {}
        t0 = time.perf_counter()
        steps = 0

        def reset_slot_cache(cache, i):
            """Zero slot ``i``'s rows so recurrent-state blocks (RWKV /
            RG-LRU carry no ``pos`` and are *not* masked by it) start the
            new request clean.  Attention caches are position-masked, so
            zeroing them is merely hygienic.  Every leaf of
            ``M.init_cache`` is a per-layer scan stack ``[layers, slots,
            ...]`` — the slot axis is always axis 1 (sizes are not used to
            guess it, so a leaf whose later dims happen to equal the slot
            count cannot be zeroed along the wrong axis)."""
            return jax.tree.map(
                lambda x: x.at[:, i].set(0)
                if x.ndim >= 2 and x.shape[1] == args.slots else x,
                cache)

        def admit_into(i, rid, prompt):
            nonlocal cache
            slots[i] = {"rid": rid, "prompt": prompt, "i": 0, "out": []}
            pos[i] = 0
            cache = reset_slot_cache(cache, i)

        def active_map():
            """slot -> (Ticket, tokens processed) for the controller."""
            return {j: (tickets[s["rid"]], int(pos[j]))
                    for j, s in enumerate(slots) if s is not None}

        def preempt(j, tick):
            """Preempt slot ``j``: its KV chunks are dropped (a DTR
            eviction of the whole request) and the request requeues with
            backoff; replaying it later is the rematerialization."""
            nonlocal cache
            s = slots[j]
            admit.requeue(tickets[s["rid"]], tick)
            queue.append((s["rid"], s["prompt"]))
            if tracer is not None and s["i"] > 0:
                tracer.retire(s["rid"], j)
            slots[j] = None
            pos[j] = 0
            cache = reset_slot_cache(cache, j)

        def refill(tick=0):
            nonlocal cache
            fresh = set()   # admitted this pass: not preemption candidates
            for i in range(args.slots):
                if slots[i] is None and queue:
                    if admit is None:
                        rid, prompt = queue.popleft()
                        admit_into(i, rid, prompt)
                        continue
                    # Arrival order, but requests backing off or waiting
                    # for space do not block eligible ones behind them.
                    for k in range(len(queue)):
                        rid, prompt = queue[k]
                        verdict, victims = admit.decide(
                            tickets[rid],
                            {j: v for j, v in active_map().items()
                             if j not in fresh}, tick)
                        if verdict == REJECT:
                            del queue[k]
                            break
                        if verdict == ADMIT:
                            del queue[k]
                            for j in victims:
                                preempt(j, tick)
                            admit_into(i, rid, prompt)
                            fresh.add(i)
                            break

        tick = idle = 0
        while queue or any(s is not None for s in slots):
            if admit is not None:
                # Injected budget squeeze (a co-tenant stole device
                # memory): shed load until usage fits again.
                for j in admit.enforce(active_map(), tick):
                    preempt(j, tick)
            refill(tick)   # mid-stream: neighbors keep their positions
            if not any(s is not None for s in slots):
                if admit is None or not queue:
                    break
                # Everything queued is backing off / waiting out a
                # squeeze: idle ticks pass without decode work.  The
                # guard bounds pathological schedules (e.g. a permanent
                # squeeze no request fits under).
                tick += 1
                idle += 1
                if idle > 10000:
                    for rid, _ in queue:
                        admit.rejected += 1
                        admit._event("reject", rid=rid, step=tick,
                                     reason="idle_guard")
                    queue.clear()
                    break
                continue
            idle = 0
            for i, s in enumerate(slots):
                if s is None:
                    tok[i, 0] = 0
                elif pos[i] < len(s["prompt"]):
                    tok[i, 0] = s["prompt"][pos[i]]
                # else: keep the model-generated token for this slot
            nxt, cache = serve(params, cache,
                               jnp.asarray(tok), jnp.asarray(pos))
            steps += 1
            tick += 1
            nxt_np = np.asarray(nxt)[..., 0] if cfg.n_codebooks else \
                np.asarray(nxt)
            for i, s in enumerate(slots):
                if s is None:
                    continue
                if tracer is not None:
                    if s["i"] == 0:
                        tracer.prefill(s["rid"], i, 1)
                    else:
                        tracer.decode(
                            s["rid"], i, int(pos[i]),
                            phase="prompt" if pos[i] < len(s["prompt"])
                            else "decode")
                    s["i"] += 1
                if pos[i] >= len(s["prompt"]) - 1:
                    s["out"].append(int(nxt_np[i, 0]))
                    tok[i, 0] = nxt_np[i, 0]
                pos[i] += 1
                if len(s["out"]) >= args.gen or pos[i] >= args.max_len:
                    completed[s["rid"]] = s["out"]
                    if tracer is not None:
                        tracer.retire(s["rid"], i)
                    if admit is not None:
                        admit.retire(tickets[s["rid"]])
                    slots[i] = None
                    # the freed slot refills on the next loop iteration —
                    # captured traces now exercise interleaved lifetimes

        dt = time.perf_counter() - t0
        print(f"served {len(completed)}/{args.requests} requests, "
              f"{steps} decode steps, {dt:.2f}s "
              f"({dt/max(steps,1)*1e3:.1f} ms/step batched x{args.slots})")
        if admit is not None:
            c = admit.counters()
            print(f"admission: admitted={c['admitted']} "
                  f"completed={c['completed']} requeued={c['requeued']} "
                  f"rejected={c['rejected']} "
                  f"preemptions={c['preemptions']} "
                  f"(kv_budget={args.kv_budget:.2f}x cache)")
        for rid in sorted(completed)[:4]:
            print(f"  req{rid}: {completed[rid][:10]}...")
        if tracer is not None:
            log = tracer.finish()
            with open(args.capture, "w") as f:
                f.write(log.dumps() + "\n")
            print(f"captured trace {log.name}: {log.op_count()} ops "
                  f"-> {args.capture}")
            if args.offload_sweep:
                _offload_sweep(log, args.device_fracs, args.host_fracs,
                               args.offload_bw)
    return ServeSummary(requests=args.requests, prompts=prompts,
                        completed=completed, steps=steps, seconds=dt)


def _offload_sweep(log, device_fracs, host_fracs, bw_rel,
                   heuristic="h_dtr_eq"):
    """Replay a captured serve trace over a device × host budget grid.

    The host tier gives the serving loop a second lever for its dominant
    memory consumer: per-slot KV chunks (and layer activations) can be
    parked in host memory over the modeled channels instead of being
    recomputed, whichever the two-choice policy prices cheaper.  Budgets
    scan the activation range (weights are pinned and cannot move);
    ``host_frac=0`` is the plain DTR baseline.
    """
    from repro.core.simulator import (measure_baseline, resolve_budget,
                                      simulate)
    from repro.offload import OffloadConfig

    peak, base_cost = measure_baseline(log)
    pinned = log.pinned_bytes()
    span = max(peak - pinned, 0.0)
    bw = bw_rel * peak / max(base_cost, 1e-12)
    print(f"offload sweep [{log.name}]: peak={peak:.4g} pinned={pinned:.4g} "
          f"bw={bw:.4g} bytes/unit-compute")
    for f in device_fracs:
        budget = resolve_budget(f, peak, pinned, "activation")
        for hf in host_fracs:
            if hf <= 0:
                r = simulate(log, heuristic, budget)
                tag = "dtr-only "
            else:
                cfg = OffloadConfig(host_budget=hf * span,
                                    h2d_bandwidth=bw, d2h_bandwidth=bw)
                r = simulate(log, heuristic, budget, offload=cfg)
                tag = f"host={hf:.2f}"
            state = (f"overhead={r.overhead:.3f} "
                     f"(compute {r.slowdown:.3f}x, stall {r.stall_time:.3g}) "
                     f"offloads={r.offloads} fetches={r.fetches} "
                     f"prefetch_hits={r.prefetch_hits}"
                     if r.ok else f"FAIL({r.error[:48]})")
            print(f"  dev={f:.2f} {tag}: {state}")


if __name__ == "__main__":
    main()
