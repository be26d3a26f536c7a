"""Training driver.

Config-driven: pick an arch, parallelism knobs over this host's devices
(fsdp, grad accum, remat policy) and run a fault-tolerant
training loop: sharded train state, deterministic seekable data, periodic
atomic checkpoints, NaN/divergence guard with restore, straggler monitoring.

  # local smoke (CPU, host mesh):
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50
  # FSDP over the four chips of one host:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --batch 32 --seq 1024 --devices 4 --fsdp --remat dtr
"""
from __future__ import annotations

import argparse
import sys
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed.monitor import (DivergenceGuard, MemoryMonitor,
                                       StragglerMonitor, Timer)
from repro.distributed.sharding import mesh_context
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (batch_shardings, make_train_step,
                                state_shardings)
from repro.models import model as M
from repro.optim import adafactor, adamw, cosine_schedule


@dataclass
class TrainSummary:
    """What one ``main`` run did, step by step.

    ``losses``/``step_seconds`` cover the steps whose update was applied
    (the first one includes compilation).  ``skipped``/``restored`` list the
    steps the divergence guard dropped or rolled back; a run with either is
    not ``clean``.  ``peak_bytes`` is the device allocator's peak, None where
    the backend reports no memory stats."""
    steps: list[int] = field(default_factory=list)
    losses: list[float] = field(default_factory=list)
    step_seconds: list[float] = field(default_factory=list)
    skipped: list[int] = field(default_factory=list)
    restored: list[int] = field(default_factory=list)
    resumed_from: Optional[int] = None
    peak_bytes: Optional[int] = None

    @property
    def clean(self) -> bool:
        return not self.skipped and not self.restored


def main(argv=None) -> TrainSummary:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--devices", type=int, default=0,
                    help="host mesh over the first N devices (0 = all)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--remat", default="dtr")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args(argv)

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    cfg = cfg.replace(remat=args.remat)
    if args.smoke:
        cfg = cfg.replace(dtype="float32")

    mesh = make_host_mesh(n_devices=args.devices)

    opt = (adafactor(lr=args.lr) if args.optimizer == "adafactor"
           else adamw(lr=cosine_schedule(args.lr, warmup=20,
                                         total=args.steps)))
    summary = TrainSummary()

    with mesh_context(mesh, fsdp=args.fsdp):
        key = jax.random.PRNGKey(0)
        p_sh, o_sh = state_shardings(cfg, mesh, opt.name, fsdp=args.fsdp)
        # Initialized in place, shard by shard: built eagerly, the whole
        # unsharded state would first land on the default device.
        params = jax.jit(lambda k: M.init_params(cfg, k),
                         out_shardings=p_sh)(key)
        opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
        # The step keeps the old state itself when it rejects an update,
        # so the donated buffers are never needed again on the host.
        step_fn = jax.jit(
            make_train_step(cfg, opt, grad_accum=args.grad_accum),
            out_shardings=(p_sh, o_sh, None), donate_argnums=(0, 1))

        n = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
        print(f"arch={cfg.name} params={n/1e6:.1f}M mesh={dict(mesh.shape)} "
              f"remat={cfg.remat} fsdp={args.fsdp} ga={args.grad_accum}")

        data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                           batch=args.batch, n_codebooks=cfg.n_codebooks)
        b_sh = batch_shardings(cfg, mesh, data.batch_at(0))
        ckpt = CheckpointManager(args.ckpt_dir,
                                 every_steps=args.ckpt_every, keep=2)
        monitor = StragglerMonitor()
        memmon = MemoryMonitor()
        guard = DivergenceGuard()

        from repro.alloc import FragStats
        dev0 = jax.local_devices()[0]

        def device_memory():
            """(peak_bytes|None, frag_stats|None) from the device allocator.

            ``memory_stats`` exposes largest_free_block on TPU/GPU backends;
            CPU returns None — telemetry degrades to no numbers."""
            stats = dev0.memory_stats()
            if stats is None:
                return None, None
            peak = stats.get("peak_bytes_in_use", 0)
            frag = None
            if "largest_free_block_bytes" in stats:
                limit = stats.get("bytes_limit", 0)
                used = stats.get("bytes_in_use", 0)
                free = max(limit - used, 0)
                largest = stats["largest_free_block_bytes"]
                frag = FragStats(
                    capacity=limit, used=used, free=free,
                    largest_free=largest,
                    frag_ratio=(1 - largest / free) if free else 0.0)
            return peak, frag

        start, restored, extra = ckpt.restore(
            {"params": params, "opt": opt_state})
        if start is not None:
            params = jax.device_put(restored["params"], p_sh)
            opt_state = jax.device_put(restored["opt"], o_sh)
            start += 1
            summary.resumed_from = start
            print(f"resumed at step {start}")
        else:
            start = 0

        prefetch = Prefetcher(data, start_step=start)
        try:
            for step in range(start, args.steps):
                _, host_batch = prefetch.next()
                batch = jax.device_put(host_batch, b_sh)
                with Timer() as t:
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch,
                        np.float32(guard.max_loss()))
                    jax.block_until_ready(metrics["loss"])
                loss = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                action = guard.record(bool(metrics["applied"]), loss)
                if action == "skip":
                    summary.skipped.append(step)
                    print(f"step {step}: bad step ({loss=:.3g}) — skipped")
                    continue
                if action == "restore":
                    summary.restored.append(step)
                    s, restored, _ = ckpt.restore(
                        {"params": params, "opt": opt_state})
                    if s is not None:
                        params = jax.device_put(restored["params"], p_sh)
                        opt_state = jax.device_put(restored["opt"], o_sh)
                        print(f"step {step}: restored from {s}")
                    continue
                summary.steps.append(step)
                summary.losses.append(loss)
                summary.step_seconds.append(t.seconds)
                st = monitor.record(step, t.seconds, loss, gn)
                peak_bytes, frag = device_memory()
                if peak_bytes is not None:
                    summary.peak_bytes = max(summary.peak_bytes or 0,
                                             peak_bytes)
                ms = memmon.record(step, peak_bytes or 0, frag=frag)
                if step % 10 == 0 or step == args.steps - 1:
                    mem = (f" mem {peak_bytes/1e6:.0f}MB"
                           if peak_bytes else "")
                    if frag is not None:
                        mem += (f" free_blk {ms.largest_free/1e6:.0f}MB"
                                f" frag {ms.frag_ratio:.2f}")
                    print(f"step {step:5d} loss {loss:8.4f} "
                          f"gnorm {gn:7.3f} {t.seconds*1e3:6.0f} ms"
                          + mem
                          + (" [straggler]" if st.flagged else ""),
                          flush=True)
                ckpt.maybe_save(step, {"params": params, "opt": opt_state},
                                extra={"data_step": step})
        finally:
            prefetch.stop()
        ms = memmon.summary()
        frag_note = ("" if ms["min_largest_free"] is None else
                     f" min_free_blk {ms['min_largest_free']/1e6:.0f}MB"
                     f" max_frag {ms['max_frag_ratio']:.2f}")
        print(f"mem summary: peak {ms['peak_bytes']/1e6:.0f}MB" + frag_note)
    bad = len(summary.skipped) + len(summary.restored)
    print("done" if summary.clean else
          f"done with {bad} bad steps (skipped {summary.skipped}, "
          f"restored {summary.restored})")
    return summary


if __name__ == "__main__":
    sys.exit(0 if main().clean else 1)
