"""End-to-end training driver: data pipeline -> model -> optimizer ->
checkpointing -> fault tolerance, with the DTR remat policy as a first-class
config knob.

Default run trains a ~20M-param llama-family model for 300 steps on CPU
(minutes); ``--arch smollm-135m --full`` trains the real 135M config (the
~100M-class run; slower on CPU, the step function is identical).  Resuming
after an interruption is exercised by just re-running the command — the
checkpoint manager restores the latest step and the data pipeline seeks its
cursor.

  PYTHONPATH=src python examples/train_lm.py
  PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --full \
      --steps 120 --batch 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt import CheckpointManager
from repro.data.pipeline import SyntheticLM
from repro.distributed.monitor import DivergenceGuard, StragglerMonitor, Timer
from repro.launch.steps import make_train_step
from repro.models import model as M
from repro.optim import adamw, cosine_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--full", action="store_true",
                    help="use the full config instead of the smoke config")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--remat", default="dtr")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = (configs.get(args.arch) if args.full
           else configs.get_smoke(args.arch))
    # ~20M-class default: widen the smoke config a little.
    if not args.full:
        cfg = cfg.replace(n_layers=8, d_model=256, n_heads=8, n_kv_heads=4,
                          head_dim=32, d_ff=1024, vocab=8192)
    cfg = cfg.replace(remat=args.remat, dtype="float32")
    n_params_analytic = cfg.param_count()
    print(f"arch={cfg.name} params~{n_params_analytic/1e6:.1f}M "
          f"remat={cfg.remat}")

    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    print(f"materialized params: {n_params/1e6:.1f}M")

    opt = adamw(lr=cosine_schedule(3e-4, warmup=20, total=args.steps))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(cfg, opt))

    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                       n_codebooks=cfg.n_codebooks)
    ckpt = CheckpointManager(args.ckpt_dir, every_steps=args.ckpt_every,
                             keep=2)
    monitor = StragglerMonitor()
    guard = DivergenceGuard()

    # ---- resume (fault tolerance) ----
    start, restored, extra = ckpt.restore({"params": params,
                                           "opt": opt_state})
    if start is not None:
        params, opt_state = restored["params"], restored["opt"]
        start += 1
        print(f"resumed from checkpoint at step {start - 1}")
    else:
        start = 0

    losses = []
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        with Timer() as t:
            # The step applies its update only below the guard's limit.
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, np.float32(guard.max_loss()))
            jax.block_until_ready(metrics["loss"])
        loss = float(metrics["loss"])
        gn = float(metrics["grad_norm"])
        action = guard.record(bool(metrics["applied"]), loss)
        if action == "skip":
            print(f"step {step}: DIVERGENCE ({loss=:.3g} {gn=:.3g}) — "
                  f"update skipped")
            continue
        if action == "restore":
            s, restored, _ = ckpt.restore({"params": params,
                                           "opt": opt_state})
            if s is not None:
                params, opt_state = restored["params"], restored["opt"]
                print(f"step {step}: restored checkpoint from step {s}")
            continue
        st = monitor.record(step, t.seconds, loss, gn)
        losses.append(loss)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {loss:.4f}  "
                  f"grad_norm {gn:.3f}  {t.seconds*1e3:.0f}ms"
                  + ("  [straggler]" if st.flagged else ""))
        ckpt.maybe_save(step, {"params": params, "opt": opt_state},
                        extra={"data_step": step})

    first = np.mean(losses[:10])
    last = np.mean(losses[-10:])
    print(f"\nloss {first:.4f} -> {last:.4f} "
          f"({'LEARNING' if last < first else 'NOT LEARNING'})")
    print(f"step-time ewma {monitor.ewma*1e3:.0f}ms; "
          f"{sum(s.flagged for s in monitor.history)} straggler flags")


if __name__ == "__main__":
    main()
