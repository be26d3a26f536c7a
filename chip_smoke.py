"""Smoke check: the train, serve and eager-DTR paths run on one TPU.

  python chip_smoke.py            # one chip: eager DTR, train, serve
  python chip_smoke.py --chips 4  # four-chip data-parallel train vs a
                                  # one-device reference, nothing else

One process does all the work and starts no child, because the process that
touches JAX holds the chip.  Without a TPU it exits non-zero before doing
anything.  Every phase checks its results and exits non-zero when a check
fails; the last line of standard output is then never printed.  It is
one JSON object naming the device:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The timings it prints (step seconds, compile seconds) and the peak bytes are
smoke timings of one run, not benchmark numbers.

Phases on one chip, in this order:

1. Eager DTR at real size: a 30-block gated-FFN stack at SmolLM-135M widths
   over 8x1024 tokens, forward and manual backward through ``DTRContext``,
   first with no budget and then at half its activation peak.  The weight
   gradients must be bit-identical and at least one op must have been
   rematerialized.  It runs first because the device's
   ``peak_bytes_in_use`` never resets within a process.
2. Train: ``repro.launch.train.main`` on SmolLM-135M at full width, remat
   ``dtr``.  Every loss must be finite, no step skipped or restored, and
   the first loss must agree with a plain float32 forward without remat.
3. Serve: ``repro.launch.serve.main`` on Qwen2-0.5B at full width.  Every
   request must complete with in-vocabulary token ids.  The serve loop
   (slot admission, per-slot resets and positions) must have generated
   exactly the tokens of a lock-step replay of the same decode step from
   clean caches; and the model's decode, in float32, must agree with its
   full forward over the served sequences.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# Sizes of each phase; tests/test_chip_compile.py compiles the same steps.
EAGER_BLOCKS, EAGER_D_MODEL, EAGER_D_FF = 30, 576, 1536   # SmolLM-135M
EAGER_TOKENS = 8 * 1024
EAGER_BUDGET_FRAC = 0.5
TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = "smollm-135m", 8, 1024, 8
SERVE_ARCH, SERVE_SLOTS, SERVE_REQUESTS = "qwen2-0.5b", 8, 16
SERVE_GEN, SERVE_MAX_LEN = 32, 1024
# References, computed in float32 with float32 matmuls (a TPU's default
# precision multiplies float32 in bf16).  tests/test_archs.py holds decode
# logits to within 2e-2 of the largest |logit| of the full forward; the same
# bound, relative, holds the bf16 train step's first loss to the reference.
# At random init these models amplify rounding with depth and position: in
# bf16 a teacher-forced forward and the decode loop pick different greedy
# tokens, and even in float32 the decode/forward gap of a 16-layer
# Qwen2-0.5B cut reaches 1.9e-3 by position 41 on the CPU.  So the served
# sequences are checked over their first REF_DECODE_TOKENS positions only.
REF_RTOL = 2e-2
REF_DECODE_TOKENS = 16
# Four chips: loss agreement of the data-parallel run with the one-device
# gradient-accumulation reference.  Both do the same per-example math in
# bf16 (unit roundoff 2**-8 = 3.9e-3); they differ only in the order in
# which gradients and the loss mean are summed, which Adam's normalized
# update can carry into the next steps' losses.  A 1e-2 relative bound is
# about 2.5 bf16 roundoffs: far below the change a wrong gradient or a
# dropped shard makes, above what summation order alone gives.
FOUR_CHIP_LOSS_RTOL = 1e-2
# With data=4 FSDP every device holds a quarter of the state and the batch;
# a device below half the busiest device's peak did not take its share.
FOUR_CHIP_MIN_PEAK_SHARE = 0.5


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends compiling (persistent-cache reads included) and the
    persistent cache's hits and misses, from ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def peak_bytes(device) -> int:
    stats = device.memory_stats()
    check(stats is not None, f"{device} reports no memory_stats()")
    return int(stats["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# Eager DTR
# ---------------------------------------------------------------------------

def _act(g, h):
    return jax.nn.silu(g) * h


def _act_bwd(da, g, h):
    s = jax.nn.sigmoid(g)
    return da * g * s, da * h * s * (1 + g * (1 - s))


def ffn_stack_grads(ctx, weights, x0):
    """Forward and manual backward of a residual gated-FFN stack through
    ``ctx``; loss is the token mean of ``|x_L|^2 / 2``.

    ``weights`` is a list of ``(wi, wg, wo)`` DTRArrays per block and ``x0``
    the wrapped ``[tokens, d_model]`` input.  Returns the weight gradients
    as host arrays, block by block, and the high-water mark of
    ``ctx.live_bytes()`` sampled after every op.
    """
    high = [ctx.live_bytes()]

    def op(name, fn, args):
        outs = ctx.call(name, fn, args)
        high[0] = max(high[0], ctx.live_bytes())
        return outs

    xs, saved = [x0], []
    for wi, wg, wo in weights:
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
    for (wi, wg, wo), (h, g, a) in zip(reversed(weights), reversed(saved)):
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
        if xs:                    # x0 is the caller's constant
            x.release()
        dx = dx_new
        grads.append((dwi, dwg, dwo))
    dx.release()
    host = [tuple(np.asarray(t.value) for t in blk) for blk in reversed(grads)]
    for blk in grads:
        for t in blk:
            t.release()
    return host, high[0]


def eager_run(budget_bytes: float, *, blocks: int, d_model: int, d_ff: int,
              tokens: int, dtype=jnp.bfloat16, seed: int = 0):
    """One eager forward/backward of the FFN stack under ``budget_bytes``.

    Returns (grads, stats) where stats holds the runtime's peak and pinned
    bytes, the live-bytes high-water mark, remat count and evictions."""
    from repro.eager import DTRContext
    ctx = DTRContext(budget_bytes=budget_bytes)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3 * blocks + 1)
    weights = []
    for i in range(blocks):
        wi = jax.random.normal(ks[3 * i], (d_model, d_ff)) / math.sqrt(d_model)
        wg = jax.random.normal(ks[3 * i + 1], (d_model, d_ff)) / math.sqrt(
            d_model)
        # The gated product is quadratic in x, so an unnormalized residual
        # stack blows up unless each block adds little: scale the output
        # projection by 1/blocks.
        wo = jax.random.normal(ks[3 * i + 2], (d_ff, d_model)) / (
            math.sqrt(d_ff) * blocks)
        weights.append(tuple(ctx.wrap(w.astype(dtype), name=n)
                             for w, n in ((wi, "wi"), (wg, "wg"), (wo, "wo"))))
    x0 = ctx.wrap(jax.random.normal(ks[-1], (tokens, d_model)).astype(dtype),
                  name="x0")
    pinned = ctx.rt.memory
    grads, high = ffn_stack_grads(ctx, weights, x0)
    stats = {"peak": ctx.rt.peak_memory, "pinned": pinned,
             "live_high": high, "remat_runs": ctx.remat_runs,
             "evictions": ctx.rt.evictions, "budget": budget_bytes}
    return grads, stats


def eager_phase(*, blocks=EAGER_BLOCKS, d_model=EAGER_D_MODEL,
                d_ff=EAGER_D_FF, tokens=EAGER_TOKENS,
                budget_frac=EAGER_BUDGET_FRAC, dtype=jnp.bfloat16):
    """Unconstrained run, then a run at ``budget_frac`` of its activation
    peak; checks remat happened and the gradients are bit-identical."""
    shape = dict(blocks=blocks, d_model=d_model, d_ff=d_ff, tokens=tokens,
                 dtype=dtype)
    t0 = time.perf_counter()
    ref, free = eager_run(math.inf, **shape)
    t1 = time.perf_counter()
    act_peak = free["peak"] - free["pinned"]
    budget = free["pinned"] + budget_frac * act_peak
    gc.collect()
    got, tight = eager_run(budget, **shape)
    t2 = time.perf_counter()
    print(f"eager: unconstrained peak {free['peak']:.0f} B "
          f"(pinned {free['pinned']:.0f} B, activations {act_peak:.0f} B), "
          f"live high-water {free['live_high']} B, {t1 - t0:.2f} s")
    print(f"eager: budget {budget:.0f} B ({budget_frac} of activations), "
          f"runtime peak {tight['peak']:.0f} B, live high-water "
          f"{tight['live_high']} B, remat_runs {tight['remat_runs']}, "
          f"evictions {tight['evictions']}, {t2 - t1:.2f} s")
    check(tight["remat_runs"] > 0,
          "eager: the budgeted run rematerialized nothing")
    check(len(got) == len(ref) == blocks, "eager: gradient count")
    for i, (a, b) in enumerate(zip(ref, got)):
        for name, x, y in zip(("wi", "wg", "wo"), a, b):
            check(bool(np.isfinite(x.astype(np.float32)).all()),
                  f"eager: block {i} d_{name} not finite")
            check(x.dtype == y.dtype and x.shape == y.shape
                  and x.tobytes() == y.tobytes(),
                  f"eager: block {i} d_{name} differs from the "
                  f"unconstrained run")
    return free, tight


# ---------------------------------------------------------------------------
# Train and serve, through their own entry points
# ---------------------------------------------------------------------------

def train_phase(extra_args=(), *, batch=TRAIN_BATCH, steps=TRAIN_STEPS,
                label="train"):
    from repro.launch import train
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        s = train.main(["--arch", TRAIN_ARCH, "--remat", "dtr",
                        "--steps", str(steps), "--batch", str(batch),
                        "--seq", str(TRAIN_SEQ), "--ckpt-dir", ckpt,
                        "--ckpt-every", str(10 * steps), *extra_args])
    sec = s.step_seconds
    print(f"{label}: losses {s.losses}")
    print(f"{label}: first step {sec[0] if sec else float('nan'):.3f} s "
          f"(compile included), later steps "
          f"{[round(x, 4) for x in sec[1:]]} s, peak {s.peak_bytes} B")
    check(s.resumed_from is None, f"{label}: resumed from a checkpoint")
    check(s.clean, f"{label}: skipped {s.skipped}, restored {s.restored}")
    check(len(s.losses) == steps, f"{label}: {len(s.losses)}/{steps} steps")
    check(all(math.isfinite(x) for x in s.losses),
          f"{label}: non-finite loss")
    check(s.peak_bytes is not None, f"{label}: memory_stats() was null")
    return s


def _config(arch, smoke):
    from repro import configs
    return configs.get_smoke(arch) if smoke else configs.get(arch)


def first_loss_reference(arch, batch, seq, *, smoke=False) -> float:
    """Loss of ``train.main``'s first step computed plainly: the same
    initial params and first batch, a float32 forward without remat."""
    from repro.data.pipeline import SyntheticLM
    from repro.models import model as M
    cfg = _config(arch, smoke).replace(remat="none", dtype="float32")
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, batch=batch,
                       n_codebooks=cfg.n_codebooks)
    tokens = {"tokens": jnp.asarray(data.batch_at(0)["tokens"])}
    with jax.default_matmul_precision("float32"):
        # Jitted like train.main's init: eager init rounds differently.
        params = jax.jit(lambda k: M.init_params(cfg, k))(
            jax.random.PRNGKey(0))
        return float(jax.jit(M.loss_fn, static_argnums=0)(cfg, params,
                                                          tokens))


def decode_forward_gap(summary, arch, *, tokens=REF_DECODE_TOKENS,
                       smoke=False) -> float:
    """The repo's decode-versus-forward check (tests/test_archs.py) on the
    served requests: over the first ``tokens`` of each request's prompt and
    generated ids, a float32 token-by-token decode fed per-slot position
    vectors as the serve loop feeds them, against one float32 forward.
    This checks the model's decode path, not the serve loop, which
    ``served_greedy_replay`` checks.  Returns the largest |decode - forward|
    logit as a fraction of the largest |forward| logit."""
    from repro.models import model as M
    cfg = _config(arch, smoke).replace(dtype="float32")
    rids = sorted(summary.completed)
    ids = jnp.asarray(np.stack(
        [np.concatenate([summary.prompts[r], summary.completed[r]])[:tokens]
         for r in rids]).astype(np.int32))
    with jax.default_matmul_precision("float32"):
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        full = jax.jit(M.forward, static_argnums=0)(cfg, params, ids)
        step = jax.jit(lambda p, t, c, pos: M.decode_step(cfg, p, t, c, pos),
                       donate_argnums=2)
        cache = M.init_cache(cfg, len(rids), tokens)
        outs = []
        for i in range(tokens):
            logits, cache = step(params, ids[:, i:i + 1], cache,
                                 jnp.full((len(rids),), i, jnp.int32))
            outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    return float(jnp.max(jnp.abs(dec - full)) / jnp.max(jnp.abs(full)))


def served_greedy_replay(summary, arch, *, slots, max_len,
                         smoke=False) -> tuple[int, int]:
    """The served requests replayed without continuous batching.

    ``serve.main`` admits requests into free slots mid-stream, each slot at
    its own position.  Here the same decode step, at the same slot count
    and cache length and with the same params, runs the requests in waves
    of ``slots``: every request from position 0 in a fresh cache, all slots
    in lock step, teacher-forced with its prompt and served tokens.  Each
    slot's row is computed alone, so a correct serve loop generated exactly
    the greedy token this replay computes at every position.  Returns
    (generated positions that differ, generated positions)."""
    from repro.distributed.sharding import mesh_context
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_serve_step
    from repro.models import model as M
    cfg = _config(arch, smoke)
    rids = sorted(summary.completed)
    differ = total = 0
    with mesh_context(make_host_mesh()):
        params = M.init_params(cfg, jax.random.PRNGKey(0))  # as serve.main
        step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
        for w in range(0, len(rids), slots):
            wave = rids[w:w + slots]
            seqs = [np.concatenate([summary.prompts[r], summary.completed[r]])
                    for r in wave]
            cache = M.init_cache(cfg, slots, max_len)
            tok = np.zeros((slots, 1), np.int32)
            for t in range(max(len(s) for s in seqs) - 1):
                for j, s in enumerate(seqs):
                    tok[j, 0] = s[t] if t < len(s) else 0
                nxt, cache = step(params, cache, jnp.asarray(tok),
                                  jnp.full((slots,), t, jnp.int32))
                nxt = np.asarray(nxt)
                for j, (r, s) in enumerate(zip(wave, seqs)):
                    if len(summary.prompts[r]) - 1 <= t < len(s) - 1:
                        total += 1
                        differ += int(nxt[j, 0] != s[t + 1])
    return differ, total


def train_reference_check(s):
    """The train phase's first loss against ``first_loss_reference``."""
    ref = first_loss_reference(TRAIN_ARCH, TRAIN_BATCH, TRAIN_SEQ)
    rel = abs(s.losses[0] - ref) / abs(ref)
    print(f"train: first loss {s.losses[0]} vs float32 no-remat reference "
          f"{ref}, relative difference {rel} (bound {REF_RTOL})")
    check(rel <= REF_RTOL, "train: first loss disagrees with the reference")


def serve_phase():
    from repro.launch import serve
    s = serve.main(["--arch", SERVE_ARCH, "--slots", str(SERVE_SLOTS),
                    "--requests", str(SERVE_REQUESTS),
                    "--gen", str(SERVE_GEN), "--max-len", str(SERVE_MAX_LEN)])
    vocab = _config(SERVE_ARCH, False).vocab
    print(f"serve: {len(s.completed)}/{s.requests} requests, {s.steps} "
          f"decode steps, {s.seconds:.3f} s (compile included)")
    check(len(s.completed) == SERVE_REQUESTS,
          f"serve: {len(s.completed)}/{SERVE_REQUESTS} requests completed")
    for rid, toks in s.completed.items():
        check(len(toks) == SERVE_GEN,
              f"serve: request {rid} has {len(toks)}/{SERVE_GEN} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"serve: request {rid} has a token id outside [0, {vocab})")
    differ, total = served_greedy_replay(s, SERVE_ARCH, slots=SERVE_SLOTS,
                                         max_len=SERVE_MAX_LEN)
    print(f"serve: served tokens vs a lock-step replay of the same decode "
          f"step from clean caches: {differ} of {total} differ")
    check(differ == 0, "serve: the serve loop's tokens differ from the "
          "lock-step replay")
    gap = decode_forward_gap(s, SERVE_ARCH)
    print(f"serve: float32 decode vs forward over the first "
          f"{REF_DECODE_TOKENS} tokens of each request: largest gap {gap} "
          f"of max |logit| (bound {REF_RTOL})")
    check(gap <= REF_RTOL, "serve: decode disagrees with the forward")
    return s


def four_chip_phase():
    """Data=4 FSDP training at 4x the one-chip batch against the same
    global batch on one device with 4-step gradient accumulation."""
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, have {len(devices)}")
    batch = 4 * TRAIN_BATCH
    dp = train_phase(["--devices", "4", "--fsdp"], batch=batch,
                     label="train x4")
    peaks = [peak_bytes(d) for d in devices]
    print(f"train x4: per-device peak_bytes_in_use {peaks}", flush=True)
    gc.collect()
    ref = train_phase(["--devices", "1", "--grad-accum", "4"], batch=batch,
                      label="train ref")
    diffs = [abs(a - b) / abs(b) for a, b in zip(dp.losses, ref.losses)]
    print(f"train x4 vs ref: relative loss differences {diffs} "
          f"(bound {FOUR_CHIP_LOSS_RTOL})")
    check(min(peaks) >= FOUR_CHIP_MIN_PEAK_SHARE * max(peaks),
          f"train x4: device peaks {peaks} are not spread over 4 devices")
    check(max(diffs) <= FOUR_CHIP_LOSS_RTOL,
          "train x4: losses disagree with the one-device reference")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    count = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind} x{count}; "
          f"compile cache {cache_dir}", flush=True)

    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chip_phase()
        else:
            eager_phase()
            print(f"eager: device peak_bytes_in_use {peak_bytes(dev)} B",
                  flush=True)
            gc.collect()
            train_reference_check(train_phase())
            gc.collect()
            serve_phase()
            print(f"device memory_stats() after all phases "
                  f"{dev.memory_stats()}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        clock.close()
    print(f"smoke timings: {time.perf_counter() - t0:.1f} s in all, "
          f"{clock.seconds:.1f} s compiling, persistent cache "
          f"{clock.hits} hits / {clock.misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
