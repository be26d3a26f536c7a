import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count at first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:
  1. builds the production mesh (16,16) single-pod or (2,16,16) multi-pod,
  2. constructs ShapeDtypeStruct stand-ins (no allocation) for params,
     optimizer state, data batch / KV caches, with NamedShardings attached,
  3. ``jax.jit(step).lower(...).compile()`` — proving the sharding config is
     coherent end-to-end,
  4. records memory_analysis / cost_analysis / parsed collective bytes and
     the roofline terms to experiments/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.analysis.hlo import parse_collectives, xla_cost_dict
from repro.analysis.hlo_cost import analyze as analyze_hlo
from repro.analysis.roofline import (
    model_flops_decode, model_flops_prefill, model_flops_train, roofline)
from repro.data.pipeline import make_batch_specs
from repro.distributed.sharding import mesh_context, pspec
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (
    batch_shardings, cache_shardings, make_serve_step, make_train_step,
    opt_state_structs, state_shardings)
from repro.models import model as M
from repro.optim import adafactor, adamw

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}

# long_500k needs sub-quadratic context handling: run for SSM/hybrid/
# windowed archs, skip for pure full-attention archs (DESIGN.md §4).
LONG_OK = {"recurrentgemma-2b", "rwkv6-1.6b", "gemma3-1b", "mixtral-8x7b"}

# Arch-specific dry-run settings.
FSDP_ARCHS = {"deepseek-v3-671b", "mixtral-8x7b", "llama-3.2-vision-11b"}
OPTIMIZER = {"deepseek-v3-671b": "adafactor"}
# Gradient accumulation (microbatching): bounds per-step activation memory;
# cost_analysis counts the accumulation loop body once, so per-step roofline
# numbers are rescaled by this factor below.
GRAD_ACCUM = {"deepseek-v3-671b": 8, "mixtral-8x7b": 4,
              "llama-3.2-vision-11b": 4, "musicgen-large": 2}
# bf16 params for the two giants (DeepSeek-V3 trained in FP8; bf16 is the
# conservative TPU equivalent — DESIGN.md §5).
BF16_PARAMS = {"deepseek-v3-671b", "mixtral-8x7b", "llama-3.2-vision-11b"}

_CANONICAL = [
    "recurrentgemma-2b", "smollm-135m", "llama3.2-1b", "qwen2-0.5b",
    "gemma3-1b", "llama-3.2-vision-11b", "musicgen-large", "rwkv6-1.6b",
    "deepseek-v3-671b", "mixtral-8x7b",
]
CELLS = [(a, s) for a in _CANONICAL for s in SHAPES]


def build_cell(arch: str, shape: str, mesh, overrides=None, remat="full",
               extra_cfg=None, grad_accum=None, flash_analytic=False,
               fsdp=None):
    """Lower + compile one cell; returns result dict."""
    spec = SHAPES[shape]
    cfg = configs.get(arch)
    if arch in BF16_PARAMS:
        cfg = cfg.replace(param_dtype="bfloat16")
    cfg = cfg.replace(remat=remat, **(extra_cfg or {}))
    if fsdp is None:
        fsdp = arch in FSDP_ARCHS
    opt_name = OPTIMIZER.get(arch, "adamw")
    chips = int(np.prod(list(mesh.shape.values())))

    rule_overrides = {"seq": "model"} if spec["kind"] == "train" else {}
    if shape == "prefill_32k":
        rule_overrides = {"seq": "model"}
    if shape == "decode_32k":
        # Context parallelism: KV cache sequence dim over the model axis
        # (batch is already over pod×data).
        rule_overrides = {"kv_seq": "model"}
    if shape == "long_500k":
        # Batch=1: all parallelism comes from sharding the 512k context.
        rule_overrides = {"batch": None,
                          "kv_seq": ("pod", "data", "model")}
    rule_overrides.update(overrides or {})

    with mesh_context(mesh, overrides=rule_overrides, fsdp=fsdp):
        p_sh, o_sh = state_shardings(cfg, mesh, opt_name, fsdp=fsdp)
        p_structs = jax.tree.map(
            lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                                sharding=sh),
            M.param_structs(cfg), p_sh)

        if spec["kind"] == "train":
            opt = (adafactor() if opt_name == "adafactor" else
                   adamw(lr=3e-4))
            o_structs = jax.tree.map(
                lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                                    sharding=sh),
                opt_state_structs(cfg, opt_name), o_sh)
            b_specs = make_batch_specs(cfg, spec["batch"], spec["seq"])
            b_sh = batch_shardings(cfg, mesh, b_specs)
            b_structs = jax.tree.map(
                lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                                    sharding=sh),
                b_specs, b_sh)
            ga = grad_accum or GRAD_ACCUM.get(arch, 1)
            step = make_train_step(cfg, opt, grad_accum=ga)
            # Donate params/opt-state: in-place update, halves live bytes.
            jitted = jax.jit(step, out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
            max_loss = jax.ShapeDtypeStruct(
                (), jnp.float32, sharding=NamedSharding(mesh, P()))
            lowered = jitted.lower(p_structs, o_structs, b_structs, max_loss)
            mf = model_flops_train(cfg, spec["batch"] * spec["seq"])
            cost_scale = ga
        elif spec["kind"] == "prefill":
            b_specs = make_batch_specs(cfg, spec["batch"], spec["seq"])
            b_sh = batch_shardings(cfg, mesh, b_specs)
            b_structs = jax.tree.map(
                lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                                    sharding=sh),
                b_specs, b_sh)

            def prefill(params, batch):
                logits = M.forward(cfg, params, batch["tokens"],
                                   batch.get("img_embed"))
                return logits[:, -1].astype(jnp.float32)

            lowered = jax.jit(prefill).lower(p_structs, b_structs)
            mf = model_flops_prefill(cfg, spec["batch"] * spec["seq"])
        else:  # decode
            b = spec["batch"]
            c_sh = cache_shardings(cfg, mesh, b, spec["seq"])
            c_structs = jax.tree.map(
                lambda st, sh: jax.ShapeDtypeStruct(st.shape, st.dtype,
                                                    sharding=sh),
                M.cache_structs(cfg, b, spec["seq"]), c_sh)
            tok_shape = (b, 1, cfg.n_codebooks) if cfg.n_codebooks else (b, 1)
            tok = jax.ShapeDtypeStruct(
                tok_shape, np.dtype("int32"),
                sharding=NamedSharding(mesh, pspec("batch", mesh=mesh)
                                       if b > 1 else P()))
            pos = jax.ShapeDtypeStruct((), np.dtype("int32"),
                                       sharding=NamedSharding(mesh, P()))
            serve = make_serve_step(cfg)
            # Donate the KV cache: updated in place across decode steps.
            jitted = jax.jit(serve, donate_argnums=(1,))
            args = [p_structs, c_structs, tok, pos]
            if cfg.cross_attn_dim:
                img = jax.ShapeDtypeStruct(
                    (b, cfg.cross_attn_tokens, cfg.cross_attn_dim),
                    np.dtype("bfloat16"),
                    sharding=NamedSharding(mesh, pspec("batch", mesh=mesh)
                                           if b > 1 else P()))
                lowered = jitted.lower(*args, img)
            else:
                lowered = jitted.lower(*args)
            mf = model_flops_decode(cfg, b)

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
        mem = compiled.memory_analysis()
        cost_xla = xla_cost_dict(compiled.cost_analysis())
        hlo = compiled.as_text()
        # Loop-aware analyzer: while bodies (layer scans, grad-accum,
        # blocked attention) weighted by known_trip_count — XLA's own
        # cost_analysis counts each body once (analysis/hlo_cost.py).
        thr = None
        if flash_analytic:
            ga = GRAD_ACCUM.get(arch, 1) if spec["kind"] == "train" else 1
            if grad_accum and spec["kind"] == "train":
                ga = grad_accum
            thr = ga * cfg.n_layers
        hc = analyze_hlo(hlo, flash_tile_threshold=thr)
        cost = hc.as_cost_dict()
        coll = parse_collectives(hlo)   # unweighted, kept for reference
        rt = roofline(cost, hc.collective_bytes, chips,
                      model_flops=mf, per_device=True)

    return {
        "arch": arch, "shape": shape,
        "mesh": dict(mesh.shape), "chips": chips,
        "remat": remat, "fsdp": fsdp, "optimizer": opt_name,
        "grad_accum": locals().get("cost_scale", 1) if spec["kind"] == "train" else 1,
        "rule_overrides": {k: str(v) for k, v in rule_overrides.items()},
        "compile_s": compile_s,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes_per_device": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes),
        },
        "cost": {k: cost.get(k, 0.0)
                 for k in ("flops", "bytes accessed", "transcendentals")},
        "cost_xla_unscaled": {k: cost_xla.get(k, 0.0)
                              for k in ("flops", "bytes accessed")},
        "collectives": {"total_bytes": hc.collective_bytes,
                        "by_kind": {k: float(v)
                                    for k, v in hc.coll_by_kind.items()},
                        "unweighted": coll.summary()},
        "roofline": rt.as_dict(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--overrides", default=None,
                    help="JSON dict of logical-rule overrides")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = CELLS if args.all else [(args.arch, args.shape)]
    overrides = json.loads(args.overrides) if args.overrides else None

    failures = []
    for arch, shape in cells:
        if shape == "long_500k" and arch not in LONG_OK:
            print(f"SKIP {arch} x {shape} (full-attention arch; DESIGN.md)")
            continue
        for multi in meshes:
            mesh = make_production_mesh(multi_pod=multi)
            tag = f"{arch}_{shape}_{'multi' if multi else 'single'}"
            t0 = time.time()
            try:
                res = build_cell(arch, shape, mesh, overrides=overrides,
                                 remat=args.remat)
                path = os.path.join(args.out, tag + ".json")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1, allow_nan=False)
                r = res["roofline"]
                print(f"OK   {tag}: compile={res['compile_s']:.1f}s "
                      f"mem/dev={res['memory']['peak_bytes_per_device']/2**30:.2f}GiB "
                      f"compute={r['compute_s']*1e3:.2f}ms "
                      f"memory={r['memory_s']*1e3:.2f}ms "
                      f"coll={r['collective_s']*1e3:.2f}ms "
                      f"dom={r['dominant']} "
                      f"roofline={r['roofline_frac']*100:.1f}%",
                      flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"FAIL {tag} ({time.time()-t0:.0f}s): {e!r}",
                      flush=True)
                traceback.print_exc(limit=3)
    if failures:
        print(f"\n{len(failures)} failures:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print("\nall cells OK")


if __name__ == "__main__":
    main()
