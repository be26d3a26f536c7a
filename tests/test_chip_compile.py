"""Compiles for a described TPU v5e: the Pallas kernels at model widths and
the steps ``chip_smoke.py`` runs, at full size.  No chip is needed: the TPU
compiler is installed and compiles for a ``v5e:2x2`` topology that is only
described, so what it refuses (tiling, VMEM, memory that does not fit the
16 GiB of HBM) is caught here.  Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the pytest workers that are not
given this file must not try.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke as cs
from repro import configs
from repro.distributed.sharding import mesh_context
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gemm import moe_grouped_gemm
from repro.kernels.rwkv6_chunk import rwkv6_chunk
from repro.launch.steps import (make_serve_step, make_train_step,
                                state_shardings)
from repro.models import model as M
from repro.optim import adamw

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # A described chip's compile cannot be read back without the chip, so
    # the persistent cache stays off while this file compiles.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _structs(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding
    or a matching tree of them)."""
    if not isinstance(sharding, (SingleDeviceSharding, NamedSharding)):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, sharding)
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


# ---------------------------------------------------------------------------
# Pallas kernels at model widths (interpret=False: the Mosaic compiler)
# ---------------------------------------------------------------------------

def _kernel_case(name):
    bf16 = jnp.bfloat16
    if name == "flash":          # SmolLM-135M: 9 q / 3 kv heads x 64, 1k
        q = (8, 9, 1024, 64)
        kv = (8, 3, 1024, 64)
        return (lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True),
                [(q, bf16), (kv, bf16), (kv, bf16)])
    if name == "rwkv6":          # RWKV6-1.6B: 32 heads x 64, batch 8, 1k
        seq = (8 * 32, 1024, 64)
        return (lambda r, k, v, w, u: rwkv6_chunk(r, k, v, w, u),
                [(seq, bf16)] * 4 + [((8 * 32, 64), bf16)])
    # Mixtral-8x7B expert FFN: 8 experts, d 4096 -> 14336, 1280 slots each
    return (lambda x, w: moe_grouped_gemm(x, w),
            [((8, 1280, 4096), bf16), ((8, 4096, 14336), bf16)])


@pytest.mark.parametrize("name", ["flash", "rwkv6", "moe"])
def test_kernel_compiles_at_model_width(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


# ---------------------------------------------------------------------------
# The full-width steps chip_smoke.py runs
# ---------------------------------------------------------------------------

def _train_step_args(cfg, batch, placement):
    """(params, opt_state, batch, max_loss) structs for ``make_train_step``
    with AdamW, as ``launch/train.py`` builds them."""
    opt = adamw(lr=3e-4)
    params = M.param_structs(cfg)
    opt_state = jax.eval_shape(opt.init, params)
    p_sh, o_sh, b_sh, s_sh = placement(opt)
    tokens = jax.ShapeDtypeStruct((batch, cs.TRAIN_SEQ), jnp.int32)
    return opt, (_structs(params, p_sh), _structs(opt_state, o_sh),
                 _structs({"tokens": tokens}, b_sh),
                 jax.ShapeDtypeStruct((), jnp.float32, sharding=s_sh))


def test_train_step_fits_one_chip(one_chip):
    cfg = configs.get(cs.TRAIN_ARCH).replace(remat="dtr")
    opt, args = _train_step_args(
        cfg, cs.TRAIN_BATCH, lambda opt: (one_chip,) * 4)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    compiled = step.lower(*args).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_train_step_fits_four_chips_fsdp(topo):
    """The ``--chips 4`` path: data=4 FSDP at 4x the one-chip batch.  Every
    device holds a share and the gradients are reduced across devices."""
    cfg = configs.get(cs.TRAIN_ARCH).replace(remat="dtr")
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"))

    p_sh, o_sh = state_shardings(cfg, mesh, "adamw", fsdp=True)
    with mesh_context(mesh, fsdp=True):
        opt, args = _train_step_args(
            cfg, 4 * cs.TRAIN_BATCH,
            lambda opt: (p_sh, o_sh, NamedSharding(mesh, P("data")),
                         NamedSharding(mesh, P())))
        step = jax.jit(make_train_step(cfg, opt),
                       out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1))
        compiled = step.lower(*args).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo


def test_serve_step_fits_one_chip(one_chip):
    cfg = configs.get(cs.SERVE_ARCH)
    params = _structs(M.param_structs(cfg), one_chip)
    cache = _structs(M.cache_structs(cfg, cs.SERVE_SLOTS, cs.SERVE_MAX_LEN),
                     one_chip)
    token = jax.ShapeDtypeStruct((cs.SERVE_SLOTS, 1), jnp.int32,
                                 sharding=one_chip)
    pos = jax.ShapeDtypeStruct((cs.SERVE_SLOTS,), jnp.int32,
                               sharding=one_chip)
    step = jax.jit(make_serve_step(cfg), donate_argnums=(1,))
    compiled = step.lower(params, cache, token, pos).compile()
    assert _device_bytes(compiled) < HBM_BYTES
