"""The program's named scopes reach the compiled train step, and
``scopes.py`` turns a trace and the step's HLO text into device time per
scope."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.tpu import scopes, trace
from bench_tpu_smoke import SMOKE_CONF


@pytest.fixture(scope="module")
def smoke_step_ops():
    """``scopes.instructions`` of the compiled smoke-size train step, remat
    ``dtr``, as the train cells build it."""
    from benchmarks.tpu.kinds import train
    from repro.launch import steps
    from repro.models import model as M
    from repro.optim import adamw
    cfg = train.program_config(SMOKE_CONF, {"remat": "dtr",
                                            "dtype": "bfloat16",
                                            "param_dtype": "float32"})
    opt = adamw(lr=1e-3)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.key(0)))
    state = jax.eval_shape(opt.init, params)
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    compiled = jax.jit(steps.make_train_step(cfg, opt)).lower(
        params, state, {"tokens": tokens}, np.float32(math.inf)).compile()
    return list(scopes.instructions(compiled.as_text()).values())


def _phase(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recompute"
    return "backward" if "transpose(" in op_name else "forward"


@pytest.mark.parametrize("scope,phases", [
    ("attn", {"forward", "backward", "recompute"}),
    ("ffn", {"forward", "backward", "recompute"}),
    # The head is outside the remat'd layer scan: nothing recomputes it.
    ("lm_head", {"forward", "backward"}),
])
def test_scope_reaches_every_phase_of_the_step(smoke_step_ops, scope,
                                               phases):
    seen = {_phase(op_name) for _, op_name in smoke_step_ops
            if scopes.in_scope(op_name, scope)}
    assert seen == phases


def test_in_scope_takes_off_transformation_wrappers():
    assert scopes.in_scope("jit(step)/transpose(jvp(lm_head))/dot_general",
                           "lm_head")
    assert scopes.in_scope("jit(step)/checkpoint/rematted_computation/attn"
                           "/exp", "attn")
    assert not scopes.in_scope("jit(step)/attn_out/add", "attn")
    assert not scopes.in_scope(None, "attn")


HLO = """\
HloModule jit_train_step

%fused_head (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %exp.1 = f32[4]{0} exponential(f32[4]{0} %param_0), metadata={op_name="jit(train_step)/transpose(jvp(lm_head))/exp"}
  ROOT %bitcast.1 = f32[4]{0} bitcast(f32[4]{0} %exp.1)
}

%body (p: (f32[4])) -> (f32[4]) {
  %p = (f32[4]{0}) parameter(0)
  %fusion.1 = f32[4]{0} fusion((f32[4]{0}) %p), kind=kLoop, calls=%fused_head, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/attn/dot_general"}
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %fusion.1), kind=kLoop, calls=%fused_head, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/checkpoint/rematted_computation/attn/exp"}
  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %fusion.2), kind=kOutput, calls=%fused_head, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ffn/dot_general"}
  %add.4 = f32[4]{0} add(f32[4]{0} %fusion.3, f32[4]{0} %fusion.3), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/add"}
  ROOT %tuple.5 = (f32[4]{0}) tuple(f32[4]{0} %add.4)
}

ENTRY %main.6 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %tuple.6 = (f32[4]{0}) tuple(f32[4]{0} %x)
  %while.7 = (f32[4]{0}) while((f32[4]{0}) %tuple.6), condition=%cond, body=%body, metadata={op_name="jit(train_step)/attn/while"}
  %get-tuple-element.8 = f32[4]{0} get-tuple-element((f32[4]{0}) %while.7), index=0
  %fusion.9 = f32[4]{0} fusion(f32[4]{0} %get-tuple-element.8), kind=kLoop, calls=%fused_head
  ROOT %fusion.10 = f32[4]{0} fusion(f32[4]{0} %fusion.9), kind=kLoop, calls=%fused_head, metadata={op_name="jit(train_step)/transpose(jvp(lm_head))/mul"}
}
"""


def test_instructions_read_opcode_and_op_name():
    ins = scopes.instructions(HLO)
    assert ins["while.7"] == ("while", "jit(train_step)/attn/while")
    assert ins["fusion.3"][0] == "fusion"
    # No metadata of its own: the named instruction nearest its fused
    # computation's root (the root is an unnamed bitcast).
    assert ins["fusion.9"] == ("fusion", "jit(train_step)/transpose(jvp("
                                         "lm_head))/exp")
    assert ins["get-tuple-element.8"] == ("get-tuple-element", None)


def test_scope_shares_by_hand():
    # Window 1002 .. 1100 on the device clock, as in the trace tests.  The
    # while (1003..1060, itself in ``attn``) encloses its body's ops and
    # counts nothing; fusion.10 runs past the window and is cut at 1100.
    host = [(0, 1, trace.MARKER_SPAN), (100, 101, trace.MARKER_SPAN)]
    mods = {"/device:TPU:0": [(1000, 1002, trace.MARKER_MODULE + "(1)"),
                              (1100, 1101, trace.MARKER_MODULE + "(1)")]}
    ops = {"/device:TPU:0": [
        (1003, 1060, "%while.7 = (f32[4]{0}) while(...)"),
        (1005, 1020, "%fusion.1 = f32[4]{0} fusion(...)"),      # attn
        (1020, 1030, "%fusion.2 = f32[4]{0} fusion(...)"),      # attn
        (1030, 1040, "%fusion.3 = f32[4]{0} fusion(...)"),      # ffn
        (1040, 1060, "%add.4 = f32[4]{0} add(...)"),            # none
        (1070, 1090, "%fusion.9 = f32[4]{0} fusion(...)"),      # lm_head
        (1095, 1110, "%fusion.10 = f32[4]{0} fusion(...)"),     # lm_head
    ]}
    res = scopes.scope_shares(host, ops, mods, scopes.instructions(HLO))
    busy = 57 + 20 + 5
    assert res["busy_s"] == pytest.approx(busy * 1e-9)
    assert res["shares"] == pytest.approx({
        "attn": 100 * 25 / busy, "lm_head": 100 * 25 / busy,
        "ffn": 100 * 10 / busy})
    assert sum(res["shares"].values()) <= 100
    assert [n for n, _ in res["top_ops"]["attn"]] == ["fusion.1",
                                                      "fusion.2"]
