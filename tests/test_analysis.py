"""Tests for the loop-aware HLO cost analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_cost import analyze, parse_module


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


class TestHloCost:
    def test_dot_flops_exact(self):
        a = jax.ShapeDtypeStruct((64, 128), np.float32)
        b = jax.ShapeDtypeStruct((128, 32), np.float32)
        txt = _compile(lambda x, y: x @ y, a, b)
        c = analyze(txt)
        # 2*M*N*K
        assert c.flops == pytest.approx(2 * 64 * 32 * 128, rel=0.05)

    def test_scan_trip_count_multiplies(self):
        a = jax.ShapeDtypeStruct((64, 64), np.float32)

        def f(x):
            def body(c, _):
                return c @ c, None
            out, _ = jax.lax.scan(body, x, None, length=10)
            return out

        c = analyze(_compile(f, a))
        one = 2 * 64 * 64 * 64
        assert c.flops == pytest.approx(10 * one, rel=0.2), c.flops

    def test_bytes_scale_with_tensor_size(self):
        small = jax.ShapeDtypeStruct((64, 64), np.float32)
        big = jax.ShapeDtypeStruct((512, 512), np.float32)
        f = lambda x: jnp.tanh(x) * 2 + 1
        cs = analyze(_compile(f, small))
        cb = analyze(_compile(f, big))
        assert cb.bytes_accessed > 30 * cs.bytes_accessed

    def test_parse_module_structure(self):
        a = jax.ShapeDtypeStruct((32, 32), np.float32)
        comps, entry = parse_module(_compile(lambda x: (x @ x).sum(), a))
        assert entry is not None
        assert entry in comps


# Optimized HLO names a collective's operands without their shapes, so a
# collective counts the bytes of its per-device result.
_SUM = """%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}
"""
_GROUPS = "channel_id=1, replica_groups={{0,1,2,3}}"


def _entry(param: str, *lines: str) -> str:
    body = "\n".join("  " + ln for ln in lines)
    return (f"HloModule m\n\n{_SUM}\n"
            f"ENTRY %main (p0: {param}) -> f32[] {{\n"
            f"  %p0 = {param}{{1,0}} parameter(0)\n{body}\n}}\n")


_WHILE = _SUM + """
%body (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %arg = (s32[], f32[8,128]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,128]{1,0} get-tuple-element(%arg), index=1
  %ar = f32[8,128]{1,0} all-reduce(%x), """ + _GROUPS + """, to_apply=%sum
  ROOT %t = (s32[], f32[8,128]{1,0}) tuple(%i, %ar)
}

%cond (arg: (s32[], f32[8,128])) -> pred[] {
  %arg = (s32[], f32[8,128]{1,0}) parameter(0)
  ROOT %go = pred[] constant(true)
}

ENTRY %main (p0: f32[8,128]) -> (s32[], f32[8,128]) {
  %p0 = f32[8,128]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,128]{1,0}) tuple(%zero, %p0)
  ROOT %w = (s32[], f32[8,128]{1,0}) while(%init), condition=%cond, \
body=%body, backend_config={"known_trip_count":{"n":"6"}}
}
"""

_COLLECTIVE_CASES = {
    "all-gather": (_entry(
        "bf16[8,128]", "ROOT %ag = bf16[32,128]{1,0} all-gather(%p0), "
        + _GROUPS + ", dimensions={0}"), {"all-gather": 32 * 128 * 2}),
    "all-reduce": (_entry(
        "f32[8,128]", "ROOT %ar = f32[8,128]{1,0} all-reduce(%p0), "
        + _GROUPS + ", to_apply=%sum"), {"all-reduce": 8 * 128 * 4}),
    "reduce-scatter": (_entry(
        "f32[8,128]", "ROOT %rs = f32[2,128]{1,0} reduce-scatter(%p0), "
        + _GROUPS + ", dimensions={0}, to_apply=%sum"),
        {"reduce-scatter": 2 * 128 * 4}),
    "all-to-all": (_entry(
        "f32[8,128]", "ROOT %a2a = f32[8,128]{1,0} all-to-all(%p0), "
        + _GROUPS + ", dimensions={0}"), {"all-to-all": 8 * 128 * 4}),
    "collective-permute": (_entry(
        "s32[8,128]", "ROOT %cp = s32[8,128]{1,0} collective-permute(%p0), "
        "channel_id=1, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}"),
        {"collective-permute": 8 * 128 * 4}),
    # The -start of an async pair carries the transfer; its -done does not.
    "async-pair": (_entry(
        "f32[8,128]",
        "%ars = f32[8,128]{1,0} all-reduce-start(%p0), " + _GROUPS
        + ", to_apply=%sum",
        "ROOT %ard = f32[8,128]{1,0} all-reduce-done(%ars)"),
        {"all-reduce": 8 * 128 * 4}),
    # A collective in a while body runs once per trip.
    "while-body": (_WHILE, {"all-reduce": 6 * 8 * 128 * 4}),
}


@pytest.mark.parametrize("case", list(_COLLECTIVE_CASES))
def test_collective_bytes_by_kind(case):
    text, want = _COLLECTIVE_CASES[case]
    cost = analyze(text)
    assert cost.coll_by_kind == want
    assert cost.collective_bytes == sum(want.values())
