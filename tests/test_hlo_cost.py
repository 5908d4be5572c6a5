"""Unit tests for the trip-count-aware HLO cost walker (the §Roofline
measurement instrument — these encode the caveats it exists to fix)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo_cost import HloCost, parse_computations

HLO_WHILE = """
HloModule t
%wrapped_compare_computation (a: s32[], b: s32[]) -> pred[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %c = pred[] compare(%a, %b), direction=LT
}
%body.1 (arg: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %arg = (s32[], f32[64,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[64,64]{1,0} get-tuple-element(%arg), index=1
  %d = f32[64,64]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[64,64]{1,0} all-reduce(%d), to_apply=%wrapped_compare_computation
  ROOT %t = (s32[], f32[64,64]{1,0}) tuple(%i, %ar)
}
%cond.2 (arg: (s32[], f32[64,64])) -> pred[] {
  %arg = (s32[], f32[64,64]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}
ENTRY %main.3 (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[64,64]{1,0}) tuple(%z, %p)
  %w = (s32[], f32[64,64]{1,0}) while(%tup), condition=%cond.2, body=%body.1
  ROOT %r = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_scaling_flops_and_collectives():
    t = HloCost(HLO_WHILE).totals()
    assert t["flops"] == pytest.approx(5 * 2 * 64**3)
    assert t["all-reduce"] == 5 * 64 * 64 * 4
    assert t["coll_total"] == t["all-reduce"]


def test_tuple_types_with_index_comments_parse():
    """/*index=N*/ comments inside tuple types contain '=' and broke the
    first parser (every while was silently skipped)."""
    hlo = """
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %w = (s32[], bf16[2,3]{1,0}, /*index=2*/f32[4]{0}) while(%p), condition=%c, body=%b
  ROOT %r = f32[4]{0} get-tuple-element(%w), index=2
}
"""
    comps = parse_computations(hlo)
    ops = [i.op for i in comps["main.1"].instrs]
    assert "while" in ops


def test_matches_compiled_scan_exactly():
    def body(x, w):
        return jnp.tanh(x @ w), None

    def f(x, ws):
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    t = HloCost(compiled.as_text()).totals()
    assert t["flops"] == pytest.approx(7 * 2 * 64**3, rel=0.01)
    # raw cost_analysis counts ONE iteration — the caveat this walker fixes
    raw = compiled.cost_analysis()["flops"]
    assert raw == pytest.approx(2 * 64**3, rel=0.01)


def test_dynamic_slice_counts_slice_not_operand():
    hlo = """
ENTRY %main.1 (p: f32[100,64], i: s32[]) -> f32[1,64] {
  %p = f32[100,64]{1,0} parameter(0)
  %i = s32[] parameter(1)
  ROOT %ds = f32[1,64]{0,1} dynamic-slice(%p, %i), dynamic_slice_sizes={1,64}
}
"""
    t = HloCost(hlo).totals()
    assert t["bytes"] == 2 * 1 * 64 * 4  # 2×slice, not the 100×64 operand
