"""Loop-aware HLO cost model: validated against XLA's cost_analysis on
loop-free programs, and against known trip counts on scans."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.launch import hlo_cost


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_matches_cost_analysis_on_plain_matmul():
    xs = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = _compile(lambda a, b: a @ b, xs, xs)
    ours = hlo_cost.analyze_module(c.as_text(), 1)
    theirs = c.cost_analysis()
    assert ours.flops == pytest.approx(theirs["flops"], rel=0.01)


def test_scan_flops_multiplied_by_trip_count():
    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)

    def f(x, w):
        return lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
    c = _compile(f, xs, ws)
    ours = hlo_cost.analyze_module(c.as_text(), 1)
    want = 12 * 2 * 128 ** 3
    assert ours.flops == pytest.approx(want, rel=0.05)
    # XLA's own analysis undercounts by the trip count — the reason
    # this module exists:
    assert c.cost_analysis()["flops"] < want / 6


def test_scan_carry_bytes_not_inflated_by_buffer():
    """dus-rooted fusions must count the update, not the whole stacked
    output buffer, per iteration."""
    xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((64, 128, 128), jnp.float32)

    def f(x, w):
        return lax.scan(lambda c, wi: (c @ wi, c.sum()), x, w)
    c = _compile(f, xs, ws)
    ours = hlo_cost.analyze_module(c.as_text(), 1)
    # loose upper bound: per iter ~ 3 x (128x128x4) + eps; 64 iters
    per_iter = 6 * 128 * 128 * 4
    assert ours.bytes < 64 * per_iter * 4


def test_collectives_counted_with_ring_factors():
    hlo = """
HloModule m

ENTRY %main.1 (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %ag = f32[64,64]{1,0} all-gather(%p0), replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %ar = f32[64,64]{1,0} all-reduce(%ag), replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
}
"""
    c = hlo_cost.analyze_module(hlo, 8)
    size = 64 * 64 * 4
    assert c.coll_bytes["all-gather"] == pytest.approx(size * 3 / 4)
    assert c.coll_bytes["all-reduce"] == pytest.approx(2 * size * 3 / 4)
    assert c.coll_ops["all-gather"] == 1


def test_collectives_inside_loops_multiplied():
    hlo = """
HloModule m

%body (t: (s32[], f32[32])) -> (s32[], f32[32]) {
  %t = (s32[], f32[32]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[32] get-tuple-element(%t), index=1
  %ar = f32[32]{0} all-reduce(%x), replica_groups=[1,8]<=[8]
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %r = (s32[], f32[32]) tuple(%ni, %ar)
}

%cond (t: (s32[], f32[32])) -> pred[] {
  %t = (s32[], f32[32]) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main.2 (p: f32[32]) -> (s32[], f32[32]) {
  %p = f32[32]{0} parameter(0)
  %z = s32[] constant(0)
  %t = (s32[], f32[32]) tuple(%z, %p)
  ROOT %w = (s32[], f32[32]) while(%t), condition=%cond, body=%body
}
"""
    c = hlo_cost.analyze_module(hlo, 8)
    assert c.coll_ops["all-reduce"] == 5      # trip count from condition
    assert c.coll_bytes["all-reduce"] == pytest.approx(
        5 * 2 * 32 * 4 * 7 / 8)


def test_transcendentals_and_elementwise():
    xs = jax.ShapeDtypeStruct((1024,), jnp.float32)
    c = _compile(lambda x: jnp.tanh(x) + x * 2, xs)
    ours = hlo_cost.analyze_module(c.as_text(), 1)
    assert ours.transcendentals >= 1024
    assert ours.flops >= 2 * 1024
