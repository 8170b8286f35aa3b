"""HLO cost walker: exactness on loop-free modules, trip-count awareness,
fusion-group byte model sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hlo_cost as HC


def compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_exact_loop_free():
    a = jnp.ones((256, 512))
    b = jnp.ones((512, 1024))
    c = jnp.ones((1024, 128))
    w = HC.module_cost(compile_text(lambda a, b, c: jnp.tanh(a @ b) @ c, a, b, c))
    assert w.dot_flops == 2 * 256 * 512 * 1024 + 2 * 256 * 1024 * 128


def test_scan_multiplies_by_trip_count():
    def g(x, ws):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, x, ws)
        return x

    x = jnp.ones((128, 128))
    ws = jnp.ones((10, 128, 128))
    w = HC.module_cost(compile_text(g, x, ws))
    assert w.dot_flops == 10 * 2 * 128 ** 3
    ca = jax.jit(g).lower(x, ws).compile().cost_analysis()
    assert ca["flops"] < w.dot_flops / 5  # cost_analysis is loop-blind


def test_nested_scan_trip_counts():
    def g(x, ws):
        def outer(x, wi):
            def inner(x, _):
                return jnp.tanh(x @ wi), None
            x, _ = jax.lax.scan(inner, x, None, length=3)
            return x, None
        x, _ = jax.lax.scan(outer, x, ws)
        return x

    x = jnp.ones((64, 64))
    ws = jnp.ones((5, 64, 64))
    w = HC.module_cost(compile_text(g, x, ws))
    assert w.dot_flops == 5 * 3 * 2 * 64 ** 3


def test_fusion_group_bytes_below_unfused_sum():
    """A long elementwise chain must be billed ~ inputs + outputs, not per op
    (the Eq. (1) fusion-group model applied to HLO)."""
    def chain(x):
        for _ in range(12):
            x = jnp.tanh(x) * 1.01 + 0.1
        return x

    x = jnp.ones((1024, 1024))
    w = HC.module_cost(compile_text(chain, x))
    nbytes = 1024 * 1024 * 4
    # unfused accounting would be >= 24x; grouped must stay within ~6x
    assert w.bytes <= 6 * nbytes, w.bytes


def test_bytes_scale_with_scan_length():
    def g(ws):
        def body(x, wi):
            return jnp.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, jnp.ones((64, 64)), ws)
        return x

    w5 = HC.module_cost(compile_text(g, jnp.ones((5, 64, 64))))
    w10 = HC.module_cost(compile_text(g, jnp.ones((10, 64, 64))))
    assert w10.bytes > 1.5 * w5.bytes


def test_collective_parse_from_synthetic_hlo():
    hlo = """
HloModule m

ENTRY %main (p0: f32[16,128]) -> f32[16,128] {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0), replica_groups={}, to_apply=%add
  %ag = f32[32,128]{1,0} all-gather(%ar), dimensions={0}
  ROOT %out = f32[16,128]{1,0} slice(%ag), slice={[0:16], [0:128]}
}
"""
    w = HC.module_cost(hlo)
    assert w.coll["all-reduce"] == 16 * 128 * 4
    assert w.coll["all-gather"] == 32 * 128 * 4


def test_shape_parser():
    assert HC._total_bytes("bf16[4,8]{1,0}") == 64
    assert HC._total_bytes("(f32[2,2], s8[4])") == 20
    assert HC._total_bytes("f32[]") == 4
    assert HC._dims_of("f32[3,5,7]{2,1,0}") == [3, 5, 7]
