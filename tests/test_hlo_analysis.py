"""Unit tests for the HLO-text analyzer (the roofline's instrument)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import (
    HloMetrics,
    _is_s2_tensor,
    _type_bytes,
    analyze_hlo,
)
from repro.launch.mesh import make_test_mesh


def _compile(f, *args, in_shardings=None):
    jf = jax.jit(f) if in_shardings is None else jax.jit(
        f, in_shardings=in_shardings)
    return jf.lower(*args).compile()


def test_type_bytes():
    assert _type_bytes("f32[8,64]{1,0}") == 8 * 64 * 4
    assert _type_bytes("bf16[2,3]") == 12
    assert _type_bytes("(s32[], f32[4,4]{1,0})") == 4 + 64
    assert _type_bytes("pred[]") == 1


def test_s2_detection():
    assert _is_s2_tensor("f32[1,32,4096,4096]{3,2,1,0}")
    assert not _is_s2_tensor("f32[4096,128]{1,0}")
    assert not _is_s2_tensor("f32[]")


def test_dot_flops_exact():
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    compiled = _compile(lambda a, b: a @ b, x, w)
    m = analyze_hlo(compiled.as_text())
    assert m.flops == 2 * 32 * 128 * 64


def test_while_trip_count_multiplies():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((8, 32), jnp.float32)
    ws = jax.ShapeDtypeStruct((11, 32, 32), jnp.float32)
    m = analyze_hlo(_compile(f, x, ws).as_text())
    assert 11 in m.while_trips.values()
    assert m.flops == 11 * 2 * 8 * 32 * 32


def test_nested_scan_trips():
    def f(x, ws):
        def outer(c, wpair):
            def inner(ci, w):
                return jnp.tanh(ci @ w), ()
            c, _ = jax.lax.scan(inner, c, wpair)
            return c, ()
        y, _ = jax.lax.scan(outer, x, ws)
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((4, 16), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 3, 16, 16), jnp.float32)  # 5 outer × 3 inner
    m = analyze_hlo(_compile(f, x, ws).as_text())
    assert m.flops == 15 * 2 * 4 * 16 * 16


def test_collective_detection_and_wire():
    mesh = make_test_mesh()
    # single-device: no collectives expected — the parser must return 0
    with jax.set_mesh(mesh):
        compiled = _compile(lambda a: jnp.sum(a),
                            jax.ShapeDtypeStruct((8, 8), jnp.float32))
    m = analyze_hlo(compiled.as_text())
    assert m.collective_bytes == 0


def test_dynamic_slice_not_overcounted():
    """Reading one layer from a stacked (L, d, d) param inside scan must
    charge the SLICE bytes per iteration, not the full stack (the L²
    overcount bug caught during bring-up)."""
    def f(x, ws):
        def body(c, i):
            w = jax.lax.dynamic_index_in_dim(ws, i, 0, keepdims=False)
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, jnp.arange(16))
        return jnp.sum(y)

    x = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((16, 64, 64), jnp.float32)
    m = analyze_hlo(_compile(f, x, ws).as_text())
    stack_bytes = 16 * 64 * 64 * 4
    # total traffic must be well under trips × full-stack (16×) reads
    assert m.hbm_bytes < 0.5 * 16 * stack_bytes, m.hbm_bytes


def test_metrics_scaled_add():
    a = HloMetrics(flops=2.0, hbm_bytes=10.0, s2_bytes=1.0,
                   wire_bytes=4.0, wire_bytes_by_group={4: 4.0})
    b = a.scaled(3)
    assert b.flops == 6.0 and b.wire_bytes_by_group[4] == 12.0
    a.add(b)
    assert a.flops == 8.0 and a.s2_bytes == 4.0
