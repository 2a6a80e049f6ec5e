"""Compile-only tests of the serving kernels for a described TPU v5e chip.

Interpret mode cannot see what the TPU compiler refuses: unaligned
blocks, shape casts Mosaic cannot lay out, ops it cannot legalize.  So
each test compiles one kernel at stablelm-3b's published widths for a
v5e chip that is described, not attached (no chip is needed, and
nothing runs), and asserts that the compiled program holds the kernel
(``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU
library, and the test runner's workers all import every test file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.qlinear import QuantizedWeight
from repro.kernels.fused_qlinear import fused_qlinear
from repro.kernels.paged_attention import paged_attention

D_MODEL, D_FF = 2560, 6912          # stablelm-3b


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with a mesh over it entered
    per test and the persistent compilation cache off: a compile for a
    described chip is written to the cache but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    mesh = jax.sharding.Mesh(
        np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    yield SingleDeviceSharding(topo.devices[0]), mesh
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(one_chip, fn, *args) -> str:
    sharding, mesh = one_chip
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    with jax.set_mesh(mesh):
        return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n", [4, 64])
@pytest.mark.parametrize("k,m,had_dim,smooth", [
    (D_MODEL, D_FF, 0, False),        # gate/up shape, no rotation
    (D_MODEL, D_MODEL, D_MODEL, False),   # Paley_20 ⊗ H_128
    (D_FF, D_MODEL, D_FF, True),      # down_proj: Paley_108 ⊗ H_64 + smooth
], ids=["no_rotation", "had2560", "had6912_smooth"])
@pytest.mark.parametrize("bits", [4, 8], ids=["w4_packed", "w8"])
def test_fused_qlinear_compiles(one_chip, bits, k, m, had_dim, smooth, n):
    packed = bits == 4
    qw = QuantizedWeight(
        w_q=jax.ShapeDtypeStruct((k // 2 if packed else k, m), jnp.int8),
        scale=jax.ShapeDtypeStruct((1, m), jnp.float32),
        smooth=jax.ShapeDtypeStruct((k,), jnp.float32) if smooth else None,
        bits=bits, packed=packed, had_dim=had_dim)
    x = jax.ShapeDtypeStruct((n, k), jnp.bfloat16)
    assert "tpu_custom_call" in _compiled_text(one_chip, fused_qlinear, x, qw)


@pytest.mark.parametrize("hq,hkv,d", [(32, 32, 80), (32, 8, 128)],
                         ids=["stablelm_mha_d80", "gqa8_d128"])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_attention_compiles(one_chip, kv_dtype, hq, hkv, d):
    b, page, width = 4, 64, 4
    pool = (b * width, page, hkv, d)
    kv = {"k": jax.ShapeDtypeStruct(pool, kv_dtype),
          "v": jax.ShapeDtypeStruct(pool, kv_dtype)}
    if kv_dtype == jnp.int8:
        kv["k_scale"] = jax.ShapeDtypeStruct((*pool[:3], 1), jnp.float32)
        kv["v_scale"] = jax.ShapeDtypeStruct((*pool[:3], 1), jnp.float32)
    text = _compiled_text(
        one_chip, paged_attention,
        jax.ShapeDtypeStruct((b, 1, hq, d), jnp.bfloat16), kv,
        jax.ShapeDtypeStruct((b, width), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    assert "tpu_custom_call" in text
