"""Pallas TPU kernel: fused online Hadamard transform + per-token quantize.

The serving path's down_proj/o_proj inputs need an *online* rotation
(QuaRot-style) before quantization.  GPU implementations use warp-level
FWHT butterflies; the TPU-native formulation (DESIGN.md §3) applies the
power-of-two Hadamard factor H_b as a dense (b × b) matmul on the MXU —
each (block_n, d) activation tile is reshaped to (block_n · d/b, b),
multiplied by H_b/√b held in VMEM, per-token |·|-reduced, scaled, rounded
and written as int8 codes — transform + quantize in ONE HBM round-trip
instead of two.

The grouped (block-diagonal) transform with b ≤ 512 is exactly the
rotation the serving fold applies on the weight side (see
serving/fold.py), so numerical equivalence holds end-to-end.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.hadamard import sylvester
from repro.core.quantizer import qmax

__all__ = ["fused_hadamard_quant", "vmem_rotation_factor"]


def vmem_rotation_factor(block: int, lanes: int | None = None) -> jax.Array:
    """H_block/√block as f32 — the VMEM-resident trailing rotation factor
    shared by this kernel and the one-pass ``fused_qlinear``.

    ``lanes`` (a multiple of ``block``) returns the block-diagonal
    kron(I_{lanes/block}, H_block/√block) instead: the same rotation on
    ``lanes``-wide groups, so a kernel whose factor is narrower than the
    128-lane vreg keeps its rows 128 lanes wide (Mosaic rejects the
    lane-changing reshape to ``block`` columns)."""
    h = sylvester(block).astype(np.float32) / math.sqrt(block)
    if lanes is not None and lanes != block:
        h = np.kron(np.eye(lanes // block, dtype=np.float32), h)
    return jnp.asarray(h)


def _fhq_kernel(x_ref, h_ref, q_ref, s_ref, *, levels: int, block: int):
    bn, d = x_ref.shape
    x = x_ref[...].astype(jnp.float32)
    h = h_ref[...]                       # (block, block) = H/√b in VMEM
    xr = x.reshape(bn * (d // block), block)
    xt = jax.lax.dot_general(
        xr, h, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).reshape(bn, d)
    absmax = jnp.max(jnp.abs(xt), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax) / levels
    q = jnp.clip(jnp.round(xt / scale), -levels, levels)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("block", "bits", "block_n", "interpret")
)
def fused_hadamard_quant(x: jax.Array, *, block: int = 128, bits: int = 4,
                         block_n: int = 8, interpret: bool = False):
    """x: (n, d) float, block | d, block = 2^p ≤ 512.

    Returns (codes int8 (n, d), per-token scales f32 (n, 1)).
    VMEM: block_n·d·4 (f32 tile) + block²·4 (H) + block_n·d (codes)
    — e.g. 8 × 16384 × 4 + 128² × 4 ≈ 0.6 MiB.
    """
    n, d = x.shape
    if d % block or block & (block - 1):
        raise ValueError(f"block {block} must be a power of two dividing d={d}")
    n_p = -(-n // block_n) * block_n  # pad ragged/tiny-n (decode) row counts
    if n_p != n:
        x = jnp.pad(x, ((0, n_p - n), (0, 0)))
    h = vmem_rotation_factor(block)
    grid = (n_p // block_n,)
    q, s = pl.pallas_call(
        functools.partial(_fhq_kernel, levels=qmax(bits), block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block, block), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_p, d), jnp.int8),
            jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
        ],
        interpret=interpret,
        name="fused_hadamard_quant",
    )(x, h)
    return q[:n], s[:n]
