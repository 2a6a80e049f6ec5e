"""Pallas TPU kernel: per-token symmetric RTN quantization.

One HBM pass per activation tile: read a (block_n, d) tile into VMEM,
lane-reduce |x| per row on the VPU, scale, round, clip, emit int8 codes
and f32 per-token scales.  This is the activation-quantization stage of
the W4A4 serving path when the Hadamard transform is folded (no online
rotation needed); otherwise use fused_hadamard_quant.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantizer import qmax

__all__ = ["quantize_per_token"]


def _quantize_kernel(x_ref, q_ref, s_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax) / levels
    q = jnp.clip(jnp.round(x / scale), -levels, levels)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def quantize_per_token(x: jax.Array, *, bits: int = 4, block_n: int = 8,
                       interpret: bool = False):
    """x: (n, d) float → (codes int8 (n, d), scales f32 (n, 1)).

    BlockSpec keeps whole rows in VMEM (per-token absmax is a full-row
    reduction); block_n rows per grid step bounds VMEM at
    block_n × d × (2B in + 1B out) — e.g. 8 × 53248 ≈ 1.2 MiB.
    """
    n, d = x.shape
    n_p = -(-n // block_n) * block_n  # pad ragged/tiny-n (decode) row counts
    if n_p != n:
        x = jnp.pad(x, ((0, n_p - n), (0, 0)))
    grid = (n_p // block_n,)
    q, s = pl.pallas_call(
        functools.partial(_quantize_kernel, levels=qmax(bits)),
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, d), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_p, d), jnp.int8),
            jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
        ],
        interpret=interpret,
        name="quantize_per_token",
    )(x)
    return q[:n], s[:n]
