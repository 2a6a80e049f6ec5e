"""Pallas TPU kernel: integer matmul with fused dual-scale dequant.

Computes Y = (Aq · Wq) ⊙ (Δ_a ⊗ Δ_w) where Aq (n,k) and Wq (k,m) are int8
codes, Δ_a per-token, Δ_w per-output-channel.  Tiles (block_n × block_k)
· (block_k × block_m) through VMEM with an f32←i32 accumulator scratch,
k as the innermost ("arbitrary") grid dimension, and the scale outer
product fused into the epilogue on the last k step — one HBM write of the
bf16 output, no intermediate int32 round-trip.

A packed variant unpacks int4 nibbles (two codes per int8 byte along k)
in VMEM right before the MXU dot, halving Wq HBM traffic — the dominant
serving cost (DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_matmul", "quant_matmul_packed"]


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _round_up(a: int, m: int) -> int:
    return _cdiv(a, m) * m


def _pad2(a: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad a 2-D array up to (rows, cols)."""
    pr, pc = rows - a.shape[0], cols - a.shape[1]
    if pr == 0 and pc == 0:
        return a
    return jnp.pad(a, ((0, pr), (0, pc)))


def _blocks(n: int, m: int, k: int, block_n: int, block_m: int,
            block_k: int) -> tuple[int, int, int]:
    """Hardware-aligned block sizes: pad to tile boundaries instead of the
    old largest-divisor heuristic, which degenerated to divisor-1 (scalar-
    ish grids) for prime/odd dims and for the engine's tiny-n decode rows.
    Sublane/lane minimums: 8 rows, 128 lanes."""
    bn = min(block_n, _round_up(n, 8))
    bm = min(block_m, _round_up(m, 128))
    bk = min(block_k, _round_up(k, 128))
    bk += bk % 2  # packed nibble pairs must not straddle blocks
    return bn, bm, bk


def _qmm_kernel(aq_ref, wq_ref, as_ref, ws_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        aq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * as_ref[...] * ws_ref[...]
        ).astype(o_ref.dtype)


def _unpack_nibbles(packed: jax.Array) -> jax.Array:
    """(bk/2, bm) int8 bytes → (bk, bm) int8 codes, pairs along axis 0.

    The shifts run on int32: Mosaic cannot legalize an int8 ``shli``."""
    w = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(w, 28), 28)  # sign-extended low
    hi = jnp.right_shift(w, 4)
    bk2, bm = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bm).astype(jnp.int8)


def _qmm_packed_kernel(aq_ref, wq_ref, as_ref, ws_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wq = _unpack_nibbles(wq_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        aq_ref[...], wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        o_ref[...] = (
            acc_ref[...].astype(jnp.float32) * as_ref[...] * ws_ref[...]
        ).astype(o_ref.dtype)


def _call(kernel, aq, wq, a_scale, w_scale, *, k: int, m: int, n: int,
          block_n: int, block_m: int, block_k: int, packed: bool,
          out_dtype, interpret: bool):
    k_steps = _cdiv(k, block_k)
    grid = (_cdiv(n, block_n), _cdiv(m, block_m), k_steps)
    wk_block = block_k // 2 if packed else block_k
    return pl.pallas_call(
        functools.partial(kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((wk_block, block_m), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_n, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_m), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_n, block_m), jnp.int32)],
        interpret=interpret,
        name="quant_matmul_packed" if packed else "quant_matmul",
    )(aq, wq, a_scale, w_scale)


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_m", "block_k", "out_dtype", "interpret"),
)
def quant_matmul(aq: jax.Array, wq: jax.Array, a_scale: jax.Array,
                 w_scale: jax.Array, *, block_n: int = 128, block_m: int = 128,
                 block_k: int = 512, out_dtype=jnp.bfloat16,
                 interpret: bool = False) -> jax.Array:
    """Unpacked int8 × int8 → out_dtype.  aq (n,k), wq (k,m)."""
    n, k = aq.shape
    _, m = wq.shape
    bn, bm, bk = _blocks(n, m, k, block_n, block_m, block_k)
    n_p, m_p, k_p = _round_up(n, bn), _round_up(m, bm), _round_up(k, bk)
    y = _call(_qmm_kernel, _pad2(aq, n_p, k_p), _pad2(wq, k_p, m_p),
              _pad2(a_scale, n_p, 1), _pad2(w_scale, 1, m_p),
              k=k_p, m=m_p, n=n_p, block_n=bn, block_m=bm, block_k=bk,
              packed=False, out_dtype=out_dtype, interpret=interpret)
    return y[:n, :m]


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_m", "block_k", "out_dtype", "interpret"),
)
def quant_matmul_packed(aq: jax.Array, wq_packed: jax.Array, a_scale: jax.Array,
                        w_scale: jax.Array, *, block_n: int = 128,
                        block_m: int = 128, block_k: int = 512,
                        out_dtype=jnp.bfloat16, interpret: bool = False) -> jax.Array:
    """int4-packed weights: wq_packed (k/2, m) bytes, k codes along rows.

    Blocks are 128-lane aligned (even), so nibble pairs never straddle a
    block boundary; padded rows are zero bytes = two zero codes.
    """
    n, k = aq.shape
    _, m = wq_packed.shape
    bn, bm, bk = _blocks(n, m, k, block_n, block_m, block_k)
    n_p, m_p, k_p = _round_up(n, bn), _round_up(m, bm), _round_up(k, bk)
    y = _call(_qmm_packed_kernel, _pad2(aq, n_p, k_p),
              _pad2(wq_packed, k_p // 2, m_p), _pad2(a_scale, n_p, 1),
              _pad2(w_scale, 1, m_p), k=k_p, m=m_p, n=n_p, block_n=bn,
              block_m=bm, block_k=bk, packed=True, out_dtype=out_dtype,
              interpret=interpret)
    return y[:n, :m]
