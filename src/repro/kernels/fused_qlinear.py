"""Pallas TPU kernel: the ONE-pass fused quantized linear (decode hot path).

The paper's hybrid pipeline (smoothing before rotation, Eq. 4) puts a

    x / s  →  online Hadamard  →  per-token RTN quantize  →  int matmul
    →  (Δ_a ⊗ Δ_w) dequant

chain on every quantized linear at serving time.  The staged kernels
(`hadamard_kernel.py` + `quant_matmul.py`) cost THREE activation HBM
round trips per linear: the XLA pre-rotation writes x', the fused
hadamard-quant kernel re-reads x' and writes int8 codes + scales, and
the quant-matmul kernel re-reads the codes.  This kernel collapses the
whole chain into ONE ``pallas_call``:

  * the activation tile (block_n, k) is read from HBM ONCE per row
    block — its BlockSpec index is constant over the m/k grid axes, so
    the pipeline never refetches it;
  * smooth-divide, the trailing power-of-two Hadamard factor H_b (held
    in VMEM, applied as an MXU matmul over contiguous b-groups; a factor
    under 128 — down_proj's 6912 = Paley_108 ⊗ H_64 — is applied as
    kron(I_{128/b}, H_b) on 128-lane groups, which keeps every row a
    whole vreg wide), and the per-token absmax quantize
    run on the first visit of each row block, writing int8 codes and
    f32 scales into VMEM *scratch* — never to HBM;
  * a traced ``had_mask`` scalar gates the rotation IN-KERNEL, so mixed
    layerwise autoplan stacks (rotated and un-rotated layers sharing
    one scanned QuantizedWeight) stay on the fused path;
  * the int8 (or int4-nibble-packed) weight streams through VMEM in
    (block_k, block_m) tiles accumulating into an f32←i32 scratch, and
    the dual-scale dequant epilogue writes the bf16 output ONCE.

Kronecker dims whose rotation has leading factors (e.g. 4096 = H_512 ⊗
H_8, 1536 = Paley_12 ⊗ H_128) keep those factors as XLA matmuls before
the kernel — smoothing must precede them, so it moves to XLA too — and
fuse the trailing power-of-two factor; pure-Paley trailing factors
(e.g. d = 12) rotate fully in XLA and fuse quantize + matmul.  Either
way there is exactly ONE ``pallas_call`` per quantized linear
(docs/kernels.md has the full accounting table).

Decode-shaped inputs — the serving engine's ``(max_slots, 1)`` tick
flattens to n = max_slots rows — are padded up to one (8, k) tile
instead of degrading to divisor-1 blocks; padded rows quantize to zero
codes (absmax 0 → Δ = 1) and are sliced off the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hadamard import (
    apply_hadamard,
    kernel_fusable_factor,
    plan_hadamard,
)
from repro.core.qlinear import QuantizedWeight
from repro.core.quantizer import qmax
from repro.kernels.hadamard_kernel import vmem_rotation_factor
from repro.kernels.quant_matmul import _round_up, _unpack_nibbles

__all__ = ["fused_qlinear"]

# Indirection so the dispatch-count tests can assert "one kernel launch
# per qlinear" by wrapping it (fused_qlinear is deliberately NOT wrapped
# in a module-level jax.jit: callers jit the surrounding model step).
_pallas_call = pl.pallas_call


def _kernel(*refs, k_steps: int, levels: int, group: int, block_k: int,
            packed: bool, has_smooth: bool, has_had: bool, has_mask: bool):
    it = iter(refs)
    x_ref = next(it)
    s_ref = next(it) if has_smooth else None
    h_ref = next(it) if has_had else None
    hm_ref = next(it) if has_mask else None
    w_ref, ws_ref, o_ref = next(it), next(it), next(it)
    acc_ref, xq_ref, xs_ref = next(it), next(it), next(it)

    j, kk = pl.program_id(1), pl.program_id(2)

    @pl.when((j == 0) & (kk == 0))
    def _transform_quantize():
        # first visit of this row block: smooth → H_b → quantize, codes
        # and scales land in VMEM scratch and are reused by every (j, kk)
        x = x_ref[...].astype(jnp.float32)              # (bn, k)
        if has_smooth:
            x = x / s_ref[...]                          # (1, k) broadcast
        if has_had:
            # H_b acts on ``group``-lane rows (kron(I, H_b) when b < 128)
            bn, k = x.shape
            xr = x.reshape(bn * (k // group), group)
            xt = jax.lax.dot_general(
                xr, h_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).reshape(bn, k)
            # had_mask multiplexes rotated/un-rotated layers of a mixed
            # layerwise stack without leaving the fused path
            x = jnp.where(hm_ref[0, 0] > 0, xt, x) if has_mask else xt
        absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.where(absmax == 0, 1.0, absmax) / levels
        xq_ref[...] = jnp.clip(jnp.round(x / scale), -levels, levels
                               ).astype(jnp.int8)
        xs_ref[...] = scale.astype(jnp.float32)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    aq = xq_ref[:, pl.ds(kk * block_k, block_k)]
    wq = _unpack_nibbles(w_ref[...]) if packed else w_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        aq, wq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    @pl.when(kk == k_steps - 1)
    def _epilogue():
        o_ref[...] = (acc_ref[...].astype(jnp.float32) * xs_ref[...]
                      * ws_ref[...]).astype(o_ref.dtype)


def fused_qlinear(x: jax.Array, qw: QuantizedWeight, *, act_bits: int = 4,
                  interpret: bool = False, block_n: int = 8,
                  block_m: int = 256, block_k: int = 512) -> jax.Array:
    """[smooth] → [online Hadamard] → quantize → int matmul → dequant,
    ONE ``pallas_call``.  x: (n, c_in) float → (n, c_out) in x.dtype.

    Numerics match ``qlinear``'s XLA path (same full rotation, same
    int32 accumulation); ``ref.fused_qlinear_ref`` is the oracle.
    """
    n, k = x.shape
    if k != qw.c_in:
        raise ValueError(f"x has {k} channels, weight expects {qw.c_in}")
    out_dtype = x.dtype
    smooth, had_mask = qw.smooth, qw.had_mask
    last = kernel_fusable_factor(qw.had_dim) if qw.had_dim else 0

    if qw.had_dim and last < 2:
        # pure-Paley trailing factor: the rotation has no contiguous
        # power-of-two group structure — smooth + full rotation in XLA,
        # quantize + matmul fuse (2 HBM round trips instead of 3)
        if smooth is not None:
            x = x / smooth.astype(x.dtype)
        xr = apply_hadamard(x, qw.had_dim)
        x = xr if had_mask is None else jnp.where(had_mask > 0, xr, x)
        smooth = had_mask = None
        block = 0
    elif qw.had_dim and len(plan_hadamard(qw.had_dim).factors) > 1:
        # multi-factor Kronecker: leading factors (and smoothing, which
        # must precede them) run in XLA; the trailing power-of-two
        # factor fuses.  The mask gates BOTH stages consistently: an
        # un-rotated layer feeds the raw (smoothed) x through and the
        # kernel skips H_b for it via the same scalar.
        if smooth is not None:
            x = x / smooth.astype(x.dtype)
        xpre = apply_hadamard(x, qw.had_dim, skip_last=True)
        x = xpre if had_mask is None else jnp.where(had_mask > 0, xpre, x)
        smooth = None
        block = last
    else:
        block = last  # 0 (no rotation) or the single fully-fused factor

    has_smooth = smooth is not None
    has_had = block >= 2
    has_mask = has_had and had_mask is not None
    levels = qmax(act_bits)

    # --- tiling: pad to tile boundaries instead of degenerate divisors ---
    # the rotation works on rows of ``group`` lanes: H_b itself, or the
    # block-diagonal kron(I_{128/b}, H_b) for factors under a vreg width
    group = max(block, 128)                     # block | group (powers of 2)
    bn = min(block_n, _round_up(n, 8))
    bm = min(block_m, _round_up(m_ := qw.c_out, 128))
    # bk must stay a multiple of group: Hadamard groups may not straddle
    # the padded region, and packed nibble pairs may not straddle blocks
    # (group is even) — guards caller-overridden odd/unaligned block_k
    bk = _round_up(min(block_k, _round_up(k, group)), group)
    n_p, m_p, k_p = _round_up(n, bn), _round_up(m_, bm), _round_up(k, bk)

    x_p = jnp.pad(x, ((0, n_p - n), (0, k_p - k)))
    row_pad = (k_p - k) // 2 if qw.packed else k_p - k
    w_p = jnp.pad(qw.w_q, ((0, row_pad), (0, m_p - m_)))
    ws_p = jnp.pad(qw.scale, ((0, 0), (0, m_p - m_)))

    inputs = [x_p]
    in_specs = [pl.BlockSpec((bn, k_p), lambda i, j, kk: (i, 0))]
    if has_smooth:
        s_p = jnp.pad(smooth.astype(jnp.float32).reshape(1, k),
                      ((0, 0), (0, k_p - k)), constant_values=1.0)
        inputs.append(s_p)
        in_specs.append(pl.BlockSpec((1, k_p), lambda i, j, kk: (0, 0)))
    if has_had:
        inputs.append(vmem_rotation_factor(block, group))
        in_specs.append(pl.BlockSpec((group, group), lambda i, j, kk: (0, 0)))
    if has_mask:
        inputs.append(jnp.asarray(had_mask, jnp.float32).reshape(1, 1))
        in_specs.append(pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)))
    wblk = bk // 2 if qw.packed else bk
    inputs += [w_p, ws_p]
    in_specs += [pl.BlockSpec((wblk, bm), lambda i, j, kk: (kk, j)),
                 pl.BlockSpec((1, bm), lambda i, j, kk: (0, j))]

    y = _pallas_call(
        functools.partial(
            _kernel, k_steps=k_p // bk, levels=levels, group=group,
            block_k=bk, packed=qw.packed, has_smooth=has_smooth,
            has_had=has_had, has_mask=has_mask),
        grid=(n_p // bn, m_p // bm, k_p // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_p, m_p), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bn, bm), jnp.int32),     # f32←i32 accumulator
            pltpu.VMEM((bn, k_p), jnp.int8),     # per-row int8 codes
            pltpu.VMEM((bn, 1), jnp.float32),    # per-token Δ_a
        ],
        interpret=interpret,
        name="fused_qlinear",
    )(*inputs)
    return y[:n, :m_]
