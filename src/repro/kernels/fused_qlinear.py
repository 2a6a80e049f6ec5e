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

The tiles come from the call's shape (``_tiles``): a decode or verify
call is ONE row block, so each weight tile is fetched from HBM and
unpacked once per call, and the weight tiles divide the padded dims,
so the weight is never padded per call.  Rows pad up to whole 8-row
sublanes; padded rows quantize to zero codes (absmax 0 → Δ = 1) and
are sliced off the output.
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
from repro.kernels.quant_matmul import _cdiv, _pad2, _round_up, _unpack_nibbles

__all__ = ["fused_qlinear"]

# Indirection so the dispatch-count tests can assert "one kernel launch
# per qlinear" by wrapping it (fused_qlinear is deliberately NOT wrapped
# in a module-level jax.jit: callers jit the surrounding model step).
_pallas_call = pl.pallas_call

# --- tiling (docs/kernels.md "Tiling") ---------------------------------
# One row block holds every row of a decode or verify call, so each
# weight tile is fetched from HBM and unpacked once per call; tiles
# divide the padded dims, so the weight is never padded per call.
ROW_CAP = 512                # rows per block: a 4 x 256 prefill is 2 blocks
COL_CAP = 512                # output lanes per block: keeps the (bn, bm)
                             # accumulator and output small at ROW_CAP rows
SCOPED_VMEM = 16 << 20       # v5e's default scoped VMEM: no limit below it
VMEM_CEILING = 96 << 20      # the most a call may ask of v5e's 128 MiB
ACT_VMEM = 28 << 20          # the (bn, k) activation tile's share
WEIGHT_VMEM = 8 << 20        # the (bk, bm) weight tile's share
# Scoped VMEM per element, bounding what Mosaic allocates on v5e
# (docs/kernels.md has the measurements): a packed int4 weight element
# takes its DMA buffers plus the unpack's int32 temporaries, an int8
# one only its buffers; an activation element its buffers, its int8
# code and the quantize step's f32 temporaries (most without rotation).
_W_BYTES = {True: 6, False: 2.5}
_X_BYTES = 8


def _aligned_divisors(total: int, unit: int) -> list[int]:
    """Multiples of ``unit`` that divide ``total`` (itself a multiple)."""
    q = total // unit
    return [unit * d for d in range(1, q + 1) if q % d == 0]


def _tiles(n: int, k: int, m: int, packed: bool, group: int
           ) -> tuple[int, int, int]:
    """(bn, bm, bk) for an (n, k) x (k, m) call.

    bk is a multiple of ``group`` dividing round_up(k, group), bm a
    multiple of 128 dividing round_up(m, 128) and at most ``COL_CAP``;
    of those, the pair with the most weight elements whose working set
    fits ``WEIGHT_VMEM`` (ties to the longer bk: fewer accumulator
    passes).  bn holds all n rows up to ``ROW_CAP`` (fewer where the
    activation tile would outgrow ``ACT_VMEM``), split evenly over as
    few blocks as that allows."""
    k_p, m_p = _round_up(k, group), _round_up(m, 128)
    fits = [(bk * bm, bk, bm)
            for bk in _aligned_divisors(k_p, group)
            for bm in _aligned_divisors(m_p, 128)
            if bm <= COL_CAP and bk * bm * _W_BYTES[packed] <= WEIGHT_VMEM]
    _, bk, bm = max(fits, default=(0, group, 128))
    cap = max(8, min(ROW_CAP, ACT_VMEM // (k_p * _X_BYTES) // 8 * 8))
    bn = _round_up(_cdiv(n, _cdiv(n, cap)), 8)
    return bn, bm, bk


def _vmem_bytes(bn: int, bm: int, bk: int, k_p: int, packed: bool,
                group: int) -> int:
    """VMEM working set of one grid step, every buffer and temporary
    counted at its widest (f32 activations and outputs)."""
    rows = bn * k_p * _X_BYTES                   # activation tile + codes
    weight = int(bk * bm * _W_BYTES[packed])     # weight tile + unpack
    out = bn * bm * (2 * 4 + 4 + 4)              # out buffers, acc, dot
    small = (2 * group * group * 4               # H_b
             + 2 * 8 * k_p * 4                   # smooth row (8 sublanes)
             + 2 * 8 * bm * 4                    # weight scales
             + bn * 128 * 4)                     # per-token scales
    return rows + weight + out + small


def _vmem_limit(nbytes: int) -> int | None:
    """Scoped-VMEM limit for a step that needs ``nbytes``: the default
    where it fits, else the footprint with a quarter spare."""
    if nbytes <= SCOPED_VMEM:
        return None
    return min(VMEM_CEILING, _round_up(nbytes * 5 // 4, 1 << 20))


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
                  interpret: bool = False, block_n: int | None = None,
                  block_m: int | None = None,
                  block_k: int | None = None) -> jax.Array:
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

    # --- tiling: derived from the call's shape (docs/kernels.md) ---
    # the rotation works on rows of ``group`` lanes: H_b itself, or the
    # block-diagonal kron(I_{128/b}, H_b) for factors under a vreg width
    group = max(block, 128)                     # block | group (powers of 2)
    m_ = qw.c_out
    bn, bm, bk = _tiles(n, k, m_, qw.packed, group)
    bn = bn if block_n is None else min(block_n, _round_up(n, 8))
    bm = bm if block_m is None else min(block_m, _round_up(m_, 128))
    # bk must stay a multiple of group: Hadamard groups may not straddle
    # the padded region, and packed nibble pairs may not straddle blocks
    # (group is even) — guards caller-overridden odd/unaligned block_k
    if block_k is not None:
        bk = _round_up(min(block_k, _round_up(k, group)), group)
    n_p, m_p, k_p = _round_up(n, bn), _round_up(m_, bm), _round_up(k, bk)
    vmem = _vmem_bytes(bn, bm, bk, k_p, qw.packed, group)

    x_p = _pad2(x, n_p, k_p)
    w_p = _pad2(qw.w_q, k_p // 2 if qw.packed else k_p, m_p)
    ws_p = _pad2(qw.scale, 1, m_p)
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
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=interpret,
        name="fused_qlinear",
    )(*inputs)
    return y[:n, :m_]
