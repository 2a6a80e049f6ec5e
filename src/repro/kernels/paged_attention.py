"""Pallas TPU kernel: paged-attention GQA decode over the shared page pool.

The paged serving engine (docs/serving.md) backs attention KV with
fixed-size pages from a shared ``(L, n_pages, page, hkv, d)`` pool,
indexed by a per-slot page table.  The XLA decode path materializes each
slot's pages into a contiguous ``(b, width·page, hkv, d)`` view per
layer (``common.paged_view`` — an HBM gather) and re-reads that view in
attention: every cached byte crosses HBM **three** times per layer per
tick (pool read → contiguous write → attention read), and int8 pools
additionally inflate the intermediate to bf16.

This kernel indexes pages **in-VMEM** instead.  The page table and the
per-slot length vector ride in as scalar-prefetch operands
(``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps — which
run *before* the kernel body and drive the pipeline's DMAs — can look up
``table[slot, j]`` and fetch exactly that physical pool page into VMEM.
No contiguous view ever exists:

  * grid ``(b, hkv, width)``: each (slot, kv-head) pair walks its
    logical pages in order, carrying an online-softmax (max, sum, acc)
    accumulator in VMEM scratch — FlashAttention-style, the
    ``(g, width·page)`` probability row never materializes;
  * per-page **length-prefix masking**: positions ``≥ lengths[slot]``
    score ``-1e30`` (the engine allocates pages contiguously, so page
    validity ≡ the length prefix — same contract as ``paged_view``);
    pages wholly past the prefix (or unassigned, table ``-1``) are
    skipped via ``pl.when``;
  * int8 pools dequantize **in-kernel** from the paged scale leaves
    (``(n_pages, page, hkv, 1)`` f32, fetched through the same table
    indirection), mirroring ``paged_view``'s
    ``(codes·scale) → bf16`` numerics bit for bit;
  * the GQA group's ``(g, d)`` query block is resident across the page
    walk (its block index is constant over ``j``), and the output block
    is written once on the final page.

HBM traffic per layer per tick drops from
``3 × (pool bytes) [+ bf16 inflation]`` to ``1 × (pool bytes)`` —
``benchmarks/kernel_bench.py`` carries the exact accounting and the
TPU-v5e roofline model; docs/paged_attention.md has the design note.

Numerics: online softmax in f32 (running max/sum), matching
``attention_scores``'s masked-softmax reference to f32 reassociation
(greedy decode is token-identical in practice —
tests/test_serving_paged.py pins it engine-to-engine).  The serving
engines reach this kernel through ``ops.resolve_backend`` /
``common.paged_attn_backend``: ``auto`` → compiled on TPU hosts,
``interpret`` → the Pallas interpreter (CPU CI), ``never``/ineligible →
the XLA ``paged_view`` gather path as the parity fallback.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention"]

# Indirection so dispatch-count tests can assert "one kernel launch per
# layer" by wrapping it (mirrors kernels/fused_qlinear.py; deliberately
# NOT jitted at module level — callers jit the surrounding decode step).
_pallas_call = pl.pallas_call


def _kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, *refs, page: int,
            quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        # -1e30 (not -inf): matches the reference mask value and keeps
        # exp(m - m_new) finite when a row's first page is fully masked
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    valid = len_ref[i]
    live = (tab_ref[i, j] >= 0) & (j * page < valid)

    @pl.when(live)
    def _page_step():
        # one logical page of this slot, every kv head: blocks were DMA'd
        # by the table-driven index maps, so k/v arrive already "gathered"
        k = k_ref[0]                               # (page, hkv, d)
        v = v_ref[0]
        if quantized:
            # in-kernel dequant from the paged scale leaves — identical
            # staging to paged_view: (int8 · f32 scale) → bf16 → f32
            k = (k.astype(jnp.float32) * ks_ref[0]).astype(jnp.bfloat16)
            v = (v.astype(jnp.float32) * vs_ref[0]).astype(jnp.bfloat16)
        q = q_ref[0].astype(jnp.float32)           # (hkv, g, d)
        d = q.shape[-1]
        # the GQA groups reduce against their kv head in one batched dot
        s = jnp.einsum("hgd,phd->hgp", q, k.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * (d ** -0.5)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < valid, s, -1e30)
        # online-softmax update (running max / sum / weighted accumulator)
        m_new = jnp.maximum(m_ref[...], s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_ref[...] - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.einsum(
            "hgp,phd->hgd", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _epilogue():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention(q: jax.Array, layer_kv: dict, page_table: jax.Array,
                    lengths: jax.Array, *, interpret: bool = False
                    ) -> jax.Array:
    """GQA decode attention over one layer's paged KV pool, in-VMEM.

    q: ``(b, 1, hq, d)`` decode queries (one new token per slot).
    layer_kv: dict(k, v[, k_scale, v_scale]) with POOL shapes
    ``(n_pages, page, hkv, d)`` (scales ``(n_pages, page, hkv, 1)`` f32
    when int8).  page_table: ``(b, width)`` int32, ``-1`` = unassigned
    (skipped).  lengths: ``(b,)`` int32 — the number of VALID positions
    per slot *including* the token written this tick.

    Returns ``(b, 1, hq, d)`` in ``q.dtype``.  Rows whose length is 0
    return zeros (inactive slots decode garbage that is never sampled).
    """
    b, sq, hq, d = q.shape
    if sq != 1:
        raise ValueError(f"paged_attention is a decode kernel (sq=1), got "
                         f"sq={sq}")
    kp, vp = layer_kv["k"], layer_kv["v"]
    quantized = layer_kv.get("k_scale") is not None
    n_pages, page, hkv, _ = kp.shape
    if hq % hkv:
        raise ValueError(f"hq={hq} not a multiple of hkv={hkv}")
    g = hq // hkv
    width = page_table.shape[1]
    qg = q[:, 0].reshape(b, hkv, g, d)
    table = jnp.asarray(page_table, jnp.int32)
    # scalar lengths (attn_apply's single-sequence contract) broadcast
    # to the per-slot vector the scalar-prefetch operand expects
    lens = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))

    def page_map(i, j, t, ln):
        # the table drives the DMA: physical page of logical page j;
        # dead entries (-1) clamp to page 0 — fetched but never read
        # (the pl.when(live) gate skips the body)
        return (jnp.maximum(t[i, j], 0), 0, 0, 0)

    # a page block spans every kv head: the pool's trailing (hkv, d) dims
    # are then whole, which is what the TPU block tiling accepts
    in_specs = [
        pl.BlockSpec((1, hkv, g, d), lambda i, j, t, ln: (i, 0, 0, 0)),
        pl.BlockSpec((1, page, hkv, d), page_map),
        pl.BlockSpec((1, page, hkv, d), page_map),
    ]
    inputs = [qg, kp, vp]
    if quantized:
        in_specs += [pl.BlockSpec((1, page, hkv, 1), page_map),
                     pl.BlockSpec((1, page, hkv, 1), page_map)]
        inputs += [layer_kv["k_scale"], layer_kv["v_scale"]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, width),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d),
                               lambda i, j, t, ln: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),   # running max m
            pltpu.VMEM((hkv, g, 1), jnp.float32),   # running sum l
            pltpu.VMEM((hkv, g, d), jnp.float32),   # weighted accumulator
        ],
    )
    out = _pallas_call(
        functools.partial(_kernel, page=page, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(table, lens, *inputs)
    return out.reshape(b, 1, hq, d)
