"""Shared model components: norms, RoPE, GQA attention, SwiGLU, linears.

Design notes
------------
* Pure functional: params are plain dict pytrees; configs are static.
* Every linear goes through :func:`dense`, which executes either the bf16
  weight (training) or a folded+quantized :class:`QuantizedWeight`
  (serving) — the paper's technique is a first-class execution mode, not
  a bolt-on.
* Layer stacks run under ``jax.lax.scan`` with params stacked on axis 0
  (constant-size HLO for 126-layer models; remat policy per config).
* KV caches support bf16 or int8 (per-token-per-head scales) storage.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qlinear import QuantPolicy, qlinear

Params = dict[str, Any]

__all__ = [
    "dense", "init_linear", "rms_norm", "init_rms", "rope_angles", "apply_rope",
    "attention_scores", "init_attn", "attn_apply", "init_mlp", "mlp_apply",
    "init_embedding", "embed", "cross_entropy", "KVCache", "init_kv_cache",
    "cache_update", "cache_read", "stack_layer_params", "scan_layers",
    "batch_slot_cache", "cache_at", "write_slot",
    "PagedKVCache", "init_paged_kv_cache", "pages_per_slot", "paged_update",
    "paged_view", "quant_roundtrip_kv", "gather_page_rows", "take_last_valid",
    "flash_decode", "paged_attn_backend",
]


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def shard_act(x: jax.Array, *, sp: bool = False) -> jax.Array:
    """Constrain an activation to batch-over-dp, replicated elsewhere.

    Anchors GSPMD propagation at block boundaries so the residual stream
    never silently picks up a model-axis sharding (which would insert
    per-layer activation all-gathers).  No-op without an active mesh or
    when the batch doesn't divide the dp axes.

    ``sp=True`` (sequence parallelism, Korthikanti et al.): additionally
    shard the sequence axis over 'model' between blocks — GSPMD then
    replaces each TP all-reduce with a reduce-scatter + all-gather pair
    at half the wire bytes, and layer-boundary residuals shrink 16×.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or mesh.size == 1:
        return x
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import get_strategy

    dp = (tuple(mesh.axis_names) if get_strategy() == "fsdp"
          else tuple(a for a in mesh.axis_names if a != "model"))
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if not dp or x.shape[0] % size:
        return x
    seq_axis = None
    if (sp and x.ndim == 3 and "model" in mesh.axis_names
            and "model" not in dp
            and x.shape[1] % mesh.shape["model"] == 0):
        seq_axis = "model"
    spec = P(dp if len(dp) > 1 else dp[0], seq_axis,
             *([None] * (x.ndim - 2)))
    return jax.lax.with_sharding_constraint(x, spec)


def init_linear(key, d_in: int, d_out: int, *, bias: bool = False,
                scale: float | None = None, dtype=jnp.bfloat16) -> Params:
    scale = (d_in ** -0.5) if scale is None else scale
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(x: jax.Array, p: Params, policy: QuantPolicy | None = None) -> jax.Array:
    """Apply a linear from either a bf16 or a quantized param leaf.

    Quantized leaves dispatch through ``qlinear`` → ``kernels.ops``: on
    TPU the whole smooth→rotate→quantize→matmul chain is ONE fused
    Pallas kernel per linear (docs/kernels.md) — this is the call site
    the engine's ``(max_slots, 1)`` decode tick bottoms out in."""
    if "qw" in p:
        y = qlinear(x, p["qw"], policy or QuantPolicy())
    else:
        y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def init_rms(d: int, dtype=jnp.bfloat16) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rms_norm(x: jax.Array, p: Params | None, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    y = xf * inv
    if p is not None:  # folded (weightless) norms pass None — DESIGN.md §3
        y = y * p["g"].astype(jnp.float32)
    return y.astype(x.dtype)


def rope_angles(positions: jax.Array, head_dim: int, theta: float):
    """cos/sin tables for given integer positions, shape (..., head_dim/2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (b, s, h, d). cos/sin: (b, s, d/2) or (s, d/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


_CHUNK_Q_THRESHOLD = 8192   # switch to query-chunked attention beyond this
_CHUNK_Q = 512
_FLASH_KV_CHUNK = 1024
_FLASH_Q_CHUNK = 512


def flash_attention(q, k, v, *, causal: bool, q_offset=0,
                    length=None, bf16_io: bool = True):
    """Online-softmax (FlashAttention-style) GQA in pure XLA.

    Double scan over (q-chunk × kv-chunk) with running (max, sum, acc)
    carries: the (sq × sk) probability matrix NEVER materializes in HBM —
    traffic drops from O(s²) to O(s·d) per pass.  This is the XLA twin of
    the Pallas flash kernel a TPU build fuses; block sizes follow the
    same VMEM reasoning (q 512 × kv 1024 tiles).  Exact (not approximate):
    matches naive attention to bf16 rounding.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    g = hq // hkv
    dv = v.shape[-1]
    qb = _FLASH_Q_CHUNK if sq % _FLASH_Q_CHUNK == 0 else sq
    kb = _FLASH_KV_CHUNK if sk % _FLASH_KV_CHUNK == 0 else sk
    scale = d ** -0.5
    qg = q.reshape(b, sq, hkv, g, d)
    nq, nk = sq // qb, sk // kb
    k_pos0 = jnp.arange(kb)
    q_pos0 = jnp.arange(qb)

    def q_chunk(_, qi):
        qc = jax.lax.dynamic_slice_in_dim(qg, qi * qb, qb, 1)
        q_pos = q_pos0 + qi * qb + q_offset

        def kv_chunk(carry, ki):
            m, l, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(k, ki * kb, kb, 1)
            vc = jax.lax.dynamic_slice_in_dim(v, ki * kb, kb, 1)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc.astype(jnp.float32),
                           kc.astype(jnp.float32)) * scale
            k_pos = k_pos0 + ki * kb
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if length is not None:
                mask &= k_pos[None, :] < jnp.asarray(length).reshape(-1)[0]
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            p_cast = p.astype(jnp.bfloat16) if bf16_io else p
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p_cast,
                            vc if bf16_io else vc.astype(jnp.float32))
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv.astype(
                jnp.float32)
            return (m_new, l_new, acc_new), ()

        init = (jnp.full((b, hkv, g, qb), -jnp.inf, jnp.float32),
                jnp.zeros((b, hkv, g, qb), jnp.float32),
                jnp.zeros((b, hkv, g, qb, dv), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_chunk, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (b, hkv, g, qb, dv) → (b, qb, hq, dv)
        out = jnp.moveaxis(out, 3, 1).reshape(b, qb, hq, dv)
        return (), out

    _, chunks = jax.lax.scan(q_chunk, (), jnp.arange(nq))
    out = jnp.moveaxis(chunks, 0, 1).reshape(b, sq, hq, dv)
    return out.astype(q.dtype)


def attention_scores(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool, q_offset: jax.Array | int = 0,
                     window: int = 0, length: jax.Array | None = None,
                     bf16_io: bool = False) -> jax.Array:
    """Grouped-query attention core.

    q: (b, sq, hq, d); k/v: (b, sk, hkv, d); hq % hkv == 0.
    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``window``: sliding-window size (0 = full).  ``length``: valid kv
    prefix length for decode against a preallocated cache.  ``q_offset``
    and ``length`` may each be a scalar or a (b,) vector of per-row
    values (slot-major batched serving, incl. multi-token chunks).
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if sq > _CHUNK_Q_THRESHOLD and sq % _CHUNK_Q == 0:
        # Long-prefill path: scan over query chunks so the logits tensor
        # is (chunk, sk) not (sq, sk) — O(sq·sk) FLOPs, O(chunk·sk) memory.
        def one_chunk(carry, idx):
            qc = jax.lax.dynamic_slice_in_dim(q, idx * _CHUNK_Q, _CHUNK_Q, 1)
            oc = attention_scores(
                qc, k, v, causal=causal,
                q_offset=(jnp.asarray(q_offset) + idx * _CHUNK_Q),
                window=window, length=length, bf16_io=bf16_io)
            return carry, oc
        _, chunks = jax.lax.scan(one_chunk, (),
                                 jnp.arange(sq // _CHUNK_Q))
        # chunks: (n, b, CHUNK, hq, d) → (b, sq, hq, d)
        return jnp.moveaxis(chunks, 0, 1).reshape(b, sq, hq, v.shape[-1])
    group = hq // hkv
    qg = q.reshape(b, sq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    # q_offset and length may each be a scalar or a (b,) per-row vector
    # (slot-major batched serving); masks carry a leading broadcast axis
    # of size B ∈ {1, b} so every combination shares one code path.
    q_off = jnp.asarray(q_offset).reshape(-1)          # (1,) or (b,)
    larr = None if length is None else jnp.asarray(length).reshape(-1)
    B = max(q_off.size, 1 if larr is None else larr.size)
    q_pos = q_off[:, None] + jnp.arange(sq)[None]      # (1|b, sq)
    k_pos = jnp.arange(sk)
    mask = jnp.ones((B, sq, sk), bool)
    if causal:
        mask &= k_pos[None, None, :] <= q_pos[:, :, None]
    if window:
        mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
    if larr is not None:
        mask &= k_pos[None, None, :] < larr[:, None, None]
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if bf16_io:  # cast before P·V: cotangents (and any TP collectives on
        # them) stay bf16 — halves backward wire bytes (§Perf)
        probs = probs.astype(jnp.bfloat16)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.bfloat16))
    else:
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)  # v dim ≠ qk dim in MLA


def init_attn(key, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 4)
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    return {
        "wq": init_linear(ks[0], cfg.d_model, hq * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(ks[1], cfg.d_model, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], cfg.d_model, hkv * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], hq * hd, cfg.d_model, dtype=dtype),
        "ln": init_rms(cfg.d_model, dtype),
    }


# ---------------------------------------------------------------------------
# KV cache (bf16 or int8-quantized storage)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Per-stack KV cache; leading axis = layer (scanned).

    int8 mode stores codes + per (b, s, h) scales — 2× HBM saving, the
    serving-path default (QuantPolicy.kv_cache_bits = 8).
    """

    k: jax.Array                     # (L, b, S, hkv, d) bf16|int8
    v: jax.Array
    k_scale: jax.Array | None        # (L, b, S, hkv, 1) f32 when int8
    v_scale: jax.Array | None
    length: jax.Array                # () int32 — tokens filled

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def init_kv_cache(cfg: ModelConfig, n_layers: int, batch: int, max_len: int,
                  *, bits: int | None = None, dtype=jnp.bfloat16,
                  head_dim: int | None = None, kv_heads: int | None = None) -> KVCache:
    hkv = cfg.num_kv_heads if kv_heads is None else kv_heads
    hd = cfg.head_dim if head_dim is None else head_dim
    if cfg.attn_window:
        max_len = min(max_len, cfg.attn_window)
    shape = (n_layers, batch, max_len, hkv, hd)
    if bits == 8:
        return KVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros((*shape[:4], 1), jnp.float32),
            v_scale=jnp.zeros((*shape[:4], 1), jnp.float32),
            length=jnp.zeros((), jnp.int32),
        )
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   k_scale=None, v_scale=None, length=jnp.zeros((), jnp.int32))


def _quant_kv(x: jax.Array):
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax) / 127.0
    return jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                    ).astype(jnp.int8), scale


def cache_update(layer_kv: dict, k_new: jax.Array, v_new: jax.Array,
                 length: jax.Array, *, window: int = 0):
    """Write new k/v at position ``length`` into one layer's cache slice.

    layer_kv: dict(k, v[, k_scale, v_scale]) with shapes (b, S, h, d).
    ``length`` may be a scalar (all rows at the same position — train /
    single-sequence serving) or a (b,) vector of per-row positions (the
    slot-major batched decode, where every slot sits at its own depth).
    Sliding-window caches write modulo the window (ring buffer).
    """
    S = layer_kv["k"].shape[1]
    pos = jnp.asarray((length % S) if window else length)
    if pos.ndim:  # per-slot write positions: vmap the row update
        def put(buf, val):
            def row(b1, v1, p1):
                return jax.lax.dynamic_update_slice(
                    b1, v1.astype(b1.dtype), (p1,) + (0,) * (b1.ndim - 1))
            return jax.vmap(row)(buf, val, pos)
    else:
        def put(buf, val):
            return jax.lax.dynamic_update_slice(
                buf, val.astype(buf.dtype), (0, pos, 0, 0))
    out = dict(layer_kv)
    if "k_scale" in layer_kv and layer_kv["k_scale"] is not None:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        out["k"], out["v"] = put(layer_kv["k"], kq), put(layer_kv["v"], vq)
        out["k_scale"] = put(layer_kv["k_scale"], ks)
        out["v_scale"] = put(layer_kv["v_scale"], vs)
    else:
        out["k"], out["v"] = put(layer_kv["k"], k_new), put(layer_kv["v"], v_new)
    return out


def cache_read(layer_kv: dict):
    """Dequantized (k, v) views of one layer's cache slice."""
    k, v = layer_kv["k"], layer_kv["v"]
    if layer_kv.get("k_scale") is not None:
        k = (k.astype(jnp.float32) * layer_kv["k_scale"]).astype(jnp.bfloat16)
        v = (v.astype(jnp.float32) * layer_kv["v_scale"]).astype(jnp.bfloat16)
    return k, v


# -- slot-major batched caches (serving engine) -----------------------------
#
# The serving engine stacks ``max_slots`` independent sequences into ONE
# cache pytree so a single (max_slots, 1) decode program serves every
# active slot per tick.  Convention shared by all family caches (KVCache,
# SSMCache, HybridCache): data leaves carry the slot axis at position 1
# (layer-major stacking puts layers at axis 0), and bookkeeping leaves
# (``length``) are scalars per sequence — vectorized to (max_slots,) by
# :func:`batch_slot_cache` so every slot tracks its own depth.


def batch_slot_cache(cache):
    """Vectorize a cache's scalar ``length`` leaves to per-slot (b,) vectors.

    ``cache`` comes from ``model.make_cache(cfg, max_slots, max_len)``;
    data leaves already carry the slot axis at position 1 (they are
    untouched), scalar leaves become (max_slots,) zeros-initialized
    vectors so decode can thread per-slot positions.
    """
    wide = [a for a in jax.tree.leaves(cache) if jnp.ndim(a) >= 2]
    slots = wide[0].shape[1]
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (slots,)) if jnp.ndim(a) == 0 else a,
        cache)


def cache_at(cache, slot: int):
    """Batch-1 view of one slot of a slot-major batched cache.

    Per-slot ``length`` vectors collapse back to the scalar the
    single-sequence prefill/decode path expects, so the view is
    interchangeable with a fresh ``make_cache(cfg, 1, max_len)``.
    """
    return jax.tree.map(
        lambda a: a[slot] if a.ndim <= 1 else a[:, slot:slot + 1], cache)


def write_slot(cache, slot_cache, slot: int):
    """Write a batch-1 cache (e.g. a freshly prefilled prompt) into slot
    ``slot`` of a slot-major batched cache.

    Copies the FULL slot extent — including zero (or zero-scale) tail
    positions — so a reused slot cannot leak stale keys/values or stale
    int8 dequant scales from the previous occupant.
    """
    def put(dst, src):
        if dst.ndim <= 1:  # per-slot length ← scalar slot length
            return dst.at[slot].set(jnp.asarray(src).reshape(()).astype(dst.dtype))
        return dst.at[:, slot].set(src[:, 0].astype(dst.dtype))
    return jax.tree.map(put, cache, slot_cache)


# -- paged KV cache (serving engine, continuous batching) -------------------
#
# The dense slot-major layout above reserves a full (max_len) extent per
# slot.  The paged layout replaces it with fixed-size PAGES drawn from a
# SHARED pool: data leaves are (L, n_pages, page, hkv, d), a per-slot
# page table maps logical page j of a slot (tokens [j·page, (j+1)·page))
# to a physical pool page, and slots grow one page at a time — freed
# pages return to the pool on retirement.  int8 KV scale leaves page
# alongside their data leaves with the same indirection.  Pages are
# allocated CONTIGUOUSLY per slot, so the per-page validity mask reduces
# to the per-row valid-length prefix mask attention already applies.


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Paged slot-major KV cache over a shared page pool.

    ``page_table[slot, j]`` is the physical page holding the slot's
    tokens ``[j*page, (j+1)*page)``; ``-1`` marks an unassigned logical
    page.  Writes routed through an unassigned page are DROPPED (the
    scatter goes out of bounds), reads clamp to page 0 and rely on the
    valid-length mask — the engine's host-side allocator owns the table.
    """

    k: jax.Array                     # (L, n_pages, page, hkv, d) bf16|int8
    v: jax.Array
    k_scale: jax.Array | None        # (L, n_pages, page, hkv, 1) f32 when int8
    v_scale: jax.Array | None
    page_table: jax.Array            # (slots, pages_per_slot) int32, -1 = free
    length: jax.Array                # (slots,) int32 — tokens filled per slot

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]


def pages_per_slot(max_len: int, page_size: int) -> int:
    """Page-table width: logical pages needed to back ``max_len`` tokens."""
    return -(-max_len // page_size)


def init_paged_kv_cache(cfg: ModelConfig, n_layers: int, slots: int,
                        max_len: int, *, page_size: int = 64,
                        n_pages: int | None = None, bits: int | None = None,
                        dtype=jnp.bfloat16, head_dim: int | None = None,
                        kv_heads: int | None = None) -> PagedKVCache:
    """Shared page pool + empty page table.  ``n_pages=None`` sizes the
    pool for zero overcommit (slots × pages_per_slot — every slot can
    reach ``max_len``); smaller pools overcommit and rely on the
    engine's admission backpressure."""
    if cfg.attn_window:
        raise ValueError("paged KV does not support sliding-window (ring) "
                         "caches; use the dense slot-major layout")
    hkv = cfg.num_kv_heads if kv_heads is None else kv_heads
    hd = cfg.head_dim if head_dim is None else head_dim
    width = pages_per_slot(max_len, page_size)
    if n_pages is None:
        n_pages = slots * width
    shape = (n_layers, n_pages, page_size, hkv, hd)
    table = jnp.full((slots, width), -1, jnp.int32)
    length = jnp.zeros((slots,), jnp.int32)
    if bits == 8:
        return PagedKVCache(
            k=jnp.zeros(shape, jnp.int8), v=jnp.zeros(shape, jnp.int8),
            k_scale=jnp.zeros((*shape[:4], 1), jnp.float32),
            v_scale=jnp.zeros((*shape[:4], 1), jnp.float32),
            page_table=table, length=length)
    return PagedKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                        k_scale=None, v_scale=None, page_table=table,
                        length=length)


def paged_update(layer_kv: dict, k_new: jax.Array, v_new: jax.Array,
                 length: jax.Array, page_table: jax.Array, *,
                 valid_new: jax.Array | None = None) -> dict:
    """Scatter-write new k/v into pool pages through the page table.

    layer_kv: dict(k, v[, k_scale, v_scale]) with POOL shapes
    (n_pages, page, h, d).  k_new/v_new: (b, s, h, d) written at per-row
    positions ``length + [0, s)`` — ``length`` is a scalar (all rows at
    the same depth) or a (b,) vector of per-row depths (slot-major
    batched decode / mixed-depth prefill); int8 pools quantize here and
    write the per-(position, head) scales through the same indirection.
    ``valid_new``: optional (b,) count of REAL new tokens per row
    (batched prefill right-pads mixed prompt lengths) — writes beyond it
    are dropped.  Any write that resolves to an unassigned (-1) or
    out-of-range logical page is routed out of bounds and dropped by the
    scatter, so padding rows and stalled slots cannot corrupt the pool.
    """
    n_pages, page = layer_kv["k"].shape[0], layer_kv["k"].shape[1]
    b, s = k_new.shape[0], k_new.shape[1]
    width = page_table.shape[1]
    pos = jnp.broadcast_to(
        jnp.asarray(length).reshape(-1, 1) + jnp.arange(s)[None], (b, s))
    logical = pos // page
    phys = jnp.take_along_axis(page_table, jnp.minimum(logical, width - 1),
                               axis=1)
    ok = (logical < width) & (phys >= 0)
    if valid_new is not None:
        ok &= jnp.arange(s)[None] < jnp.asarray(valid_new).reshape(-1, 1)
    phys = jnp.where(ok, phys, n_pages)          # out of bounds → dropped
    pflat, oflat = phys.reshape(-1), (pos % page).reshape(-1)

    def put(buf, val):
        flat = val.reshape(b * s, *val.shape[2:]).astype(buf.dtype)
        return buf.at[pflat, oflat].set(flat, mode="drop")

    out = dict(layer_kv)
    if layer_kv.get("k_scale") is not None:
        kq, ks = _quant_kv(k_new)
        vq, vs = _quant_kv(v_new)
        out["k"], out["v"] = put(layer_kv["k"], kq), put(layer_kv["v"], vq)
        out["k_scale"] = put(layer_kv["k_scale"], ks)
        out["v_scale"] = put(layer_kv["v_scale"], vs)
    else:
        out["k"], out["v"] = put(layer_kv["k"], k_new), put(layer_kv["v"], v_new)
    return out


def paged_view(layer_kv: dict, page_table: jax.Array):
    """Contiguous dequantized (k, v) views of a paged pool, per slot.

    This is the XLA gather that the Pallas paged-attention kernel
    (kernels/paged_attention.py) eliminates on the decode hot path: it
    materializes every cached byte into a fresh (b, width·page, h, d)
    HBM buffer per layer per call (int8 pools additionally inflate to
    bf16), which attention then re-reads.  It remains the parity
    fallback (``paged_attn_backend() == "xla"``) and the prefill-side
    view.

    Masking contract: positions past a slot's valid length read
    clamped (-1 → page 0) or stale pages and MUST be masked by the
    caller's length-prefix mask.  They always can be: the engine
    allocates pages contiguously per slot, so page validity ≡ the
    per-row valid-length prefix that ``attention_scores(length=...)``
    already applies.
    """
    idx = jnp.maximum(page_table, 0)                      # (b, width)
    k, v = layer_kv["k"][idx], layer_kv["v"][idx]         # (b, w, page, h, d)
    if layer_kv.get("k_scale") is not None:
        k = (k.astype(jnp.float32) * layer_kv["k_scale"][idx]
             ).astype(jnp.bfloat16)
        v = (v.astype(jnp.float32) * layer_kv["v_scale"][idx]
             ).astype(jnp.bfloat16)
    b, w, page = k.shape[0], k.shape[1], k.shape[2]
    return (k.reshape(b, w * page, *k.shape[3:]),
            v.reshape(b, w * page, *v.shape[3:]))


def gather_page_rows(page_table: jax.Array, slots) -> jax.Array:
    """Page-table rows for a batch of admitted slots.

    ``slots`` may contain the sentinel value ``page_table.shape[0]``
    (batched prefill pads the admission batch to a bucketed row count):
    sentinel rows resolve to all-unassigned (-1) so their writes drop.
    """
    n_slots = page_table.shape[0]
    sl = jnp.asarray(slots)
    rows = page_table[jnp.clip(sl, 0, n_slots - 1)]
    return jnp.where((sl[:, None] >= 0) & (sl[:, None] < n_slots), rows, -1)


def take_last_valid(x: jax.Array, lengths) -> jax.Array:
    """(n, s, d) → (n, 1, d) at each row's last valid position (the
    right-padded batched-prefill logits gather)."""
    idx = jnp.maximum(jnp.asarray(lengths) - 1, 0)
    return jnp.take_along_axis(x, idx[:, None, None], axis=1)


def spec_accept_greedy(proposed, greedy) -> list[int]:
    """Greedy speculative acceptance: longest matching prefix + one
    corrected token (docs/speculative.md).

    ``proposed``: the k drafted tokens for one slot; ``greedy``: the
    target's argmax at each of the k+1 verified positions (position j
    scored the context ending in draft j's predecessor, so ``greedy[j]``
    is what plain greedy decode would have emitted there).  Accept
    drafts while they match, then emit the target's own token at the
    first divergence — the emitted stream is exactly what plain greedy
    decode produces, one token at a time.  Always emits ≥ 1 token.
    """
    m = 0
    while m < len(proposed) and int(proposed[m]) == int(greedy[m]):
        m += 1
    return [int(t) for t in proposed[:m]] + [int(greedy[m])]


def quant_roundtrip_kv(x: jax.Array) -> jax.Array:
    """Quantize→dequantize through the int8 KV path (what a reader of the
    cache would see).  Batched prefill attends over LOCAL fresh k/v
    instead of reading them back from the pool; int8 caches must
    roundtrip so the local view matches the per-slot oracle bit for bit."""
    q, s = _quant_kv(x)
    return (q.astype(jnp.float32) * s).astype(jnp.bfloat16)


def flash_decode(q, layer_kv: dict, valid, *, dp_spec) -> jax.Array:
    """Distributed online-softmax decode over a SEQUENCE-sharded KV cache.

    Each model-shard scores its local KV slice (dequantizing int8 codes
    locally — the full cache never leaves its shard), computes a local
    (max, sum, partial output), and three tiny psums combine them:
    wire per layer drops from the (b,h,1,S) f32 logits all-gather
    (~137 MB for llama decode_32k) to (b,h,[1+1+hd]) f32 (~0.5 MB).
    q: (b, 1, hq, d); cache slices (b, S, hkv, ·).  §Perf cell C it2.

    ``valid`` — the number of visible cache positions per row, INCLUDING
    the token written this tick — may be a scalar (every row at the same
    depth: single-sequence serving) or a (b,) vector of per-row depths
    (the slot-major batched engine, where each slot decodes at its own
    length).  Either way it is broadcast to (b,) and sharded with the
    batch, and each shard masks its local positions against its own
    rows' depths — so the batched engine's ONE (max_slots, 1) tick
    reaches this flash path, not just scalar-length callers.
    ``dp_spec``: the batch-sharding spec from :func:`_flash_decode_ok`
    (None = batch replicated).
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    b, sq, hq, d = q.shape
    S = layer_kv["k"].shape[1]
    quantized = layer_kv.get("k_scale") is not None

    def local(qc, k, v, ks, vs, valid_):
        idx = jax.lax.axis_index("model")
        b_loc, sq_, hq_, d_ = qc.shape  # LOCAL shapes (batch may be sharded)
        s_loc = k.shape[1]
        if quantized:
            k = (k.astype(jnp.float32) * ks).astype(jnp.bfloat16)
            v = (v.astype(jnp.float32) * vs).astype(jnp.bfloat16)
        hkv = k.shape[2]
        g = hq_ // hkv
        qg = qc.reshape(b_loc, sq_, hkv, g, d_)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * (d ** -0.5)
        pos = jnp.arange(s_loc) + idx * s_loc  # global slot positions
        # valid_ is the (b_loc,) per-row depth slice: mask each row's
        # local positions against ITS depth (scalar callers were
        # broadcast before the shard_map)
        mask = pos[None, None, None, None, :] \
            < valid_[:, None, None, None, None]
        s = jnp.where(mask, s, -1e30)
        m_loc = s.max(-1)                                    # (b,h,g,1)
        m_glob = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(s - m_glob[..., None])
        l = jax.lax.psum(p.sum(-1), "model")                 # (b,h,g,1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(jnp.float32),
                       v.astype(jnp.float32))
        o = jax.lax.psum(o, "model")
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(
            b_loc, sq_, hq_, v.shape[-1]).astype(qc.dtype)

    kv_spec = P(dp_spec, "model", None, None)
    ks = layer_kv.get("k_scale")
    vs = layer_kv.get("v_scale")
    scale_spec = kv_spec if quantized else P()
    valid_vec = jnp.broadcast_to(
        jnp.asarray(valid, jnp.int32).reshape(-1), (b,))
    return jax.shard_map(
        local,
        in_specs=(P(dp_spec, None, None, None), kv_spec, kv_spec,
                  scale_spec, scale_spec, P(dp_spec)),
        out_specs=P(dp_spec, None, None, None), check_vma=False
    )(q, layer_kv["k"], layer_kv["v"],
      ks if quantized else jnp.zeros((), jnp.float32),
      vs if quantized else jnp.zeros((), jnp.float32),
      valid_vec)


def _flash_decode_ok(cfg: ModelConfig, q, layer_kv) -> tuple[bool, Any]:
    """Eligibility + the dp spec for flash_decode under the ambient mesh.

    Length-shape-agnostic: scalar and per-slot (b,) cache depths are
    both eligible (flash_decode broadcasts/shards the depth vector).
    Requires a sequence-sharded cache to exist at all — a mesh with a
    'model' axis that the kv-head count does NOT divide (head-sharded
    caches keep the plain gather path) and S divisible by the axis."""
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return False, None
    b, sq = q.shape[0], q.shape[1]
    S, hkv = layer_kv["k"].shape[1], layer_kv["k"].shape[2]
    if sq != 1 or cfg.attn_window or S % mesh.shape["model"]:
        return False, None
    if hkv % mesh.shape["model"] == 0:
        return False, None  # head-sharded caches don't need it
    dp = tuple(a for a in mesh.axis_names if a != "model")
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    dp_spec = (dp if len(dp) > 1 else dp[0]) if (dp and b % size == 0) \
        else None
    return True, dp_spec


def paged_attn_backend(cfg: ModelConfig,
                       policy: QuantPolicy | None = None) -> str:
    """Resolved executor for decode attention over a PAGED KV pool.

    The single dispatch point for the paged decode hot path, sharing
    ``kernels.ops.resolve_backend`` with the quantized linears so one
    policy knob (``QuantPolicy.use_kernels``) governs both:

      * ``"pallas"`` / ``"interpret"`` — the in-VMEM Pallas
        paged-attention kernel (compiled on TPU / via the interpreter);
      * ``"xla"``  — the ``paged_view`` gather + ``attention_scores``
        parity fallback.  MLA latent pools resolve here by
        construction: the latent must be up-projected (``wukv``) into
        per-head K/V *before* attention, so the gather is load-bearing,
        not an attention implementation detail (docs/paged_attention.md
        has the full dispatch table).  ``attn_bf16_io`` configs also
        fall back (the kernel accumulates f32).
      * ``"none"`` — the family has no attention KV to page (pure SSM).

    Engines surface this in ``run_stats["paged_attention_backend"]``.
    """
    if not cfg.uses_attention:
        return "none"
    if cfg.kv_lora_rank or cfg.attn_bf16_io or cfg.attn_window:
        return "xla"
    from repro.kernels import ops

    return ops.resolve_backend(policy.use_kernels if policy is not None
                               else "auto")


def attn_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
               layer_kv: dict | None = None, length: jax.Array | int = 0,
               policy: QuantPolicy | None = None, taps: dict | None = None,
               page_table: jax.Array | None = None,
               valid_new: jax.Array | None = None,
               prefill_local: bool = False):
    """Full attention block (pre-norm). Returns (y, updated layer_kv).

    Per-slot length contract: ``length`` — the number of tokens already
    in the cache BEFORE this call — may be a scalar or a (b,) vector of
    per-row depths (slot-major batched decode).  RoPE positions, the
    cache write position, and the valid-length mask
    (``valid = min(length + s, S)``, which includes the tokens written
    this call) are all applied per row; rows at depth 0 with nothing
    written are inactive slots whose output is garbage by contract
    (never sampled).

    ``page_table`` switches ``layer_kv`` to the PAGED layout: leaves are
    pool-shaped (n_pages, page, h, d) and writes/reads go through
    :func:`paged_update` / :func:`paged_view`.  Decode (s == 1) then
    dispatches via :func:`paged_attn_backend`: on the Pallas modes the
    kernel indexes pages in-VMEM and the contiguous gather never
    materializes; on "xla" the ``paged_view`` fallback runs.
    ``prefill_local`` (paged batched prefill, rows all at length 0)
    attends over the freshly computed k/v instead of gathering them
    back from the pool — the causal mask alone covers validity, and
    ``valid_new`` masks the right-padding rows' writes.

    Dense-cache decode reaches :func:`flash_decode` when
    ``cfg.decode_flash`` and the mesh sequence-shards the cache —
    including per-slot (b,) depth vectors (the batched engine's tick).
    """
    b, s, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    h = rms_norm(x, p.get("ln"), cfg.norm_eps)
    if taps is not None:  # q/k/v share this input (paper §III-A)
        taps["k_proj"] = h
    q = dense(h, p["wq"], policy).reshape(b, s, hq, hd)
    k = dense(h, p["wk"], policy).reshape(b, s, hkv, hd)
    v = dense(h, p["wv"], policy).reshape(b, s, hkv, hd)
    larr = jnp.asarray(length)
    pos = (larr[:, None] + jnp.arange(s)[None]) if larr.ndim \
        else (jnp.arange(s) + larr)
    cos, sin = rope_angles(pos, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if layer_kv is not None and page_table is not None:  # paged cache
        layer_kv = paged_update(layer_kv, k, v, length, page_table,
                                valid_new=valid_new)
        if prefill_local:
            kc, vc = k, v
            if layer_kv.get("k_scale") is not None:
                kc, vc = quant_roundtrip_kv(k), quant_roundtrip_kv(v)
            out = attention_scores(q, kc, vc, causal=True, q_offset=length,
                                   bf16_io=cfg.attn_bf16_io)
        else:
            width, page = page_table.shape[1], layer_kv["k"].shape[1]
            valid = jnp.minimum(larr + s, width * page)
            mode = paged_attn_backend(cfg, policy)
            if s == 1 and mode in ("pallas", "interpret"):
                # in-VMEM page indexing: the kernel DMAs each slot's
                # pages through the table and never materializes the
                # contiguous view (kernels/paged_attention.py)
                from repro.kernels import ops

                out = ops.paged_attention(q, layer_kv, page_table, valid,
                                          interpret=(mode == "interpret"))
            else:
                kc, vc = paged_view(layer_kv, page_table)
                out = attention_scores(q, kc, vc, causal=(s > 1),
                                       q_offset=length, length=valid,
                                       bf16_io=cfg.attn_bf16_io)
    elif layer_kv is not None:  # decode / cached prefill
        layer_kv = cache_update(layer_kv, k, v, length, window=cfg.attn_window)
        valid = jnp.minimum(larr + s, layer_kv["k"].shape[1])
        use_fd, dp_spec = (False, None)
        if cfg.decode_flash:  # per-slot (b,) depths are eligible too
            use_fd, dp_spec = _flash_decode_ok(cfg, q, layer_kv)
        if use_fd:
            out = flash_decode(q, layer_kv, valid, dp_spec=dp_spec)
        else:
            kc, vc = cache_read(layer_kv)
            # Ring-buffer caches: every stored slot is within the window
            # and causally valid; keys carry absolute RoPE so slot order
            # is irrelevant (attention is permutation-invariant over
            # keys).  Cached prefill (s > 1, non-ring) additionally needs
            # the causal mask since cache slots ARE absolute positions.
            out = attention_scores(q, kc, vc, causal=(s > 1),
                                   q_offset=length, window=0, length=valid,
                                   bf16_io=cfg.attn_bf16_io)
    elif cfg.attn_impl == "flash" and not cfg.attn_window:
        out = flash_attention(q, k, v, causal=True,
                              bf16_io=cfg.attn_bf16_io)
    else:
        out = attention_scores(q, k, v, causal=True, window=cfg.attn_window,
                               bf16_io=cfg.attn_bf16_io)
    o_in = out.reshape(b, s, hq * hd)
    if taps is not None:
        taps["o_proj"] = o_in
    y = dense(o_in, p["wo"], policy)
    return x + y, layer_kv


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wg": init_linear(ks[0], d_model, d_ff, dtype=dtype),
        "wu": init_linear(ks[1], d_model, d_ff, dtype=dtype),
        "wd": init_linear(ks[2], d_ff, d_model, dtype=dtype),
        "ln": init_rms(d_model, dtype),
    }


def mlp_apply(p: Params, x: jax.Array, cfg: ModelConfig,
              policy: QuantPolicy | None = None, *, residual: bool = True,
              taps: dict | None = None):
    h = (rms_norm(x, p.get("ln"), cfg.norm_eps)
         if "ln" in p and p["ln"] is not None else x)
    if taps is not None:  # gate/up share this input (paper §III-A)
        taps["gate_proj"] = h
    g = dense(h, p["wg"], policy)
    u = dense(h, p["wu"], policy)
    a = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    if taps is not None:
        taps["down_proj"] = a
    y = dense(a, p["wd"], policy)
    return x + y if residual else y


# ---------------------------------------------------------------------------
# embedding / loss
# ---------------------------------------------------------------------------


def init_embedding(key, vocab: int, d: int, dtype=jnp.bfloat16) -> Params:
    return {"e": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["e"], tokens, axis=0)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits.astype(jnp.float32), labels[..., None],
                             axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


# ---------------------------------------------------------------------------
# layer stacking
# ---------------------------------------------------------------------------


def stack_layer_params(keys, init_fn):
    """vmap an init over layer keys → params stacked on axis 0."""
    return jax.vmap(init_fn)(keys)


def scan_layers(block_fn, params_stacked, x, *, remat: bool, extras=None,
                sp: bool = False, remat_policy: str = "full"):
    """Run ``block_fn(layer_params, x, extra) -> (x, y)`` over the stack.

    ``extras``: optional pytree with leading layer axis scanned alongside
    (e.g. per-layer KV cache slices).  Returns (x, stacked ys).
    ``sp``: sequence-parallel the residual stream between blocks.
    ``remat_policy='dots_no_batch'``: save linear (no-batch-dim) dot
    outputs, recompute attention scores/probs in backward — one fewer
    weight all-gather pass than full remat, and no s² residency (the
    contract a fused flash-attention backward provides on TPU).
    """
    group = 1
    if remat and remat_policy.startswith("group"):
        group = int(remat_policy[len("group"):] or 2)
        fn = block_fn
    elif remat and remat_policy == "dots_no_batch":
        fn = jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif remat:
        fn = jax.checkpoint(block_fn)
    else:
        fn = block_fn

    if group > 1:
        # grouped remat: one residual stored per GROUP of g layers (126
        # layers × 134 MB does not fit HBM at 405B scale; 126/g does) —
        # backward recomputes the g-layer group once.
        L = jax.tree.leaves(params_stacked)[0].shape[0]
        if L % group:
            group = 1  # fall back silently for non-divisible stacks
    if group > 1:
        regroup = lambda t: jax.tree.map(
            lambda a: a.reshape(a.shape[0] // group, group, *a.shape[1:]), t)
        pg = regroup(params_stacked)
        eg = regroup(extras) if extras is not None else None

        # inner layers carry the dots-no-batch policy so the group's
        # backward live-set holds linear outputs only (never s² probs)
        inner_fn = jax.checkpoint(
            block_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        @jax.checkpoint
        def group_step(carry, group_in):
            lp_g, extra_g = group_in

            def inner(c, one):
                lp, ex = one
                c, y = inner_fn(lp, c, ex)
                return shard_act(c, sp=sp), y

            carry, ys = jax.lax.scan(inner, carry, (lp_g, extra_g))
            return carry, ys

        x, ys = jax.lax.scan(group_step, shard_act(x, sp=sp), (pg, eg))
        ys = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), ys)
        return x, ys

    def step(carry, layer_in):
        lp, extra = layer_in
        carry, y = fn(lp, carry, extra)
        return shard_act(carry, sp=sp), y

    x, ys = jax.lax.scan(step, shard_act(x, sp=sp), (params_stacked, extras))
    return x, ys
