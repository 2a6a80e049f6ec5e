"""Pallas paged-attention decode kernel: parity vs the XLA gather path.

The kernel (kernels/paged_attention.py) must match the
``paged_view`` + reference-attention composition the engines fall back
to, across:

  * bf16 AND int8-KV pools (in-kernel dequant from the paged scale
    leaves);
  * page-boundary lengths (exactly at / one past a page edge);
  * ragged per-slot lengths (every slot at its own depth, dead table
    entries skipped);
  * GQA group sizes (MQA g=hq, grouped, MHA g=1).

Runs through the Pallas INTERPRETER on CPU (the same mode
``use_kernels="interpret"`` selects in the engines — CI's kernels job
exercises exactly this path); ``tests/test_serving_paged.py`` pins the
end-to-end greedy token-identity with the kernel enabled.  Also pins
``flash_decode`` under per-slot (b,) length vectors (the batched
engine's flash path) and the ``paged_attn_backend`` dispatch table.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.kernels import ops, ref
from repro.kernels import paged_attention as pa
from repro.models import common as cm

KEY = jax.random.PRNGKey(0)

# the engine's actual KV quantizer — parity must cover what the serving
# pool really stores, not a lookalike scheme
_quant_pool = cm._quant_kv


def _make_case(b, hq, hkv, d, page, width, lengths, *, quantized=False,
               seed=0):
    """Random pool + a scattered (non-identity) page table.

    Pages are assigned logically-contiguously per slot (the engine's
    allocation invariant) but to arbitrary physical pages, so parity
    failures in the table indirection cannot hide behind an identity
    layout.  Unassigned logical pages are -1.
    """
    rng = np.random.default_rng(seed)
    n_pages = b * width + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kp = jax.random.normal(ks[0], (n_pages, page, hkv, d)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[1], (n_pages, page, hkv, d)).astype(jnp.bfloat16)
    q = jax.random.normal(ks[2], (b, 1, hq, d)).astype(jnp.bfloat16)
    perm = rng.permutation(n_pages)
    table = np.full((b, width), -1, np.int32)
    nxt = 0
    for i, ln in enumerate(lengths):
        for j in range(-(-int(ln) // page)):
            table[i, j] = perm[nxt]
            nxt += 1
    layer_kv = {"k": kp, "v": vp}
    if quantized:
        kq, ksc = _quant_pool(kp)
        vq, vsc = _quant_pool(vp)
        layer_kv = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    return q, layer_kv, jnp.asarray(table), jnp.asarray(lengths, jnp.int32)


def _parity(q, layer_kv, table, lengths, atol=2e-2):
    out = ops.paged_attention(q, layer_kv, table, lengths, interpret=True)
    want = ref.paged_attention_ref(q, layer_kv, table, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    # and vs the exact engine fallback: paged_view + attention_scores
    kc, vc = cm.paged_view(layer_kv, table)
    want2 = cm.attention_scores(q, kc, vc, causal=False, length=lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want2, np.float32), atol=atol)


# ---------------------------------------------------------------------------
# parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (4, 1)],
                         ids=["mha", "gqa4", "mqa"])
def test_parity_ragged_lengths(hq, hkv, quantized):
    """Per-slot ragged depths across GQA group sizes × pool dtypes."""
    q, kv, table, lens = _make_case(4, hq, hkv, 16, page=4, width=5,
                                    lengths=[1, 7, 20, 13],
                                    quantized=quantized, seed=1)
    _parity(q, kv, table, lens)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("hq,hkv,d", [(32, 32, 80), (32, 8, 128)],
                         ids=["stablelm_mha", "gqa8_d128"])
def test_parity_published_head_geometry(hq, hkv, d, quantized):
    """Published head counts and widths: each grid step's block holds
    every kv head of one page (hkv, d whole), and the GQA groups reduce
    in-kernel — here at hkv=32 with d=80 (not a lane multiple) and at
    g=4 with d=128."""
    q, kv, table, lens = _make_case(3, hq, hkv, d, page=8, width=3,
                                    lengths=[17, 8, 1], quantized=quantized,
                                    seed=11)
    _parity(q, kv, table, lens)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("length", [4, 5, 8, 9, 16],
                         ids=["at_edge", "past_edge", "at_edge2",
                              "past_edge2", "full"])
def test_parity_page_boundaries(length, quantized):
    """Lengths exactly at and one past a page edge: the masked tail of a
    partially filled page and the first row of a fresh page are where a
    wrong prefix mask or off-by-one page index would show."""
    q, kv, table, lens = _make_case(2, 4, 2, 16, page=4, width=4,
                                    lengths=[length, max(length - 1, 1)],
                                    quantized=quantized, seed=2)
    _parity(q, kv, table, lens)


def test_scalar_length_broadcasts():
    """attn_apply's single-sequence contract passes a SCALAR valid
    length; the wrapper must broadcast it per slot, not reshape-crash."""
    q, kv, table, lens = _make_case(2, 4, 2, 16, page=4, width=3,
                                    lengths=[9, 9], seed=9)
    out = ops.paged_attention(q, kv, table, 9, interpret=True)
    want = ref.paged_attention_ref(q, kv, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_parity_under_jit_and_odd_dims():
    """Jitted call, non-square head dims, width-1 table."""
    q, kv, table, lens = _make_case(3, 6, 3, 8, page=2, width=1,
                                    lengths=[1, 2, 2], seed=3)
    out = jax.jit(functools.partial(ops.paged_attention, interpret=True)
                  )(q, kv, table, lens)
    want = ref.paged_attention_ref(q, kv, table, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_dead_table_entries_are_skipped():
    """Entries past a slot's pages are -1; poisoning every unassigned
    physical page with huge values must not leak into the output (the
    kernel's pl.when gate + length mask)."""
    q, kv, table, lens = _make_case(2, 4, 2, 16, page=4, width=4,
                                    lengths=[5, 3], seed=4)
    used = np.unique(np.asarray(table)[np.asarray(table) >= 0])
    poison = np.setdiff1d(np.arange(kv["k"].shape[0]), used)
    kv2 = dict(kv,
               k=kv["k"].at[poison].set(jnp.asarray(300.0, kv["k"].dtype)),
               v=kv["v"].at[poison].set(jnp.asarray(300.0, kv["v"].dtype)))
    out = ops.paged_attention(q, kv2, table, lens, interpret=True)
    want = ops.paged_attention(q, kv, table, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=1e-6)


def test_zero_length_row_returns_finite():
    """Inactive slots (length 0, all pages -1) decode garbage by
    contract — but it must be FINITE garbage (zeros), not NaN from an
    all-masked softmax."""
    q, kv, table, lens = _make_case(2, 4, 2, 16, page=4, width=2,
                                    lengths=[6, 0], seed=5)
    out = np.asarray(ops.paged_attention(q, kv, table, lens, interpret=True),
                     np.float32)
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out[1], 0.0, atol=1e-6)


def test_one_pallas_call_per_invocation(monkeypatch):
    """ONE kernel launch per layer invocation (the fused contract)."""
    calls = []
    orig = pa._pallas_call

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(pa, "_pallas_call", counting)
    q, kv, table, lens = _make_case(2, 4, 2, 16, page=4, width=2,
                                    lengths=[3, 6], seed=6)
    ops.paged_attention(q, kv, table, lens, interpret=True)
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# dispatch table (common.paged_attn_backend)
# ---------------------------------------------------------------------------


def test_paged_attn_backend_dispatch(monkeypatch):
    """The resolver shares ops.resolve_backend with the linears; MLA /
    bf16_io / pure-SSM configs pin the documented fallbacks."""
    dense = get_config("stablelm_3b").reduced()
    assert cm.paged_attn_backend(dense, None) == "xla"          # CPU auto
    assert cm.paged_attn_backend(
        dense, QuantPolicy(use_kernels="interpret")) == "interpret"
    assert cm.paged_attn_backend(
        dense, QuantPolicy(use_kernels="never")) == "xla"
    monkeypatch.setattr(ops, "use_pallas", lambda backend="auto": True)
    assert cm.paged_attn_backend(dense, None) == "pallas"       # TPU auto
    mla = get_config("deepseek_v2_lite_16b").reduced()
    assert cm.paged_attn_backend(
        mla, QuantPolicy(use_kernels="interpret")) == "xla"     # latent gather
    import dataclasses

    bf16io = dataclasses.replace(dense, attn_bf16_io=True)
    assert cm.paged_attn_backend(
        bf16io, QuantPolicy(use_kernels="interpret")) == "xla"
    ssm = get_config("mamba2_780m").reduced()
    assert cm.paged_attn_backend(ssm, None) == "none"


# ---------------------------------------------------------------------------
# flash_decode under per-slot length vectors (satellite)
# ---------------------------------------------------------------------------


def _dense_case(b, S, hq, hkv, d, *, quantized=False, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, S, hkv, d)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, S, hkv, d)).astype(jnp.bfloat16)
    layer_kv = {"k": k, "v": v}
    if quantized:
        kq, ksc = _quant_pool(k)
        vq, vsc = _quant_pool(v)
        layer_kv = {"k": kq, "v": vq, "k_scale": ksc, "v_scale": vsc}
    return q, layer_kv


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
def test_flash_decode_per_slot_length_vector(quantized):
    """flash_decode with a (b,) depth vector == the masked reference at
    each row's own depth — the batched engine's ONE (max_slots, 1) tick
    is now flash-eligible, not just scalar-length callers.  (The autouse
    test mesh provides the 'model' axis the shard_map needs.)"""
    q, layer_kv = _dense_case(3, 16, 4, 2, 16, quantized=quantized, seed=7)
    valid = jnp.array([5, 16, 1], jnp.int32)
    out = cm.flash_decode(q, layer_kv, valid, dp_spec=None)
    kc, vc = cm.cache_read(layer_kv)
    want = cm.attention_scores(q, kc, vc, causal=False, length=valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def test_flash_decode_scalar_length_still_exact():
    """Scalar depths (the original contract) broadcast to the vector
    path unchanged."""
    q, layer_kv = _dense_case(2, 8, 4, 4, 16, seed=8)
    out = cm.flash_decode(q, layer_kv, 6, dp_spec=None)
    want = cm.attention_scores(q, layer_kv["k"], layer_kv["v"], causal=False,
                               length=6)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
