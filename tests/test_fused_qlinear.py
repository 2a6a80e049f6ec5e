"""One-pass fused quant-linear kernel + unified backend dispatch.

Parity contract: ``fused_qlinear`` (interpret mode) must match the
``ref.fused_qlinear_ref`` oracle and ``qlinear``'s XLA path across the
full matrix {packed int4, unpacked int8} × {smooth, no-smooth} ×
{had_dim 0/rotated} × {had_mask None/0/1} × {act_bits 4, 8}, including
the serving engine's (max_slots, 1) decode shape.  Codes may flip ±1 on
exact rounding ties (bf16 inputs hit x/Δ = .5 often; XLA fuses the
divide differently than the interpreter), so comparisons are
tensor-level relative norms, not exact — matching tests/test_kernels.py.

Dispatch contract: ``ops.resolve_backend`` is the ONE authority mapping
``QuantPolicy.use_kernels`` to {pallas, xla, interpret}; ``qlinear``
must route auto/interpret through ``ops.fused_qlinear`` (ONE
``pallas_call`` per linear — asserted by counting kernel launches),
with NO XLA fallback for had_mask-gated mixed layerwise stacks.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hadamard import apply_hadamard
from repro.core.qlinear import QuantPolicy, qlinear, quantize_weight
from repro.core.quantizer import pack_int4, unpack_int4
from repro.kernels import fused_qlinear as fq
from repro.kernels import ops, ref
from repro.kernels import quant_matmul as qm
from repro.serving.engine import ServingEngine

KEY = jax.random.PRNGKey(0)


def _rel(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-9)


def _mk_qw(d, m, *, w_bits=4, packed=True, smooth=False, had=True,
           had_mask=None, seed=1):
    """Fold a weight the way serving/fold.py would: smooth scaling first,
    then Rᵀ — except un-rotated layers of a mixed stack (had_mask=0),
    whose weights keep had_dim metadata but no rotation."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (d, m)) * 0.05
    s = None
    wf = w.astype(jnp.float32)
    if smooth:
        s = jnp.abs(jax.random.normal(jax.random.PRNGKey(seed + 1), (d,))) + 0.5
        wf = wf * s[:, None]
    if had and (had_mask is None or had_mask > 0):
        wf = apply_hadamard(wf, axis=0)
    qw = quantize_weight(wf, bits=w_bits, pack=packed,
                         had_dim=d if had else 0, smooth=s)
    if had and had_mask is not None:
        qw = dc.replace(qw, had_mask=jnp.asarray(float(had_mask)))
    return qw


# ---------------------------------------------------------------------------
# parity matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("w_bits,packed", [(4, True), (8, False)])
@pytest.mark.parametrize("smooth", [False, True])
@pytest.mark.parametrize("had", [False, True])
@pytest.mark.parametrize("had_mask", [None, 0, 1])
@pytest.mark.parametrize("act_bits", [4, 8])
def test_parity_matrix(w_bits, packed, smooth, had, had_mask, act_bits):
    if not had and had_mask is not None:
        pytest.skip("had_mask only gates rotated stacks")
    d, m = 256, 64
    x = jax.random.normal(KEY, (8, d)).astype(jnp.bfloat16)
    qw = _mk_qw(d, m, w_bits=w_bits, packed=packed, smooth=smooth, had=had,
                had_mask=had_mask)
    y_fused = fq.fused_qlinear(x, qw, act_bits=act_bits, interpret=True)
    y_ref = ref.fused_qlinear_ref(x, qw, act_bits=act_bits)
    y_xla = qlinear(x, qw, QuantPolicy(act_bits=act_bits,
                                       use_kernels="never"))
    assert _rel(y_fused, y_ref) < 0.05, (w_bits, smooth, had, had_mask)
    assert _rel(y_fused, y_xla) < 0.06, (w_bits, smooth, had, had_mask)


@pytest.mark.parametrize("d,structure", [
    (1536, "paley-kronecker"),   # Paley_12 ⊗ H_128: leading factor in XLA
    (4096, "sylvester-split"),   # H_512 ⊗ H_8: the decode hot-path dim class
    (12, "pure-paley"),          # no fusable trailing factor: XLA rotation
    (24, "block-fallback"),      # grouped H_8 within 3 groups, fully fused
    # trailing factors under 128 lanes run as kron(I_{128/b}, H_b)
    (64, "sylvester-sub128"),    # H_64 alone, padded to one 128-lane group
    (768, "paley-sub128"),       # Paley_12 ⊗ H_64
    (6912, "down-proj"),         # Paley_108 ⊗ H_64: stablelm-3b's d_ff
])
def test_parity_structured_dims(d, structure):
    m = 32
    x = jax.random.normal(KEY, (5, d)).astype(jnp.bfloat16)
    qw = _mk_qw(d, m, smooth=True, had=True)
    y_fused = fq.fused_qlinear(x, qw, interpret=True)
    y_ref = ref.fused_qlinear_ref(x, qw)
    y_xla = qlinear(x, qw, QuantPolicy(use_kernels="never"))
    assert _rel(y_fused, y_ref) < 0.05, structure
    assert _rel(y_fused, y_xla) < 0.06, structure


def test_unpack_nibbles_matches_unpack_int4():
    """The kernel's in-VMEM unpack (int32 shifts) reads every byte value
    exactly as the XLA path's ``unpack_int4`` does: the packing format
    is unchanged."""
    codes = jnp.asarray(np.arange(-8, 8, dtype=np.int8))
    pairs = jnp.stack(jnp.meshgrid(codes, codes, indexing="ij"), -1)
    q = pairs.reshape(-1, 2).T.reshape(2, 256)           # (k=2, m=256)
    packed = jnp.swapaxes(pack_int4(jnp.swapaxes(q, 0, 1)), 0, 1)
    want = jnp.swapaxes(unpack_int4(jnp.swapaxes(packed, 0, 1)), 0, 1)
    np.testing.assert_array_equal(np.asarray(qm._unpack_nibbles(packed)),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(q))


@pytest.mark.parametrize("had_mask", [0, 1])
def test_had_mask_gates_multifactor_on_fused_path(had_mask):
    """Mixed layerwise stacks on a Kronecker dim: the XLA pre-stage and
    the in-kernel trailing factor must gate CONSISTENTLY on the scalar."""
    d, m = 1536, 32
    x = jax.random.normal(KEY, (4, d)).astype(jnp.bfloat16)
    qw = _mk_qw(d, m, smooth=True, had=True, had_mask=had_mask)
    y_fused = fq.fused_qlinear(x, qw, interpret=True)
    y_xla = qlinear(x, qw, QuantPolicy(use_kernels="never"))
    assert _rel(y_fused, y_xla) < 0.06


def test_decode_slot_shapes():
    """The engine's (max_slots, 1) tick reaches qlinear as (slots·1, d)
    rows — tall-skinny tiles must pad, not degrade to divisor-1 grids."""
    d = 256
    qw = _mk_qw(d, 64, smooth=True, had=True)
    for slots in (1, 3, 4):
        x = jax.random.normal(KEY, (slots, 1, d)).astype(jnp.bfloat16)
        y_i = qlinear(x, qw, QuantPolicy(use_kernels="interpret"))
        y_r = ref.fused_qlinear_ref(x.reshape(slots, d), qw)
        y_x = qlinear(x, qw, QuantPolicy(use_kernels="never"))
        assert y_i.shape == (slots, 1, 64)
        assert _rel(y_i.reshape(slots, 64), y_r) < 0.05, slots
        # few rows × few cols: single ±1 tie flips carry more relative
        # weight than in the matrix tests — loose bound vs the bf16 XLA
        # path, tight bound vs the oracle above
        assert _rel(y_i, y_x) < 0.12, slots


def test_fused_matches_staged_composition():
    """The one-pass kernel must agree with the staged 3-round-trip
    composition it replaces (ops.fused_quant_matmul)."""
    d, m = 1536, 64
    x = jax.random.normal(KEY, (8, d)).astype(jnp.bfloat16)
    qw = _mk_qw(d, m, smooth=True, had=True)
    y_fused = fq.fused_qlinear(x, qw, interpret=True)
    y_staged = ops.fused_quant_matmul(x, qw, interpret=True)
    assert _rel(y_fused, y_staged) < 0.05


# ---------------------------------------------------------------------------
# dispatch: ops.resolve_backend is the single authority
# ---------------------------------------------------------------------------


def test_resolve_backend_table(monkeypatch):
    assert ops.resolve_backend("never") == "xla"
    assert ops.resolve_backend("interpret") == "interpret"
    assert ops.resolve_backend("auto") == "xla"        # CPU test host
    monkeypatch.setattr(ops, "use_pallas", lambda backend="auto": True)
    assert ops.resolve_backend("auto") == "pallas"     # TPU host
    with pytest.raises(ValueError):
        ops.resolve_backend("sometimes")


def test_auto_routes_through_ops_fused_qlinear(monkeypatch):
    """Regression (the PR-3 dispatch gap): use_kernels="auto" on a TPU
    host must call ops.fused_qlinear with interpret=False — the seed
    routed auto to the XLA path and never exercised the kernels."""
    d = 256
    x = jax.random.normal(KEY, (4, d)).astype(jnp.bfloat16)
    qw = _mk_qw(d, 32, had=True)
    seen = {}

    def recording(x2, qw_, *, act_bits=4, interpret=False):
        seen["interpret"] = interpret
        return fq.fused_qlinear(x2, qw_, act_bits=act_bits, interpret=True)

    monkeypatch.setattr(ops, "use_pallas", lambda backend="auto": True)
    monkeypatch.setattr(ops, "fused_qlinear", recording)
    qlinear(x, qw, QuantPolicy(use_kernels="auto"))
    assert seen == {"interpret": False}


def test_auto_on_cpu_and_never_stay_on_xla(monkeypatch):
    """auto (CPU host) and never must not touch the kernel layer."""
    d = 256
    x = jax.random.normal(KEY, (4, d)).astype(jnp.bfloat16)
    qw = _mk_qw(d, 32, had=True)

    def boom(*a, **k):
        raise AssertionError("XLA mode must not reach ops.fused_qlinear")

    monkeypatch.setattr(ops, "fused_qlinear", boom)
    qlinear(x, qw, QuantPolicy(use_kernels="auto"))
    qlinear(x, qw, QuantPolicy(use_kernels="never"))


@pytest.mark.parametrize("case", ["plain", "smooth_had", "had_mask",
                                  "kronecker"])
def test_interpret_issues_exactly_one_pallas_call(case, monkeypatch):
    """ONE pallas_call — one activation HBM read, one bf16 write — per
    quantized linear on the fused path, INCLUDING had_mask-gated mixed
    stacks (previously forced onto the XLA fallback)."""
    d = 1536 if case == "kronecker" else 256
    qw = {
        "plain": lambda: _mk_qw(d, 32, had=False),
        "smooth_had": lambda: _mk_qw(d, 32, smooth=True, had=True),
        "had_mask": lambda: _mk_qw(d, 32, smooth=True, had=True, had_mask=0),
        "kronecker": lambda: _mk_qw(d, 32, smooth=True, had=True, had_mask=1),
    }[case]()
    x = jax.random.normal(KEY, (4, d)).astype(jnp.bfloat16)
    calls = []
    orig = fq._pallas_call
    monkeypatch.setattr(fq, "_pallas_call",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
    qlinear(x, qw, QuantPolicy(use_kernels="interpret"))
    assert len(calls) == 1, case


def test_engine_reports_resolved_backend():
    """The serving engine surfaces the resolved dispatch for ops teams;
    it must mirror ops.resolve_backend, not re-derive it."""
    eng = object.__new__(ServingEngine)
    eng.policy = QuantPolicy(use_kernels="interpret")
    assert eng.kernel_backend == "interpret"
    eng.policy = QuantPolicy(use_kernels="never")
    assert eng.kernel_backend == "xla"
    eng.policy = None
    assert eng.kernel_backend == "bf16"
