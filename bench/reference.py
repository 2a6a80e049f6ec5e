"""Plain float32 reference of the served models, and its lower-precision
control.

Nothing here imports the program or takes anything it has made.  The
weights are the benchmark's own (``bench/weights.py``); the quantized
model is built again from its definition: the paper's fold (SmoothQuant
scales from calibration absmax on down_proj inputs, alpha 0.5, then a
Hadamard rotation of every linear's input, Kronecker-built from a Paley-I
and a Sylvester factor as in QuIP#), symmetric round-to-nearest weights
per output channel and activations per token, and a KV cache rounded per
token and head.  All arithmetic is float32 with matmuls at ``HIGHEST``.

The model runs layer by layer over whole sequences (teacher-forced on
prompt plus served tokens), holding one folded layer at a time, so that
it fits beside nothing else: after the window, with the program's state
freed.  What is particular to a model family (its layer, its fold, its
calibration, and whether sequences run batched) is in
``bench/families/<family>.py``; this module holds what every family
shares and the entry points that walk the layers.

``Precision`` names what the configuration states (bf16 activations,
int8 KV, float32 SSM state) and the control's step below each of them
(float8 e4m3 activations, int4 KV, bf16 state).  The linears stay W4A4
in both: nothing lies below 4 bits in the ladder.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Precision:
    """Where values are rounded, beyond the W4A4 linears."""

    act: str = "f32"          # "f32" | "e4m3": linear inputs, residual stream
    kv_bits: int = 8          # KV cache rounding (per token and head)
    state: str = "f32"        # "f32" | "bf16": the SSM recurrent state


SOUND = Precision()
CONTROL = Precision(act="e4m3", kv_bits=4, state="bf16")


# ---------------------------------------------------------------------------
# Hadamard rotation
# ---------------------------------------------------------------------------


def _is_prime(n: int) -> bool:
    return n > 1 and all(n % f for f in range(2, math.isqrt(n) + 1))


def _sylvester(n: int) -> np.ndarray:
    h = np.ones((1, 1))
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


def _paley(n: int) -> np.ndarray:
    """Paley-I Hadamard matrix of order n = q + 1, q prime, q = 3 mod 4."""
    q = n - 1
    residues = {(i * i) % q for i in range(1, q)}
    chi = np.array([0] + [1 if x in residues else -1 for x in range(1, q)])
    jac = chi[(np.arange(q)[:, None] - np.arange(q)[None, :]) % q]
    h = np.ones((n, n))
    h[1:, 0] = -1
    h[1:, 1:] = jac + np.eye(q)
    return h


@functools.lru_cache(maxsize=None)
def rotation_factors(d: int) -> tuple[np.ndarray, ...]:
    """Orthonormal Kronecker factors of the d x d rotation: the power of
    two alone (Sylvester), or the smallest Paley-I order m * 2^k (m the
    odd part of d) followed by the Sylvester factor of the rest."""
    a = (d & -d).bit_length() - 1
    m = d >> a
    if m == 1:
        mats = [_sylvester(d)]
    else:
        for k in range(a + 1):
            size = m << k
            if _is_prime(size - 1) and (size - 1) % 4 == 3:
                mats = [_paley(size)]
                if d // size > 1:
                    mats.append(_sylvester(d // size))
                break
        else:
            raise ValueError(f"no Paley-I construction for d={d}")
    return tuple(h / math.sqrt(h.shape[0]) for h in mats)


def rotate(x: jax.Array, d: int) -> jax.Array:
    """x @ R along the last axis, one Kronecker factor at a time."""
    facs = rotation_factors(d)
    lead = x.shape[:-1]
    y = x.reshape(*lead, *(f.shape[0] for f in facs))
    for i, f in enumerate(facs):
        ax = len(lead) + i
        y = jnp.moveaxis(jnp.tensordot(y, jnp.asarray(f, F32), ((ax,), (0,)),
                                       precision=HIGHEST), -1, ax)
    return y.reshape(*lead, d)


# ---------------------------------------------------------------------------
# rounding
# ---------------------------------------------------------------------------


def rtn(x: jax.Array, bits: int, axis: int) -> jax.Array:
    """Symmetric round-to-nearest on 2^(bits-1) - 1 levels, absmax scale
    per slice along ``axis``, back in float32."""
    lv = 2 ** (bits - 1) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax) / lv
    return jnp.clip(jnp.round(x / scale), -lv, lv) * scale


def act_round(x: jax.Array, p: Precision) -> jax.Array:
    if p.act == "e4m3":
        return jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(F32)
    return x


def state_round(x: jax.Array, p: Precision) -> jax.Array:
    return x.astype(jnp.bfloat16).astype(F32) if p.state == "bf16" else x


# ---------------------------------------------------------------------------
# the folded W4A4 linear
# ---------------------------------------------------------------------------


def fold(w: jax.Array, absmax: jax.Array | None, *, bits: int,
         alpha: float) -> tuple[jax.Array, jax.Array | None]:
    """(c_in, c_out) weight -> (rotated, quantized weight, smoothing s)."""
    w = w.astype(F32)
    s = None
    if absmax is not None:
        aw = jnp.max(jnp.abs(w), axis=-1)
        s = jnp.maximum(jnp.maximum(absmax, 1e-8) ** alpha
                        / jnp.maximum(aw, 1e-8) ** (1.0 - alpha), 1e-8)
        w = w * s[:, None]
    w = rotate(w.T, w.shape[0]).T
    return rtn(w, bits, axis=0), s


def qlinear(x: jax.Array, wq: jax.Array, s: jax.Array | None, *,
            act_bits: int) -> jax.Array:
    if s is not None:
        x = x / s
    x = rtn(rotate(x, x.shape[-1]), act_bits, axis=-1)
    return jnp.matmul(x, wq, precision=HIGHEST)


def rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * inv * g.astype(F32)


def _logits(x, g, w, *, m):
    return jnp.matmul(rms(x, g, m["norm_eps"]), w.astype(F32),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _bucket(n: int, b: int = 256) -> int:
    return -(-n // b) * b


class Frozen(dict):
    """A hashable dict, so a model description can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def frozen(d: dict) -> Frozen:
    return d if isinstance(d, Frozen) else Frozen(d)


def embed(weights, tokens) -> jax.Array:
    return jnp.take(weights.top["embed"]["e"], jnp.asarray(tokens), 0).astype(F32)


def forward(weights, m: dict, q: dict, seqs: list[np.ndarray],
            valid: list[int], precisions: tuple[Precision, ...],
            keep_layers: tuple[int, ...], calib_absmax=None):
    """Teacher-forced forward of ``seqs`` under each precision, one
    folded layer at a time (``weights`` is a ``bench.weights.Weights``).

    Returns, per precision, a dict with ``x``: per sequence the final
    hidden states (T_bucket, d), and ``cache``: per kept layer, per
    sequence, the cache content over the first ``valid`` positions, as
    the family's ``layer`` gives it.
    """
    fam = spec.family(m["family"])
    mf, qf = frozen(m), frozen(q)
    pad = lambda s, n: np.pad(s, (0, n - len(s)))
    if fam.BATCHED:
        width = max(_bucket(len(s)) for s in seqs)
        xs = {p: embed(weights, np.stack([pad(s, width) for s in seqs]))
              for p in precisions}
    else:
        xs = {p: [embed(weights, pad(s, _bucket(len(s)))) for s in seqs]
              for p in precisions}
    kept = {p: {} for p in precisions}
    for i in range(m["num_hidden_layers"]):
        fw = fam.fold_layer(weights.layer(i), i, calib_absmax, mf, qf)
        for p in precisions:
            xs[p], caches = fam.layer(xs[p], fw, valid, mf, qf, p)
            if i in keep_layers:
                kept[p][i] = caches
        del fw
    return {p: {"x": list(xs[p]), "cache": kept[p]} for p in precisions}


@functools.partial(jax.jit, static_argnames=("m",))
def _gaps(x, g, w, rows, toks, x_alt, *, m):
    """Gap of each token below the best logit, at the given rows of the
    reference's final states ``x``: for ``toks`` (the served tokens), and
    for the token that ``x_alt`` (the control) puts first."""
    lg = _logits(x[rows], g, w, m=m)
    best = jnp.max(lg, -1)
    served = best - jnp.take_along_axis(lg, toks[:, None], 1)[:, 0]
    alt = jnp.argmax(_logits(x_alt[rows], g, w, m=m), -1)
    alt_gap = best - jnp.take_along_axis(lg, alt[:, None], 1)[:, 0]
    return served, alt_gap


def gaps(weights, m: dict, x_ref, x_alt, seq: np.ndarray,
         n_prompt: int) -> tuple[np.ndarray, np.ndarray]:
    """Per served token of one sequence (prompt then served tokens): how
    far its reference logit lies below the reference's best, and the
    same for the token the control ranks first at that position."""
    rows = np.arange(n_prompt - 1, len(seq) - 1)
    served, alt = _gaps(x_ref, weights.top["final_ln"]["g"],
                        weights.top["lm_head"]["w"], jnp.asarray(rows),
                        jnp.asarray(seq[n_prompt:]), x_alt, m=frozen(m))
    return np.asarray(served), np.asarray(alt)
