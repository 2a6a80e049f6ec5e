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
prompt plus served tokens), so that it fits beside nothing else: after
the window, with the program's state freed.

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


def _layer(tree, i: int):
    return jax.tree.map(lambda a: a[i], tree)


# ---------------------------------------------------------------------------
# dense transformer (stablelm family)
# ---------------------------------------------------------------------------


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary over the whole head, halves paired: x (T, h, d)."""
    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(t, dtype=F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attend(q, k, v):
    """Causal attention, q/k/v (T, h, d), kv heads repeated for GQA."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    t = q.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("m", "q"))
def _dense_calib_layer(x, lw, *, m, q):
    """One unquantized float32 layer over calibration rows x (B, T, d);
    returns the next x and the absmax of the down_proj input."""
    del q
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    a, f = lw["attn"], lw["mlp"]
    mm = lambda u, w: jnp.matmul(u, w["w"].astype(F32), precision=HIGHEST)

    def one(xr):
        t, d = xr.shape
        h = rms(xr, a["ln"]["g"], eps)
        qh = _rope(mm(h, a["wq"]).reshape(t, hq, -1), theta)
        kh = _rope(mm(h, a["wk"]).reshape(t, hkv, -1), theta)
        vh = mm(h, a["wv"]).reshape(t, hkv, -1)
        xr = xr + mm(_attend(qh, kh, vh).reshape(t, -1), a["wo"])
        h = rms(xr, f["ln"]["g"], eps)
        act = jax.nn.silu(mm(h, f["wg"])) * mm(h, f["wu"])
        return xr + mm(act, f["wd"]), jnp.max(jnp.abs(act), 0)

    xs, amax = jax.vmap(one)(x)
    return xs, jnp.max(amax, 0)


@functools.partial(jax.jit, static_argnames=("m", "q"))
def _dense_fold_layer(lw, absmax_down, *, m, q):
    bits, alpha = q["weight_bits"], q["alpha"]
    f = lambda w, am=None: fold(w["w"], am, bits=bits, alpha=alpha)
    a, p = lw["attn"], lw["mlp"]
    return {"wq": f(a["wq"]), "wk": f(a["wk"]), "wv": f(a["wv"]),
            "wo": f(a["wo"]), "wg": f(p["wg"]), "wu": f(p["wu"]),
            "wd": f(p["wd"], absmax_down),
            "ln1": a["ln"]["g"], "ln2": p["ln"]["g"]}


@functools.partial(jax.jit, static_argnames=("m", "q", "p"))
def _dense_layer(x, fw, *, m, q, p):
    """One folded layer over one sequence x (T, d); returns the next x
    and the layer's K and V as the cache holds them (after rotary and
    rounding)."""
    t = x.shape[0]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    eps, theta, ab = m["norm_eps"], m["rope_theta"], q["act_bits"]
    lin = lambda u, name: qlinear(act_round(u, p), *fw[name], act_bits=ab)
    h = rms(x, fw["ln1"], eps)
    qh = _rope(lin(h, "wq").reshape(t, hq, -1), theta)
    kh = rtn(_rope(lin(h, "wk").reshape(t, hkv, -1), theta), p.kv_bits, -1)
    vh = rtn(lin(h, "wv").reshape(t, hkv, -1), p.kv_bits, -1)
    x = act_round(x + lin(_attend(qh, kh, vh).reshape(t, -1), "wo"), p)
    h = rms(x, fw["ln2"], eps)
    act = jax.nn.silu(lin(h, "wg")) * lin(h, "wu")
    x = act_round(x + lin(act, "wd"), p)
    return x, kh, vh


# ---------------------------------------------------------------------------
# Mamba2 (SSD) family
# ---------------------------------------------------------------------------


def _ssm_sizes(m):
    di = m["expand"] * m["hidden_size"]
    gn = m["n_groups"] * m["state_size"]
    return di, gn, di // m["head_dim"]


def _ssm_block(x, lw, proj, m, p, valid):
    """One Mamba2 block over one sequence x (T, d), the recurrence run
    token by token; positions at or past ``valid`` leave the state as it
    is.  ``proj(u, name)`` applies in_proj / out_proj.  Returns the next
    x and the decode state after ``valid`` tokens: the SSM state
    (h, p, n) and the conv tail, the last ``conv_kernel - 1`` conv inputs
    (zeros before the start)."""
    di, gn, h = _ssm_sizes(m)
    pd, n, g = m["head_dim"], m["state_size"], m["n_groups"]
    t = x.shape[0]
    u = rms(x, lw["ln"]["g"], m["norm_eps"])
    zxbcdt = proj(u, "in_proj")
    z, xc = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * gn]
    dt = zxbcdt[:, 2 * di + 2 * gn:]
    k = lw["conv_w"].shape[0]
    pad = jnp.pad(xc, ((k - 1, 0), (0, 0)))
    conv = sum(pad[i:i + t] * lw["conv_w"][i].astype(F32) for i in range(k))
    conv = jax.nn.silu(conv + lw["conv_b"].astype(F32))
    xs = conv[:, :di].reshape(t, h, pd)
    B = jnp.repeat(conv[:, di:di + gn].reshape(t, g, n), h // g, 1)
    C = jnp.repeat(conv[:, di + gn:].reshape(t, g, n), h // g, 1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    dt = jnp.where(jnp.arange(t)[:, None] < valid, dt, 0.0)
    A = -jnp.exp(lw["A_log"])

    def step(S, inp):
        x1, b1, c1, dt1 = inp
        S = state_round(S * jnp.exp(dt1 * A)[:, None, None]
                        + jnp.einsum("hn,h,hp->hpn", b1, dt1, x1,
                                     precision=HIGHEST), p)
        y1 = jnp.einsum("hn,hpn->hp", c1, S, precision=HIGHEST)
        return S, y1 + x1 * lw["D"][:, None]

    S, y = jax.lax.scan(step, jnp.zeros((h, pd, n), F32), (xs, B, C, dt))
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = rms(y, lw["gate_ln"]["g"], m["norm_eps"])
    idx = valid - (k - 1) + jnp.arange(k - 1)
    tail = jnp.where((idx >= 0)[:, None], xc[jnp.maximum(idx, 0)], 0.0)
    return act_round(x + proj(y, "out_proj"), p), (S, tail)


@functools.partial(jax.jit, static_argnames=("m", "q"))
def _ssm_fold_layer(lw, *, m, q):
    bits, alpha = q["weight_bits"], q["alpha"]
    out = dict(lw)
    for name in ("in_proj", "out_proj"):
        out[name] = fold(lw[name]["w"], None, bits=bits, alpha=alpha)
    return out


@functools.partial(jax.jit, static_argnames=("m", "q", "p"))
def _ssm_layer(x, fw, valid, *, m, q, p):
    """One folded Mamba2 layer over a batch of sequences x (B, T, d)."""
    proj = lambda u, name: qlinear(act_round(u, p), *fw[name],
                                   act_bits=q["act_bits"])
    return jax.vmap(lambda xb, vb: _ssm_block(xb, fw, proj, m, p, vb))(x, valid)


def _logits(x, g, w, *, m):
    return jnp.matmul(rms(x, g, m["norm_eps"]), w.astype(F32),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def _bucket(n: int, b: int = 256) -> int:
    return -(-n // b) * b


def calibrate(weights, m: dict, q: dict, calib_tokens: np.ndarray):
    """Per-layer absmax of the down_proj input over the calibration rows
    (dense family; the SSM family's plan smooths nothing)."""
    if m["family"] == "ssm":
        return None
    x = jnp.take(weights["embed"]["e"], jnp.asarray(calib_tokens), 0).astype(F32)
    out = []
    for i in range(m["num_hidden_layers"]):
        x, amax = _dense_calib_layer(x, _layer(weights["layers"], i),
                                     m=_frozen(m), q=_frozen(q))
        out.append(amax)
    return out


class _Frozen(dict):
    """A hashable dict, so a model description can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _frozen(d: dict) -> _Frozen:
    return d if isinstance(d, _Frozen) else _Frozen(d)


def forward(weights, m: dict, q: dict, seqs: list[np.ndarray],
            valid: list[int], precisions: tuple[Precision, ...],
            keep_layers: tuple[int, ...], calib_absmax=None):
    """Teacher-forced forward of ``seqs`` under each precision.

    Returns, per precision, a dict with ``x``: per sequence the final
    hidden states (T_bucket, d), and ``cache``: per kept layer, per
    sequence, the cache content over the first ``valid`` positions
    (dense: (K, V) as rounded for the cache; ssm: the SSM state and the
    conv tail after ``valid`` tokens).
    """
    mf, qf = _frozen(m), _frozen(q)
    ssm = m["family"] == "ssm"
    # the SSM family runs every sequence at once (its token-by-token
    # recurrence is long and narrow); the dense family one at a time
    # (its attention scores are T x T per head)
    width = max(_bucket(len(s)) for s in seqs)
    pad = lambda s, n: np.pad(s, (0, n - len(s)))
    embed = lambda t: jnp.take(weights["embed"]["e"], jnp.asarray(t), 0).astype(F32)
    if ssm:
        xs = {p: [embed(np.stack([pad(s, width) for s in seqs]))]
              for p in precisions}
        vlen = jnp.asarray(valid, jnp.int32)
    else:
        xs = {p: [embed(pad(s, _bucket(len(s)))) for s in seqs]
              for p in precisions}
    kept = {p: {} for p in precisions}
    for i in range(m["num_hidden_layers"]):
        lw = _layer(weights["layers"], i)
        if ssm:
            fw = _ssm_fold_layer(lw, m=mf, q=qf)
        else:
            fw = _dense_fold_layer(lw, calib_absmax[i], m=mf, q=qf)
        for p in precisions:
            if ssm:
                xs[p][0], (S, tail) = _ssm_layer(xs[p][0], fw, vlen, m=mf,
                                                 q=qf, p=p)
                caches = list(zip(S, tail))
            else:
                caches = []
                for j, x in enumerate(xs[p]):
                    xs[p][j], kh, vh = _dense_layer(x, fw, m=mf, q=qf, p=p)
                    caches.append((kh[:valid[j]], vh[:valid[j]]))
            if i in keep_layers:
                kept[p][i] = caches
        del fw
    if ssm:
        xs = {p: list(xs[p][0]) for p in precisions}
    return {p: {"x": xs[p], "cache": kept[p]} for p in precisions}


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
    served, alt = _gaps(x_ref, weights["final_ln"]["g"],
                        weights["lm_head"]["w"], jnp.asarray(rows),
                        jnp.asarray(seq[n_prompt:]), x_alt, m=_frozen(m))
    return np.asarray(served), np.asarray(alt)
