"""The dense transformer family (stablelm): pre-norm attention with
rotary and GQA, then a SwiGLU MLP; K and V live in the engine's paged
pool.

Everything the harness knows of the family is here, behind the names
every family module provides (``bench/test_families.py`` lists them):
the configuration keys, the weights, the plain reference's layer, what
the timed path's cache holds and how it is compared, and the work a
dispatch asks of the chip.  The reference part imports nothing of the
program; ``served_params`` and ``snapshot`` are the program's side.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.check import rel
from bench.reference import (F32, HIGHEST, act_round, embed, fold, frozen,
                             qlinear, rms, rtn)
from bench.work import linear

# the configuration keys the family reads -> the program's ModelConfig fields
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "norm_eps": "norm_eps",
}

# the CPU tests' sizes, and engine knobs to match
TINY = {
    "model": {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "intermediate_size": 128, "vocab_size": 256},
    "engine": {"max_slots": 4, "max_len": 160, "page_size": 16,
               "n_pages": None, "prefill_bucket": 16, "prefill_chunk": 32},
}

# the reference runs one sequence at a time (attention scores are T x T
# per head)
BATCHED = False


def program_fields(m: dict) -> dict:
    return {"head_dim": m["hidden_size"] // m["num_attention_heads"]}


def shapes(m: dict) -> dict:
    """The derived sizes of one model description (``bench.spec.model``)."""
    return {**m, **program_fields(m)}


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _make(key, m: dict) -> dict:
    s = shapes(m)
    L, d, f, V = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], s["head_dim"]
    k = iter(jax.random.split(key, 16))
    lin = lambda a, b: {"w": W.normal(next(k), (L, a, b), a ** -0.5)}
    return {
        "embed": {"e": W.normal(next(k), (V, d), 0.02)},
        "layers": {
            "attn": {"wq": lin(d, hq * hd), "wk": lin(d, hkv * hd),
                     "wv": lin(d, hkv * hd), "wo": lin(hq * hd, d),
                     "ln": {"g": W.gain(next(k), (L, d))}},
            "mlp": {"wg": lin(d, f), "wu": lin(d, f), "wd": lin(f, d),
                    "ln": {"g": W.gain(next(k), (L, d))}},
        },
        "final_ln": {"g": W.gain(next(k), (d,))},
        "lm_head": {"w": W.normal(next(k), (d, V), d ** -0.5)},
    }


def weights(m: dict, seed: int) -> W.Weights:
    return W.sliced(W.whole_tree(_make, m, seed))


served_params = functools.partial(W.served_whole, _make)


# ---------------------------------------------------------------------------
# reference
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
def _calib_layer(x, lw, *, m, q):
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


def calibrate(weights: W.Weights, m: dict, q: dict, calib_tokens: np.ndarray):
    """Per-layer absmax of the down_proj input over the calibration rows."""
    x = embed(weights, calib_tokens)
    out = []
    for i in range(m["num_hidden_layers"]):
        x, amax = _calib_layer(x, weights.layer(i), m=frozen(m), q=frozen(q))
        out.append(amax)
    return out


@functools.partial(jax.jit, static_argnames=("m", "q"))
def _fold_layer(lw, absmax_down, *, m, q):
    bits, alpha = q["weight_bits"], q["alpha"]
    f = lambda w, am=None: fold(w["w"], am, bits=bits, alpha=alpha)
    a, p = lw["attn"], lw["mlp"]
    return {"wq": f(a["wq"]), "wk": f(a["wk"]), "wv": f(a["wv"]),
            "wo": f(a["wo"]), "wg": f(p["wg"]), "wu": f(p["wu"]),
            "wd": f(p["wd"], absmax_down),
            "ln1": a["ln"]["g"], "ln2": p["ln"]["g"]}


def fold_layer(lw, i: int, calib, m, q):
    return _fold_layer(lw, calib[i], m=m, q=q)


@functools.partial(jax.jit, static_argnames=("m", "q", "p"))
def _layer(x, fw, *, m, q, p):
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


def layer(xs: list, fw, valid: list[int], m, q, p) -> tuple[list, list]:
    """One folded layer over each sequence's states; per sequence, the
    cache content (K, V) of its first ``valid`` positions."""
    out, caches = [], []
    for x, n in zip(xs, valid):
        x, kh, vh = _layer(x, fw, m=m, q=q, p=p)
        out.append(x)
        caches.append((kh[:n], vh[:n]))
    return out, caches


# ---------------------------------------------------------------------------
# the timed path's cache, against the reference's
# ---------------------------------------------------------------------------


def snapshot(eng, slot: int, layer: int, n: int) -> tuple:
    """The K and V (dequantized, float32) the timed path wrote for the
    first ``n`` positions of ``slot`` at ``layer``, read from its pages."""
    if n != int(eng._len[slot]):
        raise RuntimeError(f"slot {slot} holds {eng._len[slot]} cached "
                           f"positions, its sequence {n}")
    cache = eng.cache
    pages = eng._pt[slot, :math.ceil(n / eng.page_size)]

    def read(data, scale):
        d = np.asarray(data[layer, pages], np.float32)
        if scale is not None:
            d = d * np.asarray(scale[layer, pages], np.float32)
        return d.reshape(-1, *d.shape[2:])[:n]

    return read(cache.k, cache.k_scale), read(cache.v, cache.v_scale)


def cache_numbers(prog: dict, got: dict, layers) -> dict:
    """``kv_l<i>``: the larger relative L2 error of K and V over every
    sampled sequence's cached positions at layer i."""
    out = {}
    for l in layers:
        k = rel([prog[j][l][0] for j in range(len(got[l]))],
                [g[0] for g in got[l]])
        v = rel([prog[j][l][1] for j in range(len(got[l]))],
                [g[1] for g in got[l]])
        out[f"kv_l{l}"] = max(k, v)
    return out


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------


def linears(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each quantized linear of one layer."""
    s = shapes(m)
    d = m["hidden_size"]
    hq, hkv, hd, f = (m["num_attention_heads"], m["num_key_value_heads"],
                      s["head_dim"], m["intermediate_size"])
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d),
            (d, f), (d, f), (f, d)]


def linear_work(m: dict, dispatch, w_bits: int) -> list:
    """Every layer runs every linear over all the dispatch's rows."""
    M = dispatch.rows * dispatch.seq
    return [(m["num_hidden_layers"],
             [linear(M, K, N, w_bits) for K, N in linears(m)])]


def paged_attention(contexts: list[int], m: dict, kv_bits: int
                    ) -> tuple[float, float]:
    """One layer's decode attention over the live positions of each row
    (``contexts`` counts the new token too)."""
    s = shapes(m)
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], s["head_dim"]
    n = sum(contexts)
    ops = 4.0 * n * hq * hd
    per_pos = hkv * (2 * hd * kv_bits / 8 + (2 * 4 if kv_bits < 16 else 0))
    nbytes = n * per_pos + len(contexts) * hq * hd * 2 * 2
    return ops, nbytes


def attention_work(m: dict, dispatch, kv_bits: int) -> list:
    """A decode dispatch's paged attention, the same in every layer."""
    contexts = [s + 1 for s in dispatch.starts]
    return [(m["num_hidden_layers"], [paged_attention(contexts, m, kv_bits)])]


def token_ops(m: dict, context: int) -> tuple[float, float]:
    """(int8 ops, bf16 ops) one token needs through the whole model at
    position ``context``: the quantized linears in int8; attention and
    the output head in bf16."""
    s = shapes(m)
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    int8 = 2.0 * L * sum(K * N for K, N in linears(m))
    per_layer = 4 * (context + 1) * m["num_attention_heads"] * s["head_dim"]
    return int8, L * per_layer + 2.0 * d * V
