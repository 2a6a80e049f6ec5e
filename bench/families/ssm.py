"""The Mamba2 (SSD) family: pre-norm blocks of in_proj, a depthwise
causal conv, the selective-state recurrence, a gated norm and out_proj;
each slot's decode state is its SSM state and conv tail.

The Mamba2 leaves follow the published initialisation: ``A = -exp(A_log)``
with ``exp(A_log)`` uniform on [1, 16], ``dt_bias`` uniform on [-4, -2]
(a softplus of about 0.02 to 0.13), ``D`` near 1, the depthwise conv
normal with std 0.2.  The reference part imports nothing of the program;
``served_params`` and ``snapshot`` are the program's side.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.check import rel
from bench.reference import (F32, HIGHEST, act_round, fold, qlinear, rms,
                             state_round)
from bench.work import linear

# the configuration keys the family reads -> the program's ModelConfig fields
FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "state_size": "ssm_state", "head_dim": "ssm_headdim",
    "expand": "ssm_expand", "conv_kernel": "ssm_conv",
    "n_groups": "ssm_ngroups", "vocab_size": "vocab_size",
    "norm_eps": "norm_eps",
}

# the CPU tests' sizes, and engine knobs to match
TINY = {
    "model": {"num_hidden_layers": 2, "hidden_size": 128, "state_size": 32,
              "head_dim": 32, "vocab_size": 256},
    "engine": {"max_slots": 4, "max_len": 160, "prefill_bucket": 32},
}

# the reference runs every sequence at once (the token-by-token
# recurrence is long and narrow)
BATCHED = True


def program_fields(m: dict) -> dict:
    return {}


def shapes(m: dict) -> dict:
    """The derived sizes of one model description (``bench.spec.model``)."""
    s = dict(m)
    s["d_inner"] = m["expand"] * m["hidden_size"]
    s["ssm_heads"] = s["d_inner"] // m["head_dim"]
    s["conv_ch"] = s["d_inner"] + 2 * m["n_groups"] * m["state_size"]
    s["d_in_proj"] = (2 * s["d_inner"] + 2 * m["n_groups"] * m["state_size"]
                      + s["ssm_heads"])
    return s


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _make(key, m: dict) -> dict:
    s = shapes(m)
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    di, h, cc = s["d_inner"], s["ssm_heads"], s["conv_ch"]
    k = iter(jax.random.split(key, 16))
    f32 = jnp.float32
    return {
        "embed": {"e": W.normal(next(k), (V, d), 0.02)},
        "layers": {
            "in_proj": {"w": W.normal(next(k), (L, d, s["d_in_proj"]), d ** -0.5)},
            "out_proj": {"w": W.normal(next(k), (L, di, d), di ** -0.5)},
            "conv_w": W.normal(next(k), (L, m["conv_kernel"], cc), 0.2),
            "conv_b": W.normal(next(k), (L, cc), 0.02),
            "A_log": jnp.log(jax.random.uniform(next(k), (L, h), f32, 1.0, 16.0)),
            "D": 1.0 + 0.1 * jax.random.normal(next(k), (L, h), f32),
            "dt_bias": jax.random.uniform(next(k), (L, h), f32, -4.0, -2.0),
            "ln": {"g": W.gain(next(k), (L, d))},
            "gate_ln": {"g": W.gain(next(k), (L, di))},
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


def calibrate(weights: W.Weights, m: dict, q: dict, calib_tokens: np.ndarray):
    """Nothing: the family's plan smooths nothing."""
    return None


def _block(x, lw, proj, m, p, valid):
    """One Mamba2 block over one sequence x (T, d), the recurrence run
    token by token; positions at or past ``valid`` leave the state as it
    is.  ``proj(u, name)`` applies in_proj / out_proj.  Returns the next
    x and the decode state after ``valid`` tokens: the SSM state
    (h, p, n) and the conv tail, the last ``conv_kernel - 1`` conv inputs
    (zeros before the start)."""
    s = shapes(m)
    di, gn, h = s["d_inner"], m["n_groups"] * m["state_size"], s["ssm_heads"]
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
def _fold_layer(lw, *, m, q):
    bits, alpha = q["weight_bits"], q["alpha"]
    out = dict(lw)
    for name in ("in_proj", "out_proj"):
        out[name] = fold(lw[name]["w"], None, bits=bits, alpha=alpha)
    return out


def fold_layer(lw, i: int, calib, m, q):
    return _fold_layer(lw, m=m, q=q)


@functools.partial(jax.jit, static_argnames=("m", "q", "p"))
def _layer(x, fw, valid, *, m, q, p):
    """One folded Mamba2 layer over a batch of sequences x (B, T, d)."""
    proj = lambda u, name: qlinear(act_round(u, p), *fw[name],
                                   act_bits=q["act_bits"])
    return jax.vmap(lambda xb, vb: _block(xb, fw, proj, m, p, vb))(x, valid)


def layer(x, fw, valid: list[int], m, q, p) -> tuple:
    """One folded layer over every sequence's states at once; per
    sequence, its (SSM state, conv tail) after ``valid`` tokens."""
    x, (S, tail) = _layer(x, fw, jnp.asarray(valid, jnp.int32), m=m, q=q, p=p)
    return x, list(zip(S, tail))


# ---------------------------------------------------------------------------
# the timed path's decode state, against the reference's
# ---------------------------------------------------------------------------


def snapshot(eng, slot: int, layer: int, n: int) -> tuple:
    """The SSM state and conv tail decode carried for ``slot`` at
    ``layer``."""
    cache = eng.cache
    return (np.asarray(cache.ssm[layer, slot]),
            np.asarray(cache.conv[layer, slot], np.float32))


def cache_numbers(prog: dict, got: dict, layers) -> dict:
    """``state_l<i>`` and ``conv_l<i>``: the relative L2 error of the SSM
    state and of the conv tail over every sampled sequence at layer i."""
    out = {}
    for l in layers:
        out[f"state_l{l}"] = rel([prog[j][l][0] for j in range(len(got[l]))],
                                 [g[0] for g in got[l]])
        out[f"conv_l{l}"] = rel([prog[j][l][1] for j in range(len(got[l]))],
                                [g[1] for g in got[l]])
    return out


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------


def linears(m: dict) -> list[tuple[int, int]]:
    """(K, N) of each quantized linear of one layer."""
    s = shapes(m)
    return [(m["hidden_size"], s["d_in_proj"]), (s["d_inner"], m["hidden_size"])]


def linear_work(m: dict, dispatch, w_bits: int) -> list:
    """Every layer runs both projections over all the dispatch's rows."""
    M = dispatch.rows * dispatch.seq
    return [(m["num_hidden_layers"],
             [linear(M, K, N, w_bits) for K, N in linears(m)])]


def attention_work(m: dict, dispatch, kv_bits: int) -> list:
    """No attention: the recurrence's work is in ``token_ops``."""
    return []


def token_ops(m: dict, context: int) -> tuple[float, float]:
    """(int8 ops, bf16 ops) one token needs through the whole model: the
    quantized linears in int8; the conv, the SSM state update and
    readout, and the output head in bf16 (none depends on the position)."""
    s = shapes(m)
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    int8 = 2.0 * L * sum(K * N for K, N in linears(m))
    per_layer = (2 * m["conv_kernel"] * s["conv_ch"]
                 + 6 * s["ssm_heads"] * m["head_dim"] * m["state_size"])
    return int8, L * per_layer + 2.0 * d * V
