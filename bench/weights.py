"""The served models' raw weights, made by the benchmark from the seed.

One jitted call on the device makes every leaf, in the type it is served
in, laid out as the program's parameter tree for that family.  The same
function and seed make the same weights again after the window, for the
plain reference (``bench/reference.py``), which therefore takes nothing
that the program has made.

Linears are normal with std ``fan_in ** -0.5``, the embedding normal
with std 0.02, and every norm gain ``1 + 0.1 * normal`` (so that a norm
applied without its gain, or with another layer's, shows).  The Mamba2
leaves follow the published initialisation: ``A = -exp(A_log)`` with
``exp(A_log)`` uniform on [1, 16], ``dt_bias`` uniform on [-4, -2] (a
softplus of about 0.02 to 0.13), ``D`` near 1, the depthwise conv normal
with std 0.2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16


def jax_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, large ones included."""
    return jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2**31)))


def _normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _gain(key, shape):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(BF16)


def shapes(m: dict) -> dict:
    """The derived sizes of one model description (``bench.spec.model``)."""
    s = dict(m)
    if m["family"] == "ssm":
        s["d_inner"] = m["expand"] * m["hidden_size"]
        s["ssm_heads"] = s["d_inner"] // m["head_dim"]
        s["conv_ch"] = s["d_inner"] + 2 * m["n_groups"] * m["state_size"]
        s["d_in_proj"] = (2 * s["d_inner"] + 2 * m["n_groups"] * m["state_size"]
                          + s["ssm_heads"])
    else:
        s["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    return s


def _dense(key, m: dict) -> dict:
    s = shapes(m)
    L, d, f, V = (m["num_hidden_layers"], m["hidden_size"],
                  m["intermediate_size"], m["vocab_size"])
    hq, hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], s["head_dim"]
    k = iter(jax.random.split(key, 16))
    lin = lambda a, b: {"w": _normal(next(k), (L, a, b), a ** -0.5)}
    return {
        "embed": {"e": _normal(next(k), (V, d), 0.02)},
        "layers": {
            "attn": {"wq": lin(d, hq * hd), "wk": lin(d, hkv * hd),
                     "wv": lin(d, hkv * hd), "wo": lin(hq * hd, d),
                     "ln": {"g": _gain(next(k), (L, d))}},
            "mlp": {"wg": lin(d, f), "wu": lin(d, f), "wd": lin(f, d),
                    "ln": {"g": _gain(next(k), (L, d))}},
        },
        "final_ln": {"g": _gain(next(k), (d,))},
        "lm_head": {"w": _normal(next(k), (d, V), d ** -0.5)},
    }


def _ssm(key, m: dict) -> dict:
    s = shapes(m)
    L, d, V = m["num_hidden_layers"], m["hidden_size"], m["vocab_size"]
    di, h, cc = s["d_inner"], s["ssm_heads"], s["conv_ch"]
    k = iter(jax.random.split(key, 16))
    f32 = jnp.float32
    return {
        "embed": {"e": _normal(next(k), (V, d), 0.02)},
        "layers": {
            "in_proj": {"w": _normal(next(k), (L, d, s["d_in_proj"]), d ** -0.5)},
            "out_proj": {"w": _normal(next(k), (L, di, d), di ** -0.5)},
            "conv_w": _normal(next(k), (L, m["conv_kernel"], cc), 0.2),
            "conv_b": _normal(next(k), (L, cc), 0.02),
            "A_log": jnp.log(jax.random.uniform(next(k), (L, h), f32, 1.0, 16.0)),
            "D": 1.0 + 0.1 * jax.random.normal(next(k), (L, h), f32),
            "dt_bias": jax.random.uniform(next(k), (L, h), f32, -4.0, -2.0),
            "ln": {"g": _gain(next(k), (L, d))},
            "gate_ln": {"g": _gain(next(k), (L, di))},
        },
        "final_ln": {"g": _gain(next(k), (d,))},
        "lm_head": {"w": _normal(next(k), (d, V), d ** -0.5)},
    }


@functools.lru_cache(maxsize=None)
def _maker(frozen: tuple):
    m = dict(frozen)
    fn = _ssm if m["family"] == "ssm" else _dense
    return jax.jit(lambda key: fn(key, m))


def make_weights(m: dict, seed: int) -> dict:
    """Every raw weight of the model described by ``m``, from ``seed``."""
    return _maker(tuple(sorted(m.items())))(jax_key(seed))
