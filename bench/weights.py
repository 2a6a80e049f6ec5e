"""The served models' raw weights, made by the benchmark from the seed.

Each family (``bench/families/<family>.py``) says how its leaves are
made, in the type they are served in and laid out as the program's
parameter tree for that family.  The same family and seed make the same
weights again after the window, for the plain reference
(``bench/reference.py``), which therefore takes nothing that the program
has made.  The reference reads them as a ``Weights``: the top leaves and
one layer at a time, so that a family whose whole tree does not fit the
chip can make each layer alone from the seed and its index.

Linears are normal with std ``fan_in ** -0.5``, the embedding normal
with std 0.02, and every norm gain ``1 + 0.1 * normal`` (so that a norm
applied without its gain, or with another layer's, shows).
"""

from __future__ import annotations

import dataclasses
import functools
import gc
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16


def jax_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, large ones included."""
    return jax.random.PRNGKey(int(np.random.default_rng(seed).integers(2**31)))


def normal(key, shape, std, dtype=BF16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def gain(key, shape):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(BF16)


@dataclasses.dataclass
class Weights:
    """A model's raw weights as the reference reads them: ``top`` holds
    the embedding (``embed.e``), the final norm (``final_ln.g``) and the
    head (``lm_head.w``); ``layer(i)`` gives layer i's leaves."""

    top: dict
    layer: Callable[[int], dict]


@functools.lru_cache(maxsize=None)
def _jitted(make, frozen: tuple):
    m = dict(frozen)
    return jax.jit(lambda key: make(key, m))


def whole_tree(make, m: dict, seed: int) -> dict:
    """Every raw weight of the model, from ``seed``, in one jitted call
    on the device: ``make(key, m)`` returns the tree, its per-layer
    leaves stacked along a leading layer axis under ``layers``."""
    return _jitted(make, tuple(sorted(m.items())))(jax_key(seed))


def sliced(tree: dict) -> Weights:
    """``Weights`` over a whole tree: layer i is the i-th slice of every
    stacked leaf."""
    top = {k: v for k, v in tree.items() if k != "layers"}
    return Weights(top=top,
                   layer=lambda i: jax.tree.map(lambda a: a[i], tree["layers"]))


def calibration_tokens(conf: dict, seed: int) -> list[np.ndarray]:
    c = conf["calibration"]
    rng = np.random.default_rng([seed, 3])
    return [rng.integers(0, conf["vocab_size"], (c["batch"], c["seq"]))
            for _ in range(c["batches"])]


def served_whole(make, model, cfg, conf: dict, m: dict, seed: int, policy):
    """The program's served parameters for a family whose whole tree
    fits the chip: the tree from ``make``, calibrated and folded by the
    functions ``serve.build`` calls."""
    from repro.core.transforms import TransformPlan
    from repro.serving.fold import collect_calibration, fold_quantize

    raw = whole_tree(make, m, seed)
    stats = collect_calibration(
        model, raw, cfg, [{"tokens": t} for t in calibration_tokens(conf, seed)])
    params = fold_quantize(raw, cfg, policy=policy,
                           plan=TransformPlan(alpha=conf["quant"]["alpha"]),
                           stats=stats)
    jax.block_until_ready(params)
    del raw, stats
    gc.collect()
    return params
