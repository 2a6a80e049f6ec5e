"""Substrate tests: optimizer, schedules, gradient compression,
checkpointing (atomic/keep-K/elastic), data pipeline, fault tolerance."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, restore_pytree, save_pytree
from repro.configs.base import get_config
from repro.data import TokenFileDataset, calibration_stream, synthetic_batches
from repro.launch.mesh import make_mesh, make_test_mesh
from repro.optim import (
    adamw,
    apply_error_feedback,
    compress_decompress,
    global_norm,
    warmup_cosine,
    warmup_linear,
)
from repro.runtime.fault_tolerance import (
    Heartbeat,
    PreemptionHandler,
    StragglerPolicy,
)

KEY = jax.random.PRNGKey(0)


# --- optimizer -------------------------------------------------------------


def test_adamw_reduces_quadratic_loss():
    opt = adamw(0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    for step in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, m = opt.step(params, state, grads, jnp.asarray(step))
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_adamw_clips_gradients():
    opt = adamw(1e-3, clip_norm=1.0)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    _, _, m = opt.step(params, state, {"w": jnp.full(4, 100.0)},
                       jnp.asarray(0))
    assert float(m["grad_norm"]) > 100  # reported pre-clip norm


def test_schedules():
    s = warmup_cosine(1e-3, 10, 100)
    assert float(s(jnp.asarray(0))) < 2e-4
    assert abs(float(s(jnp.asarray(10))) - 1e-3) < 2e-4
    assert float(s(jnp.asarray(99))) < 3e-4
    lin = warmup_linear(1.0, 0, 100)
    assert abs(float(lin(jnp.asarray(50))) - 0.5) < 0.02


def test_grad_compression_unbiased_and_error_feedback():
    g = {"a": jax.random.normal(KEY, (64, 64)) * 0.01}
    outs = [compress_decompress(g, jax.random.PRNGKey(i))["a"]
            for i in range(16)]
    mean = np.mean([np.asarray(o) for o in outs], axis=0)
    assert np.abs(mean - np.asarray(g["a"])).mean() < 2e-4  # unbiased
    # error feedback: residual carried, bounded by one quantization step
    err = jax.tree.map(jnp.zeros_like, g)
    comp, err = apply_error_feedback(g, err, KEY)
    step = float(jnp.abs(g["a"]).max()) / 127
    assert float(jnp.abs(err["a"]).max()) <= step + 1e-7


def test_compressed_psum_inside_shard_map():
    """int8-wire psum runs under shard_map and reconstructs the sum within
    one stochastic-rounding step per participant (single-device CI uses a
    size-1 'pod' axis; the cross-pod wire path is identical SPMD code)."""
    from jax.sharding import PartitionSpec as P

    from repro.optim import compressed_psum

    mesh = make_mesh((1,), ("pod",))
    g = jax.random.normal(KEY, (1, 128)) * 0.01

    with jax.set_mesh(mesh):
        def f(gl):
            return compressed_psum({"g": gl[0]}, jax.random.PRNGKey(1),
                                   axis="pod")["g"]
        out = jax.jit(jax.shard_map(f, in_specs=(P("pod", None),),
                                    out_specs=P(), check_vma=False))(g)
    expected = np.asarray(g.sum(0))
    got = np.asarray(out)
    step = np.abs(np.asarray(g)).max() / 127
    assert np.abs(got - expected).max() <= 2 * step + 1e-6


# --- checkpointing ---------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2], jnp.int32)}}
    save_pytree(tree, str(tmp_path / "ck"))
    out = restore_pytree(jax.tree.map(jnp.zeros_like, tree),
                         str(tmp_path / "ck"))
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpointer_keep_k_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, async_save=False)
    tree = {"w": jnp.zeros(3)}
    for step in (1, 2, 3, 4):
        ck.save({"w": jnp.full(3, float(step))}, step)
    assert ck.steps() == [3, 4]  # GC keeps last 2
    restored, step = ck.restore_latest(tree)
    assert step == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]), [4, 4, 4])


def test_checkpointer_atomicity(tmp_path):
    """A leftover .tmp dir from a crash is never picked up by restore."""
    ck = Checkpointer(str(tmp_path), keep=3, async_save=False)
    ck.save({"w": jnp.ones(2)}, 5)
    os.makedirs(str(tmp_path / "step_00000009.tmp"))
    assert ck.steps() == [5]


def test_elastic_restore_under_different_sharding(tmp_path):
    """Checkpoints are mesh-agnostic: restore into any target sharding."""
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    save_pytree(tree, str(tmp_path / "ck"))
    mesh = make_test_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P

    template = {"w": jax.device_put(jnp.zeros((4, 4)),
                                    NamedSharding(mesh, P("data", None)))}
    out = restore_pytree(template, str(tmp_path / "ck"))
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))


# --- data ------------------------------------------------------------------


def test_synthetic_batches_resumable_determinism():
    cfg = get_config("stablelm_3b").reduced()
    a = next(iter(synthetic_batches(cfg, 2, 8, start=7)))
    b = next(iter(synthetic_batches(cfg, 2, 8, start=7)))
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))


def test_token_file_dataset(tmp_path):
    path = str(tmp_path / "toks.bin")
    np.arange(1000, dtype=np.uint16).tofile(path)
    ds = TokenFileDataset(path, seq_len=16)
    batch = next(ds.batches(4))
    assert batch["tokens"].shape == (4, 16)
    np.testing.assert_array_equal(np.asarray(batch["labels"][:, :-1]),
                                  np.asarray(batch["tokens"][:, 1:]))


def test_calibration_stream_covers_families():
    for arch in ("stablelm_3b", "musicgen_large"):
        cfg = get_config(arch).reduced()
        batches = list(calibration_stream(cfg, n_batches=2, batch=2, seq=8))
        assert len(batches) == 2


# --- fault tolerance -------------------------------------------------------


def test_preemption_handler_flag():
    h = PreemptionHandler(signals=())
    assert not h.should_stop
    h.trigger()
    assert h.should_stop


def test_heartbeat(tmp_path):
    p = str(tmp_path / "hb")
    hb = Heartbeat(p, interval=0.05).start()
    import time

    time.sleep(0.15)
    assert Heartbeat.alive(p, timeout=5)
    hb.stop()


def test_straggler_policy():
    sp = StragglerPolicy(factor=3.0)
    for _ in range(10):
        assert not sp.observe(1.0)
    assert sp.observe(10.0)
    assert sp.flagged == 1


def test_elastic_mesh_single_device():
    from repro.runtime.fault_tolerance import elastic_mesh

    mesh = elastic_mesh(1, model_parallel=16)
    assert mesh.devices.size == 1
