"""End-to-end system tests: training converges, checkpoint-restart
resumes identically, the calibrate→fold→serve path produces coherent text
-generation behaviour, and the small-mesh dry-run (lower+compile with
sharded params) succeeds — the CPU-scale version of launch/dryrun.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.data import synthetic_batches
from repro.launch.sharding import batch_spec, param_specs
from repro.launch.train import make_train_step, shard_train_fns
from repro.models.api import get_model
from repro.optim import adamw

KEY = jax.random.PRNGKey(0)


def test_training_loss_decreases():
    """~100k-param model on structured synthetic data: loss must drop."""
    cfg = get_config("stablelm_3b").reduced(num_layers=2, d_model=64,
                                            vocab_size=64)
    model = get_model(cfg)
    opt = adamw(3e-3)
    params = model.init(KEY, cfg)
    state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, cfg, opt),
                      static_argnames=())
    losses = []
    for i, batch in enumerate(synthetic_batches(cfg, 8, 32)):
        if i >= 30:
            break
        params, state, m = step_fn(params, state, batch, jnp.asarray(i),
                                   jax.random.fold_in(KEY, i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_grad_accumulation_matches_full_batch():
    """microbatched grads ≡ full-batch grads (same loss trajectory)."""
    cfg = get_config("stablelm_3b").reduced(num_layers=2)
    model = get_model(cfg)
    opt = adamw(1e-3)
    batch = next(iter(synthetic_batches(cfg, 8, 16)))
    params = model.init(KEY, cfg)
    state = opt.init(params)
    s1 = make_train_step(model, cfg, opt, microbatches=1)
    s4 = make_train_step(model, cfg, opt, microbatches=4)
    p1, _, m1 = jax.jit(s1)(params, state, batch, jnp.asarray(0), KEY)
    p4, _, m4 = jax.jit(s4)(params, state, batch, jnp.asarray(0), KEY)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 0.02
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.05)


def test_checkpoint_restart_resumes_identically(tmp_path):
    from repro.checkpoint import Checkpointer

    cfg = get_config("stablelm_3b").reduced(num_layers=2)
    model = get_model(cfg)
    opt = adamw(1e-3)
    step_fn = jax.jit(make_train_step(model, cfg, opt))

    def run(params, state, start, n):
        for i in range(start, start + n):
            batch = next(iter(synthetic_batches(cfg, 4, 16, start=i)))
            params, state, m = step_fn(params, state, batch, jnp.asarray(i),
                                       jax.random.fold_in(KEY, i))
        return params, state, float(m["loss"])

    params = model.init(KEY, cfg)
    state = opt.init(params)
    # straight run: 6 steps
    pa, sa, loss_a = run(params, state, 0, 6)
    # interrupted run: 3 steps → checkpoint → restore → 3 more
    pb, sb, _ = run(params, state, 0, 3)
    ck = Checkpointer(str(tmp_path), async_save=False)
    ck.save({"p": pb, "s": sb}, 3)
    restored, step = ck.restore_latest({"p": pb, "s": sb})
    pc, sc, loss_c = run(restored["p"], restored["s"], 3, 3)
    assert abs(loss_a - loss_c) < 1e-3, (loss_a, loss_c)
    for a, b in zip(jax.tree.leaves(pa), jax.tree.leaves(pc)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-3)


def test_small_mesh_dryrun_lower_compile(test_mesh):
    """CPU-scale twin of launch/dryrun.py: shard specs + lower + compile
    + memory/cost analysis on a (1,1) mesh, abstract params only."""
    cfg = get_config("qwen15_4b").reduced()
    model = get_model(cfg)
    opt = adamw(1e-3)
    with jax.set_mesh(test_mesh):
        params_shape = jax.eval_shape(lambda k: model.init(k, cfg),
                                      jax.random.PRNGKey(0))
        opt_shape = jax.eval_shape(opt.init, params_shape)
        pspecs = param_specs(params_shape, cfg, test_mesh)
        ospecs = param_specs(opt_shape, cfg, test_mesh)
        bspec = batch_spec(test_mesh, 4)
        batch = {"tokens": jax.ShapeDtypeStruct((4, 16), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((4, 16), jnp.int32)}
        lowered = jax.jit(
            make_train_step(model, cfg, opt, microbatches=2),
            in_shardings=(pspecs, ospecs,
                          {"tokens": bspec, "labels": bspec}, None, None),
        ).lower(params_shape, opt_shape, batch,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        compiled = lowered.compile()
        assert compiled.memory_analysis() is not None
        from repro.launch.hlo_analysis import analyze_hlo

        metrics = analyze_hlo(compiled.as_text())
        assert metrics.flops > 0
        assert metrics.while_trips  # layer scan + microbatch scan present


def test_quantized_generation_coherent():
    """Train a tiny model until it learns the +1 token pattern, quantize
    W8A8, and check the quantized model still generates the pattern."""
    cfg = get_config("stablelm_3b").reduced(num_layers=2, d_model=64,
                                            vocab_size=32)
    model = get_model(cfg)
    opt = adamw(5e-3)
    params = model.init(KEY, cfg)
    state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, cfg, opt))
    rng = np.random.default_rng(0)
    for i in range(60):
        start = rng.integers(0, 32, size=(8, 1))
        toks = (start + np.arange(24)[None]) % 32  # strict +1 pattern
        batch = {"tokens": jnp.asarray(toks, jnp.int32),
                 "labels": jnp.asarray(toks, jnp.int32)}
        params, state, m = step_fn(params, state, batch, jnp.asarray(i),
                                   jax.random.fold_in(KEY, i))
    from repro.core.qlinear import QuantPolicy
    from repro.serving.fold import collect_calibration, fold_quantize

    toks = jnp.asarray((np.arange(16)[None] + 3) % 32, jnp.int32)
    stats = collect_calibration(model, params, cfg, [{"tokens": toks}])
    policy = QuantPolicy(weight_bits=8, act_bits=8, pack_weights=False,
                         use_kernels="never")
    qparams = fold_quantize(params, cfg, policy=policy, stats=stats)
    logits = model.forward(qparams, cfg, toks, policy=policy)
    preds = np.asarray(jnp.argmax(logits, -1))[0]
    target = np.asarray((toks[0] + 1) % 32)
    acc = (preds[4:-1] == target[4:-1]).mean()
    assert acc > 0.8, acc
