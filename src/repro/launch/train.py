"""Training driver: sharded train_step factory + CLI loop.

Features (DESIGN.md §4): pjit 2D sharding (FSDP×TP), gradient
accumulation over microbatches (lax.scan — bounds activation memory for
the 405B train cell), remat on the layer scan (per config), optional
int8 gradient compression with error feedback, checkpoint/restart,
preemption-safe saves, and XLA latency-hiding flags for compute/comm
overlap on TPU.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b \
        --steps 100 --batch 8 --seq 128 --reduced
"""

from __future__ import annotations

import argparse
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.launch.sharding import batch_spec, param_specs
from repro.models.api import get_model
from repro.optim import adamw, apply_error_feedback, warmup_cosine

# XLA flags a production TPU launcher sets for compute/comm overlap; they
# are inert on CPU and applied by the cluster launcher environment.
TPU_XLA_FLAGS = (
    "--xla_tpu_enable_async_collective_fusion=true "
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true "
)


def make_train_step(model, cfg: ModelConfig, opt, *, microbatches: int = 1,
                    grad_compression: bool = False):
    """Returns train_step(params, opt_state, batch, step, key) →
    (params, opt_state, metrics).  ``batch`` leaves are (B, ...) global;
    with microbatches=A they are reshaped to (A, B/A, ...) and grads
    accumulated under lax.scan (memory ∝ B/A)."""

    def loss_fn(p, mb):
        return model.train_loss(p, cfg, mb)

    def grads_of(params, batch):
        if microbatches == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        mbs = jax.tree.map(
            lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                *x.shape[1:]), batch)

        def acc(carry, mb):
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            loss_acc, grads_acc = carry
            return (loss_acc + l,
                    jax.tree.map(jnp.add, grads_acc, g)), ()

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params))
        (loss, grads), _ = jax.lax.scan(acc, zero, mbs)
        inv = 1.0 / microbatches
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def train_step(params, opt_state, batch, step, key):
        loss, grads = grads_of(params, batch)
        err = opt_state.err
        if grad_compression:
            grads, err = apply_error_feedback(grads, err, key)
        params, opt_state, metrics = opt.step(params, opt_state, grads, step)
        opt_state = opt_state._replace(err=err)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def shard_train_fns(model, cfg: ModelConfig, opt, mesh, global_batch: int,
                    seq: int, *, microbatches: int = 1,
                    grad_compression: bool = False):
    """jit-compiled (init_fn, train_step) with explicit shardings."""
    pspec_of = lambda tree: param_specs(tree, cfg, mesh)

    def init_all(key):
        params = model.init(key, cfg)
        return params, opt.init(params)

    params_shape, opt_shape = jax.eval_shape(init_all, jax.random.PRNGKey(0))
    pspecs = pspec_of(params_shape)
    ospecs = pspec_of(opt_shape)
    bspec = batch_spec(mesh, global_batch)
    if cfg.embeds_input and cfg.family in ("audio", "vlm"):
        bspecs = {"embeds": P(*bspec, None), "labels": bspec}
    else:
        bspecs = {"tokens": bspec, "labels": bspec}

    step_fn = make_train_step(model, cfg, opt, microbatches=microbatches,
                              grad_compression=grad_compression)
    train_step = jax.jit(
        step_fn,
        in_shardings=(pspecs, ospecs, bspecs, P(), P()),
        out_shardings=(pspecs, ospecs, P()),
        donate_argnums=(0, 1),
    )
    init_fn = jax.jit(init_all, out_shardings=(pspecs, ospecs))
    return init_fn, train_step, (pspecs, ospecs, bspecs)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU)")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--data", default="synthetic")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    mesh = make_test_mesh()  # cluster launchers construct the real mesh
    opt = adamw(warmup_cosine(args.lr, 10, args.steps),
                error_feedback=args.grad_compression)

    from repro.data.pipeline import synthetic_batches

    with jax.set_mesh(mesh):
        init_fn, train_step, _ = shard_train_fns(
            model, cfg, opt, mesh, args.batch, args.seq,
            microbatches=args.microbatches,
            grad_compression=args.grad_compression)
        key = jax.random.PRNGKey(0)
        params, opt_state = init_fn(key)
        start_step = 0
        ckpt = None
        if args.checkpoint_dir:
            from repro.checkpoint import Checkpointer

            ckpt = Checkpointer(args.checkpoint_dir, keep=3)
            restored = ckpt.restore_latest({"params": params, "opt": opt_state})
            if restored is not None:
                state, start_step = restored
                params, opt_state = state["params"], state["opt"]
                print(f"resumed from step {start_step}")
        t0 = time.time()
        for step, batch in enumerate(
                synthetic_batches(cfg, args.batch, args.seq, start=start_step),
                start=start_step):
            if step >= args.steps:
                break
            params, opt_state, metrics = train_step(
                params, opt_state, batch, jnp.asarray(step),
                jax.random.fold_in(key, step))
            if step % 5 == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} ({dt:.1f}s)")
            if ckpt and step and step % args.save_every == 0:
                ckpt.save({"params": params, "opt": opt_state}, step)
        if ckpt:
            ckpt.save({"params": params, "opt": opt_state}, args.steps)
    print("done")


if __name__ == "__main__":
    main()
