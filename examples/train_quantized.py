"""End-to-end training driver example: train a ~100M-param dense LM for a
few hundred steps with the production train_step (grad accumulation,
checkpointing, preemption handling), then quantize and compare.

The model (~100M params at d_model=512, L=8, d_ff=2048, V=32k) is the
task-spec "train ~100M model for a few hundred steps" driver.  On CPU
this is slow; --steps and --scale let CI shrink it (defaults are sized
for a few minutes of CPU time; pass --full for the real thing).

Run:  PYTHONPATH=src python examples/train_quantized.py [--full]
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import Checkpointer
from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.data import synthetic_batches
from repro.launch.mesh import make_test_mesh
from repro.launch.train import make_train_step
from repro.models.api import get_model
from repro.optim import adamw, warmup_cosine
from repro.runtime.fault_tolerance import PreemptionHandler, StragglerPolicy
from repro.serving.fold import collect_calibration, fold_quantize


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="~100M params, 300 steps (minutes-hours on CPU)")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--ckpt", default="/tmp/repro_train_quantized")
    args = ap.parse_args(argv)

    if args.full:
        cfg = get_config("stablelm-3b").reduced(
            num_layers=8, d_model=512, num_heads=8, num_kv_heads=8,
            d_ff=2048, vocab_size=32768, head_dim=64)
        steps, batch, seq, microbatches = args.steps or 300, 16, 256, 4
    else:
        cfg = get_config("stablelm-3b").reduced(num_layers=2, d_model=128,
                                                vocab_size=512)
        steps, batch, seq, microbatches = args.steps or 60, 8, 64, 2

    n_params_est = (cfg.vocab_size * cfg.d_model * 2
                    + cfg.num_layers * (4 * cfg.d_model ** 2
                                        + 3 * cfg.d_model * cfg.d_ff))
    print(f"config: {cfg.num_layers}L d={cfg.d_model} ff={cfg.d_ff} "
          f"V={cfg.vocab_size}  (~{n_params_est/1e6:.1f}M params)")

    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh()
    model = get_model(cfg)
    opt = adamw(warmup_cosine(3e-3, 20, steps))
    preempt = PreemptionHandler()
    straggler = StragglerPolicy()
    ckpt = Checkpointer(args.ckpt, keep=2)

    with jax.set_mesh(mesh):
        params = model.init(key, cfg)
        state = opt.init(params)
        start = 0
        restored = ckpt.restore_latest({"p": params, "s": state})
        if restored:
            (tree, start) = restored
            params, state = tree["p"], tree["s"]
            print(f"resumed from step {start}")
        step_fn = jax.jit(make_train_step(model, cfg, opt,
                                          microbatches=microbatches))
        t_prev = time.time()
        for i, batch_data in enumerate(
                synthetic_batches(cfg, batch, seq, start=start), start=start):
            if i >= steps or preempt.should_stop:
                break
            params, state, m = step_fn(params, state, batch_data,
                                       jnp.asarray(i),
                                       jax.random.fold_in(key, i))
            dt = time.time() - t_prev
            t_prev = time.time()
            if straggler.observe(dt):
                print(f"  [straggler] step {i} took {dt:.2f}s")
            if i % 20 == 0:
                print(f"step {i:4d} loss {float(m['loss']):.4f} "
                      f"({dt:.2f}s/step)")
            if i and i % 50 == 0:
                ckpt.save({"p": params, "s": state}, i)
        ckpt.save({"p": params, "s": state}, i, block=True)
        if preempt.should_stop:
            print("preempted — checkpoint saved, exiting cleanly")
            return

        # quantize the trained model (the paper's serving pipeline)
        calib = [next(iter(synthetic_batches(cfg, 2, seq, start=s)))
                 for s in range(2)]
        stats = collect_calibration(model, params, cfg, calib)
        policy = QuantPolicy(weight_bits=4, act_bits=4, use_kernels="never")
        qparams = fold_quantize(params, cfg, policy=policy, stats=stats)
        toks = calib[0]["tokens"]
        lf = model.forward(params, cfg, toks)
        lq = model.forward(qparams, cfg, toks, policy=policy)
        agree = float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean())
        print(f"final loss {float(m['loss']):.4f}; "
              f"W4A4 top-1 agreement {agree:.2f}")


if __name__ == "__main__":
    main()
