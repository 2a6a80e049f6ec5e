"""Quickstart: the paper's pipeline in 60 lines.

1. Build a small LM, train it briefly;
2. calibrate activation statistics (one forward with taps);
3. fold SmoothRotation transforms + RTN-quantize to W4A4;
4. compare bf16 vs quantized generations.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.core.transforms import TransformPlan
from repro.data import synthetic_batches
from repro.launch.mesh import make_test_mesh
from repro.launch.train import make_train_step
from repro.models.api import get_model
from repro.optim import adamw
from repro.serving.fold import collect_calibration, fold_quantize


def main():
    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        # -- 1. a small llama-family model, briefly trained ---------------
        cfg = get_config("stablelm-3b").reduced(num_layers=2, d_model=64,
                                                vocab_size=64)
        model = get_model(cfg)
        opt = adamw(3e-3)
        params = model.init(key, cfg)
        state = opt.init(params)
        step = jax.jit(make_train_step(model, cfg, opt))
        for i, batch in enumerate(synthetic_batches(cfg, 8, 32)):
            if i >= 20:
                break
            params, state, m = step(params, state, batch, jnp.asarray(i),
                                    jax.random.fold_in(key, i))
        print(f"trained 20 steps, loss {float(m['loss']):.3f}")

        # -- 2. calibrate (paper §III: absmax per channel per module) -----
        calib = [next(iter(synthetic_batches(cfg, 2, 32, start=s)))
                 for s in range(2)]
        stats = collect_calibration(model, params, cfg, calib)
        print(f"calibrated modules: {sorted(stats)}")

        # -- 3. fold transforms + quantize (paper §IV-E default plan) -----
        policy = QuantPolicy(weight_bits=4, act_bits=4, use_kernels="never")
        qparams = fold_quantize(params, cfg, policy=policy,
                                plan=TransformPlan(), stats=stats)

        # -- 4. compare ----------------------------------------------------
        toks = next(iter(synthetic_batches(cfg, 2, 16)))["tokens"]
        lf = model.forward(params, cfg, toks)
        lq = model.forward(qparams, cfg, toks, policy=policy)
        agree = float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean())
        rel = float(jnp.linalg.norm((lq - lf).astype(jnp.float32))
                    / jnp.linalg.norm(lf.astype(jnp.float32)))
        print(f"W4A4 vs bf16: top-1 agreement {agree:.2f}, "
              f"logit rel err {rel:.3f}")
        w_bits = sum(x.size * (0.5 if x.dtype == jnp.int8 and q else 2)
                     for q, x in [(True, l) for l in jax.tree.leaves(qparams)])
        print("done — see examples/analyze_quantization.py for the "
              "paper's full analysis loop")


if __name__ == "__main__":
    main()
