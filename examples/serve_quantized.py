"""Serving example: continuous-batched engine over a fold+quantized model
with an int8 KV cache — the deployment the paper's technique enables.

Run:  PYTHONPATH=src python examples/serve_quantized.py
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.data import synthetic_batches
from repro.launch.mesh import make_test_mesh
from repro.launch.train import make_train_step
from repro.models.api import get_model
from repro.optim import adamw
from repro.serving.engine import Request, ServingEngine
from repro.serving.fold import collect_calibration, fold_quantize


def main():
    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        cfg = get_config("qwen1.5-4b").reduced(num_layers=2, d_model=128,
                                               vocab_size=256)
        model = get_model(cfg)
        opt = adamw(3e-3)
        params = model.init(key, cfg)
        state = opt.init(params)
        step = jax.jit(make_train_step(model, cfg, opt))
        for i, batch in enumerate(synthetic_batches(cfg, 8, 64)):
            if i >= 25:
                break
            params, state, _ = step(params, state, batch, jnp.asarray(i),
                                    jax.random.fold_in(key, i))

        # quantize for serving: W4A4 weights, int8 KV
        stats = collect_calibration(
            model, params, cfg,
            [next(iter(synthetic_batches(cfg, 2, 64)))])
        policy = QuantPolicy(weight_bits=4, act_bits=4, kv_cache_bits=8,
                             use_kernels="never")
        qparams = fold_quantize(params, cfg, policy=policy, stats=stats)

        rng = np.random.default_rng(0)
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=(4 + 2 * i,)),
                        max_new_tokens=8,
                        temperature=0.0 if i % 2 == 0 else 0.8)
                for i in range(6)]

        for label, p, pol, kv in (("bf16", params, None, None),
                                  ("W4A4+int8KV", qparams, policy, 8)):
            eng = ServingEngine(model, p, cfg, max_slots=3, max_len=64,
                                policy=pol, kv_bits=kv)
            for r in reqs:
                r.out_tokens, r.done = [], False
                eng.submit(r)
            t0 = time.time()
            done = eng.run(max_ticks=200)
            dt = time.time() - t0
            toks = sum(len(r.out_tokens) for r in done)
            print(f"[{label:12s}] {len(done)} requests, {toks} tokens "
                  f"in {dt:.2f}s ({toks/dt:.1f} tok/s CPU)")
            print(f"   sample: {done[0].out_tokens}")


if __name__ == "__main__":
    main()
