"""The paper's analysis loop (§III–IV), end to end, on a real (small)
trained model: record activations with taps, measure layer-wise error
and quantization difficulty per module, apply all four transforms, and
print a Fig.4-style table.

Run:  PYTHONPATH=src python examples/analyze_quantization.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.difficulty import (
    layerwise_error_transformed,
    quantization_difficulty,
)
from repro.core.transforms import get_transform
from repro.data import synthetic_batches
from repro.launch.mesh import make_test_mesh
from repro.launch.train import make_train_step
from repro.models.api import get_model
from repro.optim import adamw

KINDS = ("none", "smooth", "rotate", "smooth_rotate")


def main():
    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        cfg = get_config("qwen1.5-4b").reduced(num_layers=4, d_model=128,
                                               d_ff=256, vocab_size=128)
        model = get_model(cfg)
        opt = adamw(3e-3)
        params = model.init(key, cfg)
        state = opt.init(params)
        step = jax.jit(make_train_step(model, cfg, opt))
        for i, batch in enumerate(synthetic_batches(cfg, 8, 64)):
            if i >= 30:
                break
            params, state, _ = step(params, state, batch, jnp.asarray(i),
                                    jax.random.fold_in(key, i))

        # record activations (paper §III-A: hooks → taps)
        toks = next(iter(synthetic_batches(cfg, 2, 128)))["tokens"]
        _, taps = model.forward_with_taps(params, cfg, toks)

        # per layer × module: error under each transform (Fig. 4 table)
        w_of = {
            "k_proj": params["layers"]["attn"]["wq"]["w"],
            "o_proj": params["layers"]["attn"]["wo"]["w"],
            "gate_proj": params["layers"]["mlp"]["wg"]["w"],
            "down_proj": params["layers"]["mlp"]["wd"]["w"],
        }
        hdr = f"{'module':>22s} {'difficulty':>10s} " + "".join(
            f"{k:>14s}" for k in KINDS)
        print(hdr)
        print("-" * len(hdr))
        for module, tap in sorted(taps.items()):
            L = tap.shape[0]
            for layer in range(L):
                x = tap[layer].reshape(-1, tap.shape[-1])
                w = w_of[module][layer].astype(jnp.float32)
                diff = float(quantization_difficulty(x))
                errs = [float(layerwise_error_transformed(
                    x, w, get_transform(k))) for k in KINDS]
                cells = "".join(f"{e:14.4g}" for e in errs)
                best = KINDS[int(np.argmin(errs))]
                print(f"{module + '_' + str(layer):>22s} {diff:10.3f} "
                      f"{cells}  <- {best}")
        print("\n(expect: rotate/smooth_rotate lowest — paper Fig. 4)")


if __name__ == "__main__":
    main()
