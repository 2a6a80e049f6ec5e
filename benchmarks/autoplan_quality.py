"""Auto-plan vs fixed §V plan: summed Eq. (2) layer-wise error across
model families (dense, moe, ssm).

The searched per-layer plan force-includes the fixed plan's choice per
(layer, module) in its candidate set, so under the shared error metric
the auto plan is ≤ the fixed plan by construction — this benchmark
measures HOW MUCH better the per-layer search is, per family, and emits
the machine-readable rows EXPERIMENTS tracking consumes.

Usage: PYTHONPATH=src python -m benchmarks.autoplan_quality
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import emit
from repro.autoplan import LayerwisePlan, SearchConfig, plan_errors, search_plan
from repro.configs.base import get_config
from repro.core.transforms import TransformPlan
from repro.launch.mesh import make_test_mesh
from repro.models.api import get_model
from repro.serving.fold import collect_calibration

ARCHS = (
    ("stablelm_3b", "dense"),
    ("deepseek_v2_lite_16b", "moe"),
    ("mamba2_780m", "ssm"),
)


def run(keep_samples: int = 128) -> dict:
    key = jax.random.PRNGKey(0)
    mesh = make_test_mesh()
    out: dict[str, float] = {}
    with jax.set_mesh(mesh):
        for arch, family in ARCHS:
            cfg = get_config(arch).reduced()
            model = get_model(cfg)
            params = model.init(key, cfg)
            toks = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
            stats = collect_calibration(model, params, cfg, [{"tokens": toks}],
                                        keep_samples=keep_samples)
            search = SearchConfig()
            auto, _ = search_plan(params, cfg, stats, search=search)
            fixed = LayerwisePlan.from_global(
                TransformPlan(), auto.num_layers, arch=cfg.name)
            e_auto = sum(float(np.sum(v)) for v in
                         plan_errors(auto, params, cfg, stats, search).values())
            e_fixed = sum(float(np.sum(v)) for v in
                          plan_errors(fixed, params, cfg, stats, search).values())
            win = e_auto <= e_fixed
            gain = 0.0 if e_fixed == 0 else 100.0 * (1 - e_auto / e_fixed)
            out[f"{arch}_auto"] = e_auto
            out[f"{arch}_fixed"] = e_fixed
            emit(f"autoplan_error_{family}_{arch}", 0.0,
                 f"auto={e_auto:.4g};fixed={e_fixed:.4g};"
                 f"gain={gain:.1f}%;auto_le_fixed={win}")
    return out


if __name__ == "__main__":
    run()
