"""Autoplan CLI: derive and save a per-layer quantization plan.

Usage:
    PYTHONPATH=src python -m repro.autoplan --arch stablelm-3b --reduced
    PYTHONPATH=src python -m repro.autoplan --arch mamba2-780m --reduced \
        --alpha-grid 0.5,0.65,0.8 --top-k 4 --out plan.json

Loads (or randomly initializes) the model, runs the calibration stream
with per-layer sample retention, searches the transform/α grid per
(layer, module), and writes the plan JSON plus a telemetry artifact
under experiments/autoplan/.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro.autoplan.plan import LayerwisePlan
from repro.autoplan.search import SearchConfig, plan_errors, search_plan
from repro.autoplan.telemetry import collect_telemetry, summarize, write_telemetry
from repro.checkpoint import Checkpointer
from repro.configs.base import get_config
from repro.core.transforms import TransformPlan
from repro.data import calibration_stream
from repro.launch.mesh import make_test_mesh
from repro.models.api import get_model
from repro.serving.fold import collect_calibration


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.autoplan")
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--checkpoint", default="",
                    help="checkpoint dir (else random init)")
    ap.add_argument("--out", default="",
                    help="plan JSON path (default experiments/autoplan/"
                         "<arch>_plan.json)")
    ap.add_argument("--telemetry-out", default="",
                    help="telemetry JSON path (default alongside the plan)")
    ap.add_argument("--batches", type=int, default=2)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--keep-samples", type=int, default=128,
                    help="calibration tokens retained per module per layer")
    ap.add_argument("--alpha-grid", default="0.5,0.65,0.7,0.8")
    ap.add_argument("--top-k", type=int, default=3,
                    help="difficulty-prefilter survivors per layer")
    ap.add_argument("--weight-bits", type=int, default=4, choices=[4, 8])
    ap.add_argument("--act-bits", type=int, default=4, choices=[4, 8])
    args = ap.parse_args(argv)

    try:
        alpha_grid = tuple(float(a) for a in args.alpha_grid.split(","))
    except ValueError:
        ap.error(f"--alpha-grid must be comma-separated floats, "
                 f"got {args.alpha_grid!r}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = get_model(cfg)
    search = SearchConfig(
        alpha_grid=alpha_grid, top_k=args.top_k,
        weight_bits=args.weight_bits, act_bits=args.act_bits)

    with jax.set_mesh(make_test_mesh()):
        params = model.init(jax.random.PRNGKey(0), cfg)
        if args.checkpoint:
            restored = Checkpointer(args.checkpoint).restore_latest({"p": params})
            if restored:
                params = restored[0]["p"]
                print(f"restored checkpoint step {restored[1]}")

        t0 = time.time()
        stats = collect_calibration(
            model, params, cfg,
            list(calibration_stream(cfg, n_batches=args.batches,
                                    batch=args.batch, seq=args.seq)),
            keep_samples=args.keep_samples)
        t_calib = time.time() - t0

        t0 = time.time()
        plan, info = search_plan(params, cfg, stats, search=search)
        t_search = time.time() - t0

        out = args.out or os.path.join(
            "experiments", "autoplan", f"{cfg.name}_plan.json")
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        plan.save(out)

        fixed = LayerwisePlan.from_global(TransformPlan(), plan.num_layers,
                                          arch=cfg.name)
        e_auto = plan_errors(plan, params, cfg, stats, search)
        e_fixed = plan_errors(fixed, params, cfg, stats, search)
        tel = collect_telemetry(plan, params, cfg, stats)
        tel_out = args.telemetry_out or os.path.join(
            os.path.dirname(out), f"{cfg.name}_telemetry.json")
        write_telemetry(tel_out, cfg.name, tel, extra={
            "error_auto": {m: v.tolist() for m, v in e_auto.items()},
            "error_fixed": {m: v.tolist() for m, v in e_fixed.items()},
        })

    print(plan.summary())
    print()
    print(summarize(tel))
    a, f = (sum(float(np.sum(v)) for v in e.values())
            for e in (e_auto, e_fixed))
    print(f"\nsummed layerwise error: auto={a:.4g}  fixed §V={f:.4g} "
          f"({'auto wins' if a <= f else 'FIXED WINS — check search'})")
    print(f"calibration {t_calib:.1f}s, search {t_search:.1f}s")
    print(f"plan → {out}\ntelemetry → {tel_out}")


if __name__ == "__main__":
    main()
