import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the REAL step function (train_step with
gradient accumulation for train shapes; fold+quantized serve prefill /
decode for inference shapes), lowers it under the production mesh with
explicit in_shardings, compiles, and records:

  * memory_analysis()  — per-device argument/temp/output bytes (fits?)
  * cost_analysis()    — raw XLA numbers (per-device, loop bodies once)
  * hlo_analysis       — trip-corrected FLOPs / HBM / collective bytes
  * roofline terms     — compute/memory/collective seconds + dominant

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json and are
summarized into EXPERIMENTS.md §Dry-run/§Roofline by benchmarks/report.

NOTE the XLA_FLAGS line above MUST precede any jax import (jax locks the
device count at first backend init) — which is why this module sets it
at line 1-2 and why tests/benchmarks never import this module.
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, ModelConfig, ShapeCell, get_config, list_archs
from repro.core.qlinear import QuantPolicy
from repro.core.transforms import TransformPlan
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import dp_size, make_production_mesh
from repro.launch.roofline import roofline
from repro.launch.sharding import batch_spec, cache_specs, param_specs
from repro.models.api import get_model
from repro.optim import adamw
from repro.serving.fold import fold_quantize

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

# long-context needs sub-quadratic attention: full-attention archs run the
# documented sliding-window VARIANT (DESIGN.md §5); SSM/hybrid run native.
WINDOW_FOR_LONG = 8192


def effective_config(cfg: ModelConfig, cell: ShapeCell, *,
                     opt: str = "") -> tuple[ModelConfig, str]:
    """opt: comma-joined subset of {flash, bf16io, sp, µN} — the §Perf
    beyond-paper optimizations (baseline = none of them)."""
    note = ""
    if cell.name == "long_500k" and not cfg.sub_quadratic:
        cfg = dataclasses.replace(cfg, attn_window=WINDOW_FOR_LONG)
        note = (f"windowed-attention variant (window={WINDOW_FOR_LONG}): "
                "pure full-attention arch cannot decode 500k natively")
    opts = set(filter(None, opt.split(",")))
    if "flash" in opts and not cfg.attn_window:
        cfg = dataclasses.replace(cfg, attn_impl="flash")
        note += " +flash"
    if "bf16io" in opts:
        cfg = dataclasses.replace(cfg, attn_bf16_io=True)
        note += " +bf16io"
    if "sp" in opts:
        cfg = dataclasses.replace(cfg, seq_parallel=True)
        note += " +sp"
    if "noremat" in opts:
        cfg = dataclasses.replace(cfg, remat=False)
        note += " +noremat"
    if "rematdots" in opts:
        cfg = dataclasses.replace(cfg, remat_policy="dots_no_batch")
        note += " +rematdots"
    if "flashdecode" in opts:
        cfg = dataclasses.replace(cfg, decode_flash=True)
        note += " +flashdecode"
    for o in opts:
        if o.startswith("group") and o != "flashdecode":
            cfg = dataclasses.replace(cfg, remat_policy=o)
            note += f" +{o}"
    return cfg, note


def microbatches_for(cfg: ModelConfig, cell: ShapeCell, mesh) -> int:
    if cell.kind != "train":
        return 1
    b_dev = max(1, cell.global_batch // dp_size(mesh))
    # target ≤2 sequences per microbatch per device for the big models
    big = cfg.d_model >= 6144 or cfg.num_layers >= 48
    target = 2 if big else 8
    mb = max(1, b_dev // target)
    while cell.global_batch % (mb * dp_size(mesh)) and mb > 1:
        mb -= 1
    return mb


def synthetic_stats(cfg: ModelConfig):
    """Abstract-friendly calibration stats (ones) for fold tracing."""
    import numpy as np

    from repro.core.calibration import CalibStats

    L = cfg.num_layers
    ones = lambda *shape: jnp.ones(shape, jnp.float32)
    if cfg.family in ("dense", "audio", "vlm"):
        return {
            "k_proj": CalibStats(ones(L, cfg.d_model)),
            "o_proj": CalibStats(ones(L, cfg.num_heads * cfg.head_dim)),
            "gate_proj": CalibStats(ones(L, cfg.d_model)),
            "down_proj": CalibStats(ones(L, cfg.d_ff)),
        }
    if cfg.family == "moe":
        Lm = cfg.num_layers - cfg.first_dense_layers
        o_dim = (cfg.num_heads * cfg.v_head_dim if cfg.kv_lora_rank
                 else cfg.num_heads * cfg.head_dim)
        st = {
            "k_proj": CalibStats(ones(Lm, cfg.d_model)),
            "o_proj": CalibStats(ones(Lm, o_dim)),
            "gate_proj": CalibStats(ones(Lm, cfg.d_model)),
            "down_proj": CalibStats(ones(Lm, cfg.d_ff)),
        }
        if cfg.kv_lora_rank:
            st["kv_up"] = CalibStats(ones(Lm, cfg.kv_lora_rank))
        return st
    return {  # ssm / hybrid
        "in_proj": CalibStats(ones(cfg.num_layers, cfg.d_model)),
        "out_proj": CalibStats(ones(cfg.num_layers, cfg.d_inner)),
    }


def build_cell(arch: str, cell: ShapeCell, mesh, *, quantized: bool = True,
               microbatches: int | None = None, opt: str = ""):
    """Returns (fn, arg_shapes, in_shardings, note)."""
    cfg, note = effective_config(get_config(arch), cell, opt=opt)
    model = get_model(cfg)
    policy = QuantPolicy(weight_bits=4, act_bits=4, use_kernels="never",
                         kv_cache_bits=8)
    b, s = cell.global_batch, cell.seq_len

    params_shape = jax.eval_shape(lambda k: model.init(k, cfg),
                                  jax.random.PRNGKey(0))

    if cell.kind == "train":
        from repro.launch.train import make_train_step

        import jax.numpy as _jnp

        moment = _jnp.bfloat16 if "bf16mom" in opt else _jnp.float32
        if "bf16mom" in opt:
            note += " +bf16mom"
        opt_ = adamw(3e-4, moment_dtype=moment)
        mb = microbatches or microbatches_for(cfg, cell, mesh)
        note += f" microbatches={mb}"
        opt_shape = jax.eval_shape(opt_.init, params_shape)
        step_fn = make_train_step(model, cfg, opt_, microbatches=mb)
        bspec = batch_spec(mesh, b)
        if cfg.embeds_input and cfg.family in ("audio", "vlm"):
            batch = {"embeds": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                                    jnp.bfloat16),
                     "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
            bspecs = {"embeds": P(*bspec, None), "labels": bspec}
        else:
            batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                     "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
            bspecs = {"tokens": bspec, "labels": bspec}
        pspecs = param_specs(params_shape, cfg, mesh)
        ospecs = param_specs(opt_shape, cfg, mesh)
        args = (params_shape, opt_shape, batch,
                jax.ShapeDtypeStruct((), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))
        shardings = (pspecs, ospecs, bspecs, P(), P())
        return step_fn, args, shardings, note, cfg

    # --- serving cells: fold+quantized weights (the paper's pipeline) ---
    if quantized:
        stats = synthetic_stats(cfg)
        serve_params = jax.eval_shape(
            lambda p: fold_quantize(p, cfg, policy=policy,
                                    plan=TransformPlan(), stats=stats),
            params_shape)
        note += " W4A4+smooth_rotate serve params, int8 KV"
    else:
        serve_params = params_shape
        note += " bf16 serve params"

    cache_shape = jax.eval_shape(
        lambda: model.make_cache(cfg, b, s, bits=policy.kv_cache_bits))
    pspecs = param_specs(serve_params, cfg, mesh)
    cspecs = cache_specs(cfg, mesh, cache_shape)
    if cell.kind == "prefill":
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)

        def fn(p, t, c):
            return model.prefill(p, cfg, t, c, policy=policy)
    else:
        tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)

        def fn(p, t, c):
            return model.decode_step(p, cfg, t, c, policy=policy)

    args = (serve_params, tokens, cache_shape)
    shardings = (pspecs, batch_spec(mesh, b), cspecs)
    return fn, args, shardings, note, cfg


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str, *,
             quantized: bool = True, save_dir: str | None = None,
             microbatches: int | None = None, verbose: bool = True,
             opt: str = "", strategy: str = "2d"):
    from repro.launch.sharding import set_strategy

    set_strategy(strategy)
    cell = SHAPES[shape_name]
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args, shardings, note, cfg = build_cell(
            arch, cell, mesh, quantized=quantized, microbatches=microbatches,
            opt=opt)
        if strategy != "2d":
            note += f" strategy={strategy}"
        donate = (0, 1) if cell.kind == "train" else (2,)
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    metrics = analyze_hlo(compiled.as_text())
    chips = mesh.devices.size
    pod = mesh.shape.get("pod", 1)
    rep = roofline(metrics, cfg, cell, mesh_name=mesh_name, chips=chips,
                   pod_size=pod, notes=note.strip())
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "note": note.strip(),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "peak_gb_estimate": (mem.argument_size_in_bytes
                                 + mem.temp_size_in_bytes
                                 + mem.output_size_in_bytes
                                 - mem.alias_size_in_bytes) / 1e9,
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))
                          and k in ("flops", "bytes accessed",
                                    "utilization")},
        "hlo": {
            "flops_per_device": metrics.flops,
            "flops_by_dtype": metrics.flops_by_dtype,
            "hbm_gb_per_device": metrics.hbm_bytes / 1e9,
            "collective_raw_gb": metrics.collective_bytes / 1e9,
            "wire_gb_per_device": metrics.wire_bytes / 1e9,
            "wire_by_group_gb": {str(g): v / 1e9 for g, v
                                 in metrics.wire_bytes_by_group.items()},
            "n_collectives": len(metrics.collectives),
            "while_trips": metrics.while_trips,
        },
        "roofline": rep.row(),
    }
    if save_dir:
        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, f"{arch}__{shape_name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    if verbose:
        r = result["roofline"]
        print(f"  {arch:22s} {shape_name:12s} compile={t_compile:6.1f}s "
              f"mem={result['memory']['peak_gb_estimate']:7.2f}GB/dev "
              f"compute={r['compute_s']:.4f}s mem={r['memory_s']:.4f}s "
              f"coll={r['collective_s']:.4f}s dcn={r['dcn_s']:.4f}s "
              f"→ {r['dominant']}", flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--bf16-serve", action="store_true",
                    help="serve cells with bf16 weights (baseline compare)")
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--opt", default="",
                    help="comma list: flash,bf16io,sp (§Perf options)")
    ap.add_argument("--strategy", default="2d", choices=["2d", "fsdp"])
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args(argv)

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        mesh_name = "2x16x16" if multi else "16x16"
        save = os.path.join(args.out, mesh_name)
        print(f"== mesh {mesh_name} ({mesh.devices.size} devices) ==",
              flush=True)
        for arch in archs:
            for shape in shapes:
                try:
                    run_cell(arch, shape, mesh, mesh_name,
                             quantized=not args.bf16_serve, save_dir=save,
                             microbatches=args.microbatches or None,
                             opt=args.opt, strategy=args.strategy)
                except Exception as e:  # noqa: BLE001 — report & continue
                    failures.append((mesh_name, arch, shape, repr(e)))
                    print(f"  {arch:22s} {shape:12s} FAILED: {e!r}",
                          flush=True)
                    traceback.print_exc()
    print(f"\n{'=' * 60}")
    if failures:
        print(f"FAILURES ({len(failures)}):")
        for f in failures:
            print("  ", *f)
        raise SystemExit(1)
    print("ALL CELLS COMPILED OK")


if __name__ == "__main__":
    main()
