"""Print how a profiler trace of the served path names its planes, lines,
programs and kernels.

    python3 bench/trace_dump.py --out <dir> [--layers 2]

One process on one chip.  Serves a few requests through the paged engine
for stablelm-3b and mamba2-780m at published widths but ``--layers``
deep, traces a few decode ticks with the benchmark's host spans around
them, and writes for each model a summary of the trace (plane and line
names, the busiest device events with their stats) and the raw
``.xplane.pb`` into ``--out``.  It is the look at a real trace that the
reduction in ``bench/tracefile.py`` is written against.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
from collections import Counter, defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))


def summarize(path: str, out) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events", file=out)
            if not evs:
                continue
            tot = defaultdict(float)
            cnt = Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                print(f"    {ns / 1e6:10.3f} ms  x{cnt[name]:<5d} {name[:150]}",
                      file=out)
            shown = 0
            for e in evs:
                if shown >= 3:
                    break
                stats = dict(e.stats)
                if stats:
                    print(f"    stats of {e.name[:60]!r}: "
                          f"{ {k: str(v)[:160] for k, v in stats.items()} }",
                          file=out)
                    shown += 1


def run(arch: str, layers: int, out_dir: str) -> None:
    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.core.qlinear import QuantPolicy
    from repro.core.transforms import TransformPlan
    from repro.launch.mesh import make_test_mesh
    from repro.models.api import get_model
    from repro.serving.engine import EngineConfig, PagedServingEngine, Request
    from repro.serving.fold import collect_calibration, fold_quantize

    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    model = get_model(cfg)
    with jax.set_mesh(make_test_mesh()):
        params = model.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        calib = [{"tokens": rng.integers(0, cfg.vocab_size, (2, 64))}]
        stats = collect_calibration(model, params, cfg, calib)
        policy = QuantPolicy(weight_bits=4, act_bits=4, kv_cache_bits=8)
        qp = fold_quantize(params, cfg, policy=policy,
                           plan=TransformPlan(alpha=0.5), stats=stats)
        del params
        eng = PagedServingEngine(model, qp, cfg, config=EngineConfig(
            max_slots=8, max_len=512, policy=policy, kv_bits=8,
            page_size=64, prefill_bucket=128))
        for i in range(8):
            eng.submit(Request(uid=i, prompt=rng.integers(
                0, cfg.vocab_size, (100 + 10 * i,)), max_new_tokens=64))
        for _ in range(4):
            eng.step()
        tdir = tempfile.mkdtemp()
        jax.profiler.start_trace(tdir)
        for _ in range(6):
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(path, os.path.join(out_dir, f"{arch}.xplane.pb"))
        with open(os.path.join(out_dir, f"{arch}.txt"), "w") as fh:
            summarize(path, fh)
        shutil.rmtree(tdir, ignore_errors=True)
        print(f"{arch}: trace summary in {out_dir}/{arch}.txt; "
              f"{eng.stats()['dispatch_backends']}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--out", required=True)
    ap.add_argument("--arch", nargs="*", default=["stablelm-3b",
                                                   "mamba2-780m"])
    args = ap.parse_args()
    for arch in args.arch:
        run(arch, args.layers, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
