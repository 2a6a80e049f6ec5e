"""Time ``fused_qlinear`` on the chip at stablelm-3b's linear shapes.

    python3 tools/qlinear_tiles.py [--reps 20] [--out DIR]

On a TPU.  For each distinct (K, N) of stablelm-3b's seven quantized
linears (q/k/v/o 2560 x 2560, gate/up 2560 x 6912, down 6912 x 2560
with smoothing), at 32 rows (a decode tick) and 1024 (a 4 x 256
prefill), it runs three variants:

  * ``derived``: the tiles ``fused_qlinear`` derives from the shape;
  * ``rows8``: 8-row blocks with 256 x 512 weight tiles, so every 8
    rows stream and unpack the whole weight again;
  * ``w8``: int8 weights (no nibble unpack) with derived tiles.

Each variant runs ``--reps`` times under the profiler; the time per call
is the device time of the ``fused_qlinear`` ops in the trace over the
calls.  One JSON line per case: tiles, grid steps, microseconds per call,
the least time the work allows (int4 weight bytes over HBM bandwidth,
int8 ops over the peak, from ``bench/peaks.json``), and the largest
absolute difference of ``derived`` from ``rows8``, which should be 0.
With ``--out`` the lines also go to ``DIR/qlinear_tiles.jsonl``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import spec, tracefile  # noqa: E402
from repro.core.hadamard import kernel_fusable_factor  # noqa: E402
from repro.core.qlinear import QuantizedWeight  # noqa: E402
from repro.kernels import fused_qlinear as fq  # noqa: E402

SHAPES = {"qkvo": (2560, 2560, False), "gate_up": (2560, 6912, False),
          "down": (6912, 2560, True)}
ROWS8 = dict(block_n=8, block_m=256, block_k=512)


def _weight(key, k, m, bits, smooth):
    kw, ks, kd = jax.random.split(key, 3)
    rows = k // 2 if bits == 4 else k
    lo, hi = (-128, 128) if bits == 4 else (-127, 128)
    return QuantizedWeight(
        w_q=jax.random.randint(kw, (rows, m), lo, hi, jnp.int32
                               ).astype(jnp.int8),
        scale=jax.random.uniform(ks, (1, m), jnp.float32, 0.005, 0.02),
        smooth=(jax.random.uniform(kd, (k,), jnp.float32, 0.5, 1.5)
                if smooth else None),
        bits=bits, packed=bits == 4, had_dim=k)


def _per_call_s(fn, x, qw, reps: int) -> tuple[float, int]:
    fn(x, qw).block_until_ready()                 # compile and warm
    d = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            y = fn(x, qw)
        y.block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
        ops = [e for e in tracefile.load(path).ops
               if tracefile.op_kind(e.name) == "fused_qlinear"]
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return sum(e.dur for e in ops) / reps, len(ops)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("qlinear_tiles: needs a TPU")
    dev = jax.devices()[0]
    peak = spec.peaks(dev.device_kind)
    lines = []
    for name, (k, m, smooth) in SHAPES.items():
        group = max(kernel_fusable_factor(k), 128)
        for n in (32, 1024):
            x = jax.random.normal(jax.random.PRNGKey(n), (n, k)
                                  ).astype(jnp.bfloat16)
            w4 = _weight(jax.random.PRNGKey(k + m), k, m, 4, smooth)
            w8 = _weight(jax.random.PRNGKey(k + m), k, m, 8, smooth)
            derived = jax.jit(fq.fused_qlinear)
            rows8 = jax.jit(lambda x, w: fq.fused_qlinear(x, w, **ROWS8))
            diff = float(jnp.max(jnp.abs(
                derived(x, w4).astype(jnp.float32)
                - rows8(x, w4).astype(jnp.float32))))
            least = max(k * m / 2 / peak["hbm_bytes"],
                        2.0 * n * k * m / peak["int8_ops"])
            for variant, fn, w, bits in (("derived", derived, w4, 4),
                                         ("rows8", rows8, w4, 4),
                                         ("w8", derived, w8, 8)):
                reps = a.reps if variant != "rows8" or n < 1024 else 3
                t, count = _per_call_s(fn, x, w, reps)
                if variant == "rows8":
                    bn, bm, bk = 8, 256, 512
                else:
                    bn, bm, bk = fq._tiles(n, k, m, bits == 4, group)
                steps = (-(-n // bn)) * (-(-m // bm)) * (-(-k // bk))
                line = {"shape": name, "k": k, "n_out": m, "rows": n,
                        "variant": variant, "tiles": [bn, bm, bk],
                        "grid_steps": steps, "us_per_call": t * 1e6,
                        "ops_seen": count, "reps": reps,
                        "least_us_w4": least * 1e6,
                        "max_abs_diff_vs_rows8": diff,
                        "device_kind": dev.device_kind}
                print(json.dumps(line), flush=True)
                lines.append(line)
    if a.out:
        os.makedirs(a.out, exist_ok=True)
        with open(os.path.join(a.out, "qlinear_tiles.jsonl"), "w") as fh:
            fh.writelines(json.dumps(ln) + "\n" for ln in lines)


if __name__ == "__main__":
    main()
