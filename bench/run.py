"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with a TPU.  The cell (its
configuration, traffic and metrics) comes from ``BENCHMARK.json`` and
the files under ``bench/``.  The run makes the weights from the seed,
calibrates and folds them, warms every shape the traffic uses (all of
that is ``setup_s``), drives the paged engine for ``--seconds``, then
checks what the window produced against the plain reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window's middle and
from the engine's own events.  The last stdout line is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``[,
``breakdown``], ``checks``); the last stderr lines are each compared
number beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.

``--control 1`` also reads the control (the reference one precision
step below the configuration) in the program's place on the same
sequences, and prints its numbers and verdict.  ``--fault <name>``
breaks the timed path as ``serve_cell.inject`` describes, to read what
the check makes of it.  The benchmark's own runs use neither.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import check, spec, tracefile  # noqa: E402
from bench import serve_cell as sc  # noqa: E402


def _say(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _lateness(run) -> str:
    late = [run.fed[u] - run.due[u] for u in run.fed
            if u in run.due and run.in_window(run.due[u])]
    if not late:
        return "generator: no request fell due in the window"
    late = np.asarray(late)
    return (f"generator: {len(late)} requests due in the window; handed to "
            f"the engine after their due time by p50 {np.median(late):.6f} s, "
            f"p95 {np.percentile(late, 95):.6f} s, max {late.max():.6f} s")


def _steps(run) -> str:
    s = np.asarray(run.step_s)
    if not len(s):
        return "engine steps in the window: none"
    n, gcs = run.gc_in_window
    return (f"engine steps in the window: {len(s)}; p50 {np.median(s):.6f} s, "
            f"longest {s.max():.6f} s; {int((s > 1.0).sum())} over 1 s "
            f"taking {s[s > 1.0].sum():.6f} s; garbage collection {n} times, "
            f"{gcs:.6f} s")


def _attempted(run) -> int:
    return sum(1 for u in set(run.due) | set(run.tokens)
               if (u in run.due and run.in_window(run.due[u]))
               or any(run.in_window(t) for t in run.tokens.get(u, ())))


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             control: bool = False, fault: str | None = None,
             peaks: dict | None = None, t_start: float = T_START) -> dict:
    """Set up, serve, read the metrics, check; returns the result line."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        run, eng = sc.serve(cell, seed, seconds, trace=trace,
                            t_start=t_start, trace_dir=trace_dir, fault=fault)
        run.peaks = peaks or {}
        _say(f"setup_s {run.setup_s:.3f}; window {seconds} s; "
             f"tokens in the window "
             f"{sum(1 for ts in run.tokens.values() for t in ts if run.in_window(t))}; "
             f"requests finished {len(run.finished)}")
        _say(_lateness(run))
        _say(_steps(run))
        _say(f"compilations inside the window: {run.compiles_in_window}")
        breakdown = None
        if trace and trace_dir:
            files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                              recursive=True)
            run.trace = tracefile.load(files[0]) if files else None
        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            v = spec.read_metric(m["name"], run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": run.memory_peak_bytes}
        if run.trace is not None:
            lo, hi = run.trace.window()
            device["busy_s"] = tracefile.busy_seconds(run.trace)
            device["window_s"] = hi - lo
            breakdown = {"device_ops": [list(x) for x in tracefile.top_ops(run.trace)],
                         "idle_gaps": [list(x) for x in
                                       tracefile.idle_gaps(run.trace)[:10]]}
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    sc.snapshot(eng, run, check.keep_layers(run.m), check.N_LIVE)
    del eng
    gc.collect()
    nums = check.numbers(run, control=control)
    correct, checks = check.judge(nums, cell.limits)
    for k, v in nums.items():
        _say(f"reading {k}: {v}")
    if control:
        c_ok, c_checks = check.judge(nums, cell.limits, prefix="control.")
        for k, c in c_checks.items():
            _say(f"control {k}: {c['value']} (limit {c['limit']})")
        _say(f"control correct: {c_ok}")
    for k, c in checks.items():
        _say(f"{k}: {c['value']} (limit {c['limit']})")
    out = {"correct": bool(correct), "attempted": _attempted(run),
           "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--control", type=int, choices=[0, 1], default=0)
    ap.add_argument("--fault", choices=sc.FAULTS, default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _say(f"bench: needs a TPU; JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        _say(f"bench: {cell.name} needs {cell.chips} chips; found {len(devs)}")
        return 2
    peaks = spec.peaks(devs[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   control=bool(args.control), fault=args.fault, peaks=peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
