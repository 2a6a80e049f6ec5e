"""Find a cell's knee: the highest open-loop rate it sustains.

    python3 bench/sweep.py --workload stablelm-3b-w4a4.chat --seed 1 \
        --seconds 30 --rates 1 2 3 4 5

One process on the chip: set up once, then for each rate run the cell's
traffic at that rate for ``--seconds`` from an empty engine, and print
one JSON line per rate: requests due, finished, the backlog (due and
still without a first token) at the half and at the close, and the
cell's end-to-end metrics.  The knee is the highest rate whose backlog
does not grow from the half to the close.  A cell's traffic file then takes about four
fifths of it as a fixed number; the benchmark itself never searches.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import serve_cell as sc  # noqa: E402
from bench import spec  # noqa: E402
from bench.traffic import Traffic  # noqa: E402


def _backlog(run, t: float) -> int:
    """Requests due by ``t`` still waiting for their first token."""
    return sum(1 for u, d in run.due.items()
               if d <= t and not any(ts <= t for ts in run.tokens.get(u, ())[:1]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)

    import jax

    from repro.launch.mesh import make_test_mesh

    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    with jax.set_mesh(make_test_mesh()):
        m, _, eng = sc.build(cell, args.seed, obs=False)
        sc.warm(eng, sc.shape_set(cell.config, cell.traffic, eng._chunked),
                m["vocab_size"])
        for rate in args.rates:
            tr = copy.deepcopy(cell.traffic)
            tr["arrival"]["rate"] = rate
            traffic = Traffic(tr, args.seed, m["vocab_size"])
            run = sc.Run(cell=cell, m=m, seed=args.seed, seconds=args.seconds)
            drv = sc.Load(eng, run, traffic, sc.ADMISSION_CAP)
            run.t0 = time.perf_counter()
            run.t_end = run.t0 + args.seconds
            sc._window(drv, traffic, run, False, None)
            half = _backlog(run, run.t0 + args.seconds / 2)
            close = _backlog(run, run.t_end)
            row = {"rate": rate, "due": sum(1 for d in run.due.values()
                                            if run.in_window(d)),
                   "finished": len(run.finished), "backlog_half": half,
                   "backlog_close": close}
            for metric in cell.end_to_end:
                if metric["name"] != "setup_s":
                    row[metric["name"]] = spec.read_metric(metric["name"], run)
            print(json.dumps(row), flush=True)
            drv.front.clear()                   # start the next rate empty
            for req in list(eng.queue) + [r for r in eng.slots if r is not None]:
                eng.cancel(req.uid)
            eng.pop_retired()
    return 0


if __name__ == "__main__":
    sys.exit(main())
