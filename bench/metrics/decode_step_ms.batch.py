"""decode_step_ms.batch: device time of the decode program per dispatch
in the traced steps, in milliseconds (the program the ``bench.decode`` spans
dispatch)."""

from bench.tracefile import program_runs


def read(run):
    progs = program_runs(run.trace, "bench.decode") if run.trace else []
    return 1e3 * sum(p.dur for p in progs) / len(progs) if progs else None
