"""decode_copy_ms: device self time of the copies (``copy``, ``copy-start``,
``copy-done``) inside the runs of the decode program (``jit_decode_step``)
in the traced steps, per run, in milliseconds: the decode cache the
non-donated decode copies, beside what else it copies.  Programs not
named ``jit_decode_step`` give nothing to read."""

import bisect

from bench.tracefile import op_kind, self_times

COPIES = ("copy", "copy-start", "copy-done")


def read(run):
    if not run.trace:
        return None
    lo, hi = run.trace.window()
    runs = [m for m in run.trace.modules if m.name.startswith("jit_decode_step(")
            and m.start >= lo and m.end <= hi]
    if not runs:
        return None
    starts = [m.start for m in runs]
    total = 0.0
    for e, t in self_times(run.trace.ops):
        if op_kind(e.name) not in COPIES:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= runs[i].end:
            total += t
    return 1e3 * total / len(runs)
