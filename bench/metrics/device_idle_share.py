"""device_idle_share: the share of the traced steps in which no program
ran on the device, in percent (1 - union of program intervals / window)."""

from bench.tracefile import busy_seconds


def read(run):
    if not run.trace or not run.trace.modules:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - busy_seconds(run.trace) / (hi - lo))
