"""fused_qlinear_roofline: over every quantized-linear call of the traced
steps, the least time the chip could take (the larger of int8 ops over
the int8 peak and bytes over HBM bandwidth, from the algorithm's shapes)
over the kernel's device time, in percent."""

from bench.tracefile import kernel_seconds
from bench.work import linear_seconds


def read(run):
    t = kernel_seconds(run.trace, "fused_qlinear") if run.trace else 0.0
    if t <= 0:
        return None
    wb = run.cell.config["quant"]["weight_bits"]
    ideal = sum(linear_seconds(d, run.m, wb, run.peaks) for d in run.dispatches)
    return 100.0 * ideal / t
