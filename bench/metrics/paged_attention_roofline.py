"""paged_attention_roofline: over every paged decode-attention call of
the traced steps, the least time the chip could take for the live
positions of each slot (bytes of K, V and scales over HBM bandwidth, or
ops over the bf16 peak) over the kernel's device time, in percent."""

from bench.tracefile import kernel_seconds
from bench.work import paged_attention_seconds


def read(run):
    t = kernel_seconds(run.trace, "paged_attention") if run.trace else 0.0
    if t <= 0:
        return None
    kv = run.cell.config["quant"]["kv_bits"] or 16
    ideal = sum(paged_attention_seconds(d, run.m, kv, run.peaks)
                for d in run.dispatches if d.kind == "decode" and d.starts)
    return 100.0 * ideal / t
