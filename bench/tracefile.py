"""Reduce a profiler trace to the numbers the per-layer metrics read.

Read with ``jax.profiler.ProfileData`` (nothing else is needed).  On a
TPU the device plane is ``/device:TPU:<n>``; its line ``XLA Modules``
holds one event per program run (named ``jit_<fn>(<fingerprint>)``) and
``XLA Ops`` one per HLO op, nested (a layer-scan ``while`` holds the
kernels it runs).  A Pallas kernel is an op whose HLO text has
``custom_call_target="tpu_custom_call"``; its operand shapes are in the
name.  The benchmark's own host spans (``bench.*``) are on
``/host:CPU`` in the same time base.  All times here are seconds.
"""

from __future__ import annotations

import dataclasses
import gzip
import re

Interval = tuple[float, float]


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    modules: list[Event]          # program runs on the device, by start
    ops: list[Event]              # HLO ops on the device, by start
    spans: list[Event]            # the benchmark's host spans, by start
    n_devices: int

    def window(self) -> Interval:
        """From the first to the last host span the trace holds (the
        traced steps), or the device events' extent without spans."""
        evs = self.spans or self.modules or self.ops
        return min(e.start for e in evs), max(e.end for e in evs)


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped one)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            pd = ProfileData.from_serialized_xspace(fh.read())
    else:
        pd = ProfileData.from_file(path)
    modules, ops, spans, n_dev = [], [], [], 0
    for plane in pd.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            n_dev += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += _events(line)
                elif line.name == "XLA Ops":
                    ops += _events(line)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [e for e in _events(line) if e.name.startswith("bench.")]
    key = lambda e: e.start
    return Trace(sorted(modules, key=key), sorted(ops, key=key),
                 sorted(spans, key=key), n_dev)


def union(intervals: list[Interval]) -> list[Interval]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: list[Interval], lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def busy(trace: Trace) -> list[Interval]:
    """Union of the intervals in which a program ran on a device."""
    evs = trace.modules or trace.ops
    lo, hi = trace.window()
    return clip(union([(e.start, e.end) for e in evs]), lo, hi)


def busy_seconds(trace: Trace) -> float:
    """Seconds with a program running, averaged over the devices."""
    return sum(e - s for s, e in busy(trace)) / max(trace.n_devices, 1)


def idle_gaps(trace: Trace) -> list[tuple[str, float]]:
    """Idle device time in the traced window, summed by the innermost
    host span open at the middle of each gap (``none`` where none is)."""
    lo, hi = trace.window()
    b = busy(trace)
    gaps, t = [], lo
    for s, e in b:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    by: dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        open_ = [sp for sp in trace.spans if sp.start <= mid < sp.end]
        name = min(open_, key=lambda sp: sp.dur).name if open_ else "none"
        by[name] = by.get(name, 0.0) + (e - s)
    return sorted(by.items(), key=lambda kv: -kv[1])


def self_times(ops: list[Event]) -> list[tuple[Event, float]]:
    """Each op's time less that of the ops nested inside it."""
    out: list[list] = []
    stack: list[list] = []
    for e in ops:
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        rec = [e, e.dur]
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= e.dur
        out.append(rec)
        stack.append(rec)
    return [(e, max(t, 0.0)) for e, t in out]


_PAGED = re.compile(r"custom-call\(s32\[\d+,\d+\](\{[^}]*\})?[^,]*, s32\[\d+\]")


def op_kind(name: str) -> str:
    """A short stable name for an HLO op: the two Pallas kernels by
    their name or, while they carry none, by their operand signature
    (paged attention takes the page table and the lengths first; the
    quantized linear takes an activation and an int8 weight); any other
    op by its instruction name without the number."""
    head = name.split(" = ", 1)[0].lstrip("%")
    if "tpu_custom_call" in name:
        if "paged_attention" in name or _PAGED.search(name):
            return "paged_attention"
        if "fused_qlinear" in name or " s8[" in name:
            return "fused_qlinear"
        return "pallas:" + re.sub(r"\.\d+$", "", head)
    return re.sub(r"\.\d+$", "", head)


def kernel_seconds(trace: Trace, kind: str) -> float:
    lo, hi = trace.window()
    return sum(min(e.end, hi) - max(e.start, lo) for e in trace.ops
               if op_kind(e.name) == kind and e.end > lo and e.start < hi)


def top_ops(trace: Trace, n: int = 10) -> list[tuple[str, float]]:
    by: dict[str, float] = {}
    for e, t in self_times(trace.ops):
        k = op_kind(e.name)
        by[k] = by.get(k, 0.0) + t
    return sorted(by.items(), key=lambda kv: -kv[1])[:n]


def program_runs(trace: Trace, span_name: str) -> list[Event]:
    """Every run of the program that the host spans of that name
    dispatch.  Host and device clocks are not aligned to the dispatch,
    so the program is found by count: among the programs that ran about
    as often as the spans were opened (within a tenth, or one), the one
    with the most device time."""
    n = sum(1 for s in trace.spans if s.name == span_name)
    if not n:
        return []
    runs: dict[str, list[Event]] = {}
    for e in trace.modules:
        runs.setdefault(e.name, []).append(e)
    fits = [r for r in runs.values() if abs(len(r) - n) <= max(1, n // 10)]
    return max(fits, key=lambda r: sum(e.dur for e in r)) if fits else []
