"""Tie each device program run of a profiler trace to the engine span
that launched it, and break the device's idle time down by engine span.

The engine (``repro.serving.engine``) opens ``engine.*`` host spans
around its phases whether or not a profiler runs; in a trace they sit
on the Python thread, on the profiler's clock.  A program run on the
device (``XLA Modules``) is tied to the host by the trace's own
identifiers, never by counting or by time alone: the runtime records
flow ids on its events (``_p``: this event produces flow id n, ``_c``:
it consumes it), so a run's consumer id leads to the host event that
enqueued it; from there each hop takes the flow that the event or an
event enclosing it on its thread consumes, back to the event that
produced it, until the hop lands on the thread that holds the engine
spans.  The run belongs to the innermost engine span open there at that
moment (the same thread, so nesting in time is exact).  A run no flow
leads back from stays unattributed (``none``).

    python3 bench/engine_trace.py <trace.xplane.pb[.gz]>

prints both breakdowns.  All times are seconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import gzip
import os
import re
import sys
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))))

from bench import tracefile  # noqa: E402

MAX_HOPS = 8


@dataclasses.dataclass
class HostEvent:
    name: str
    start: float
    end: float
    line: str
    stats: dict

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ProgramRun:
    name: str                 # ``jit_<fn>`` without the fingerprint
    start: float
    end: float
    flow: str | None          # the consumer flow id (``_c``)


@dataclasses.dataclass
class EngineTrace:
    trace: tracefile.Trace    # as ``tracefile.load`` reads the same file
    engine_spans: list[HostEvent]
    runs: list[ProgramRun]
    host: dict[str, list[HostEvent]]   # flow-carrying events by line


def _profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            return ProfileData.from_serialized_xspace(fh.read())
    return ProfileData.from_file(path)


def _stats(e) -> dict:
    return {k: (v if isinstance(v, (int, float)) else str(v))
            for k, v in e.stats}


def _ev(e, line: str) -> HostEvent:
    s = e.start_ns * 1e-9
    return HostEvent(e.name, s, s + e.duration_ns * 1e-9, line, _stats(e))


def load(path: str) -> EngineTrace:
    """Read a trace: ``tracefile.load``'s reduction, the engine spans,
    every device program run with its flow id, and the host events that
    carry one."""
    runs, spans = [], []
    host: dict[str, list[HostEvent]] = defaultdict(list)
    for plane in _profile(path).planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for e in line.events:
                    st = _stats(e)
                    s = e.start_ns * 1e-9
                    runs.append(ProgramRun(
                        re.sub(r"\(\d+\)$", "", e.name), s,
                        s + e.duration_ns * 1e-9,
                        str(st["_c"]) if "_c" in st else None))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        spans.append(_ev(e, line.name))
                    else:
                        ev = _ev(e, line.name)
                        if {"_p", "_c"} & set(ev.stats):
                            host[line.name].append(ev)
    key = lambda e: e.start  # noqa: E731
    for evs in host.values():
        evs.sort(key=key)
    return EngineTrace(tracefile.load(path), sorted(spans, key=key),
                       sorted(runs, key=key), dict(host))


def _enclosing(evs: list[HostEvent], t: float) -> list[HostEvent]:
    """Events of one line open at ``t``, innermost first."""
    i = bisect.bisect_right([e.start for e in evs], t)
    return sorted((e for e in evs[:i] if e.end >= t), key=lambda e: e.dur)


def innermost(spans: list[HostEvent], t: float,
              line: str | None = None) -> HostEvent | None:
    """The shortest engine span open at ``t`` (on ``line``, if given)."""
    open_ = [s for s in spans if s.start <= t < s.end
             and (line is None or s.line == line)]
    return min(open_, key=lambda s: s.dur) if open_ else None


def launcher(et: EngineTrace, run: ProgramRun,
             producers: dict[str, HostEvent]) -> HostEvent | None:
    """The innermost engine span that launched ``run``, following flow
    ids from ``producers`` (flow id to the host event producing it)."""
    engine_lines = {s.line for s in et.engine_spans}
    ev = producers.get(run.flow) if run.flow else None
    for _ in range(MAX_HOPS):
        if ev is None:
            return None
        if ev.line in engine_lines:
            return innermost(et.engine_spans, ev.start, ev.line)
        nxt = None
        for enc in [ev] + _enclosing(et.host.get(ev.line, []), ev.start):
            c = enc.stats.get("_c")
            if c is not None and str(c) in producers \
                    and producers[str(c)] is not enc:
                nxt = producers[str(c)]
                break
        ev = nxt
    return None


def _producers(et: EngineTrace) -> dict[str, HostEvent]:
    return {str(e.stats["_p"]): e for evs in et.host.values() for e in evs
            if "_p" in e.stats}


def launches(et: EngineTrace) -> list[tuple[ProgramRun, HostEvent | None]]:
    """Every program run inside the traced window with the engine span
    that launched it (``None`` where no flow leads back)."""
    lo, hi = et.trace.window()
    producers = _producers(et)
    return [(r, launcher(et, r, producers)) for r in et.runs
            if r.end > lo and r.start < hi]


def programs_by_span(et: EngineTrace) -> list[tuple[str, str, int, float]]:
    """(engine span, program, runs, device seconds clipped to the
    window), the most device time first."""
    lo, hi = et.trace.window()
    by: dict[tuple[str, str], list] = {}
    for r, sp in launches(et):
        rec = by.setdefault((sp.name if sp else "none", r.name), [0, 0.0])
        rec[0] += 1
        rec[1] += min(r.end, hi) - max(r.start, lo)
    return sorted(((s, p, n, t) for (s, p), (n, t) in by.items()),
                  key=lambda x: -x[3])


def idle_by_span(et: EngineTrace) -> list[tuple[str, float]]:
    """The device's idle time over ``tracefile``'s window, summed by the
    innermost engine span open at the middle of each gap (``none``
    where none is): the gaps ``tracefile.idle_gaps`` sums by benchmark
    span, so the totals agree."""
    tr = et.trace
    lo, hi = tr.window()
    gaps, t = [], lo
    for s, e in tracefile.busy(tr):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    by: dict[str, float] = {}
    for s, e in gaps:
        sp = innermost(et.engine_spans, 0.5 * (s + e))
        name = sp.name if sp else "none"
        by[name] = by.get(name, 0.0) + (e - s)
    return sorted(by.items(), key=lambda kv: -kv[1])


def report(et: EngineTrace) -> list[str]:
    """The two breakdowns, one line each."""
    idle = idle_by_span(et)
    progs = programs_by_span(et)
    return [
        "idle by engine span (s): total "
        f"{sum(t for _, t in idle):.6f}; "
        + ", ".join(f"{n} {t:.6f}" for n, t in idle),
        "device programs by launching engine span (runs, device ms): "
        + ", ".join(f"{s} > {p} x{n} {1e3 * t:.3f}" for s, p, n, t in progs),
    ]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[2].strip(), file=sys.stderr)
        return 2
    for line in report(load(argv[0])):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
