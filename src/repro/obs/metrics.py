"""Zero-dependency metrics primitives for the serving stack.

A :class:`MetricsRegistry` names three instrument kinds:

  * :class:`Counter`   — monotone float (dispatch counts, token totals,
    compiles);
  * :class:`Gauge`     — last-write-wins float (pool occupancy);
  * :class:`Histogram` — fixed upper-bound buckets for cheap shape
    inspection PLUS an exact reservoir of every observation, so the
    p50/p90/p99 the latency reports quote are nearest-rank EXACT (no
    bucket interpolation).  Serving runs observe thousands of spans,
    not millions — the reservoir is bounded by ``reservoir_cap`` and
    decimates deterministically (every 2nd kept) if a run overflows it,
    which keeps percentiles exact for every workload the benchmarks and
    tests drive.

Every clock in the subsystem is injectable (:class:`ManualClock` in
tests) so span durations and percentiles are deterministic under test.
The registry itself never touches a clock — callers time spans and
``observe`` the durations.
"""

from __future__ import annotations

import dataclasses
import math

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "ManualClock", "exact_percentile", "percentile_summary"]

# upper bounds in seconds, tuned for serve-time spans (sub-ms ticks to
# multi-second prefills); +inf is implicit
DEFAULT_BUCKETS = (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 1e-1, 3e-1, 1.0,
                   3.0, 10.0)


class ManualClock:
    """Deterministic test clock: call → current time, advance() moves it."""

    def __init__(self, start: float = 0.0):
        self.t = float(start)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += float(dt)
        return self.t


def exact_percentile(sorted_xs: list[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 100]) of an ASCENDING list."""
    if not sorted_xs:
        return float("nan")
    rank = max(int(math.ceil(q / 100.0 * len(sorted_xs))), 1)
    return sorted_xs[rank - 1]


def percentile_summary(xs: list[float]) -> dict:
    """count/mean/min/max + exact p50/p90/p99 of a sample list."""
    if not xs:
        return {"count": 0}
    s = sorted(xs)
    return {
        "count": len(s),
        "mean": sum(s) / len(s),
        "min": s[0],
        "max": s[-1],
        "p50": exact_percentile(s, 50),
        "p90": exact_percentile(s, 90),
        "p99": exact_percentile(s, 99),
    }


@dataclasses.dataclass
class Counter:
    name: str
    value: float = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counter {self.name}: negative increment")
        self.value += amount


@dataclasses.dataclass
class Gauge:
    name: str
    value: float = 0.0

    def set(self, value: float) -> None:
        self.value = float(value)


class Histogram:
    """Fixed-bucket histogram + exact observation reservoir."""

    def __init__(self, name: str, buckets: tuple[float, ...] = DEFAULT_BUCKETS,
                 reservoir_cap: int = 65536):
        self.name = name
        self.buckets = tuple(sorted(buckets))
        self.bucket_counts = [0] * (len(self.buckets) + 1)  # last = +inf
        self.count = 0
        self.total = 0.0
        self.reservoir: list[float] = []
        self.reservoir_cap = reservoir_cap

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        for i, ub in enumerate(self.buckets):
            if value <= ub:
                self.bucket_counts[i] += 1
                break
        else:
            self.bucket_counts[-1] += 1
        self.reservoir.append(value)
        if len(self.reservoir) > self.reservoir_cap:
            # deterministic decimation: keep every other sample; the cap
            # is far above any bench/test workload, so in practice the
            # reservoir is the full observation set (exact percentiles)
            self.reservoir = self.reservoir[::2]

    def percentile(self, q: float) -> float:
        return exact_percentile(sorted(self.reservoir), q)

    def summary(self) -> dict:
        out = percentile_summary(self.reservoir)
        out.update(total=self.total,
                   buckets={str(ub): c for ub, c in
                            zip(self.buckets, self.bucket_counts)},
                   overflow=self.bucket_counts[-1])
        # count from the reservoir equals self.count unless decimated
        out["count"] = self.count
        return out


class MetricsRegistry:
    """Name → instrument, created on first use (prometheus-style)."""

    def __init__(self):
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        if name not in self._counters:
            self._counters[name] = Counter(name)
        return self._counters[name]

    def gauge(self, name: str) -> Gauge:
        if name not in self._gauges:
            self._gauges[name] = Gauge(name)
        return self._gauges[name]

    def histogram(self, name: str,
                  buckets: tuple[float, ...] = DEFAULT_BUCKETS) -> Histogram:
        if name not in self._histograms:
            self._histograms[name] = Histogram(name, buckets)
        return self._histograms[name]

    def counters_with_prefix(self, prefix: str) -> dict[str, float]:
        """{suffix: value} of every counter named ``prefix`` + suffix."""
        return {n[len(prefix):]: c.value for n, c in self._counters.items()
                if n.startswith(prefix)}

    def snapshot(self) -> dict:
        """JSON-serializable view of every instrument."""
        return {
            "counters": {n: c.value for n, c in sorted(self._counters.items())},
            "gauges": {n: g.value for n, g in sorted(self._gauges.items())},
            "histograms": {n: h.summary()
                           for n, h in sorted(self._histograms.items())},
        }
