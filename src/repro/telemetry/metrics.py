"""Counters / gauges / histograms for the telemetry subsystem.

The paper's analysis (§3.1–3.2) is about DISTRIBUTIONS of per-phase times,
not single samples — exposed communication is a tail phenomenon.  So the
aggregation primitive here is a histogram that keeps raw values (exact
percentiles beat bucketed approximations) and computes numpy-convention
percentiles, which is what ``Server.latency_stats`` and
``benchmarks/serve_load.py`` report instead of re-sorting request lists by
hand.  A recorder's histograms keep only the most recent ``WINDOW``
observations, so a long-lived process holds a bounded amount per span.
"""
from __future__ import annotations

from collections import deque
from typing import Dict, Optional

import numpy as np


class Counter:
    """Monotonically increasing count (steps, tokens, preemptions...)."""
    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0

    def add(self, n: int = 1) -> None:
        self.value += n


class Gauge:
    """Last-write-wins instantaneous value (active slots, free pages...)."""
    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        self.value = float(v)


WINDOW = 4096      # observations a recorder's histogram keeps


class Histogram:
    """Exact-sample histogram: numpy-convention percentiles (linear
    interpolation — the same numbers ``np.percentile`` gives, asserted in
    tests/test_telemetry.py) over every observation, or over the last
    ``window`` of them when one is given (older ones fall out)."""
    __slots__ = ("_values",)

    def __init__(self, window: Optional[int] = None) -> None:
        self._values: deque = deque(maxlen=window)

    def observe(self, v: float) -> None:
        self._values.append(float(v))

    @property
    def count(self) -> int:
        return len(self._values)

    @property
    def total(self) -> float:
        return float(sum(self._values))

    def percentile(self, p: float) -> Optional[float]:
        if not self._values:
            return None
        return float(np.percentile(np.asarray(self._values), p))

    def summary(self) -> dict:
        """{count, mean, p50, p99, max} — ``None`` stats when empty."""
        if not self._values:
            return {"count": 0, "mean": None, "p50": None, "p99": None,
                    "max": None}
        a = np.asarray(self._values)
        return {"count": int(a.size), "mean": float(a.mean()),
                "p50": float(np.percentile(a, 50)),
                "p99": float(np.percentile(a, 99)),
                "max": float(a.max())}


class NullHistogram:
    """The no-op twin ``NullRecorder.hist`` hands out: observing costs one
    attribute lookup and a pass."""
    __slots__ = ()
    count = 0
    total = 0.0

    def observe(self, v: float) -> None:
        pass

    def percentile(self, p: float) -> Optional[float]:
        return None

    def summary(self) -> dict:
        return {"count": 0, "mean": None, "p50": None, "p99": None,
                "max": None}


NULL_HISTOGRAM = NullHistogram()


def metrics_summary(counters: Dict[str, Counter],
                    gauges: Dict[str, Gauge],
                    histograms: Dict[str, Histogram]) -> dict:
    """One JSON-ready snapshot of every metric a recorder accumulated —
    the final line of the JSONL sink."""
    return {
        "counters": {k: c.value for k, c in sorted(counters.items())},
        "gauges": {k: g.value for k, g in sorted(gauges.items())},
        "histograms": {k: h.summary()
                       for k, h in sorted(histograms.items())},
    }
