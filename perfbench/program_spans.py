"""The program's own spans, read from its process recorder
(``repro.telemetry.process_recorder``): the program times each span itself,
on the monotonic clock, and keeps the last 4096 durations of each name.

A program that has no process recorder, or never recorded the span, gives
``None``: the metric is then left out of the result line.
"""
from typing import Optional


def median_ms(name: str) -> Optional[float]:
    """The median duration of the program's span ``name``, in ms."""
    try:
        from repro.telemetry import process_recorder
    except ImportError:
        return None
    summary = process_recorder().metrics()["histograms"].get(
        f"span/{name}_s")
    if not summary or not summary["count"]:
        return None
    return 1e3 * summary["p50"]
