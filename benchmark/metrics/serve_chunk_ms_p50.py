"""Median time of one ContinuousBatcher.step() in the window (the
benchmark's span around it)."""
from metrics._common import median


def read(trace, counters, cell):
    return median(counters.get("span_ms", {}).get("bench.serve_step", []))
