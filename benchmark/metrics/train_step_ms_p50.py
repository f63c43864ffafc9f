"""Median time from one step's completion to the next, in the window."""
from metrics._common import median


def read(trace, counters, cell):
    return median(counters.get("step_intervals_ms", []))
