"""Median over the traced steps of the program's `train.step` span: the
host's time in one `ShardedTrainStep.__call__` (the device runs a step
behind it)."""
import program_spans


def read(trace, counters, cell):
    return program_spans.span_ms_p50(trace, cell, "train.step")
