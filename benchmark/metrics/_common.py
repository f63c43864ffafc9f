"""Arithmetic shared by the per-layer readers.  Each reader is
`read(trace, counters, cell)`: `trace` is a trace_reduce.Trace (None in an
untraced run), `counters` what the driver counted in the window, `cell` the
configuration, workload, traffic mix, chips and peaks.  A reader that finds
nothing to read returns None."""
import statistics

import opcount
import trace_reduce


def median(values):
    return statistics.median(values) if values else None


def idle_share_percent(trace):
    if trace is None:
        return None
    share = trace_reduce.idle_share(trace)
    return None if share is None else 100.0 * share


def kernel_roofline_percent(trace, pattern, flops, nbytes, cell):
    """Least time the chips could take for (flops, bytes) over the traced
    device time of the kernels matching `pattern`, in percent."""
    if trace is None:
        return None
    seconds = trace_reduce.kernel_seconds(trace, pattern)
    if not seconds:
        return None
    least, _ = opcount.roofline_seconds(flops, nbytes, cell["peaks"],
                                        cell["chips"])
    return 100.0 * least / seconds


def serve_chunk_steps(counters, chunks):
    """[(scan steps, live tokens of each slot)] of a list of chunk records."""
    out = []
    for admit, decode, live in chunks:
        steps = admit * counters["admit_steps"] + decode * counters["chunk"]
        if steps:
            out.append((steps, live))
    return out
