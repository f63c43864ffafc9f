"""Median over the traced chunks of what the host adds to a chunk: the
program's `serve.step` span less the `serve.device_wait` inside it."""
import program_spans


def read(trace, counters, cell):
    return program_spans.serve_host_self_ms_p50(trace, cell)
