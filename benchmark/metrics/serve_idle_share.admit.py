"""Share of the traced window in which the device was idle while the host
was in `serve.evict` or `serve.admit`.  The four `serve_idle_share.*` add up to
`device_idle_share.serve`."""
import program_spans


def read(trace, counters, cell):
    return program_spans.serve_idle_share(trace, cell, "admit")
