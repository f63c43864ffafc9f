"""Share of the traced window in which the device was idle while the host
was in none of harvest, eviction, admission or dispatch: in `serve.device_wait`
(the transfer's tail), in `serve.step` itself, in its caller between two steps,
or under no program span.  The four `serve_idle_share.*` add up to
`device_idle_share.serve`."""
import program_spans


def read(trace, counters, cell):
    return program_spans.serve_idle_share(trace, cell, "other")
