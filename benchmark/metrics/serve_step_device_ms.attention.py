"""Device time a traced scan step of the serve step program's `attention`
part (`metrics/_serve_parts.py`: the first chip's leaf operations by the
program's scopes, the six parts summing to its busy time)."""
from metrics._serve_parts import step_ms


def read(trace, counters, cell):
    return step_ms(trace, cell, "attention")
