"""Share of the device's busy time in the traced steps spent in operations
whose `op_name` has neither `transpose(` nor one of the trainer's `train.*`
scopes."""
import program_spans


def read(trace, counters, cell):
    return program_spans.train_device_share(trace, cell, "forward")
