"""Share of the device's busy time in the traced steps spent in operations
of the backward pass (`transpose(jvp(..))`) and of `train.grad_reduce`."""
import program_spans


def read(trace, counters, cell):
    return program_spans.train_device_share(trace, cell, "backward")
