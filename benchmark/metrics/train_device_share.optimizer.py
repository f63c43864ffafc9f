"""Share of the device's busy time in the traced steps spent in operations
under the scopes `train.optimizer` and `train.guard` (the layout copies
that feed the fused AdamW kernel are among them)."""
import program_spans


def read(trace, counters, cell):
    return program_spans.train_device_share(trace, cell, "optimizer")
