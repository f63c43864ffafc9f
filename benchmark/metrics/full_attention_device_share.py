"""Share of the device's busy time in the traced chunks spent writing and
attending the FULL-attention layers' pages of a model with kinds of layer:
operations whose `op_name` lies under `attn.full` (the cache write and the
attention call of such a layer)."""
from metrics._scope_share import device_share_percent


def read(trace, counters, cell):
    return device_share_percent(trace, cell, ("/attn.full",))
