"""Share of the device's busy time in the traced chunks spent writing and
attending the SLIDING-WINDOW layers' rings: operations whose `op_name` lies
under `attn.window` (the cache write and the attention call of such a layer;
the projections around them are weight products and not counted here)."""
from metrics._scope_share import device_share_percent


def read(trace, counters, cell):
    return device_share_percent(trace, cell, ("/attn.window",))
