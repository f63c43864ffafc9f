"""Share of the device's busy time in the traced chunks spent reading and
writing the latent pool: operations whose `op_name` lies under `mla.attend`
or `mla.cache_write` (the projections around them are weight products and
not counted here)."""
from metrics._scope_share import device_share_percent


def read(trace, counters, cell):
    return device_share_percent(trace, cell,
                                ("/mla.attend", "/mla.cache_write"))
