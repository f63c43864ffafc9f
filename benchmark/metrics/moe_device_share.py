"""Share of the device's busy time in the traced chunks spent in the expert
layers' operations: those whose `op_name` lies under `moe.route`,
`moe.dispatch`, `moe.experts`, `moe.shared` or `moe.combine`, and the
grouped product itself, which the TPU compiler lowers to a kernel it names
`ragged-dot-*` with no scope in its `op_name` (only the expert layers call
`jax.lax.ragged_dot`)."""
from metrics._scope_share import device_share_percent


def read(trace, counters, cell):
    return device_share_percent(trace, cell, ("/moe.", "ragged-dot"))
