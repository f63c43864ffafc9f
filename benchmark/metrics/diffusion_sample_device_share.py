"""Share of the device's busy time in the traced chunks spent in the block
schedule's own operations: those whose `op_name` lies under
`diffusion.sample` (softmax confidence, argmax, the choice of lanes) or
`diffusion.update` (the block's new state, the emitted tokens)."""
from metrics._scope_share import device_share_percent


def read(trace, counters, cell):
    return device_share_percent(trace, cell,
                                ("diffusion.sample", "diffusion.update"))
