"""Roofline share of the three flash-attention kernels in the traced steps
(forward: two S x S products a layer; backward: five)."""
import opcount
from metrics._common import kernel_roofline_percent

KERNELS = r"flash_attention_(fwd|bwd_dq|bwd_dkv)"


def read(trace, counters, cell):
    cfg, mix = cell["config"], cell["mix"]
    n = cfg["num_hidden_layers"] * counters.get("traced_steps", 0)
    flops = sum(opcount.flash_attention_flops(cfg, mix["batch"],
                                              mix["sequence"])) * n
    nbytes = sum(opcount.flash_attention_bytes(cfg, mix["batch"],
                                               mix["sequence"])) * n
    return kernel_roofline_percent(trace, KERNELS, flops, nbytes, cell)
