"""Roofline share of the fused AdamW kernel in the traced steps: it is bound
by the bytes of parameter, gradient and moments it reads and writes."""
import numpy as np

import opcount
from metrics._common import kernel_roofline_percent

KERNELS = r"fused_adamw"


def read(trace, counters, cell):
    tr = cell["workload"]["trainer"]
    p, m = np.dtype(tr["param_dtype"]).itemsize, \
        (2 if tr["moment_dtype"] == "bfloat16"
         else np.dtype(tr["moment_dtype"]).itemsize)
    nbytes = opcount.fused_adamw_bytes(
        opcount.total_params(cell["config"]), p, p, m) \
        * counters.get("traced_steps", 0)
    return kernel_roofline_percent(trace, KERNELS, 0.0, nbytes, cell)
