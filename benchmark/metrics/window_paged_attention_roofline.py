"""Roofline share of the paged-attention kernel in the traced chunks of a
model with KINDS of layer: the time of the `paged_attention` kernels against
the bytes of the K and V rows the mathematics needs, whatever the kernel
walks: in a full layer every live row of every slot, in a sliding layer the
window's rows and the step's lanes' own (window + lanes - 1) where a slot is
deeper.  A kernel that walks a sliding layer from page 0 reads the same
needed bytes in more time.  Nothing to read for a configuration without
kinds, or a trace without the kernel."""
import opcount_exaone_moe
from metrics._common import kernel_roofline_percent

KERNELS = r"paged_attention"


def read(trace, counters, cell):
    cfg = cell["config"]
    if "sliding_window" not in cfg or "layer_types" not in cfg:
        return None
    kv = 1 if counters.get("kv_dtype") == "int8" else 2
    nbytes = 0
    for admit, decode, live in counters.get("traced_chunks", []):
        for steps, lanes in ((admit * counters["admit_steps"],
                              counters["prefill_chunk"]),
                             (decode * counters["chunk"], 1)):
            full, window = opcount_exaone_moe.attended_rows(cfg, live, lanes)
            nbytes += steps * opcount_exaone_moe.attention_bytes(
                cfg, full, window, kv)
    return kernel_roofline_percent(trace, KERNELS, 0.0, nbytes, cell)
