"""Roofline share of the paged-attention kernel in the traced chunks: the
bytes of the LIVE pages only, so a cheaper walk of dead pages shows."""
import opcount
from metrics._common import kernel_roofline_percent, serve_chunk_steps

KERNELS = r"paged_attention"


def read(trace, counters, cell):
    kv = 1 if counters.get("kv_dtype") == "int8" else 2
    nbytes = sum(steps * opcount.paged_attention_bytes(
        cell["config"], live, counters["page_size"], kv)
        for steps, live in
        serve_chunk_steps(counters, counters.get("traced_chunks", [])))
    return kernel_roofline_percent(trace, KERNELS, 0.0, nbytes, cell)
