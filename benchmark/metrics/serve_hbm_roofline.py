"""Share of the HBM roofline the serve step programs reach over the window:
the bytes each scan step must move (the weights once, every slot's live K
and V rows once) summed over the window's steps, over the window and the
chips' bandwidth."""
import opcount
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    chunks = serve_chunk_steps(counters, counters.get("window_chunks", []))
    if not chunks:
        return None
    kv = 1 if counters["kv_dtype"] == "int8" else 2
    nbytes = sum(steps * opcount.serve_step_bytes(cell["config"], live, 2, kv)
                 for steps, live in chunks)
    peak = cell["peaks"]["hbm_bytes_per_s"] * cell["chips"]
    return 100.0 * nbytes / counters["window_s"] / peak
