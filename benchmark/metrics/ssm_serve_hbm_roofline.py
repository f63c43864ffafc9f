"""Share of the HBM roofline the serve step programs of a hybrid
state-space model reach over the window: the bytes the window's scan steps
must move (opcount_granite_hybrid.serve_bytes: the weights and the head
once a step, every slot's SSM and conv state read and written once a step,
the K and V rows one lane a slot attends once a step in every attention
layer: 64-wide heads' bytes, however the pool lays them) over the window
and the chips' bandwidth.  Nothing to read for a configuration without
mamba layers."""
import opcount_granite_hybrid
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    cfg = cell["config"]
    chunks = serve_chunk_steps(counters, counters.get("window_chunks", []))
    if not chunks or "mamba" not in cfg.get("layer_types", ()):
        return None
    nbytes = opcount_granite_hybrid.serve_bytes(
        cfg, sum(steps for steps, _ in chunks), counters["slots"],
        opcount_granite_hybrid.attended_row_steps(chunks))
    peak = cell["peaks"]["hbm_bytes_per_s"] * cell["chips"]
    return 100.0 * nbytes / counters["window_s"] / peak
