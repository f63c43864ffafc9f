"""Share of the HBM roofline the serve step programs of a
window-and-full-attention sparse-expert model reach over the window: the
bytes the window's scan steps must move (opcount_exaone_moe.serve_bytes: the
every-token weights and the head once a step, an expert's weights per
(layer, step, held expert) that got a token, the K and V rows one lane a
slot attends once a step: a full layer's the slot's whole depth, a sliding
layer's capped at its window) over the window and the chips' bandwidth.
Nothing to read where the program counts no routing."""
import opcount_exaone_moe
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    chunks = serve_chunk_steps(counters, counters.get("window_chunks", []))
    if not chunks or "moe_expert_steps_hit" not in counters:
        return None
    cfg = cell["config"]
    full, window = opcount_exaone_moe.attended_row_steps(cfg, chunks)
    nbytes = opcount_exaone_moe.serve_bytes(
        cfg, sum(steps for steps, _ in chunks), full, window,
        counters["moe_expert_steps_hit"])
    peak = cell["peaks"]["hbm_bytes_per_s"] * cell["chips"]
    return 100.0 * nbytes / counters["window_s"] / peak
