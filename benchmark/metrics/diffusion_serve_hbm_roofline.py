"""Share of the HBM roofline the serve step programs of a block-diffusion
sparse-expert model reach over the window: the bytes the window's scan steps
must move (opcount_sdar_moe.serve_bytes: the every-token weights and the head
once a step, an expert's weights per (layer, step, expert) that got a lane,
live K and V rows once a step) over the window and the chips' bandwidth.
Nothing to read where the program runs no block schedule."""
import opcount_sdar_moe
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    chunks = serve_chunk_steps(counters, counters.get("window_chunks", []))
    if not chunks or "decode_lanes" not in counters \
            or "moe_expert_steps_hit" not in counters:
        return None
    nbytes = opcount_sdar_moe.serve_bytes(
        cell["config"], sum(steps for steps, _ in chunks),
        sum(steps * sum(live) for steps, live in chunks),
        counters["moe_expert_steps_hit"])
    peak = cell["peaks"]["hbm_bytes_per_s"] * cell["chips"]
    return 100.0 * nbytes / counters["window_s"] / peak
