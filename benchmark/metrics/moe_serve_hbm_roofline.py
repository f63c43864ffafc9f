"""Share of the HBM roofline the serve step programs of a latent-attention,
sparse-expert model reach over the window: the bytes the window's scan steps
must move (opcount_mla_moe.serve_bytes: the every-token weights and the head
once a step, an expert's weights per (layer, step, held expert) that got a
token, live latent rows once a step) over the window and the chips'
bandwidth.  Nothing to read where the program counts no routing."""
import opcount_mla_moe
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    chunks = serve_chunk_steps(counters, counters.get("window_chunks", []))
    if not chunks or "moe_expert_steps_hit" not in counters:
        return None
    nbytes = opcount_mla_moe.serve_bytes(
        cell["config"], sum(steps for steps, _ in chunks),
        sum(steps * sum(live) for steps, live in chunks),
        counters["moe_expert_steps_hit"])
    peak = cell["peaks"]["hbm_bytes_per_s"] * cell["chips"]
    return 100.0 * nbytes / counters["window_s"] / peak
