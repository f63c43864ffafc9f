"""Model FLOP/s utilization of serving a block-diffusion sparse-expert model
over the window: the whole step's NEEDED operations (opcount_sdar_moe:
every-token parameters per valid lane processed, an expert's per assignment,
the head per block lane of a decode pass, attention per (decode lane, live
row)) over the window and the chips' bf16 peak.  Prefill's own attention is
left out, as in `serve_step_mfu`: a little low, never high.  Nothing to read
where the program runs no block schedule."""
import opcount_sdar_moe
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    lanes = counters.get("decode_lanes", 0)
    if not lanes or "moe_assignments" not in counters:
        return None
    chunks = serve_chunk_steps(counters, counters["window_chunks"])
    slot_steps = sum(steps * len(live) for steps, live in chunks)
    live_rows = sum(steps * sum(live) for steps, live in chunks)
    flops = opcount_sdar_moe.serve_flops(
        cell["config"], counters["prefill_tokens"] + lanes, lanes,
        lanes * live_rows / max(slot_steps, 1), counters["moe_assignments"])
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / counters["window_s"] / peak
