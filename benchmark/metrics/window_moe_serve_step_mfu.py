"""Model FLOP/s utilization of serving a window-and-full-attention
sparse-expert model over the window: the whole step's NEEDED operations
(opcount_exaone_moe: every-token parameters per processed valid token, an
expert's per assignment that landed on an expert held here, the head per
delivered token, attention per (decode step, attended row): a full layer's
over the slot's whole depth, a sliding layer's capped at its window) over
the window and the chips' bf16 peak.  Prefill's own attention is left out,
as in `serve_step_mfu`: a little low, never high.  Nothing to read where the
program counts no routing."""
import opcount_exaone_moe
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    processed = counters.get("prefill_tokens", 0) \
        + counters.get("decode_tokens", 0)
    if not processed or "moe_assignments_held" not in counters:
        return None
    cfg = cell["config"]
    full, window = opcount_exaone_moe.attended_row_steps(
        cfg, serve_chunk_steps(counters, counters["window_chunks"]))
    flops = opcount_exaone_moe.serve_flops(
        cfg, processed, counters["tokens_delivered"], full, window,
        counters["moe_assignments_held"])
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / counters["window_s"] / peak
