"""Model FLOP/s utilization of serving a hybrid state-space model over the
window: the whole step's NEEDED operations (opcount_granite_hybrid: every
weight per processed valid token, the head per delivered token, the
recurrence per processed token in every mamba layer, attention per (decode
step, attended row) in every attention layer) over the window and the
chips' bf16 peak.  Prefill's own attention is left out, as in
`serve_step_mfu`: a little low, never high.  Nothing to read for a
configuration without mamba layers."""
import opcount_granite_hybrid
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    cfg = cell["config"]
    processed = counters.get("prefill_tokens", 0) \
        + counters.get("decode_tokens", 0)
    if not processed or "mamba" not in cfg.get("layer_types", ()):
        return None
    rows = opcount_granite_hybrid.attended_row_steps(
        serve_chunk_steps(counters, counters["window_chunks"]))
    flops = opcount_granite_hybrid.serve_flops(
        cfg, processed, counters["tokens_delivered"], rows)
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / counters["window_s"] / peak
