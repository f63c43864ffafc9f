"""Model FLOP/s utilization of serving over the window: 2 per block
parameter and processed token (prefill and decode), the head per delivered
token, attention per (decode step, live token), over the chips' bf16 peak.
Prefill's own attention is left out (about 1 % of a token's operations at
these lengths), so the share is a little low, never high."""
import opcount
from metrics._common import serve_chunk_steps


def read(trace, counters, cell):
    processed = counters.get("prefill_tokens", 0) \
        + counters.get("decode_tokens", 0)
    if not processed:
        return None
    attended = sum(steps * sum(live) for steps, live in
                   serve_chunk_steps(counters, counters["window_chunks"]))
    flops = opcount.serve_flops(cell["config"], processed,
                                counters["tokens_delivered"], attended)
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / counters["window_s"] / peak
