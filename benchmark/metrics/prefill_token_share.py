"""Prompt tokens' share of the tokens the step programs advanced in the
window (the batcher's own counts)."""


def read(trace, counters, cell):
    total = counters.get("prefill_tokens", 0) + counters.get("decode_tokens", 0)
    return 100.0 * counters["prefill_tokens"] / total if total else None
