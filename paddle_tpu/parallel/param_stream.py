"""Parameter streaming scope for ZeRO-3 host offload.

Reference: `python/paddle/distributed/fleet/meta_parallel/sharding/
group_sharded_stage3.py:110,127,294` — `offload=True` parks parameter
slices on host and fetches them per-layer around each forward/backward.

TPU-native design: parameters live in pinned_host memory between steps.
Inside the jitted step, each decoder block's `recompute` region begins
with an in-graph host→HBM `device_put` of THAT block's parameters — so
the transfer sits INSIDE the rematerialized region:

  * forward: block params stream in, block computes, the device copies
    die at region exit (only the residual-stream boundary is saved);
  * backward: `jax.checkpoint` replays the region, which re-streams the
    params — HBM never holds more than ~one block's parameters;
  * gradients: autodiff of `device_put(host→device)` is the reverse
    transfer, so grads MATERIALIZE in host memory — the all-params grad
    buffer leaves HBM too;
  * XLA's latency-hiding scheduler overlaps the next block's DMA with
    the current block's compute (the double-buffered prefetch the
    reference implements by hand with CUDA streams).

NOTE: the scheduler-dependent overlap above measured poorly in round 5
(pre-ledger; not measured since) — `parallel/offload_pipeline.py` is the
explicit double-buffered replacement for block-stacked models; this
scope remains the mechanism for irregular models.

The scope maps parameter-Tensor OBJECT ids to their device shardings —
object identity is stable across `_swapped_state` value swaps, which is
what makes the trainer↔recompute handshake work without name plumbing.

Every table entry must be consulted by the traced step: a parameter
that is never visited would silently train against a stale HBM copy
(or not stream at all), so `param_stream_scope` raises on clean exit
when entries go unvisited.
"""
from __future__ import annotations

from contextlib import contextmanager

__all__ = ["param_stream_scope", "stream_sharding_for"]

_ACTIVE: list = []


@contextmanager
def param_stream_scope(table, names=None):
    """table: {id(param_tensor): NamedSharding(..., memory_kind="device")}
    — active while TRACING the train step's forward.

    names: optional {id(param_tensor): name} used to report unvisited
    entries.  On clean exit, any table entry the traced step never
    looked up via `stream_sharding_for` raises a RuntimeError — the
    previous behavior was a silent no-op (the param simply never
    streamed), which surfaced as wrong placement only under a profiler.
    """
    visited: set = set()
    _ACTIVE.append((table, visited))
    try:
        yield
    finally:
        _ACTIVE.pop()
    missing = set(table) - visited
    if missing:
        labels = sorted(
            (names or {}).get(i, f"<param id {i}>") for i in missing)
        raise RuntimeError(
            "param_stream_scope: {} streamed parameter(s) were never "
            "visited by the traced step: {} — every parameter in the "
            "stream table must be consumed inside the traced forward "
            "(is the block skipped, or the tensor replaced rather than "
            "value-swapped?)".format(len(missing), labels))


def stream_sharding_for(tensor_obj):
    """Device sharding for this parameter if the active scope streams
    it, else None.  Marks the entry visited (see the scope's exit
    check)."""
    if not _ACTIVE:
        return None
    table, visited = _ACTIVE[-1]
    sh = table.get(id(tensor_obj))
    if sh is not None:
        visited.add(id(tensor_obj))
    return sh
