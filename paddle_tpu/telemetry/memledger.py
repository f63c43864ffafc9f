"""HBM memory ledger — per-program byte accounting from XLA's own
`compiled.memory_analysis()` (ROADMAP item 5c: the SCALE/PROFILE peak-
HBM numbers were hand-derived because nothing in the repo ever asked
XLA; now every trainer and the serve step registers its program here
and `telemetry.memory_report()` answers with measured bytes).

Cost model (the plane's usual contract):

  * Trainers REGISTER a provider at first call — one `seen`-set check
    per step, one aval-ization (ShapeDtypeStructs, no live buffers
    pinned) on the first.  Registration never lowers, never compiles,
    never touches the step program (tests/test_program_contracts.py
    `test_observability_surface_leaves_the_train_step_identical`).
  * RESOLUTION is lazy and explicit: `memory_report()` (or
    `analysis.lint_peak_hbm`) lowers+compiles each pending provider
    once and caches the stats — the cost is paid exactly when someone
    asks for the numbers.  The AOT path (FLAGS_compile_cache_dir) captures stats for
    free at its own `.lower()`/compile.
  * Labels are a small fixed space ("jit.TrainStep.step",
    "ShardedTrainStep.step", "serve_step.decode", ...): a new trainer
    REPLACES its label's entry, so a long test suite or notebook never
    grows the ledger past the program zoo's size.

Report shape (per program): argument/output/temp/alias/generated-code
bytes straight from CompiledMemoryStats, plus ``peak_bytes`` =
arguments + outputs + temps − aliased (donated buffers counted once —
the number to hold against device HBM) and its share of the device's
reported capacity when the backend exposes one.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

import numpy as np

__all__ = ["register", "note_jit", "capture", "memory_report",
           "snapshot", "device_hbm_bytes", "reset"]

_lock = threading.Lock()
_programs: Dict[str, dict] = {}     # label -> entry (insertion-ordered)


def _stats_from(compiled) -> dict:
    """CompiledMemoryStats -> plain byte dict (+ derived peak)."""
    ma = compiled.memory_analysis()
    stats = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    stats["peak_bytes"] = max(
        0, stats["argument_bytes"] + stats["output_bytes"]
        + stats["temp_bytes"] - stats["alias_bytes"])
    return stats


def register(label: str, provider: Callable[[], Any], meta:
             Optional[dict] = None):
    """Register a pending program under `label`; `provider()` must
    return a jax Compiled (anything with `.memory_analysis()`) when the
    ledger resolves.  Same label replaces — the ledger tracks the
    CURRENT program per label, not history."""
    with _lock:
        _programs[label] = {"label": label, "status": "pending",
                            "provider": provider,
                            "meta": dict(meta or {})}
    # the label now describes a NEW program — the cost ledger's
    # measured walls / entry for the old one must not leak onto it
    try:
        from . import costledger
        costledger.program_changed(label)
    except Exception:
        pass


def note_jit(owner, kind: str, jitfn, args: tuple, label: str,
             mesh=None, sig=None):
    """The trainers' one-line hook: on the first call of `kind` for
    this `owner`, aval-ize `args` (ShapeDtypeStructs — the ledger must
    not pin donated buffers) and register a provider that re-lowers the
    jitted step for those avals on demand.  Subsequent calls are one
    tuple build + set lookup.

    `sig` is a cheap retrace discriminator (the trainers pass their
    batch shapes): a call whose sig DIFFERS from the previous call's
    re-REGISTERS — the jit has retraced (e.g. run_steps at a new K),
    so the label must describe the CURRENT program and the cost
    ledger must drop the old program's measured walls, not mix them
    (tracking the last sig rather than a seen-set keeps an
    alternating-K workload honest too)."""
    last = owner.__dict__.setdefault("_memledger_sig", {})
    if kind in last and last[kind] == sig:
        return
    refreshed = kind in last
    last[kind] = sig
    if refreshed:
        # the call that triggers a retrace pays the XLA compile in its
        # own wall — step_event must treat it as cold for the cost
        # ledger's measured window, like every first use
        owner.__dict__.setdefault("_memledger_fresh", set()).add(kind)
    # remember the ledger label per program kind: step_event feeds the
    # cost ledger's measured walls by looking the label up here
    owner.__dict__.setdefault("_memledger_labels", {})[kind] = label
    import jax
    mesh_devs = None if mesh is None else set(np.asarray(mesh.devices).flat)

    def _aval_sharding(a):
        # carry each argument's sharding AND memory kind: a host-
        # offloaded trainer's pinned_host stacks must lower exactly as
        # placed, or the analysis counts them as device HBM — but an
        # UNCOMMITTED scalar (lr, step count) materialized on device 0
        # must NOT pin the aval there: under a size>1 mesh the live
        # call auto-places it, while a pinned aval makes the provider's
        # re-lower fail with incompatible-devices
        s = getattr(a, "sharding", None)
        if s is None or mesh_devs is None:
            return s
        try:
            return s if set(s.device_set) == mesh_devs else None
        except Exception:
            return None
    try:
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=_aval_sharding(a)), args)
    except Exception:
        return                      # odd leaf: skip, never break a step

    def provider():
        import contextlib
        ctx = mesh if mesh is not None else contextlib.nullcontext()
        with ctx:
            return jitfn.lower(*avals).compile()
    register(label, provider)


def capture(label: str, compiled, meta: Optional[dict] = None):
    """Record stats from an ALREADY-compiled executable (the AOT path
    has one in hand at `.lower()` time — its memory accounting is
    free).  Failures record an error entry rather than raising."""
    try:
        stats = _stats_from(compiled)
    except Exception as e:          # noqa: BLE001
        with _lock:
            _programs[label] = {"label": label, "status": "error",
                                "error": f"{type(e).__name__}: {e}",
                                "meta": dict(meta or {})}
        return None
    entry = {"label": label, "status": "ok", "meta": dict(meta or {}),
             **stats}
    with _lock:
        _programs[label] = entry
    _publish(entry)
    _ingest_cost(label, compiled, meta)
    return entry


def _ingest_cost(label: str, compiled, meta=None):
    """Hand the in-hand executable to the compute cost ledger — the
    one Compiled serves both ledgers (costledger's zero-extra-compiles
    contract).  Never breaks the memory side."""
    try:
        from . import costledger
        costledger.ingest(label, compiled, meta=meta)
    except Exception:
        pass


def _publish(entry: dict):
    """mem.program event + counter — so a fleet JSONL log carries the
    ledger and fleet_report can render a memory section offline."""
    from .registry import counter as _counter, emit as _emit
    _counter("mem.programs").inc()
    _emit("mem.program",
          {k: v for k, v in entry.items() if k != "provider"})


def _resolve(entry: dict) -> dict:
    with _lock:
        # claim the provider atomically: two concurrent reports must
        # not both compile (or leave the loser seeing half a record)
        provider = entry.pop("provider", None)
    if provider is None:
        return entry
    try:
        compiled = provider()
        stats = _stats_from(compiled)
    except Exception as e:          # noqa: BLE001
        entry["status"] = "error"
        entry["error"] = f"{type(e).__name__}: {e}"
        return entry
    entry.update(stats)
    entry["status"] = "ok"
    _publish(entry)
    _ingest_cost(entry["label"], compiled, entry.get("meta"))
    return entry


def device_hbm_bytes() -> Optional[int]:
    """The device's reported memory capacity (TPU: memory_stats
    bytes_limit), or None when the backend doesn't say (CPU)."""
    try:
        import jax
        stats = jax.devices()[0].memory_stats()
        if stats:
            limit = stats.get("bytes_limit")
            if limit:
                return int(limit)
    except Exception:
        pass
    return None


def _live_buffers(top: int = 10) -> List[dict]:
    """Top live device allocations grouped by (shape, dtype) — the
    census a peak-HBM post-mortem wants next to the per-program plan
    (same source as the watchdog's hang report)."""
    if top <= 0:
        return []                   # dump()/bench ask for none: free
    try:
        import jax
        arrs = jax.live_arrays()
    except Exception:
        return []
    groups: Dict[tuple, dict] = {}
    for a in arrs:
        try:
            key = (tuple(a.shape), str(a.dtype))
            nbytes = int(a.size) * a.dtype.itemsize
        except Exception:
            continue
        g = groups.setdefault(key, {"shape": list(key[0]),
                                    "dtype": key[1], "count": 0,
                                    "bytes": 0})
        g["count"] += 1
        g["bytes"] += nbytes
    out = sorted(groups.values(), key=lambda g: -g["bytes"])[:top]
    return out


def memory_report(resolve: bool = True, top_buffers: int = 10) -> dict:
    """The ledger's answer: per-program byte accounting (resolving any
    pending providers unless resolve=False — resolution compiles, so
    an idle dump() passes False), device capacity, per-program peak
    share, the fleet-wide max peak, and the top live device buffers."""
    with _lock:
        entries = list(_programs.values())
    if resolve:
        for e in entries:
            if e.get("status") == "pending":
                _resolve(e)
    hbm = device_hbm_bytes()
    programs = {}
    peak = 0
    for e in entries:
        rec = {k: v for k, v in e.items()
               if k not in ("provider", "label")}
        if e.get("status") == "ok":
            peak = max(peak, e["peak_bytes"])
            # a backend without memory_stats()/bytes_limit (CPU
            # tier-1) degrades to share=None — never a KeyError or a
            # raise downstream
            rec["peak_share"] = round(e["peak_bytes"] / hbm, 4) \
                if hbm else None
        programs[e["label"]] = rec
    return {"programs": programs,
            "device_hbm_bytes": hbm,
            "peak_hbm_bytes": peak,
            "peak_hbm_share": round(peak / hbm, 4) if (hbm and peak)
            else None,
            "live_buffers": _live_buffers(top_buffers)}


def snapshot() -> dict:
    """The ledger without resolution — registered-but-pending entries
    stay pending and nothing compiles (what telemetry.dump() embeds)."""
    return memory_report(resolve=False, top_buffers=0)


def reset():
    with _lock:
        _programs.clear()
