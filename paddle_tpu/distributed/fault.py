"""Deterministic, flag-gated fault injection.

Reference taxonomy: large-cluster training reports (MegaScale §5, the
OPT logbook) classify recoverable failures as (a) torn / corrupt
checkpoint writes, (b) transient control-plane RPC errors, (c) lost
heartbeats / preempted workers, and (d) numerically bad steps.  Every
recovery path in this runtime is driven through ONE registry so a test
(or `tools/chaos_check.py`) can plant exactly the failure it wants,
deterministically, and prove the corresponding recovery machinery
works.

Spec grammar (``FLAGS_fault_injection``)::

    spec      := point-spec (';' point-spec)*
    point-spec:= POINT (':' key '=' value)*
    POINT     := dotted name, e.g. ckpt.write, kv.request, step.begin
    keys      := step   — fire on the Nth hit of the point (1-based)
                 after  — fire on every hit > N
                 times  — how many firings total (default 1; '*' = all)
                 mode   — error | truncate | corrupt | nan | skip |
                          kill | delay   (default error)
                 match  — only hits whose key contains this substring
                 code   — process exit code for mode=kill (default 137)
                 secs   — sleep seconds for mode=delay (default 0.2)

Examples::

    FLAGS_fault_injection="ckpt.write:step=3:mode=truncate"
    FLAGS_fault_injection="kv.request:step=1:times=2;step.data:mode=nan"

Call sites thread a *point* through their failure-prone operation::

    f = fault.hit("ckpt.write", key=fname)
    if f is not None and f.mode == "truncate":
        ...write a torn shard...

``hit`` handles the process-level modes itself (``error`` raises
:class:`FaultError`, ``kill`` calls ``os._exit``, ``delay`` sleeps) and
returns the :class:`Fault` for data modes (truncate/corrupt/nan/skip)
the call site must implement.  When ``FLAGS_fault_injection`` is unset
the whole machinery is a single cached-string comparison — no parsing,
no counters, no syscalls (tests/test_fault_tolerance.py
`TestZeroOverhead` holds it).

Determinism: hits are counted per point, only while a spec is armed,
and `reset()` (or re-arming a different spec) zeroes the counters —
"the 3rd ckpt.write after arming" means the same write in every run.

Registered injection points (each exercised by `chaos_check --selftest`):

    ckpt.write        one shard file write        (checkpoint/__init__)
    ckpt.manifest     metadata.json commit        (checkpoint/__init__)
    ckpt.latest       the `latest` pointer commit (checkpoint/__init__)
    kv.request        one KV-store HTTP request   (launch/master)
    launch.heartbeat  one heartbeat stamp         (launch/controller)
    step.begin        train-step entry            (parallel trainers, hapi)
    step.data         the batch fed to a step     (parallel trainers)

Serve-plane points (ISSUE 9, inference/serving.py; exercised by
`chaos_check --serve --selftest`) — keys carry the request/slot the
hit belongs to (``req<id>:<slo>`` / ``slot<i>:req<id>``) so `match=`
can target one request:

    serve.admit       taking a queued request into a slot (error =
                      transient admission fault, retried FIFO-in-place;
                      skip = admission rejected, request shed)
    serve.kv_alloc    the KV page-pool allocation for one admission
                      (error = transient allocator fault -> FIFO defer;
                      skip = simulated pool exhaustion -> defer)
    serve.chunk       one compiled chunk dispatch (error fires BEFORE
                      the donated carries are touched -> the chunk
                      retries at the next boundary; delay = hung chunk,
                      detected by the serve watchdog)
    serve.decode      per live slot after a chunk (error/corrupt/nan =
                      that slot's decode is poisoned -> pages evicted,
                      request requeued or shed, rest of batch keeps
                      decoding)

Autoscaler points (ISSUE 19, fleet/autoscaler.py; exercised by
`chaos_check --autoscale --selftest`) — keys carry the daemon tick /
epoch / target replica (``tick<N>`` / ``epoch<E>:rep<i>``) so `match=`
can target one decision or one scale action:

    autoscale.decide  one daemon policy evaluation (error = the tick
                      degrades to a no-op and retries next poll — a
                      broken metrics read never crashes the daemon)
    autoscale.drain   the drain_replica call of a scale-in/role-flip
                      (error = bounded retry with backoff, then
                      rollback: replica returned to rotation)
    autoscale.reform  the re-form half: spawning/adding a replica on
                      scale-out, or the role switch + undrain of a
                      role-flip (error = bounded retry, then rollback)
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

from ..framework.flags import get_flag  # FLAGS_fault_injection is
# defined in framework/flags.py (core set) so env pickup precedes any
# subsystem import

__all__ = ["Fault", "FaultError", "FaultSpecError", "hit", "is_active",
           "reset", "scope", "parse_specs", "POINTS"]

# the documented injection points; hit() accepts only these so a typo'd
# spec or call site fails loudly instead of never firing
POINTS = ("ckpt.write", "ckpt.manifest", "ckpt.latest", "kv.request",
          "launch.heartbeat", "step.begin", "step.data",
          "serve.admit", "serve.kv_alloc", "serve.chunk",
          "serve.decode",
          "autoscale.decide", "autoscale.drain", "autoscale.reform")

MODES = ("error", "truncate", "corrupt", "nan", "skip", "kill", "delay")


class FaultError(IOError):
    """An injected fault (mode=error).  Subclasses IOError so IO retry
    paths classify it as transient — exactly what a planted 'transient
    connection blip / write error' test needs."""


class FaultSpecError(ValueError):
    """Malformed FLAGS_fault_injection spec."""


class Fault:
    """One armed point-spec."""

    __slots__ = ("point", "step", "after", "times", "mode", "match",
                 "code", "secs", "fired")

    def __init__(self, point: str, step: int = 0, after: int = 0,
                 times: int = 1, mode: str = "error",
                 match: Optional[str] = None, code: int = 137,
                 secs: float = 0.2):
        if point not in POINTS:
            raise FaultSpecError(
                f"unknown injection point {point!r}; known: {POINTS}")
        if mode not in MODES:
            raise FaultSpecError(
                f"unknown mode {mode!r} for {point}; known: {MODES}")
        self.point = point
        self.step = int(step)
        self.after = int(after)
        self.times = times          # -1 = unlimited
        self.mode = mode
        self.match = match
        self.code = int(code)
        self.secs = float(secs)
        self.fired = 0

    def _wants(self, n_hit: int, key: Optional[str]) -> bool:
        if self.times >= 0 and self.fired >= self.times:
            return False
        if self.match is not None and (key is None
                                       or self.match not in str(key)):
            return False
        if self.step:
            # fire from the Nth hit on; `times` (checked above) caps
            # the total, so step=3:times=2 fires at hits 3 and 4 —
            # the default times=1 keeps "exactly the Nth hit"
            return n_hit >= self.step
        if self.after:
            return n_hit > self.after
        return True

    def __repr__(self):
        return (f"Fault({self.point}:mode={self.mode}:step={self.step}"
                f":times={self.times}:fired={self.fired})")


def parse_specs(raw: str) -> List[Fault]:
    """Parse a FLAGS_fault_injection string into Fault objects."""
    out = []
    for part in raw.split(";"):
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        point, kw = fields[0].strip(), {}
        for f in fields[1:]:
            k, eq, v = f.partition("=")
            if not eq:
                raise FaultSpecError(
                    f"bad field {f!r} in spec {part!r} (want key=value)")
            k = k.strip()
            v = v.strip()
            if k in ("step", "after", "code"):
                kw[k] = int(v)
            elif k == "times":
                kw[k] = -1 if v == "*" else int(v)
            elif k == "secs":
                kw[k] = float(v)
            elif k in ("mode", "match"):
                kw[k] = v
            else:
                raise FaultSpecError(
                    f"unknown key {k!r} in spec {part!r}")
        out.append(Fault(point, **kw))
    return out


# -- registry state ---------------------------------------------------------
_lock = threading.Lock()
_raw_cache: str = ""            # last seen flag value
_armed: Optional[List[Fault]] = None
_hits: Dict[str, int] = {}      # per-point hit counters (armed only)


def _sync() -> Optional[List[Fault]]:
    """Re-parse iff the flag string changed (the unset fast path is one
    string compare + None return — no parsing, no locking)."""
    global _raw_cache, _armed
    raw = get_flag("fault_injection") or ""
    if raw == _raw_cache:
        return _armed
    with _lock:
        if raw != _raw_cache:
            _armed = parse_specs(raw) if raw else None
            _raw_cache = raw
            _hits.clear()
    return _armed


def is_active() -> bool:
    return _sync() is not None


def reset():
    """Zero the hit counters and re-arm the current flag value."""
    global _raw_cache
    with _lock:
        _raw_cache = "\0invalidated"   # force re-parse on next _sync
        _hits.clear()
    _sync()


def hit(point: str, key: Optional[str] = None) -> Optional[Fault]:
    """Record one hit of `point`; fire any matching armed spec.

    Returns None when nothing fires (including always when
    FLAGS_fault_injection is unset).  Process-level modes act here:
    mode=error raises FaultError, mode=kill exits the process
    (`os._exit(code)` — a preemption has no epilogue), mode=delay
    sleeps `secs`.  Data modes (truncate/corrupt/nan/skip) return the
    Fault for the call site to apply."""
    armed = _sync()
    if armed is None:
        return None
    if point not in POINTS:     # not an assert: must survive python -O
        raise FaultSpecError(
            f"unregistered injection point {point!r}; known: {POINTS}")
    with _lock:
        n = _hits.get(point, 0) + 1
        _hits[point] = n
        live = None
        for f in armed:
            if f.point == point and f._wants(n, key):
                f.fired += 1
                live = f
                break
    if live is None:
        return None
    # a FIRED injection is never the hot path — publish it so chaos
    # runs correlate recovery behavior with the exact planted failure
    # (the unset fast path returned above untouched)
    try:
        from .. import telemetry as _telemetry
        _telemetry.counter("fault.fired").inc()
        _telemetry.emit("fault.hit", point=point, mode=live.mode,
                        hit=n, key=str(key))
    except Exception:
        pass
    if live.mode == "error":
        raise FaultError(
            f"injected fault at {point} (hit {n}, key={key!r})")
    if live.mode == "kill":
        os._exit(live.code)
    if live.mode == "delay":
        time.sleep(live.secs)
        return None
    return live


def hit_counts() -> Dict[str, int]:
    """Per-point hit counters (armed periods only) — introspection for
    chaos_check and the zero-overhead bench assertion."""
    with _lock:
        return dict(_hits)


def fired_counts() -> Dict[str, int]:
    """point -> total firings of the currently armed specs."""
    armed = _sync() or []
    out: Dict[str, int] = {}
    for f in armed:
        out[f.point] = out.get(f.point, 0) + f.fired
    return out


class scope:
    """Arm a spec for a `with` block (tests): sets
    FLAGS_fault_injection, resets counters, restores the previous value
    (and counters) on exit."""

    def __init__(self, spec: str):
        self._spec = spec
        self._prev = None

    def __enter__(self):
        from ..framework.flags import set_flags
        self._prev = get_flag("fault_injection") or ""
        set_flags({"FLAGS_fault_injection": self._spec})
        reset()
        return self

    def __exit__(self, *exc):
        from ..framework.flags import set_flags
        set_flags({"FLAGS_fault_injection": self._prev})
        reset()
        return False
