"""MetricsRegistry + the in-process event bus.

Reference: `python/paddle/profiler/` keeps host-side instrumentation in a
module-global event list guarded by a recording flag; fleet-scale
operability needs the inverse shape — ONE always-importable plane that
every producer (trainers, serving batcher, watchdog, fault registry,
checkpoint runtime, data loader) publishes into, with the cost model of
the analysis subsystem: **near-zero when nothing is attached**.

Cost contract (bench-asserted, like analysis/fault):

  * `emit()` with no sink attached is one module-global truthiness
    check and a return — no dict building, no timestamps, no locking.
  * `span()` is always a `jax.profiler.TraceAnnotation`: while a
    profiler session runs it lies in the session's trace, on the clock
    of the device's operations; while none runs it costs what an
    inactive `TraceMe` costs (under a microsecond).  With no sink
    attached it builds no record and reads no clock of its own.
  * Counters/gauges always accumulate (a few ns: one dict lookup and an
    int add) so `telemetry.dump()` can snapshot lifetime totals even
    when no sink ever ran; histograms keep a bounded reservoir.
  * Nothing here ever touches the compiled step — the plane is
    host-side only (of jax it uses the profiler's annotation alone),
    so arming/disarming sinks cannot change a program (bench asserts
    byte-identical HLO across an attach/detach cycle).

Sinks are objects with a ``record(rec: dict)`` method (and optionally
``flush()``/``close()``); see exporters.py.  A raising sink is detached
rather than allowed to kill a train step.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "registry", "counter", "gauge", "histogram",
           "add_sink", "remove_sink", "sinks", "active", "emit", "span",
           "mark", "configure", "config", "reset",
           "set_rank", "rank_info", "percentile_of", "percentiles_of",
           "summary_of"]


# one lock for all instrument mutation: `value += n` is LOAD/ADD/STORE
# under the GIL, and the producers span threads (loader prefetch,
# watchdog monitor, checkpoint writer) — a lost increment would flake
# exactly the count-pinning regression tests this plane feeds
_METRICS_LOCK = threading.Lock()


def percentile_of(values, q) -> float:
    """One percentile over a value list (key-naming handled here —
    fractional q like 99.9 works)."""
    key = f"p{int(q) if float(q).is_integer() else q}"
    return percentiles_of(values, (q,))[key]


def percentiles_of(values, qs=(50, 90, 99)) -> Dict[str, float]:
    """Nearest-rank percentiles over a value list — THE one percentile
    derivation (Histogram.percentiles, stats() blocks and the report
    CLIs all call this; the rounding convention changes in one place)."""
    out = {f"p{int(q) if float(q).is_integer() else q}": 0.0
           for q in qs}
    if not values:
        return out
    xs = sorted(float(v) for v in values)
    for q in qs:
        k = min(len(xs) - 1,
                max(0, int(round(q / 100.0 * (len(xs) - 1)))))
        out[f"p{int(q) if float(q).is_integer() else q}"] = xs[k]
    return out


def summary_of(values, qs=(50, 90, 99)) -> Dict[str, float]:
    """Count + TRUE min/max + nearest-rank percentiles over a value
    list — THE one window-summary derivation (ISSUE 14: the serving
    latency blocks and the report CLIs read through here).  The
    percentiles come from whatever window the caller kept, but min/max
    are exact over it — reservoir-style sampling upstream of this call
    is what loses the extreme straggler/TTFT outliers an incident
    investigation needs, so keep the raw window and summarize HERE."""
    vals = [float(v) for v in values]
    out = {"count": len(vals),
           "min": min(vals) if vals else 0.0,
           "max": max(vals) if vals else 0.0}
    out.update(percentiles_of(vals, qs))
    return out


class Counter:
    """Monotonic int counter."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def inc(self, n: int = 1):
        with _METRICS_LOCK:
            self.value += n
            return self.value


class Gauge:
    """Last-value-wins float."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def set(self, v: float):
        with _METRICS_LOCK:
            self.value = float(v)
            return self.value


class Histogram:
    """Running count/sum/min/max plus a bounded reservoir of recent
    observations (enough for p50/p99 over the window without unbounded
    growth in a long-lived server — same discipline as the serving
    batcher's chunk-time deque)."""

    __slots__ = ("name", "count", "total", "min", "max", "_window",
                 "_cap", "_i")

    def __init__(self, name: str, window: int = 1024):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._window: List[float] = []
        self._cap = window
        self._i = 0

    def observe(self, v: float):
        v = float(v)
        with _METRICS_LOCK:
            self.count += 1
            self.total += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            if len(self._window) < self._cap:
                self._window.append(v)
            else:                   # ring overwrite: keep the recent cap
                self._window[self._i] = v
                self._i = (self._i + 1) % self._cap

    def percentile(self, q: float) -> float:
        key = f"p{int(q) if float(q).is_integer() else q}"
        return self.percentiles((q,))[key]

    def percentiles(self, qs=(50, 90, 99)) -> Dict[str, float]:
        """{pN: value} over the reservoir window — consumers (dump(),
        stats() blocks, the report CLIs) read these instead of
        re-deriving percentiles from raw reservoir dumps."""
        with _METRICS_LOCK:
            window = list(self._window)
        return percentiles_of(window, qs)

    def summary(self) -> dict:
        if not self.count:
            return {"count": 0}
        pct = self.percentiles((50, 90, 99))
        return {"count": self.count,
                "sum": round(self.total, 4),
                "min": round(self.min, 4),
                "max": round(self.max, 4),
                "p50": round(pct["p50"], 4),
                "p90": round(pct["p90"], 4),
                "p99": round(pct["p99"], 4)}


class MetricsRegistry:
    """Name → instrument store.  get-or-create accessors are the hot
    path, so instruments are cached in plain dicts; the lock only guards
    creation (worker threads — loader prefetch, watchdog monitor,
    checkpoint writer — all publish here)."""

    def __init__(self):
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._hists: Dict[str, Histogram] = {}
        self._lock = threading.Lock()

    def counter(self, name: str) -> Counter:
        c = self._counters.get(name)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self._gauges.get(name)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(name, Gauge(name))
        return g

    def histogram(self, name: str, window: int = 1024) -> Histogram:
        h = self._hists.get(name)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(name,
                                           Histogram(name, window))
        return h

    def dump(self) -> dict:
        return {
            "counters": {n: c.value for n, c in
                         sorted(self._counters.items())},
            "gauges": {n: g.value for n, g in sorted(self._gauges.items())},
            "histograms": {n: h.summary() for n, h in
                           sorted(self._hists.items())},
        }

    def reset(self):
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


def counter(name: str) -> Counter:
    return _REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return _REGISTRY.gauge(name)


def histogram(name: str, window: int = 1024) -> Histogram:
    return _REGISTRY.histogram(name, window)


# ---------------------------------------------------------------------------
# event bus

_SINKS: List = []           # truthiness of this list IS the fast path
_SINKS_LOCK = threading.Lock()

# fleet identity: (rank, world), stamped onto every emitted record once
# distributed.env (or telemetry.fleet.init_from_env) announces it.
# None until then — a single uninitialized process emits exactly the
# records it always did (readers treat a missing rank as rank 0).
_RANK: Optional[tuple] = None


def set_rank(rank: int, world: int = 1):
    """Announce this process's fleet identity.  From here on every
    emitted event carries `rank` (and `world` when > 1) so per-rank
    JSONL logs merge into one rank-laned timeline.  Called by
    distributed.env.init_parallel_env; idempotent."""
    global _RANK
    _RANK = (int(rank), max(1, int(world)))


def rank_info() -> Optional[tuple]:
    """(rank, world) once announced, else None (treat as (0, 1))."""
    return _RANK

# plane configuration — host-side behavior switches only (nothing here
# may change a compiled program):
#   sync_steps: trainers block_until_ready the loss inside the step
#     span so wall_ms is exact step wall (default off: with donated
#     buffers steady-state dispatch wall tracks step wall, and a forced
#     sync stalls the host's dispatch-ahead every step)
_CONFIG_DEFAULTS = {"sync_steps": False}
_CONFIG = dict(_CONFIG_DEFAULTS)


def configure(**kw):
    """Update plane config; unknown keys raise (typo'd switches must
    fail loudly, not silently do nothing)."""
    for k, v in kw.items():
        if k not in _CONFIG:
            raise KeyError(f"unknown telemetry config key {k!r}; "
                           f"known: {sorted(_CONFIG)}")
        _CONFIG[k] = v
    return dict(_CONFIG)


def config(key: str):
    return _CONFIG[key]


def add_sink(sink):
    """Attach a sink; returns it (so `s = add_sink(JsonlSink(p))`)."""
    with _SINKS_LOCK:
        if sink not in _SINKS:
            _SINKS.append(sink)
    return sink


def remove_sink(sink, close: bool = True):
    with _SINKS_LOCK:
        if sink in _SINKS:
            _SINKS.remove(sink)
    if close:
        try:
            sink.close()
        except Exception:
            pass


def sinks() -> list:
    return list(_SINKS)


def active() -> bool:
    """True iff at least one sink is attached — producers consult this
    before doing ANY per-event work beyond the check itself."""
    return bool(_SINKS)


def emit(event: str, fields: Optional[dict] = None, **kw):
    """Publish one event to every attached sink.  No sink → return
    immediately (the zero-overhead contract)."""
    if not _SINKS:
        return
    rec = {"ts": time.time(), "event": event}
    if fields:
        rec.update(fields)
    if kw:
        rec.update(kw)
    if _RANK is not None:
        # rank-aware records (ISSUE 10): every producer — trainers,
        # watchdog, fault registry, checkpoint runtime, serving — gets
        # the fleet identity for free, so no call site can forget it
        rec.setdefault("rank", _RANK[0])
        if _RANK[1] > 1:
            rec.setdefault("world", _RANK[1])
    for s in list(_SINKS):
        try:
            s.record(rec)
        except Exception as e:      # noqa: BLE001
            # a broken sink (disk full, closed file) must not take the
            # training loop down with it — detach (close=True attempts
            # a final flush of buffered lines; remove_sink swallows a
            # failing close) and SAY SO: a silently dying step log is
            # the failure mode this plane exists to prevent
            import warnings
            warnings.warn(
                f"telemetry: detaching sink {type(s).__name__} after "
                f"record() failed ({type(e).__name__}: {e}); events "
                "from here on are not exported to it", RuntimeWarning)
            remove_sink(s, close=True)


class _Open(threading.local):
    """Per thread: the (name, number) of the spans open on it, for a
    record's parent, and the running number the last one got.  Only
    kept while a sink listens."""

    def __init__(self):
        self.stack = []
        self.number = 0


_OPEN = _Open()


class _Span:
    """One span: a profiler annotation for as long as the body runs and,
    when a sink is attached at its start, one record at its end."""

    __slots__ = ("event", "ids", "_ann", "_rec")

    def __init__(self, event: str, ids: dict):
        self.event = event
        self.ids = ids
        self._ann = TraceAnnotation(event, **ids)
        self._rec = None

    def set(self, **ids):
        """Ids known only once the body has run (a count): they join the
        annotation's stats and the record."""
        self.ids.update(ids)
        self._ann.set_metadata(**ids)

    def __enter__(self):
        self._ann.__enter__()
        if _SINKS:
            stack = _OPEN.stack
            number = _OPEN.number = _OPEN.number + 1
            self._rec = (time.time(), time.perf_counter(), number,
                         stack[-1] if stack else None)
            stack.append((self.event, number))
        return self

    def __exit__(self, exc_type, exc, tb):
        rec, self._rec = self._rec, None
        if rec is not None:
            t0, started, number, parent = rec
            dur = (time.perf_counter() - started) * 1e3
            stack = _OPEN.stack
            while stack and stack.pop()[1] != number:
                pass
            fields = dict(self.ids, t0=t0, dur_ms=round(dur, 4),
                          span=number)
            if parent is not None:
                fields["parent"], fields["parent_span"] = parent
            if exc_type is not None:
                # a raising body must be distinguishable from a clean
                # one in the trace (ISSUE 14): mark the span and
                # RE-raise — an incident bundle's timeline then shows
                # the failing phase
                fields["error"] = exc_type.__name__
            emit(self.event, fields)
        self._ann.__exit__(exc_type, exc, tb)
        return False


def span(event: str, **ids):
    """THE way to open a span: `with telemetry.span("serve.step",
    chunk=7):`.  Always a profiler annotation named `event` with `ids`
    as its stats (see the module docstring for what that costs).  With a
    sink attached it also emits, at its end, one record: `ts` (end),
    `t0` (start), `dur_ms`, `span` (its running number on this thread),
    `parent` / `parent_span` (name and number of the span it lies in),
    the ids, and `error=<type>` where the body raised."""
    return _Span(event, ids)


def mark(event: str, **ids):
    """An instant at the place something happens (a request is admitted):
    a zero-length annotation, and an event where a sink listens."""
    with TraceAnnotation(event, **ids):
        pass
    if _SINKS:
        if _OPEN.stack:
            ids["parent"], ids["parent_span"] = _OPEN.stack[-1]
        emit(event, ids)


def reset():
    """Detach every sink, clear the registry, drop the fleet identity
    and restore the default config (test isolation — the whole plane
    back to pristine)."""
    global _RANK
    for s in list(_SINKS):
        remove_sink(s)
    _REGISTRY.reset()
    _CONFIG.clear()
    _CONFIG.update(_CONFIG_DEFAULTS)
    _RANK = None
