"""Telemetry exporters: JSONL step log, Chrome-trace timeline, and the
in-memory sink tests and the profiler facade build on.

Reference: the profiler's `export_chrome_tracing` handler wrote a
`{"traceEvents": [...]}` document after a RECORD window closed; here
any sink can be attached/detached at any time and the trainers publish
continuously, so export is a property of the sink, not of a profiler
state machine.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
from typing import IO, List, Optional, Union

from .registry import add_sink

__all__ = ["JsonlSink", "ChromeTraceSink", "MemorySink",
           "attach_jsonl", "attach_chrome_trace", "chrome_event"]


class JsonlSink:
    """One JSON object per line per event — the fleet step log.  Each
    record is written (and flushed by default) as it arrives, so a
    preempted worker's log is complete up to its last event — the same
    torn-tail discipline as the checkpoint runtime.

    An `atexit` hook flushes whatever a `flush_every > 1` batch still
    buffers, so a SIGTERM drain (sys.exit path) or an uncaught crash
    loses nothing the process ever emitted — only a hard `os._exit`
    (mode=kill preemption) can truncate the tail.

    Size-capped rotation (ISSUE 14): under ``FLAGS_telemetry_max_log_mb``
    (or `max_mb`) a path-owned sink whose file crosses the cap rotates
    it to ``<path>.1`` (existing segments shift up: .1 -> .2, ...) and
    reopens a fresh file — a long-running job's log never grows one
    unbounded file, the atexit drain-flush keeps covering the LIVE
    segment, and `telemetry.fleet.merge_jsonl_traces` reads the
    rotated segments back oldest-first."""

    def __init__(self, path_or_file: Union[str, IO], flush_every: int = 1,
                 max_mb: Optional[float] = None):
        if hasattr(path_or_file, "write"):
            self._f = path_or_file
            self.path = getattr(path_or_file, "name", None)
            self._own = False
        else:
            self.path = path_or_file
            d = os.path.dirname(path_or_file)
            if d:
                os.makedirs(d, exist_ok=True)
            self._f = open(path_or_file, "a")
            self._own = True
        if max_mb is None:
            from ..framework.flags import get_flag
            max_mb = float(get_flag("telemetry_max_log_mb", 0.0) or 0.0)
        # rotation needs to own the file AND know its name
        self._max_bytes = int(max_mb * 1e6) \
            if (max_mb and self._own and self.path) else 0
        self._bytes = 0
        if self._max_bytes:
            try:
                self._bytes = os.path.getsize(self.path)
            except OSError:
                pass
        self._flush_every = max(1, int(flush_every))
        self._n = 0
        self._lock = threading.Lock()
        self._closed = False
        atexit.register(self._drain_flush)

    def record(self, rec: dict):
        line = json.dumps(rec, default=_jsonable)
        with self._lock:
            self._f.write(line + "\n")
            self._n += 1
            if self._n % self._flush_every == 0:
                self._f.flush()
            if self._max_bytes:
                self._bytes += len(line) + 1
                if self._bytes >= self._max_bytes:
                    self._rotate_locked()

    def _rotate_locked(self):
        """Shift <path>.i -> <path>.(i+1) (highest first), publish the
        live file as <path>.1, reopen fresh.  Called under self._lock;
        a rotation failure (permissions, races) keeps writing to the
        current file rather than losing events — and keeps the TRUE
        byte count, so the cap retries at the next record instead of
        granting another full segment of unbounded growth."""
        try:
            self._f.flush()
            self._f.close()
        except Exception:           # noqa: BLE001 — reopen below anyway
            pass
        rotated = True
        try:
            n = 1
            while os.path.exists(f"{self.path}.{n}"):
                n += 1
            for i in range(n, 1, -1):
                os.replace(f"{self.path}.{i - 1}", f"{self.path}.{i}")
            os.replace(self.path, f"{self.path}.1")
        except OSError:
            rotated = False
        self._f = open(self.path, "a")
        if rotated:
            self._bytes = 0
        else:
            try:
                self._bytes = os.path.getsize(self.path)
            except OSError:
                self._bytes = 0

    def flush(self):
        with self._lock:
            self._f.flush()

    def _drain_flush(self):
        # interpreter-exit path: never raise (the file may already be
        # gone), never double-close
        try:
            if not self._closed:
                self.flush()
        except Exception:
            pass

    def close(self):
        atexit.unregister(self._drain_flush)
        with self._lock:
            self._closed = True
            try:
                self._f.flush()
            finally:
                if self._own:
                    self._f.close()


def _jsonable(x):
    """Last-resort JSON coercion: numpy scalars/arrays and anything
    else stringify rather than kill the sink."""
    try:
        import numpy as np
        if isinstance(x, np.ndarray):
            return x.tolist()
        if isinstance(x, np.generic):
            return x.item()
    except Exception:
        pass
    return str(x)


def chrome_event(rec: dict, pid: Optional[int] = None,
                 tid: Optional[int] = None) -> dict:
    """One telemetry record → one chrome-trace event: ``dur_ms`` makes
    a complete ('X') slice from the record's ``t0`` (a span's own start,
    so nested spans draw nested; without one the slice ends at ts),
    anything else an instant ('i').  THE conversion both the live
    ChromeTraceSink and
    the offline per-rank log merge (telemetry.fleet) share — the lane
    identity (pid) is the caller's choice: process id live, RANK in a
    merged fleet trace."""
    ts_us = rec.get("ts", 0.0) * 1e6
    name = rec.get("event", "event")
    pid = os.getpid() if pid is None else pid
    tid = threading.get_ident() if tid is None else tid
    args = {k: v for k, v in rec.items()
            if k not in ("ts", "t0", "event")}
    if "dur_ms" in rec:
        dur_us = float(rec["dur_ms"]) * 1e3
        start_us = rec["t0"] * 1e6 if "t0" in rec else ts_us - dur_us
        return {"name": name, "ph": "X", "pid": pid, "tid": tid,
                "ts": start_us, "dur": dur_us, "args": args}
    return {"name": name, "ph": "i", "s": "p", "pid": pid,
            "tid": tid, "ts": ts_us, "args": args}


class ChromeTraceSink:
    """Collect events as a chrome://tracing / Perfetto timeline.

    Events carrying ``dur_ms`` become complete ('X') slices; everything
    else becomes an instant ('i') event.  ``save(path)`` (or close, when
    constructed with a path) writes the `{"traceEvents": [...]}` doc.
    Constructed with a path, an `atexit` hook saves it too, so a drain
    or crash exit still leaves the timeline on disk."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.trace_events: List[dict] = []
        self._lock = threading.Lock()
        self._closed = False
        if path is not None:
            atexit.register(self._drain_save)

    def record(self, rec: dict):
        ev = chrome_event(rec)
        with self._lock:
            self.trace_events.append(ev)

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        if path is None:
            raise ValueError("ChromeTraceSink.save needs a path (none "
                             "given at construction)")
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with self._lock:
            doc = {"traceEvents": list(self.trace_events)}
        with open(path, "w") as f:
            json.dump(doc, f, default=_jsonable)
        return path

    def _drain_save(self):
        try:
            if not self._closed and self.path is not None:
                self.save()
        except Exception:
            pass

    def close(self):
        if self.path is not None:
            atexit.unregister(self._drain_save)
            self._closed = True
            self.save()


class MemorySink:
    """Record into a list — tests and the profiler summary view."""

    def __init__(self):
        self.records: List[dict] = []
        self._lock = threading.Lock()

    def record(self, rec: dict):
        with self._lock:
            self.records.append(rec)

    def close(self):
        pass


def attach_jsonl(path_or_file, flush_every: int = 1,
                 max_mb: Optional[float] = None) -> JsonlSink:
    """Create AND attach a JSONL sink; returns it (detach with
    `telemetry.remove_sink(sink)`)."""
    return add_sink(JsonlSink(path_or_file, flush_every, max_mb=max_mb))


def attach_chrome_trace(path: Optional[str] = None) -> ChromeTraceSink:
    """Create AND attach a chrome-trace sink; `remove_sink` (or
    `.save()`) writes the timeline."""
    return add_sink(ChromeTraceSink(path))
