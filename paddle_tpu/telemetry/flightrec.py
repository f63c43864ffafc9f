"""Incident flight recorder — a bounded in-memory ring of recent
telemetry events that, when an anomaly trigger fires, dumps a
self-contained *incident bundle* directory explaining the detection
(ISSUE 14's tentpole: the r14 fleet plane and r16 cost ledger DETECT
drift/stragglers/hangs/nonfinite steps; this captures the *why* so
nobody has to re-run under a profiler and hope it reproduces).

Cost contract (the plane's usual shape):

  * The recorder is a regular telemetry sink — it only ever sees
    events that were already being emitted, so with it attached the
    compiled train/serve programs stay byte-identical (bench-asserted)
    and the per-event cost is one deque append + one set lookup.
  * A TRIGGER event (`perf.drift`, `fleet.straggler`, `fleet.desync`,
    `serve.hung`, `watchdog.timeout`, `fault.hit`, `train.anomaly`)
    dumps a bundle — rate-limited PER TRIGGER KIND
    (``FLAGS_flightrec_interval_s``), with bounded retention
    (``FLAGS_flightrec_keep`` newest bundles kept), written crash-safe
    via the r9 tmp+rename idiom (a bundle directory either exists
    complete or not at all).
  * A dump failure (disk full, race) is counted, never raised — a
    raising sink would be detached by the bus, losing the recorder.

Bundle layout (rendered by `tools/incident_report.py`)::

    incident-000001-perf-drift/
      manifest.json     kind, trigger ts, ring size, file list, rank
      trigger.json      the trigger event itself
      events.jsonl      the ring's recent events (JSONL, oldest first)
      trace.json        the same window as a chrome-trace slice
      memory.json       telemetry.memledger.snapshot()
      cost.json         telemetry.costledger.snapshot()
      fingerprint.json  resolved FLAGS + the r16 capture-id env
                        fingerprint (the perf sentry's match key)
      profile/          (optional) jax.profiler trace of the next K
                        steps AFTER the trigger — the post-anomaly
                        device timeline (``FLAGS_flightrec_profile_steps``;
                        capability-gated, no-op where unsupported)

Zero-config: a process launched with ``FLAGS_flightrec_dir`` in its
environment arms the recorder at import (the compile-cache idiom);
`attach()` arms it programmatically.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from ..framework.flags import define_flag, get_flag
from .registry import add_sink, counter as _counter, emit as _emit, \
    rank_info, remove_sink

__all__ = ["FlightRecorder", "TRIGGER_EVENTS", "attach", "attached",
           "detach", "restore", "maybe_attach", "env_fingerprint",
           "capture_id", "reset"]

define_flag("flightrec_dir", "",
            "incident-bundle directory arming the flight recorder at "
            "import (a relaunched worker records from its first "
            "event); empty leaves the recorder detached — attach() "
            "arms it programmatically")
define_flag("flightrec_ring", 512,
            "events retained in the flight recorder's in-memory ring "
            "(the bundle's recent-history window)")
define_flag("flightrec_keep", 8,
            "incident bundles retained on disk; older bundles are "
            "deleted oldest-first after each dump")
define_flag("flightrec_interval_s", 60.0,
            "minimum seconds between bundles of the SAME trigger kind "
            "(a persistent drift or a straggler storm produces one "
            "bundle, not one per poll); suppressed triggers are "
            "counted, and a different kind dumps immediately")
define_flag("flightrec_profile_steps", 0,
            "arm a jax.profiler programmatic trace into the bundle's "
            "profile/ dir for the next K train.step/serve.chunk events "
            "after a trigger — the POST-anomaly device timeline; 0 "
            "disables, and unsupported backends degrade to a no-op")

# trigger event -> bundle kind (the rate-limit key); every detection
# event the observability planes emit lands here
TRIGGER_EVENTS = ("perf.drift", "fleet.straggler", "fleet.desync",
                  "serve.hung", "watchdog.timeout", "fault.hit",
                  "train.anomaly")

# step-shaped events that advance (and close) an armed post-trigger
# profiler window
_STEP_EVENTS = ("train.step", "serve.chunk")


# ---------------------------------------------------------------------------
# env fingerprint (the r16 capture-id contract: perf records compare
# only between identical fingerprints, and an incident bundle carries
# that identity)

_FINGERPRINT_FLAGS = (
    "FLAGS_fused_ce", "FLAGS_bf16_adamw_moments",
    "FLAGS_weight_only_dtype", "FLAGS_weight_only_group_size",
    "FLAGS_kv_cache_dtype", "FLAGS_kv_page_size",
    "FLAGS_serve_spec_tokens", "FLAGS_serve_draft_layers",
)
_FINGERPRINT_ENVS = ()      # no environment name changes a metric today


def env_fingerprint(flags=_FINGERPRINT_FLAGS,
                    envs=_FINGERPRINT_ENVS) -> dict:
    """Environment fingerprint (ISSUE 12): jax/jaxlib versions,
    backend + device kind, and the metric-relevant flags/envs.  THE one
    derivation, so every capture id agrees."""
    fp = {}
    try:
        import jax
        import jaxlib
        fp["jax"] = jax.__version__
        fp["jaxlib"] = jaxlib.__version__
        fp["backend"] = jax.default_backend()
        fp["device"] = jax.devices()[0].device_kind
    except Exception:
        pass
    try:
        from ..framework.flags import get_flags
        fp["flags"] = {k: v for k, v in sorted(
            get_flags(list(flags)).items())}
    except Exception:
        pass
    fp["env"] = {k: os.environ[k] for k in envs if k in os.environ}
    return fp


def capture_id(fp: Optional[dict] = None) -> str:
    """Stable id of the env fingerprint: the perf sentry's match
    key."""
    import hashlib
    blob = json.dumps(fp if fp is not None else env_fingerprint(),
                      sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


# ---------------------------------------------------------------------------
# the recorder

class FlightRecorder:
    """The sink.  Attach beside (or instead of) a JSONL log::

        rec = telemetry.flightrec.attach("incidents/")
        ... anomaly fires ...
        rec.bundles()   # -> ["incidents/incident-000001-perf-drift"]
    """

    def __init__(self, dir_path: Optional[str] = None,
                 ring: Optional[int] = None,
                 keep: Optional[int] = None,
                 interval_s: Optional[float] = None,
                 profile_steps: Optional[int] = None):
        self.dir = dir_path or get_flag("flightrec_dir") or "incidents"
        self._ring: deque = deque(
            maxlen=max(8, int(ring if ring is not None
                              else get_flag("flightrec_ring") or 512)))
        self.keep = max(1, int(keep if keep is not None
                               else get_flag("flightrec_keep") or 8))
        self.interval_s = float(
            interval_s if interval_s is not None
            else get_flag("flightrec_interval_s") or 0.0)
        self._profile_steps = int(
            profile_steps if profile_steps is not None
            else get_flag("flightrec_profile_steps") or 0)
        self._lock = threading.Lock()
        self._last_dump: Dict[str, float] = {}   # kind -> monotonic ts
        self.suppressed: Dict[str, int] = {}     # kind -> rate-limited
        self.errors = 0
        self._seq = self._next_seq()
        self._profile_left = 0
        self._profile_active = False
        self._profile_ok = True     # flips False on the first failure

    # -- sink protocol -----------------------------------------------------
    def record(self, rec: dict):
        ev = rec.get("event")
        with self._lock:
            self._ring.append(rec)
        if self._profile_active and ev in _STEP_EVENTS:
            self._profile_tick()
        if ev in TRIGGER_EVENTS:
            # dumps must never raise into the bus — a raising sink is
            # detached, and losing the recorder on a full disk is the
            # one failure mode this sink cannot afford
            try:
                self._trigger(dict(rec))
            except Exception:       # noqa: BLE001
                self.errors += 1
                _counter("flightrec.errors").inc()

    def flush(self):
        pass

    def close(self):
        self._stop_profile()

    # -- trigger path ------------------------------------------------------
    def _trigger(self, rec: dict):
        kind = rec["event"]
        now = time.monotonic()
        with self._lock:
            last = self._last_dump.get(kind)
            if (last is not None and self.interval_s > 0
                    and now - last < self.interval_s):
                self.suppressed[kind] = self.suppressed.get(kind, 0) + 1
                _counter("flightrec.suppressed").inc()
                return
            # claim the window now (a concurrent same-kind trigger must
            # not double-dump) ...
            self._last_dump[kind] = now
            ring = list(self._ring)
            seq = self._seq = self._seq + 1
        try:
            path = self._dump(seq, kind, rec, ring)
        except Exception:
            # ... but a FAILED dump releases the claim: a full disk
            # must not eat the whole interval's re-triggers — edge-
            # triggered detections (perf.drift) may never fire again
            with self._lock:
                if self._last_dump.get(kind) == now:
                    del self._last_dump[kind]
            raise
        _counter("flightrec.bundles").inc()
        _emit("flightrec.bundle", kind=kind, path=path, events=len(ring))
        self._prune()
        if self._profile_steps > 0:
            self._start_profile(path)

    def _next_seq(self) -> int:
        """Resume numbering past existing bundles so a relaunched
        worker never collides with (or reorders) its predecessor's."""
        seq = 0
        try:
            for name in os.listdir(self.dir):
                if name.startswith("incident-"):
                    try:
                        seq = max(seq, int(name.split("-")[1]))
                    except (IndexError, ValueError):
                        continue
        except OSError:
            pass
        return seq

    def _dump(self, seq: int, kind: str, trigger: dict,
              ring: List[dict]) -> str:
        from .exporters import _jsonable, chrome_event
        from . import costledger, memledger
        info = rank_info()
        # rank rides the NAME (not just the manifest): fleet workers
        # sharing one FLAGS_flightrec_dir must never collide on a seq
        name = (f"incident-{seq:06d}-r{info[0] if info else 0}-"
                f"{kind.replace('.', '-')}")
        final = os.path.join(self.dir, name)
        tmp = os.path.join(self.dir, f".tmp-{name}-{os.getpid()}")
        os.makedirs(tmp, exist_ok=True)

        def _write(fname, obj):
            with open(os.path.join(tmp, fname), "w") as f:
                json.dump(obj, f, indent=1, default=_jsonable)

        _write("trigger.json", trigger)
        with open(os.path.join(tmp, "events.jsonl"), "w") as f:
            for r in ring:
                f.write(json.dumps(r, default=_jsonable) + "\n")
        _write("trace.json",
               {"traceEvents": [chrome_event(r) for r in ring]})
        # snapshots, not reports: resolution compiles, and a trigger
        # can fire from inside a train step — the bundle records what
        # the ledgers already know, never pays a compile to know more
        _write("memory.json", memledger.snapshot())
        _write("cost.json", costledger.snapshot())
        fp = env_fingerprint()
        flags = {}
        try:
            from ..framework.flags import known_flags
            flags = {"FLAGS_" + k: v["value"]
                     for k, v in sorted(known_flags().items())}
        except Exception:
            pass
        _write("fingerprint.json",
               {"capture_id": capture_id(fp), "env": fp,
                "flags": flags})
        # `info` from the top of _dump: name and manifest must agree
        _write("manifest.json", {
            "kind": kind, "ts": trigger.get("ts"), "seq": seq,
            "events": len(ring),
            "rank": info[0] if info else 0,
            "world": info[1] if info else 1,
            "files": ["manifest.json", "trigger.json", "events.jsonl",
                      "trace.json", "memory.json", "cost.json",
                      "fingerprint.json"],
        })
        # the r9 tmp+rename publish: the final name appears only once
        # every file inside is complete — a crash mid-dump leaves a
        # .tmp-* directory, never a half bundle that parses.  A name
        # collision (two same-rank processes sharing the dir) falls
        # back to a pid-suffixed name rather than dropping the bundle
        try:
            os.rename(tmp, final)
        except OSError:
            final = f"{final}-p{os.getpid()}"
            os.rename(tmp, final)
        return final

    def _prune(self):
        try:
            names = sorted(n for n in os.listdir(self.dir)
                           if n.startswith("incident-"))
        except OSError:
            return
        for name in names[:-self.keep] if len(names) > self.keep else []:
            try:
                shutil.rmtree(os.path.join(self.dir, name))
            except OSError:
                pass

    def bundles(self) -> List[str]:
        """Finalized bundle directories, oldest first."""
        try:
            return [os.path.join(self.dir, n)
                    for n in sorted(os.listdir(self.dir))
                    if n.startswith("incident-")]
        except OSError:
            return []

    # -- post-trigger profiler window (capability-gated) -------------------
    def _start_profile(self, bundle_dir: str):
        if not self._profile_ok or self._profile_active:
            return                  # one window at a time
        try:
            import jax
            jax.profiler.start_trace(os.path.join(bundle_dir, "profile"))
            self._profile_left = self._profile_steps
            self._profile_active = True
        except Exception:           # noqa: BLE001 — unsupported backend
            self._profile_ok = False

    def _profile_tick(self):
        self._profile_left -= 1
        if self._profile_left <= 0:
            self._stop_profile()

    def _stop_profile(self):
        if not self._profile_active:
            return
        self._profile_active = False
        self._profile_left = 0
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:           # noqa: BLE001 — backend lost it
            pass


# ---------------------------------------------------------------------------
# module-level attach (one recorder per process, the sink registry's
# compile-cache idiom)

_RECORDER: Optional[FlightRecorder] = None


def attach(dir_path: Optional[str] = None, **kw) -> FlightRecorder:
    """Create AND attach the process flight recorder (idempotent: a
    second attach returns the live one — and WARNS if it asked for a
    different directory, since its bundles would land elsewhere).
    Detach with `detach()`."""
    global _RECORDER
    if _RECORDER is None:
        _RECORDER = add_sink(FlightRecorder(dir_path, **kw))
    elif dir_path and dir_path != _RECORDER.dir:
        import warnings
        warnings.warn(
            f"flightrec.attach({dir_path!r}): a recorder is already "
            f"attached at {_RECORDER.dir!r}; returning it (use "
            "detach()/restore() to scope a temporary recorder)",
            RuntimeWarning)
    return _RECORDER


def attached() -> Optional[FlightRecorder]:
    return _RECORDER


def detach() -> Optional[FlightRecorder]:
    """Detach and RETURN the process recorder (so a test scope can
    `restore()` it after running with its own temporary one — a
    production recorder armed via FLAGS_flightrec_dir must survive
    it)."""
    global _RECORDER
    rec = _RECORDER
    if rec is not None:
        remove_sink(rec, close=False)
        _RECORDER = None
    return rec


def restore(recorder: Optional[FlightRecorder]
            ) -> Optional[FlightRecorder]:
    """Re-attach a recorder previously returned by `detach()` (no-op
    on None).  The save/restore pair a test scope uses
    (tests/test_program_contracts.py)."""
    global _RECORDER
    if recorder is None:
        return None
    detach()
    _RECORDER = add_sink(recorder)
    return recorder


def maybe_attach() -> Optional[FlightRecorder]:
    """Arm the recorder iff FLAGS_flightrec_dir is set (called at
    telemetry import — a relaunched worker records from its first
    event).  Unset: one flag lookup."""
    if get_flag("flightrec_dir"):
        return attach()
    return None


def reset():
    """Drop the process recorder (test isolation; telemetry.reset()
    already detached it as a sink — this clears the module global so
    the next attach() builds fresh)."""
    global _RECORDER
    if _RECORDER is not None:
        try:
            _RECORDER.close()
        except Exception:
            pass
    _RECORDER = None
