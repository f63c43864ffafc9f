"""Fleet plane — rank-aware aggregation over the telemetry bus
(ROADMAP item 5c's cross-rank half; reference capability:
`python/paddle/distributed/fleet/` monitor + the profiler's
multi-process timeline merge).

Three pieces, all HOST-plane (nothing here can touch a compiled
program; tests/test_program_contracts.py arms the fleet identity and
the straggler flag beside the sinks and holds the train step
byte-identical):

  * :class:`FleetSink` — a regular telemetry sink a WORKER attaches
    beside its JSONL log: every N `train.step` events it PUTs a compact
    per-rank step summary (wall/step ms, arrival ts, collective kind
    counts) into the launch KV store (`distributed/launch/master.py`),
    under ``<job>/fleet/<rank>/s<step>`` plus a ``latest`` pointer,
    pruning its own keys past a rolling window.  No sink attached →
    the plane's usual zero-overhead contract holds (the sink only ever
    sees events that were already being emitted).

  * :class:`FleetAggregator` — the COORDINATOR side: ``poll()`` reads
    the per-rank summaries, and for every step all `world` ranks have
    reported judges the cross-rank wall-time skew and arrival skew.
    Past ``FLAGS_straggler_skew_ms`` it emits a ``fleet.straggler``
    event naming the slow rank (and ARMS the existing comm watchdog:
    a straggler that persists ages into the standard
    FLAGS_stop_check_timeout report/abort path; catching up disarms
    it).  Rank step-counter spread past ``FLAGS_fleet_desync_steps``
    or disagreeing per-step collective kind counts (the cross-rank
    collective-order checker's runtime shadow) emit ``fleet.desync``.

  * :func:`merge_jsonl_traces` — per-rank JSONL step logs → ONE chrome
    trace with one lane (pid) per rank, `process_name` metadata naming
    the lanes; `tools/fleet_report.py` is the CLI face.

`init_from_env()` stamps the process's (rank, world) identity onto the
bus from the launcher's env (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM)
without touching jax.distributed — `distributed.env.init_parallel_env`
calls it, and single-process stays rank 0.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Dict, List, Optional

from ..framework.flags import define_flag, get_flag
from .registry import (counter as _counter, emit as _emit,
                       set_rank)

__all__ = ["init_from_env", "FleetSink", "FleetAggregator",
           "judge_step", "tombstone_rank", "merge_jsonl_traces",
           "load_jsonl", "log_segments"]

define_flag("straggler_skew_ms", 0.0,
            "cross-rank per-step wall/arrival skew (ms) above which the "
            "fleet aggregator flags the slow rank as a straggler "
            "(fleet.straggler event + watchdog arm); 0 disables the "
            "detector (skews are still recorded)")
define_flag("fleet_report_steps", 1,
            "a FleetSink publishes one per-rank step summary to the "
            "coordinator KV store every N train.step events")
define_flag("fleet_desync_steps", 8,
            "rank step-counter spread above which the aggregator emits "
            "fleet.desync (ranks are no longer executing the same step "
            "window)")


def init_from_env():
    """Stamp (rank, world) from the launcher env onto the telemetry
    bus.  Returns the (rank, world) it announced; single process (no
    launcher vars) announces (0, 1) so 'initialized' single-process
    runs still label their events rank 0."""
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    world = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    set_rank(rank, world)
    return rank, world


# ---------------------------------------------------------------------------
# worker side

class FleetSink:
    """Telemetry sink publishing per-rank step summaries to the KV
    store.  Attach beside the JSONL sink on every rank::

        kv = KVClient(master_endpoint)
        telemetry.add_sink(FleetSink(kv, job_id=job, rank=r, world=n))

    Only `train.step` (and `collective.schedule`, folded into the next
    summary) events do any work; everything else returns on one string
    compare.  The KV PUTs run on a background publisher thread behind a
    bounded queue — a dead/hung coordinator fills the queue and later
    summaries are DROPPED (counted in `dropped`), never allowed to
    block the train step (KVClient's retry timeouts are seconds-scale).
    `close()` (remove_sink) and an `atexit` hook drain the queue
    synchronously so a finishing worker's last summaries land."""

    def __init__(self, kv, job_id: str = "fleet",
                 rank: Optional[int] = None,
                 world: Optional[int] = None,
                 every: Optional[int] = None, window: int = 64):
        import atexit
        import queue
        import threading
        if isinstance(kv, str):
            from ..distributed.launch.master import KVClient
            kv = KVClient(kv)
        self._kv = kv
        self._job = job_id
        from .registry import rank_info
        info = rank_info() or (0, 1)
        self._rank = int(info[0] if rank is None else rank)
        self._world = int(info[1] if world is None else world)
        self._every = max(1, int(every if every is not None
                                 else get_flag("fleet_report_steps") or 1))
        self._window = max(1, int(window))
        self._n = 0
        self._coll: Optional[dict] = None
        self._published: deque = deque()    # step keys, oldest first
        self.dropped = 0
        self._q: "queue.Queue" = queue.Queue(maxsize=16)
        self._stopping = threading.Event()
        self._thread = threading.Thread(target=self._publish_loop,
                                        name="fleet-publish",
                                        daemon=True)
        self._thread.start()
        atexit.register(self._drain)

    def record(self, rec: dict):
        ev = rec.get("event")
        if ev == "collective.schedule":
            self._coll = dict(rec.get("kinds") or {})
            return
        if ev != "train.step":
            return
        self._n += 1
        if self._n % self._every:
            return
        import queue
        step = int(rec.get("step", self._n))
        summary = {"rank": self._rank, "world": self._world,
                   "step": step,
                   "ts": float(rec.get("ts") or time.time()),
                   "wall_ms": rec.get("wall_ms"),
                   "step_ms": rec.get("step_ms"),
                   "k": rec.get("k", 1),
                   "cold": bool(rec.get("cold", False)),
                   "steps_seen": self._n}
        if rec.get("tokens_per_sec") is not None:
            summary["tokens_per_sec"] = rec["tokens_per_sec"]
        if self._coll is not None:
            # consume the probe result: kinds ride the NEXT summary
            # only — a stale mix smeared onto every later step would
            # read as a permanent (and un-localizable) desync
            summary["collectives"] = self._coll
            self._coll = None
        pre = f"{self._job}/fleet/{self._rank}"
        key = f"{pre}/s{step:08d}"
        # exact rolling window over the keys actually enqueued (step
        # numbers stride by k under fused multi-step trainers, so
        # "delete step-window" would miss); the pop commits only on a
        # successful enqueue — a dropped summary must not strand its
        # prune target outside the deque forever
        self._published.append(key)
        prune = self._published[0] \
            if len(self._published) > self._window else None
        try:
            self._q.put_nowait((key, f"{pre}/latest",
                                json.dumps(summary), prune))
            if prune is not None:
                self._published.popleft()
        except queue.Full:
            self._published.pop()   # this summary never reaches the
            self.dropped += 1       # store; coordinator stalled —
            #                         drop, never block the step

    def _publish_loop(self):
        import queue
        # timed gets so a close() against a FULL queue (stalled
        # coordinator — the sentinel can't be enqueued) still stops
        # the thread instead of leaking it for the process lifetime
        while not self._stopping.is_set():
            try:
                msg = self._q.get(timeout=0.2)
            except queue.Empty:
                continue
            if msg is None:
                return
            self._publish(msg)

    def _publish(self, msg):
        key, latest_key, payload, prune = msg
        try:
            self._kv.put(key, payload)
            self._kv.put(latest_key, payload)
            if prune:
                self._kv.delete(prune)
        except Exception:           # KVClient shouldn't raise; belt+
            pass                    # braces for the publisher thread

    def _drain(self):
        """Publish whatever is still queued, synchronously (close() /
        interpreter exit — a finishing worker's tail must land)."""
        import queue
        try:
            while True:
                msg = self._q.get_nowait()
                if msg is not None:
                    self._publish(msg)
        except queue.Empty:
            pass

    def flush(self):
        self._drain()

    def close(self):
        import atexit
        import queue
        atexit.unregister(self._drain)
        self._stopping.set()
        try:
            self._q.put_nowait(None)        # wake the publisher now
        except queue.Full:
            pass                            # timed get notices anyway
        self._thread.join(timeout=2.0)
        self._drain()

    def retire(self):
        """Tombstone this rank on the KV plane and close the sink — a
        replica retired by a scale-in (ISSUE 19) stops heartbeating on
        purpose, and without the tombstone its stale summaries would
        read as a straggler forever."""
        self.close()
        tombstone_rank(self._kv, self._job, self._rank)


def tombstone_rank(kv, job_id: str, rank: int) -> bool:
    """Mark `rank` as deliberately retired (scaled in / drained) under
    ``<job>/fleet/<rank>/tombstone`` — a master-clock stamp, so the
    retirement time is skew-free.  `FleetAggregator.poll()` drops a
    tombstoned rank from the judged set and shrinks the effective
    world, so a scale-in never fires a spurious ``fleet.straggler``."""
    if isinstance(kv, str):
        from ..distributed.launch.master import KVClient
        kv = KVClient(kv)
    try:
        return bool(kv.stamp(f"{job_id}/fleet/{rank}/tombstone"))
    except Exception:
        return False


# ---------------------------------------------------------------------------
# coordinator side

def judge_step(recs: Dict[int, dict], threshold_ms: float = 0.0,
               arrival_baseline: Optional[Dict[int, float]] = None
               ) -> Optional[dict]:
    """Judge ONE step's per-rank records ({rank: summary/event with
    wall_ms, ts, optional cold}) — THE skew rule the live aggregator
    and the offline fleet_report table share.  Returns None when any
    rank's record is cold (its wall includes the XLA compile), else
    {walls, skew_ms, arrival_skew_ms, worst_rank, flagged}: the worst
    rank is the slowest wall when wall skew dominates, the latest
    arrival otherwise.

    Arrival skew is judged as DRIFT relative to `arrival_baseline`
    ({rank: ts} from the first judged warm step): rank wall clocks are
    not synchronized, so a constant cross-host offset (ordinary NTP
    drift) must never read as a straggler — only offset GROWTH, a rank
    falling further behind step over step, does.  Callers judging a
    sequence pass the baseline; without one the raw ts spread is used
    (same-clock ranks only)."""
    if any(rec.get("cold") for rec in recs.values()):
        return None
    walls = {r: float(rec.get("wall_ms") or 0.0)
             for r, rec in recs.items()}
    arrivals = {r: float(rec.get("ts") or 0.0)
                for r, rec in recs.items()}
    if arrival_baseline:
        arrivals = {r: t - arrival_baseline.get(r, 0.0)
                    for r, t in arrivals.items()}
    skew = max(walls.values()) - min(walls.values())
    askew = (max(arrivals.values()) - min(arrivals.values())) * 1e3
    worst = max(walls, key=walls.get) if skew >= askew \
        else max(arrivals, key=arrivals.get)
    return {"walls": walls,
            "skew_ms": round(skew, 3),
            "arrival_skew_ms": round(askew, 3),
            "worst_rank": worst,
            "flagged": threshold_ms > 0
            and max(skew, askew) > threshold_ms}


def arrivals_of(recs: Dict[int, dict]) -> Dict[int, float]:
    """{rank: arrival ts} of one step's records — the baseline a
    sequence judge captures at its first warm step."""
    return {r: float(rec.get("ts") or 0.0) for r, rec in recs.items()}


class FleetAggregator:
    """Coordinator-side collector + straggler/desync detector.

    ``poll()`` is the driver: read every rank's summaries, judge each
    step window all `world` ranks have reported (exactly once), emit
    ``fleet.straggler`` / ``fleet.desync`` into the LOCAL telemetry
    plane (the coordinator's own sinks/log), and return a report dict
    (`tools/fleet_report.py --live` renders it).

    Watchdog arming: a detected straggler registers a named task with
    the existing CommTaskManager — under FLAGS_stop_check_timeout a
    straggler that persists past the timeout gets the standard thread-
    stack dump / abort treatment; a rank that catches up (judged clean
    on a later step) is disarmed.  With the watchdog flag off, arming
    is a no-op and the events remain the signal."""

    def __init__(self, kv, job_id: str = "fleet", world: int = 2,
                 skew_ms: Optional[float] = None,
                 desync_steps: Optional[int] = None,
                 history: int = 256):
        if isinstance(kv, str):
            from ..distributed.launch.master import KVClient
            kv = KVClient(kv)
        self._kv = kv
        self._job = job_id
        self.world = int(world)
        self._skew_ms = skew_ms
        self._desync_steps = desync_steps
        self.skews: deque = deque(maxlen=max(1, int(history)))
        self.straggler_counts: Dict[int, int] = {}
        self._last_judged = 0
        self._arrival_baseline: Optional[Dict[int, float]] = None
        self._desynced = False
        self._watch_tasks: Dict[int, object] = {}

    # -- thresholds --------------------------------------------------------
    def _threshold(self) -> float:
        if self._skew_ms is not None:
            return float(self._skew_ms)
        return float(get_flag("straggler_skew_ms") or 0.0)

    def _desync_threshold(self) -> int:
        if self._desync_steps is not None:
            return int(self._desync_steps)
        return int(get_flag("fleet_desync_steps") or 8)

    # -- watchdog ----------------------------------------------------------
    def _arm(self, rank: int):
        if rank in self._watch_tasks:
            return
        from ..distributed.watchdog import get_comm_task_manager
        task = get_comm_task_manager().start_task(
            f"fleet.straggler rank{rank}")
        if task is not None:            # watchdog disabled -> no-op
            self._watch_tasks[rank] = task

    def _disarm(self, rank: int):
        task = self._watch_tasks.pop(rank, None)
        if task is not None:
            task.done()

    def close(self):
        for rank in list(self._watch_tasks):
            self._disarm(rank)

    # -- the driver --------------------------------------------------------
    def poll(self) -> dict:
        got = self._kv.prefix(f"{self._job}/fleet")
        # tombstones first: a rank retired by a scale-in (ISSUE 19)
        # stopped heartbeating on purpose — its stale summaries must
        # not enter the judged set or read as a straggler
        tombstoned: set = set()
        for key in got:
            if key.endswith("/tombstone"):
                try:
                    tombstoned.add(int(key.split("/")[-2]))
                except ValueError:
                    continue
        per_rank: Dict[int, Dict[int, dict]] = {}
        latest: Dict[int, dict] = {}
        for key, raw in got.items():
            try:
                rec = json.loads(raw)
                rank = int(rec["rank"])
            except (ValueError, KeyError, TypeError):
                continue
            if rank in tombstoned:
                continue
            if key.endswith("/latest"):
                latest[rank] = rec
            else:
                per_rank.setdefault(rank, {})[int(rec["step"])] = rec
        # a tombstoned rank can never age into the watchdog abort path
        for rank in tombstoned:
            self._disarm(rank)
            self.straggler_counts.pop(rank, None)
        world_eff = max(1, self.world - len(tombstoned))

        stragglers_this_poll: set = set()
        judged_this_poll: List[int] = []
        thr = self._threshold()
        if len(per_rank) >= world_eff:
            common = sorted(set.intersection(
                *[set(d) for d in per_rank.values()]))
            for s in common:
                if s <= self._last_judged:
                    continue
                recs = {r: per_rank[r][s] for r in per_rank}
                self._last_judged = s
                if any(rec.get("cold") for rec in recs.values()):
                    # cold step: its wall includes the XLA compile —
                    # judging it (or baselining arrivals on it) would
                    # flag every rank whose compile ran longest
                    continue
                if self._arrival_baseline is None:
                    # first warm step anchors the per-rank clock
                    # offsets; from here arrival skew means DRIFT
                    self._arrival_baseline = arrivals_of(recs)
                verdict = judge_step(recs, thr,
                                     self._arrival_baseline)
                if verdict is None:
                    continue
                self.skews.append({"step": s,
                                   "skew_ms": verdict["skew_ms"],
                                   "arrival_skew_ms":
                                   verdict["arrival_skew_ms"],
                                   "walls": verdict["walls"]})
                judged_this_poll.append(s)
                if verdict["flagged"]:
                    worst = verdict["worst_rank"]
                    stragglers_this_poll.add(worst)
                    self.straggler_counts[worst] = \
                        self.straggler_counts.get(worst, 0) + 1
                    _counter("fleet.stragglers").inc()
                    _emit("fleet.straggler", step=s, straggler=worst,
                          skew_ms=verdict["skew_ms"],
                          arrival_skew_ms=verdict["arrival_skew_ms"],
                          threshold_ms=thr,
                          walls={str(r): round(w, 3) for r, w
                                 in verdict["walls"].items()})
                # collective-schedule divergence: the ranks ran
                # different collective mixes for the SAME step — the
                # runtime shadow of check_collective_order
                colls = {r: rec.get("collectives")
                         for r, rec in recs.items()
                         if rec.get("collectives") is not None}
                if len(colls) >= 2 and len(
                        {json.dumps(c, sort_keys=True)
                         for c in colls.values()}) > 1:
                    _counter("fleet.desyncs").inc()
                    _emit("fleet.desync", reason="collectives", step=s,
                          kinds={str(r): c for r, c in colls.items()})

        # straggler watchdog arm/disarm on this poll's verdicts
        for rank in stragglers_this_poll:
            self._arm(rank)
        if judged_this_poll:
            for rank in list(self._watch_tasks):
                if rank not in stragglers_this_poll:
                    self._disarm(rank)

        # rank step-counter spread (from the latest pointers): ranks no
        # longer executing the same step window
        steps_latest = {r: int(rec.get("step", 0))
                        for r, rec in latest.items()}
        if len(steps_latest) >= 2:
            spread = max(steps_latest.values()) - min(steps_latest.values())
            if spread > self._desync_threshold():
                if not self._desynced:      # edge-trigger, not per poll
                    _counter("fleet.desyncs").inc()
                    _emit("fleet.desync", reason="step-spread",
                          spread=spread,
                          steps={str(r): s
                                 for r, s in steps_latest.items()})
                self._desynced = True
            else:
                self._desynced = False

        return {
            "world": self.world,
            "world_effective": world_eff,
            "tombstoned": sorted(tombstoned),
            "ranks": sorted(per_rank) or sorted(latest),
            "steps_judged": self._last_judged,
            "latest_steps": steps_latest,
            "skews": list(self.skews),
            "max_skew_ms": max((s["skew_ms"] for s in self.skews),
                               default=0.0),
            "stragglers": dict(self.straggler_counts),
            "watchdog_armed": sorted(self._watch_tasks),
        }


# ---------------------------------------------------------------------------
# offline merge: per-rank JSONL logs -> one rank-laned chrome trace

def load_jsonl(path: str) -> List[dict]:
    """Parse a telemetry JSONL log; blank lines skipped, a torn tail
    line (crash mid-write) is dropped rather than failing the merge."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def log_segments(path: str) -> List[str]:
    """A JSONL log plus its size-rotated segments, OLDEST FIRST
    (``events.jsonl.N ... events.jsonl.1 events.jsonl`` — the
    JsonlSink rotation shifts older segments to higher suffixes).
    A log that never rotated is just ``[path]``."""
    segs: List[str] = []
    n = 1
    while os.path.exists(f"{path}.{n}"):
        segs.append(f"{path}.{n}")
        n += 1
    return list(reversed(segs)) + [path]


def merge_jsonl_traces(paths: List[str], out_path: Optional[str] = None,
                       ranks: Optional[List[int]] = None) -> dict:
    """Merge per-rank JSONL step logs into ONE chrome trace, one lane
    (pid) per rank.  Each record's own `rank` tag wins; a log whose
    records are untagged (single-process, pre-fleet) gets `ranks[i]`
    (default: its position in `paths`).  A log that size-rotated
    (FLAGS_telemetry_max_log_mb) contributes all its segments in
    order.  Returns the trace doc and writes it to `out_path` when
    given — load in chrome://tracing or Perfetto and every rank is a
    named lane on one timeline."""
    from .exporters import chrome_event, _jsonable
    events: List[dict] = []
    lanes: set = set()
    for i, path in enumerate(paths):
        default_rank = ranks[i] if ranks is not None else i
        for seg in log_segments(path):
            for rec in load_jsonl(seg):
                rank = int(rec.get("rank", default_rank))
                lanes.add(rank)
                events.append(chrome_event(rec, pid=rank, tid=0))
    meta = []
    for rank in sorted(lanes):
        meta.append({"name": "process_name", "ph": "M", "pid": rank,
                     "args": {"name": f"rank {rank}"}})
        meta.append({"name": "process_sort_index", "ph": "M",
                     "pid": rank, "args": {"sort_index": rank}})
    doc = {"traceEvents": meta + events}
    if out_path:
        d = os.path.dirname(out_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(doc, f, default=_jsonable)
    return doc
