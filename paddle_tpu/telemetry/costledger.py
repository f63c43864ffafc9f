"""Compute cost ledger — per-program FLOP/byte accounting from XLA's
own `compiled.cost_analysis()`, the compute twin of `memledger.py`
(ROADMAP item 5: the calibrated step-time model primitive.  r14's
memory ledger answered "will this program fit?"; this ledger answers
"is this program as fast as it should be?").

Cost model (the plane's usual contract):

  * ZERO extra compiles: the ledger has no providers of its own — it
    rides the memory ledger's.  When `memledger` resolves a pending
    provider (or the AOT path captures a free executable), the SAME
    Compiled is handed here and `cost_analysis()` extracted alongside
    `memory_analysis()`.  `cost_report()` forces resolution through
    `memledger.memory_report()`, so one compile per program serves
    both ledgers (probe-contract pinned like the memory ledger: serve
    resolution rides the side-effect-free `lower_step` probe).
  * MEASURED walls arrive from the live `train.step` / `serve.chunk`
    events: `telemetry.step_event` and the serving batcher call
    `observe(label, wall_ms, cold=...)` inside their existing
    sink-guarded blocks — with no sink attached nothing here runs
    (tests/test_program_contracts.py
    `test_observability_surface_leaves_the_train_step_identical` holds
    the program half), and cold calls (XLA compile in the wall) are
    excluded like every other timing surface in the repo.
  * The roofline verdict uses the backend's CALIBRATED peaks: the
    bf16 matmul peak and the HBM stream bandwidth (CHIP_PEAKS below),
    scaled by an efficiency anchor of 0.6 that rests on two pre-ledger
    points and has not been measured since.  `configure_peaks()` is
    the override.

Report shape (per program): flops, bytes_accessed, arithmetic
intensity (flops/byte), roofline ``bound`` ("compute" when intensity
clears the ridge point peak_flops/peak_bw, else "memory"),
``predicted_ms`` = max(compute-limb, memory-limb) at the calibrated
peaks, the measured warm-step median when events flowed, and
``attained`` = predicted/measured — the fraction of the calibrated
roofline the program actually achieves (1.0 = running exactly at the
calibrated model; below ``FLAGS_mfu_floor`` emits `perf.drift` and
trips `analysis.lint_mfu_floor`).

Per-layer attribution: the models thread `jax.named_scope` through
their block forwards, so the optimized HLO carries model-structure
names ("llama.layer3", "gpt.embed", ...) — `ingest` runs a cheap
scope census over the compiled text and each entry reports op counts
per scope instead of one opaque program (the same names land in
device chrome traces for tools/fleet_report.py lanes).
"""
from __future__ import annotations

import os
import re
import threading
from collections import deque
from typing import Dict, List, Optional

__all__ = ["cost_of", "model_train_flops", "backend_peaks",
           "chip_peak_flops", "configure_peaks", "ingest", "observe",
           "measured_ms", "program_changed", "cost_report", "snapshot",
           "reset", "scope_census", "note_comm",
           "interconnect_bytes_per_sec"]

_lock = threading.Lock()
_costs: Dict[str, dict] = {}        # label -> entry (insertion-ordered)
_comm: Dict[str, dict] = {}         # label -> {axes: comm profile} —
#                                     one profile per comm axis so a
#                                     composed (hybrid) program's
#                                     columns add instead of replacing
_measured: Dict[str, deque] = {}    # label -> warm wall_ms window
_measured_total: Dict[str, int] = {}
_drifted: set = set()               # labels currently below the floor
#                                     (perf.drift edge-triggers, like
#                                     fleet.desync — a monitoring loop
#                                     polling cost_report() counts
#                                     detections, not polls)
_MEASURED_WINDOW = 512
_peaks_override: Dict[str, float] = {}

# Per-chip peaks: ONE table keyed by what `jax.devices()[0].device_kind`
# yields, in the spellings jax's own table knows
# (jax/_src/pallas/mosaic/tpu_info.py).  flops = bf16 matmul peak and
# hbm = HBM bandwidth, from the Google Cloud TPU documentation (the
# "System architecture" page of each generation: v4, v5e, v5p, v6e);
# ici = per-chip all-reduce bandwidth (bytes/s per device, the
# bidirectional-ring figure the exposed-comm column divides by;
# PEAK_ICI_GBPS env overrides for other fabrics — DCN, PCIe hosts).
_V4 = {"chip": "v4", "flops": 275e12, "hbm": 1.23e12, "ici": 300e9}
_V5E = {"chip": "v5e", "flops": 197e12, "hbm": 0.82e12, "ici": 160e9}
_V5P = {"chip": "v5p", "flops": 459e12, "hbm": 2.77e12, "ici": 600e9}
_V6E = {"chip": "v6e", "flops": 918e12, "hbm": 1.64e12, "ici": 400e9}
CHIP_PEAKS = {"TPU v4": _V4,
              "TPU v5 lite": _V5E, "TPU v5e": _V5E,
              "TPU v5": _V5P, "TPU v5p": _V5P,
              "TPU v6 lite": _V6E, "TPU v6e": _V6E}
# pre-ledger anchor (two points on one chip, round 5): not measured
# since
CALIBRATED_EFFICIENCY = 0.6
# CPU placeholder peaks: tier-1 exercises the plumbing, not the
# numbers (tests pin behavior through configure_peaks).  Reached only
# when the backend IS the CPU — never as the fall-through for a device
# the table does not know
_CPU_PEAKS = {"chip": None, "flops": 100e9, "hbm": 50e9, "ici": 10e9}


def _chip_peaks() -> dict:
    """This backend's row of peaks — THE one device sniffing: the CPU
    placeholders on a CPU backend, else the CHIP_PEAKS row of the
    device's kind.  A device the table does not know raises: an unknown
    chip is an error, not a default."""
    import jax
    if jax.default_backend() == "cpu":
        return _CPU_PEAKS
    kind = jax.devices()[0].device_kind
    if kind not in CHIP_PEAKS:
        raise ValueError(
            f"no peak figures for device_kind {kind!r}: add its row to "
            f"telemetry.costledger.CHIP_PEAKS (known: "
            f"{sorted(CHIP_PEAKS)})")
    return CHIP_PEAKS[kind]


def chip_peak_flops() -> float:
    """Canonical bf16 matmul peak for this backend: this device's row
    of CHIP_PEAKS."""
    return _chip_peaks()["flops"]


def configure_peaks(flops_per_sec: Optional[float] = None,
                    hbm_bytes_per_sec: Optional[float] = None,
                    efficiency: Optional[float] = None,
                    ici_bytes_per_sec: Optional[float] = None):
    """Override the calibrated peaks (tools/tests; calibration runs
    feed their implied mfu back through `efficiency`).  Passing None
    for a field leaves it on the chip-table default; `reset()` clears
    every override."""
    with _lock:
        if flops_per_sec is not None:
            _peaks_override["flops_per_sec"] = float(flops_per_sec)
        if hbm_bytes_per_sec is not None:
            _peaks_override["hbm_bytes_per_sec"] = float(hbm_bytes_per_sec)
        if efficiency is not None:
            _peaks_override["efficiency"] = float(efficiency)
        if ici_bytes_per_sec is not None:
            _peaks_override["ici_bytes_per_sec"] = float(
                ici_bytes_per_sec)
    return backend_peaks()


def interconnect_bytes_per_sec() -> float:
    """Calibrated interconnect bandwidth for collective payloads (the
    denominator of the exposed-comm column): PEAK_ICI_GBPS env wins,
    then a configure_peaks override, then this device's ICI peak
    (CHIP_PEAKS; the placeholder on a CPU backend) scaled by the
    calibration efficiency."""
    if "PEAK_ICI_GBPS" in os.environ:
        return float(os.environ["PEAK_ICI_GBPS"]) * 1e9
    with _lock:
        ov = _peaks_override.get("ici_bytes_per_sec")
        eff = _peaks_override.get("efficiency", CALIBRATED_EFFICIENCY)
    if ov is not None:
        return ov
    return _chip_peaks()["ici"] * eff


def backend_peaks() -> dict:
    """The calibrated roofline peaks for this backend: raw hardware
    peaks, the calibration efficiency, and the ridge intensity
    (flops/byte) that separates compute- from memory-bound."""
    row = _chip_peaks()
    chip, flops, hbm = row["chip"], row["flops"], row["hbm"]
    source = f"chip-table:{chip}" if chip else "default:cpu"
    eff = CALIBRATED_EFFICIENCY
    with _lock:
        flops = _peaks_override.get("flops_per_sec", flops)
        hbm = _peaks_override.get("hbm_bytes_per_sec", hbm)
        eff = _peaks_override.get("efficiency", eff)
        if _peaks_override:
            source += "+override"
    return {"chip": chip, "flops_per_sec": flops,
            "hbm_bytes_per_sec": hbm, "efficiency": eff,
            "ridge_intensity": flops / hbm if hbm else None,
            "source": source}


# ---------------------------------------------------------------------------
# the ONE cost_analysis derivation (paddle.flops() and the ledger both
# read through here; jax returns a list-of-dict on some backends)

def cost_of(compiled) -> dict:
    """`compiled.cost_analysis()` -> plain {flops, bytes_accessed,
    transcendentals} floats."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def model_train_flops(n_params: float, tokens: float,
                      phase: str = "full",
                      remat_flops_per_token: float = 0.0) -> float:
    """Analytic model-FLOP accounting for dense LM training
    (regression-pinned): 2N/tok forward, 4N/tok backward, 6N/tok full
    step; `remat_flops_per_token` adds the recompute replay FLOPs the
    hardware actually executes (bwd/full phases only)."""
    per_tok = {"fwd": 2.0, "bwd": 4.0, "full": 6.0}[phase] * n_params
    if phase in ("bwd", "full"):
        per_tok += remat_flops_per_token
    return per_tok * tokens


# ---------------------------------------------------------------------------
# scope census — per-layer attribution from named_scope HLO metadata

# the scope vocabulary the model forwards thread (kept tight so
# source-file paths like ".../llama.py" in op metadata never count).
# Lookarounds instead of /-anchors: autodiff wraps scopes in transform
# frames — "jvp(llama.layer0)", "transpose(jvp(llama.layer0))" — and
# those ops belong to the layer all the same.
_SCOPE_PAT = re.compile(
    r'(?<![\w.])((?:llama|gpt|bert)\.'
    r'(?:layer\d+|embed|norm|lm_head|pooler))(?![\w.])')
_CENSUS_TEXT_CAP = 64 * 1024 * 1024


def scope_census(compiled, cap: int = 64) -> Dict[str, int]:
    """Op counts per model-structure `jax.named_scope` name found in
    the optimized HLO's op_name metadata ("llama.layer0", "gpt.embed",
    ...) — the per-layer attribution the block forwards thread in.
    Empty when the program carries no scoped metadata."""
    try:
        text = compiled.as_text()
    except Exception:
        return {}
    if not text or len(text) > _CENSUS_TEXT_CAP:
        return {}
    counts: Dict[str, int] = {}
    for m in _SCOPE_PAT.finditer(text):
        name = m.group(1)
        counts[name] = counts.get(name, 0) + 1
    if len(counts) > cap:
        top = sorted(counts.items(), key=lambda kv: -kv[1])[:cap]
        counts = dict(top)
    return counts


# ---------------------------------------------------------------------------
# ingestion (called by memledger at resolve/capture — the shared
# Compiled means the cost ledger never compiles anything itself)

def ingest(label: str, compiled, meta: Optional[dict] = None):
    """Record cost stats for an in-hand executable under `label`.
    Failures record an error entry rather than raising (the memory
    ledger's resolution must never die on the cost side)."""
    try:
        stats = cost_of(compiled)
    except Exception as e:          # noqa: BLE001
        with _lock:
            _costs[label] = {"label": label, "status": "error",
                             "error": f"{type(e).__name__}: {e}",
                             "meta": dict(meta or {})}
        return None
    entry = {"label": label, "status": "ok", "meta": dict(meta or {}),
             **stats}
    scopes = scope_census(compiled)
    if scopes:
        entry["scopes"] = scopes
    with _lock:
        _costs[label] = entry
    _publish(entry)
    return entry


def note_comm(label: str, profile: dict):
    """Attach a communication profile to `label`'s program (ISSUE 16):
    byte volumes per bucket in issue order plus the overlap shape, as
    produced by CommOverlapPlan.comm_profile().  The report derives
    the exposed-comm column from it — comm time at the calibrated ICI
    peak vs the backward compute available to hide it under — so the
    overlap win is a ledger number before any chip time.  Registered
    at trainer BUILD (zero steady-state cost).

    Profiles are keyed PER COMM AXIS (the `axes` field, e.g.
    ["dp", "sharding"] for the joint grad reduce, ["mp"] for the TP
    activation exchange): a composed hybrid program registers one
    profile per mesh axis under the same label and the report's
    columns ADD across axes — each bucket is counted exactly once,
    under the one axis whose collective drains it.  Re-noting the
    same (label, axes) replaces that axis's profile (a rebuild), never
    duplicates it.  Single-axis callers are unchanged."""
    key = tuple(profile.get("axes") or ())
    with _lock:
        _comm.setdefault(label, {})[key] = dict(profile)


def _publish(entry: dict):
    """cost.program event + counter — a fleet JSONL log carries the
    cost ledger the way it carries mem.program records."""
    from .registry import counter as _counter, emit as _emit
    _counter("cost.programs").inc()
    _emit("cost.program",
          {k: v for k, v in entry.items() if k != "scopes"})


# ---------------------------------------------------------------------------
# measured walls (fed by step_event / the serving batcher, only while
# a sink is attached — the zero-overhead contract)

def program_changed(label: str):
    """A NEW program now owns `label` (memledger.register replaces on
    the same label): the old program's measured walls, cost entry and
    drift edge must not leak onto it — a small model's sub-ms walls
    against a big model's prediction would mask (or spuriously fire)
    a drift.  Called by memledger.register; registration happens
    before the new program's first step_event, so no fresh wall is
    ever dropped."""
    with _lock:
        _measured.pop(label, None)
        _measured_total.pop(label, None)
        _costs.pop(label, None)
        _comm.pop(label, None)
        _drifted.discard(label)


def observe(label: str, wall_ms: float, cold: bool = False):
    """Record one measured warm wall for `label`'s program.  Cold
    calls (first use — the wall may include the XLA compile) are
    excluded, mirroring every other timing surface."""
    if cold:
        return
    with _lock:
        win = _measured.get(label)
        if win is None:
            win = _measured[label] = deque(maxlen=_MEASURED_WINDOW)
        win.append(float(wall_ms))
        _measured_total[label] = _measured_total.get(label, 0) + 1


def measured_ms(label: str) -> Optional[float]:
    """Median warm wall over the recent window, or None."""
    with _lock:
        win = _measured.get(label)
        vals = sorted(win) if win else None
    if not vals:
        return None
    n = len(vals)
    mid = n // 2
    return vals[mid] if n % 2 else 0.5 * (vals[mid - 1] + vals[mid])


# ---------------------------------------------------------------------------
# the report

def _floor() -> float:
    from ..framework.flags import get_flag
    try:
        return float(get_flag("mfu_floor", 0.0) or 0.0)
    except Exception:
        return 0.0


def cost_report(resolve: bool = True,
                measured: Optional[Dict[str, float]] = None) -> dict:
    """The ledger's answer: per-program FLOPs/bytes/intensity, the
    roofline bound and predicted step time at the calibrated peaks,
    and — where `train.step`/`serve.chunk` walls flowed — the measured
    median and `attained` = predicted/measured.  `resolve=True` forces
    the memory ledger's pending providers (ONE compile per program
    serves both ledgers); `measured` lets tools inject explicit walls
    per label (overrides the live window).  Programs whose `attained`
    falls below FLAGS_mfu_floor are marked `drift` and published as
    `perf.drift` events."""
    return _report(resolve=resolve, measured=measured, emit_drift=True)


def snapshot() -> dict:
    """The report without resolution or drift side effects (what
    telemetry.dump() embeds)."""
    return _report(resolve=False, measured=None, emit_drift=False)


def _report(resolve: bool, measured, emit_drift: bool) -> dict:
    if resolve:
        from . import memledger
        # one resolution pass fills BOTH ledgers: memledger compiles
        # each pending provider once and hands the Compiled to ingest
        memledger.memory_report(resolve=True, top_buffers=0)
    peaks = backend_peaks()
    eff = peaks["efficiency"]
    flops_eff = peaks["flops_per_sec"] * eff
    hbm_eff = peaks["hbm_bytes_per_sec"] * eff
    floor = _floor()
    with _lock:
        entries = [dict(e) for e in _costs.values()]
        comm_profiles = {lbl: {k: dict(v) for k, v in p.items()}
                         for lbl, p in _comm.items()}
    ici_bps = interconnect_bytes_per_sec() if comm_profiles else None
    programs: Dict[str, dict] = {}
    drifts: List[str] = []
    for e in entries:
        rec = {k: v for k, v in e.items() if k != "label"}
        if e.get("status") == "ok":
            flops = e["flops"]
            nbytes = e["bytes_accessed"]
            intensity = (flops / nbytes) if nbytes else None
            rec["intensity"] = round(intensity, 3) \
                if intensity is not None else None
            t_compute = flops / flops_eff if flops_eff else 0.0
            t_memory = nbytes / hbm_eff if hbm_eff else 0.0
            rec["bound"] = "compute" if t_compute >= t_memory \
                else "memory"
            predicted_ms = max(t_compute, t_memory) * 1e3
            rec["predicted_compute_ms"] = round(t_compute * 1e3, 4)
            rec["predicted_memory_ms"] = round(t_memory * 1e3, 4)
            rec["predicted_ms"] = round(predicted_ms, 4)
            m = None
            if measured and e["label"] in measured:
                m = float(measured[e["label"]])
            else:
                m = measured_ms(e["label"])
            if m is not None and m > 0:
                rec["measured_ms"] = round(m, 4)
                with _lock:
                    rec["measured_n"] = _measured_total.get(
                        e["label"], 0) or 1
                rec["achieved_flops_per_sec"] = round(
                    flops / (m / 1e3), 1)
                if peaks["flops_per_sec"]:
                    rec["achieved_mfu"] = round(
                        flops / (m / 1e3) / peaks["flops_per_sec"], 4)
                # attained from the UNROUNDED prediction: a sub-50ns
                # program's predicted_ms displays as 0.0 but must not
                # read as attained 0.0 (unconditional drift)
                attained = predicted_ms / m
                rec["attained"] = round(attained, 4)
                if floor > 0 and attained < floor:
                    rec["drift"] = True
                    drifts.append(e["label"])
            cp_map = comm_profiles.get(e["label"])
            if cp_map:
                # the exposed-comm columns (ISSUE 16/17): per-bucket
                # comm at the ICI peak vs the backward compute
                # available to hide it.  Backward ≈ 2/3 of a fwd+bwd
                # step (4N of 6N FLOPs) — the window the bucket chain
                # overlaps into.  One column PER COMM AXIS, summed
                # additively into the program totals: each axis's
                # buckets drain over their own links, and a bucket
                # belongs to exactly one axis profile, so a composed
                # dp×mp×sharding program never double-counts an
                # overlapped bucket.
                from ..analysis.collectives import estimate_exposed_comm
                bwd_ms = predicted_ms * (2.0 / 3.0)
                by_axis = {}
                tot = {"bytes": 0, "buckets": 0, "comm_ms": 0.0,
                       "on": 0.0, "off": 0.0}
                overlap_all = True
                for axes_key in sorted(cp_map, key=repr):
                    cp = cp_map[axes_key]
                    sizes = cp.get("bucket_bytes") \
                        or [cp.get("bytes", 0)]
                    on = estimate_exposed_comm(
                        sizes, bwd_ms, bytes_per_sec=ici_bps,
                        overlap=True)
                    off = estimate_exposed_comm(
                        sizes, bwd_ms, bytes_per_sec=ici_bps,
                        overlap=False)
                    name = "+".join(axes_key) if axes_key else "all"
                    by_axis[name] = {
                        "bytes": on["bytes"],
                        "buckets": on["buckets"],
                        "comm_ms": round(on["comm_ms"], 4),
                        "exposed_ms": round(on["exposed_ms"], 4),
                        "exposed_ms_monolithic": round(
                            off["exposed_ms"], 4)}
                    tot["bytes"] += on["bytes"]
                    tot["buckets"] += on["buckets"]
                    tot["comm_ms"] += on["comm_ms"]
                    tot["on"] += on["exposed_ms"]
                    tot["off"] += off["exposed_ms"]
                    overlap_all = overlap_all \
                        and bool(cp.get("overlap", True))
                rec["comm_bytes"] = tot["bytes"]
                rec["comm_buckets"] = tot["buckets"]
                rec["comm_ms"] = round(tot["comm_ms"], 4)
                rec["exposed_comm_ms"] = round(tot["on"], 4)
                rec["exposed_comm_ms_monolithic"] = round(
                    tot["off"], 4)
                rec["exposed_comm_by_axis"] = by_axis
                rec["overlap_efficiency"] = round(
                    1.0 - tot["on"] / tot["comm_ms"], 4) \
                    if tot["comm_ms"] else 1.0
                rec["comm_overlap"] = overlap_all
        programs[e["label"]] = rec
    if emit_drift:
        from .registry import counter as _counter, emit as _emit
        # predicted-vs-measured records for every measured program (a
        # JSONL log then carries the roofline cross-check, drifting or
        # not — telemetry_report's cost section renders them)
        for lbl, rec in programs.items():
            if "attained" in rec:
                # the measure record carries the drift STATE (the
                # perf.drift event is edge-triggered and won't repeat
                # while a drift persists — readers of the latest
                # measure must still see it)
                _emit("cost.measure", label=lbl,
                      predicted_ms=rec["predicted_ms"],
                      measured_ms=rec["measured_ms"],
                      attained=rec["attained"], bound=rec["bound"],
                      drift=bool(rec.get("drift")))
        # perf.drift is EDGE-triggered per label (the fleet.desync
        # discipline): a monitoring loop that polls cost_report()
        # while one program sits below the floor counts ONE
        # detection, not one per poll; recovery re-arms the edge.
        # snapshot() never reaches here, so it cannot swallow an edge.
        with _lock:
            new = [lbl for lbl in drifts if lbl not in _drifted]
            _drifted.clear()
            _drifted.update(drifts)
        if new:
            _counter("perf.drift").inc(len(new))
            for lbl in new:
                rec = programs[lbl]
                _emit("perf.drift", label=lbl,
                      predicted_ms=rec["predicted_ms"],
                      measured_ms=rec["measured_ms"],
                      attained=rec["attained"], floor=floor)
    return {"programs": programs, "peaks": peaks,
            "mfu_floor": floor or None}


def reset():
    with _lock:
        _costs.clear()
        _comm.clear()
        _measured.clear()
        _measured_total.clear()
        _peaks_override.clear()
        _drifted.clear()
