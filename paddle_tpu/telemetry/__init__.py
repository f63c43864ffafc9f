"""paddle_tpu.telemetry — the fleet metrics/trace plane (ROADMAP item
5c) plus the persistent compile/AOT cache (item 5a).

One in-process plane that every producer publishes into and every
exporter reads from:

  producers                         events
  ---------                         ------
  jit.TrainStep / ShardedTrainStep  train.step (wall_ms, step_ms, k,
                                    tokens_per_sec)
  OffloadPipelineStep               train.step (trainer=offload)
  PipelineEngine.train_batch        pp.train_batch (schedule, micro)
  collective_schedule()             collective.schedule (kind counts)
  ContinuousBatcher                 serve.chunk (wall_ms, tokens and the
                                    six <phase>_ms of its step()) /
                                    serve.request (latencies and the
                                    chunks it was admitted, first
                                    answered and done in) /
                                    serve.recompile / serve.kv /
                                    serve.spec, and the robustness set
                                    (ISSUE 9): serve.shed /
                                    serve.deadline_miss /
                                    serve.requeue / serve.chunk_fault /
                                    serve.hung / serve.drain
  ServeRouter                       router.route / router.handoff and
                                    the fleet's router.* events
  io.prefetch_to_device             io.step (host_wait_ms)
  distributed.watchdog              watchdog.timeout
  distributed.fault                 fault.hit
  distributed.checkpoint            ckpt.commit / ckpt.gc
  compile cache (this package)      compile.program (hit/miss, ms)
  cost / memory ledgers             cost.program / cost.measure /
                                    perf.drift

  spans (`telemetry.span`: a profiler annotation always, and with a
  sink a record with t0, dur_ms, span, parent, parent_span, the ids)
  -----
  ShardedTrainStep.__call__ /       train.step (step, k) > train.prepare,
  run_steps                         train.dispatch, train.writeback; the
                                    train.step event's fields ride the
                                    span's record (one record a step)
  the jitted train step             jax.named_scope: train.grad_reduce,
                                    train.optimizer, train.guard (beside
                                    the models' llama.layer{i}/attn/mlp)
  ContinuousBatcher.step            serve.step (chunk) > serve.evict,
                                    serve.admit (admitted),
                                    serve.dispatch (kind, chunk),
                                    serve.device_wait (kind, chunk),
                                    serve.harvest (chunk) >
                                    serve.deliver (tokens); instants
                                    (`telemetry.mark`) serve.req.admit /
                                    serve.req.first_token /
                                    serve.req.done (req, chunk)
  profiler.RecordEvent              the caller's name (kind=record_event)

Cost contract: with no sink attached an event is one truthiness check
and a span is one inactive profiler annotation (half a microsecond, no
record, no clock read), and arming/disarming sinks or
``FLAGS_compile_cache_dir`` leaves every compiled program byte-identical
(tests/test_telemetry.py and tests/test_program_spans.py assert the
host half and that the scopes are metadata alone;
tests/test_program_contracts.py the HLO across an arming cycle).
Exporters: `attach_jsonl` (step log), `attach_chrome_trace`
(chrome://tracing / Perfetto), `dump()` (one snapshot of the whole
plane).  `tools/telemetry_report.py` renders a JSONL log
into step medians/p99, the spans' durations and self times, each serve
chunk's time by phase and the cache hit rate.  On the device's clock the
spans are read from a profiler trace: `benchmark/program_spans.py`.
"""
from __future__ import annotations

from .registry import (MetricsRegistry, Counter, Gauge, Histogram,  # noqa: F401
                       registry, counter, gauge, histogram,
                       add_sink, remove_sink, sinks, active, emit, span,
                       mark, configure, config, reset as _registry_reset,
                       set_rank, rank_info, percentile_of,
                       percentiles_of, summary_of)
from .exporters import (JsonlSink, ChromeTraceSink, MemorySink,  # noqa: F401
                        attach_jsonl, attach_chrome_trace, chrome_event)
from .compile_cache import (cache_dir, maybe_enable_persistent_cache,  # noqa: F401
                            aot_compile, compile_report, clear_report)
from . import memledger  # noqa: F401
from .memledger import memory_report  # noqa: F401
from . import costledger  # noqa: F401
from .costledger import cost_report  # noqa: F401
from . import fleet  # noqa: F401
from . import flightrec  # noqa: F401
from .flightrec import FlightRecorder  # noqa: F401
from . import numerics  # noqa: F401

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram",
           "registry", "counter", "gauge", "histogram",
           "add_sink", "remove_sink", "sinks", "active", "emit", "span",
           "mark", "configure", "config", "reset",
           "set_rank", "rank_info", "percentile_of", "percentiles_of",
           "JsonlSink", "ChromeTraceSink", "MemorySink",
           "attach_jsonl", "attach_chrome_trace", "chrome_event",
           "cache_dir", "maybe_enable_persistent_cache",
           "aot_compile", "compile_report",
           "clear_report", "memledger", "memory_report",
           "costledger", "cost_report",
           "fleet", "flightrec", "FlightRecorder", "numerics",
           "summary_of", "dump", "step_event"]


def reset():
    """Detach every sink, clear registry/config/rank AND the memory +
    compute cost ledgers and the flight recorder — the whole plane
    back to pristine (test isolation)."""
    _registry_reset()
    memledger.reset()
    costledger.reset()
    flightrec.reset()
    numerics.reset()


def dump(compact: bool = False) -> dict:
    """One snapshot of the whole plane: registry instruments, the
    compile report, the fleet identity and the (already-resolved)
    memory ledger.  `compact` trims the per-program compile records to
    totals.  Never compiles —
    pending ledger entries stay pending (memory_report() resolves)."""
    out = registry().dump()
    rep = compile_report()
    if compact:
        rep = {k: v for k, v in rep.items() if k != "programs"}
    out["compile"] = rep
    info = rank_info()
    if info is not None:
        out["rank"] = {"rank": info[0], "world": info[1]}
    mem = memledger.snapshot()
    if mem["programs"]:
        out["memory"] = mem if not compact else {
            "programs": len(mem["programs"]),
            "peak_hbm_bytes": mem["peak_hbm_bytes"],
            "device_hbm_bytes": mem["device_hbm_bytes"],
        }
    cost = costledger.snapshot()
    if cost["programs"]:
        out["cost"] = cost if not compact else {
            "programs": len(cost["programs"]),
            "drifts": sum(1 for r in cost["programs"].values()
                          if r.get("drift")),
        }
    return out


# jax's persistent compilation cache is pointed at cache_dir() at
# import — BEFORE any subsystem compiles (nothing is set in code where
# JAX_COMPILATION_CACHE_DIR already configured it).
maybe_enable_persistent_cache()

# same idiom for the incident flight recorder: FLAGS_flightrec_dir in
# the environment arms the recorder before any subsystem emits; unset,
# this is one flag lookup.
try:
    flightrec.maybe_attach()
except Exception:                       # recorder must never break import
    pass


def step_event(trainer, *, label: str, kind: str, step: int, k: int,
               wall_ms: float, batch_vals=(), extra=None, span=None):
    """Publish one `train.step` event for a trainer's compiled call —
    the ONE implementation every trainer shares (jit/sharded/offload
    pass their label; schema changes land here once).

    Callers guard with `telemetry.active()` BEFORE assembling any of
    these arguments.  `wall_ms` covers the whole (possibly K-fused)
    call; per-step values are derived here.  `batch_vals` is ONE
    step's batch (token count).  A trainer whose call is a
    `train.step` span passes it as `span`: the fields then ride that
    span's record (ONE `train.step` record a step, with `t0`, `dur_ms`
    and its children `train.prepare` / `train.dispatch` /
    `train.writeback` beside it) and no second event is emitted.
    Where the DEVICE's time went is the step program's scopes'
    (`train.grad_reduce`, `train.optimizer`, `train.guard`) to say,
    in a profiler trace.
    `kind` names the compiled program ("step"/"multi"); its first event
    per trainer is marked cold=True — that wall may include the XLA
    compile, so the report CLI excludes cold steps."""
    import numpy as _np
    per_step = wall_ms / max(k, 1)
    fields = {"trainer": label, "step": int(step), "k": int(k),
              "wall_ms": round(wall_ms, 3),
              "step_ms": round(per_step, 3)}
    seen = trainer.__dict__.setdefault("_tel_seen", set())
    if kind not in seen:
        seen.add(kind)
        fields["cold"] = True
    if batch_vals and _np.issubdtype(_np.dtype(batch_vals[0].dtype),
                                     _np.integer):
        tokens = int(_np.prod(batch_vals[0].shape)) * k
        fields["tokens"] = tokens
        if wall_ms > 0:
            fields["tokens_per_sec"] = round(tokens / (wall_ms / 1e3), 1)
    if extra:
        fields.update(extra)
    # feed the cost ledger's measured-wall window (warm calls only —
    # the first call per program may include the XLA compile).  The
    # label is the memory ledger's, recorded by note_jit, so the wall
    # lands on exactly the program whose cost_analysis() it describes;
    # the whole call sits inside the caller's active() guard, keeping
    # the no-sink path at zero.
    ml_label = trainer.__dict__.get("_memledger_labels", {}).get(kind)
    if ml_label:
        # a retrace (note_jit saw a new sig) pays its compile in THIS
        # wall — exclude it like the first use
        fresh = trainer.__dict__.get("_memledger_fresh")
        refreshed = bool(fresh) and kind in fresh
        if refreshed:
            fresh.discard(kind)
        costledger.observe(ml_label, wall_ms,
                           cold="cold" in fields or refreshed)
    histogram("train.step_ms").observe(per_step)
    if span is not None:
        span.set(**fields)
    else:
        emit("train.step", fields)
    # NOTE: the train.steps counter is incremented by the trainers
    # UNCONDITIONALLY (sink or not) so dump() snapshots lifetime totals
    # — incrementing it here too would double-count
