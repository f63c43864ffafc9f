"""Persistent compilation cache + AOT executable serialization — the
cold-start killer (ROADMAP item 5a: MULTICHIP_r05 logged a 3-minute XLA
compile for ONE step; a 128-chip relaunch or re-elected elastic worker
must not pay trace+compile again — and every call to the chip starts a
new machine, so without a cache on disk it compiles everything).

Two layers over ONE directory, `cache_dir()` — the environment's
``JAX_COMPILATION_CACHE_DIR`` where set (jax reads it itself and this
module sets NOTHING in code), else one fixed path inside the checkout
(``<repo>/.jax_cache``, computed from this package's location — the
path is part of the cache key's value: a directory that moves never
hits):

  1. **XLA persistent cache** — on by default, with jax's own
     thresholds.  Every `jax.jit` in the process (trainers, generate(),
     the serving batcher's scan programs) transparently reuses compiled
     modules across processes.  Hit/miss counts are scraped from jax's
     monitoring events into `compile_report()`.
  2. **AOT executable store** — armed by a non-empty
     ``FLAGS_compile_cache_dir`` (the flag only arms it; it names no
     directory): trainers additionally `.lower()` their step once,
     fingerprint the StableHLO, and serialize the compiled executable
     to ``<cache_dir()>/aot/``; a relaunched worker deserializes and
     SKIPS the XLA compile.  NOTE the hit path still pays tracing +
     lowering (the fingerprint requires the StableHLO) — seconds for a
     big model, vs the minutes-scale compile it skips; per-program
     trace_ms in `compile_report()` shows exactly what remains.
     `jax.experimental.serialize_executable` preserves donation and
     shardings.

`compile_report()` is the telemetry face: one record per AOT program
(trace/compile/load ms, hit/miss, key) plus the process-wide XLA cache
counters — cold start becomes a first-class metric.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
import warnings
from typing import Any, Dict, List, Optional

from ..framework.flags import get_flag
# import the functions, not the module: the package __init__ re-exports
# a `registry()` accessor that shadows the submodule attribute
from .registry import counter as _counter, emit as _emit

__all__ = ["cache_dir", "maybe_enable_persistent_cache", "aot_compile",
           "aot_for", "compile_report", "clear_report"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
# <repo>/.jax_cache — fixed, derived from where this package lives
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_records: List[dict] = []
_xla_counts = {"hits": 0, "misses": 0}
_enabled = False


def cache_dir() -> str:
    """The directory in force for both layers: the environment's
    JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout default."""
    return os.environ.get(_ENV) or DEFAULT_DIR


def _aot_dir() -> Optional[str]:
    """The AOT store's directory, or None while FLAGS_compile_cache_dir
    is unset.  THE fast-path guard of the trainers: unset it is one
    dict lookup."""
    if not get_flag("compile_cache_dir"):
        return None
    return os.path.join(cache_dir(), "aot")


def _on_jax_event(event: str):
    if event == "/jax/compilation_cache/cache_hits":
        _xla_counts["hits"] += 1
        _counter("compile.xla_cache_hits").inc()
    elif event == "/jax/compilation_cache/cache_misses":
        _xla_counts["misses"] += 1
        _counter("compile.xla_cache_misses").inc()


def maybe_enable_persistent_cache() -> str:
    """Once per process (the telemetry package calls it at import):
    count jax's persistent-cache hits and misses, and — unless
    JAX_COMPILATION_CACHE_DIR already configured jax — point its cache
    at the fixed default directory.  Returns `cache_dir()`."""
    global _enabled
    with _lock:
        if not _enabled:
            import jax
            from jax._src import monitoring
            monitoring.register_event_listener(_on_jax_event)
            if not os.environ.get(_ENV):
                jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
            _enabled = True
    return cache_dir()


# ---------------------------------------------------------------------------
# AOT executable store

def _fingerprint(lowered, label: str) -> str:
    """Content key: the lowered StableHLO + versions + backend.  Any
    change to the program (shapes, flags-driven fusions, shardings,
    jax/jaxlib upgrade) changes the key — a stale executable can never
    be loaded for a different program."""
    import jax
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "?")
    except Exception:
        jl = "?"
    text = str(lowered.compiler_ir(dialect="stablehlo"))
    h = hashlib.sha256()
    h.update(text.encode())
    h.update(f"|{jax.__version__}|{jl}|{jax.default_backend()}|"
             f"{label}".encode())
    return h.hexdigest()[:24]


def _aot_path(d: str, label: str, key: str) -> str:
    safe = "".join(c if c.isalnum() or c in "-_." else "_"
                   for c in label)
    return os.path.join(d, f"{safe}-{key}.pdexec")


def _record(rec: dict):
    with _lock:
        _records.append(rec)
    # errors get their own counter — folding them into misses would make
    # dump()'s counters disagree with compile_report()'s hit/miss split
    _counter({"hit": "compile.aot_hits",
              "miss": "compile.aot_misses"}.get(rec.get("cache"),
                                                "compile.aot_errors")).inc()
    _emit("compile.program", rec)


def _execution_devices(args, mesh):
    """The devices a stored executable runs on, in assignment order:
    the mesh's, else the ONE device its arguments live on (None when
    that cannot be told).  Left unsaid, deserialize_and_load assumes
    every device of the backend — wrong for a one-device program on a
    host with several."""
    if mesh is not None:
        return list(mesh.devices.flat)
    import jax
    devs = {d for leaf in jax.tree.leaves(args)
            if isinstance(leaf, jax.Array)
            for d in leaf.sharding.device_set}
    return list(devs) if len(devs) == 1 else None


def aot_compile(jitfn, args: tuple, label: str, mesh=None):
    """Lower `jitfn` for `args`, then load-or-compile the executable
    through the AOT store.  Returns the compiled callable, or None when
    the flag is unset or anything in the AOT path fails (callers fall
    back to the plain jitted function — the cache must never be able to
    break a step).  Every outcome lands in `compile_report()`."""
    d = _aot_dir()
    if d is None:
        return None
    try:
        t0 = time.perf_counter()
        lowered = jitfn.lower(*args)
        trace_ms = (time.perf_counter() - t0) * 1e3
        key = _fingerprint(lowered, label)
        path = _aot_path(d, label, key)
        if os.path.exists(path):
            from jax.experimental import serialize_executable as se
            t0 = time.perf_counter()
            with open(path, "rb") as f:
                blob, in_tree, out_tree = pickle.load(f)
            compiled = se.deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=_execution_devices(args, mesh))
            load_ms = (time.perf_counter() - t0) * 1e3
            _record({"label": label, "key": key, "cache": "hit",
                     "trace_ms": round(trace_ms, 2),
                     "compile_ms": 0.0,
                     "load_ms": round(load_ms, 2), "path": path})
            return compiled
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_ms = (time.perf_counter() - t0) * 1e3
        # the executable is in hand — its HBM accounting is free here
        # (the memory ledger's lazy providers exist for the plain-jit
        # path, which never surfaces a Compiled)
        try:
            from . import memledger
            memledger.capture(label, compiled)
        except Exception:
            pass
        try:
            from jax.experimental import serialize_executable as se
            payload = pickle.dumps(se.serialize(compiled), protocol=4)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:          # atomic publish: a
                f.write(payload)                # concurrent reader never
            os.replace(tmp, path)               # sees a torn executable
        except Exception as e:                  # noqa: BLE001
            warnings.warn(f"compile cache: could not serialize "
                          f"{label!r} ({type(e).__name__}: {e}); "
                          "executable used un-persisted", RuntimeWarning)
        _record({"label": label, "key": key, "cache": "miss",
                 "trace_ms": round(trace_ms, 2),
                 "compile_ms": round(compile_ms, 2), "path": path})
        return compiled
    except Exception as e:                      # noqa: BLE001
        warnings.warn(f"compile cache: AOT path failed for {label!r} "
                      f"({type(e).__name__}: {e}); falling back to "
                      "plain jit", RuntimeWarning)
        _record({"label": label, "cache": "error",
                 "error": f"{type(e).__name__}: {e}"})
        return None


def aot_for(store: Dict[Any, Any], kind: str, jitfn, args: tuple,
            batch_vals, label: str, mesh=None):
    """The trainers' shared AOT swap-in: unset flag → ONE dict lookup
    and the retracing jit runs untouched; armed → the step is lowered
    once per (kind, batch-aval signature), the compiled executable is
    served from (or published to) the store, and `store` memoizes it —
    a batch shape change simply compiles a second entry.  `mesh` wraps
    the lowering so shardings resolve exactly as the jit path's
    would."""
    if _aot_dir() is None:
        return jitfn
    sig = (kind,) + tuple((tuple(b.shape), str(b.dtype))
                          for b in batch_vals)
    fn = store.get(sig)
    if fn is None:
        if mesh is not None:
            with mesh:
                fn = aot_compile(jitfn, args, label, mesh) or jitfn
        else:
            fn = aot_compile(jitfn, args, label) or jitfn
        store[sig] = fn
    return fn


def compile_report() -> dict:
    """Per-program AOT records + process-wide XLA-cache counters —
    trace/compile ms and hit/miss per program, so cold-start cost is a
    number, not a log line."""
    with _lock:
        programs = list(_records)
    hits = sum(1 for r in programs if r.get("cache") == "hit")
    misses = sum(1 for r in programs if r.get("cache") == "miss")
    return {
        "dir": cache_dir(),
        "programs": programs,
        "aot_hits": hits,
        "aot_misses": misses,
        "hit_rate": round(hits / (hits + misses), 3)
        if (hits + misses) else None,
        "xla_cache": dict(_xla_counts),
        "trace_ms_total": round(sum(r.get("trace_ms", 0.0)
                                    for r in programs), 2),
        "compile_ms_total": round(sum(r.get("compile_ms", 0.0)
                                      for r in programs), 2),
    }


def clear_report():
    with _lock:
        _records.clear()
    _xla_counts["hits"] = 0
    _xla_counts["misses"] = 0
