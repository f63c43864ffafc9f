"""Jaxpr lints — dtype/transfer/donation analyses + recompile_guard.

Reference: the reference framework's AMP debugging tooling
(`paddle/fluid/eager/amp_auto_cast.h` promotion tables + the
`FLAGS_low_precision_op_list` audit) and the memory-copy profiler
(`memcpy_h2d/d2h` op counters).  Here the traced program IS the ground
truth: every lint walks the jaxpr (recursing into scan/while/pjit
sub-jaxprs in program order), so what is linted is exactly what XLA
will compile.

  lint_dtype_promotion   silent fp32 upcasts on bf16/f16 inputs and
                         64-bit creep (x64 avals appearing from 32-bit
                         inputs) — the two ways AMP regions silently
                         lose their precision contract.
  lint_transfers         device_put eqns inside a jitted step — each is
                         a host<->device (or cross-memory-kind) copy
                         the step pays every call.  Intentional
                         streaming (offload pipeline) passes an allow
                         predicate.
  lint_donation          declared-donated buffers the lowered module
                         did not alias to any output (the executable
                         will silently keep both copies live).
  recompile_guard        context manager bounding the number of XLA
                         compilations in a region; on violation reports
                         each offending compile WITH its argument avals
                         (via jax's compile log, which carries them).
"""
from __future__ import annotations

import logging
import re
from typing import Callable, List, Optional, Sequence

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

from .base import Finding, RecompileError

__all__ = ["iter_eqns", "lint_dtype_promotion", "lint_transfers",
           "lint_donation", "lint_materialized_logits",
           "lint_grad_comm_dtype", "lint_peak_hbm",
           "lint_compiled_step", "recompile_guard",
           "note_program_build"]


# ---------------------------------------------------------------------------
# jaxpr walking

def _sub_jaxprs(params):
    for val in params.values():
        if isinstance(val, (Jaxpr, ClosedJaxpr)):
            yield val
        elif isinstance(val, (tuple, list)):
            for v in val:
                if isinstance(v, (Jaxpr, ClosedJaxpr)):
                    yield v


def iter_eqns(jaxpr, _seen=None):
    """Yield every eqn of `jaxpr` (Jaxpr or ClosedJaxpr) depth-first in
    program order, recursing into scan/while/cond/pjit sub-jaxprs.

    Each distinct sub-jaxpr OBJECT is visited once: jax caches the
    traced jaxpr of a jitted layer, so N calls to one layer produce N
    pjit eqns all referencing the SAME ClosedJaxpr — without the dedupe
    a scanned/stacked layer reports every dtype-promotion finding once
    per reference, flooding the output with copies of one defect (and
    the collective-order checker would count one program's collectives
    N times; the per-iteration order is what rendezvous matching
    depends on, same as the one-scan-iteration convention)."""
    if _seen is None:
        _seen = set()
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    if id(jaxpr) in _seen:
        return
    _seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, _seen)


def as_jaxpr(fn_or_jaxpr, *args, **kw):
    """Accept a ClosedJaxpr as-is, or trace a callable over `args`."""
    if isinstance(fn_or_jaxpr, (ClosedJaxpr, Jaxpr)):
        return fn_or_jaxpr
    return jax.make_jaxpr(fn_or_jaxpr)(*args, **kw)


def _avals(atoms):
    out = []
    for a in atoms:
        aval = getattr(a, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            out.append(aval)
    return out


# ---------------------------------------------------------------------------
# dtype promotion lint

_LOW = ("bfloat16", "float16")
_X64 = ("float64", "int64", "uint64", "complex128")


def lint_dtype_promotion(fn_or_jaxpr, *args,
                         check_upcast: bool = True,
                         check_x64: bool = True,
                         ignore_prims: Sequence[str] = ()) -> List[Finding]:
    """Findings for silent precision changes inside a traced program.

      fp32-upcast  an eqn consumes a bf16/f16 array and produces f32 —
                   inside an AMP/bf16 region that is a silent promotion
                   (deliberate loss-scale casts can be skipped via
                   ignore_prims=("convert_element_type",)).
      x64-creep    an eqn produces a 64-bit array from non-64-bit
                   inputs, or the program takes 64-bit inputs — on TPU
                   this de-optimizes every downstream op.
    """
    jaxpr = as_jaxpr(fn_or_jaxpr, *args)
    findings: List[Finding] = []
    closed = jaxpr if isinstance(jaxpr, ClosedJaxpr) else None
    if check_x64 and closed is not None:
        for v in closed.jaxpr.invars:
            aval = getattr(v, "aval", None)
            if aval is not None and str(getattr(aval, "dtype", "")) in _X64:
                findings.append(Finding(
                    "x64-input",
                    f"program input has 64-bit aval {aval} — x64 creep "
                    f"starts at the feed",
                    detail=str(aval)))
    ignore = set(ignore_prims)
    for i, eqn in enumerate(iter_eqns(jaxpr)):
        if eqn.primitive.name in ignore:
            continue
        in_avals = _avals(eqn.invars)
        out_avals = _avals(eqn.outvars)
        in_dts = [str(a.dtype) for a in in_avals]
        out_dts = [str(a.dtype) for a in out_avals]
        if check_upcast and any(d in _LOW for d in in_dts) \
                and any(d == "float32" for d in out_dts):
            findings.append(Finding(
                "fp32-upcast",
                f"eqn '{eqn.primitive.name}' promotes "
                f"{[str(a) for a in in_avals]} -> "
                f"{[str(a) for a in out_avals]}: silent fp32 upcast "
                f"inside a low-precision region",
                op_index=i,
                detail=(eqn.primitive.name, in_dts, out_dts)))
        if check_x64 and any(d in _X64 for d in out_dts) \
                and not any(d in _X64 for d in in_dts):
            findings.append(Finding(
                "x64-creep",
                f"eqn '{eqn.primitive.name}' introduces 64-bit avals "
                f"{[str(a) for a in out_avals]} from 32-bit inputs",
                op_index=i,
                detail=(eqn.primitive.name, in_dts, out_dts)))
    return findings


# ---------------------------------------------------------------------------
# transfer lint

def _transfer_dst(eqn):
    """Summarize a device_put eqn's destination (memory kind when
    annotated, else the device/sharding repr)."""
    dsts = eqn.params.get("devices") or eqn.params.get("device") or []
    if not isinstance(dsts, (tuple, list)):
        dsts = [dsts]
    out = []
    for d in dsts:
        mk = getattr(d, "memory_kind", None)
        out.append(str(mk) if mk is not None else repr(d))
    return ", ".join(out) or "<unspecified>"


def lint_transfers(fn_or_jaxpr, *args,
                   allow: Optional[Callable] = None) -> List[Finding]:
    """Findings for every `device_put` eqn inside the traced program —
    each is a host<->device or cross-memory-space copy paid on every
    call of the jitted step.  `allow(eqn) -> bool` whitelists expected
    transfers (e.g. the offload pipeline's parameter streaming)."""
    jaxpr = as_jaxpr(fn_or_jaxpr, *args)
    findings: List[Finding] = []
    for i, eqn in enumerate(iter_eqns(jaxpr)):
        if eqn.primitive.name != "device_put":
            continue
        if allow is not None and allow(eqn):
            continue
        shapes = [str(a) for a in _avals(eqn.invars)]
        findings.append(Finding(
            "in-step-transfer",
            f"device_put of {shapes} to [{_transfer_dst(eqn)}] inside "
            f"the jitted program — a copy on every step",
            op_index=i,
            detail=(shapes, _transfer_dst(eqn))))
    return findings


# ---------------------------------------------------------------------------
# donation lint

_MLIR_DT = {
    "float32": "f32", "float16": "f16", "bfloat16": "bf16",
    "float64": "f64", "int32": "i32", "int64": "i64", "int16": "i16",
    "int8": "i8", "uint8": "ui8", "uint32": "ui32", "uint64": "ui64",
    "bool": "i1",
}


def _mlir_type(aval) -> str:
    dt = _MLIR_DT.get(str(aval.dtype), str(aval.dtype))
    dims = "x".join(str(d) for d in aval.shape)
    return f"tensor<{dims}{'x' if dims else ''}{dt}>"


_ARG_SPLIT = re.compile(r"(?=%arg\d+:)")
_TENSOR_PAT = re.compile(r"tensor<[^>]*>")


def lint_donation(lowered_or_fn, *args,
                  donate_argnums: Sequence[int] = ()) -> List[Finding]:
    """Findings for declared-donated buffers the lowered module did not
    alias to any output (`tf.aliasing_output`) — the executable keeps
    both copies live, silently doubling that buffer's footprint.

    Accepts a `jax.stages.Lowered` (donation read off its
    `donate_argnums`) or a callable + args + donate_argnums.
    """
    if hasattr(lowered_or_fn, "as_text") \
            and hasattr(lowered_or_fn, "donate_argnums"):
        lowered = lowered_or_fn
    else:
        lowered = jax.jit(lowered_or_fn,
                          donate_argnums=tuple(donate_argnums)) \
            .lower(*args)
    # Lowered.donate_argnums indexes the FLATTENED argument leaves
    # (pytree args expand), matching tree_leaves(in_avals) order
    flat_avals = jax.tree_util.tree_leaves(lowered.in_avals)
    donated = [(i, flat_avals[i]) for i in lowered.donate_argnums
               if i < len(flat_avals)]
    if not donated:
        return []
    text = lowered.as_text()
    main = text[text.index("@main"):] if "@main" in text else text
    sig = main[:main.index("{\n")] if "{\n" in main else main
    # chunk per %argN: the chunk carries that arg's full attribute dict
    # (attr values may nest braces — "{replicated}" — so a flat regex
    # over the dict would truncate)
    chunks = [c for c in _ARG_SPLIT.split(sig) if c.startswith("%arg")]

    def _is_aliased(chunk):
        return ("tf.aliasing_output" in chunk
                or "jax.buffer_donor" in chunk)

    findings: List[Finding] = []
    # exact path: kept_var_idx maps flat arg indices to MLIR arg
    # positions (unused args are dropped from @main), so each donated
    # leaf is checked against ITS OWN chunk — two donated args sharing
    # an aval cannot be confused
    kept = None
    try:
        kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
    except Exception:
        pass
    if kept is not None and len(kept) == len(chunks):
        pos = {flat_i: j for j, flat_i in enumerate(kept)}
        for argnum, aval in donated:
            j = pos.get(argnum)
            if j is not None and _is_aliased(chunks[j]):
                continue
            dropped = " (dropped: unused by the computation)" \
                if j is None else ""
            findings.append(Finding(
                "donation-unaliased",
                f"donated buffer {aval} (argnum {argnum}) was not "
                f"aliased to any output by the lowered "
                f"module{dropped} — donation is a no-op for it and "
                f"both copies stay live",
                detail=(argnum, str(aval))))
        return findings
    # fallback (no kept_var_idx): multiset-match by tensor type — may
    # attribute a finding to the wrong argnum among same-aval args
    pool = [_TENSOR_PAT.search(c).group(0) for c in chunks
            if _is_aliased(c) and _TENSOR_PAT.search(c)]
    for argnum, aval in donated:
        ty = _mlir_type(aval)
        if ty in pool:
            pool.remove(ty)
        else:
            findings.append(Finding(
                "donation-unaliased",
                f"donated buffer {aval} (argnum {argnum}) was not "
                f"aliased to any output by the lowered module — "
                f"donation is a no-op for it and both copies stay "
                f"live",
                detail=(argnum, str(aval))))
    return findings


# ---------------------------------------------------------------------------
# materialized-logits lint

def lint_materialized_logits(fn_or_jaxpr, *args, vocab_size: int,
                             min_rows: Optional[int] = None
                             ) -> List[Finding]:
    """Findings for every fp32 intermediate shaped [..., vocab_size]
    inside the traced program — the full-logits buffer the fused
    chunked cross-entropy exists to eliminate (at the llama bench shape
    the [B, S, V] fp32 logits are 256 MB, the largest live allocation
    in the step; PROFILE_r05's logits/CE gap item).

    Rule: an eqn OUTPUT with dtype float32, last dim == vocab_size and
    ndim >= 3 (a batched [B, S, V] buffer).  The fused path's per-chunk
    [chunk, V] slices are 2-D and stay below the radar; so do the [H, V]
    lm-head weight gradients.  `min_rows` additionally flags 2-D
    [rows, V] buffers whose leading product reaches it (catches a
    flattened [B*S, V] materialization when the caller knows the token
    count).  Recurses into scan/while/pjit sub-jaxprs like every other
    jaxpr lint.
    """
    jaxpr = as_jaxpr(fn_or_jaxpr, *args)
    findings: List[Finding] = []
    for i, eqn in enumerate(iter_eqns(jaxpr)):
        for aval in _avals(eqn.outvars):
            shape = tuple(getattr(aval, "shape", ()))
            if len(shape) < 2 or shape[-1] != vocab_size \
                    or str(aval.dtype) != "float32":
                continue
            rows = 1
            for d in shape[:-1]:
                rows *= int(d)
            if len(shape) >= 3 or (min_rows is not None
                                   and rows >= min_rows):
                findings.append(Finding(
                    "materialized-logits",
                    f"eqn '{eqn.primitive.name}' materializes a "
                    f"[{', '.join(str(d) for d in shape)}] fp32 buffer "
                    f"with vocab-sized last dim ({vocab_size}) — "
                    f"{rows * vocab_size * 4 / 1e6:.1f} MB of full "
                    f"logits the fused cross-entropy path avoids",
                    op_index=i,
                    detail=(eqn.primitive.name, shape)))
    return findings


# ---------------------------------------------------------------------------
# grad-comm wire-width lint (ISSUE 16 satellite: the bf16-upcast audit)

def lint_grad_comm_dtype(fn_or_jaxpr, *args, plan) -> List[Finding]:
    """Jaxpr proof that the comm-overlap plan's fused grad-bucket
    collectives run at the requested wire width (FLAGS_grad_comm_dtype).

    Each bucket materializes as a 1-D `sharding_constraint` eqn of
    exactly `padded_numel` elements — the reduction point the SPMD
    partitioner lowers to the bucket's all-reduce/reduce-scatter.  A
    bucket whose constraint carries a WIDER dtype than the plan
    requested (e.g. bf16 grads silently upcast to fp32 before the
    reduce) doubles comm bytes — the regression Paddle's
    fused_allreduce passes guard with their dtype-grouped fusion.

    Stage >= 3 plans emit no fused constraint (layout-neutral by
    design — see CommOverlapPlan.reduce_grads); there the fused buffer
    is proven through the `optimization_barrier` chain instead, whose
    invars carry the flat buffer at the wire dtype.  A single-bucket
    stage-3 plan has neither eqn (no chain, no constraint) and nothing
    to prove — it is skipped, not flagged.

    Findings: a bucket with no matching constraint eqn (the fused
    reduce never materialized), or one whose every matching eqn runs
    wider than requested."""
    jaxpr = as_jaxpr(fn_or_jaxpr, *args)
    findings: List[Finding] = []
    seen: dict = {b.idx: [] for b in plan.buckets}
    by_len: dict = {}
    for b in plan.buckets:
        by_len.setdefault(int(b.padded_numel), []).append(b)
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in ("sharding_constraint",
                                      "optimization_barrier"):
            continue
        for aval in _avals(eqn.invars):
            shape = tuple(getattr(aval, "shape", ()))
            if len(shape) != 1:
                continue
            for b in by_len.get(int(shape[0]), ()):
                seen[b.idx].append(str(aval.dtype))
    for b in plan.buckets:
        want_size = _itemsize_of(b.comm_dtype)
        got = seen[b.idx]
        if not got:
            if plan.stage >= 3 and len(plan.buckets) == 1:
                continue
            findings.append(Finding(
                "grad-comm-bucket-missing",
                f"{b.describe()}: no 1-D sharding_constraint of "
                f"{b.padded_numel} elements in the traced step — the "
                f"fused reduce for this bucket never materialized",
                detail=(b.idx, b.padded_numel)))
            continue
        if b.comm_dtype in got:
            continue
        wider = [d for d in got
                 if _itemsize_of(d) > want_size]
        findings.append(Finding(
            "grad-comm-dtype-upcast" if wider else
            "grad-comm-dtype-mismatch",
            f"{b.describe()}: requested wire dtype {b.comm_dtype} but "
            f"the fused reduce materializes as {sorted(set(got))}"
            + (" — a silent upcast that multiplies comm bytes"
               if wider else ""),
            detail=(b.idx, b.comm_dtype, tuple(sorted(set(got))))))
    return findings


def _itemsize_of(dtype_name: str) -> int:
    import numpy as _np
    try:
        return int(_np.dtype(dtype_name).itemsize)
    except TypeError:
        return {"bfloat16": 2, "float8_e4m3fn": 1,
                "float8_e5m2": 1}.get(dtype_name, 4)


# ---------------------------------------------------------------------------
# peak-HBM budget lint

def lint_peak_hbm(compiled=None, *, budget_bytes: Optional[int] = None,
                  label: str = "<program>") -> List[Finding]:
    """Findings for programs whose XLA-reported peak HBM (arguments +
    outputs + temps − aliased, from `compiled.memory_analysis()`)
    exceeds `budget_bytes` — the measured replacement for hand-derived
    peak-memory claims (SCALE_r05/PROFILE_r05).

    Two modes:
      * `compiled` given (a jax Compiled, or a Lowered — compiled
        here): lint that one executable;
      * `compiled=None`: lint every program in the telemetry memory
        ledger (`telemetry.memledger`), resolving pending providers —
        the whole-process audit `tools/fleet_report.py` renders.

    `budget_bytes=None` reads the device's own reported capacity
    (TPU memory_stats bytes_limit); with neither available the lint
    has no budget to enforce and returns [].
    """
    from ..telemetry import memledger
    if budget_bytes is None:
        budget_bytes = memledger.device_hbm_bytes()
    if not budget_bytes:
        return []
    budget_bytes = int(budget_bytes)

    def judge(lbl, peak, detail) -> Optional[Finding]:
        if peak <= budget_bytes:
            return None
        return Finding(
            "peak-hbm-over-budget",
            f"program {lbl!r} peaks at {peak / 1e9:.3f} GB — over the "
            f"{budget_bytes / 1e9:.3f} GB budget by "
            f"{(peak - budget_bytes) / 1e9:.3f} GB",
            detail=detail)

    findings: List[Finding] = []
    if compiled is not None:
        if not hasattr(compiled, "memory_analysis") \
                and hasattr(compiled, "compile"):
            compiled = compiled.compile()       # accept a Lowered
        stats = memledger._stats_from(compiled)
        f = judge(label, stats["peak_bytes"], (label, stats))
        return [f] if f else []
    rep = memledger.memory_report(resolve=True, top_buffers=0)
    for lbl, rec in rep["programs"].items():
        if rec.get("status") != "ok":
            continue
        f = judge(lbl, rec["peak_bytes"], (lbl, rec))
        if f:
            findings.append(f)
    return findings


# ---------------------------------------------------------------------------
# MFU-floor lint (ISSUE 12: the cost ledger's drift check as a named
# finding, the compute twin of lint_peak_hbm)

def lint_mfu_floor(report: Optional[dict] = None, *,
                   floor: Optional[float] = None,
                   resolve: bool = True) -> List[Finding]:
    """Findings for programs whose measured step time falls below the
    calibrated roofline prediction by more than the floor allows:
    ``attained`` = predicted_ms / measured_ms < floor — the program is
    running slower than the cost model says it should (a perf drift:
    co-tenant interference, a silently disabled fusion, a degraded
    input pipeline).

    `report` defaults to `telemetry.cost_report()` (resolving pending
    ledger providers when `resolve`); `floor` defaults to
    FLAGS_mfu_floor (0 disables — returns []).  Programs without
    measured walls (no sink ever flowed step/chunk events) are
    skipped, never guessed at.
    """
    from ..framework.flags import get_flag
    if floor is None:
        floor = float(get_flag("mfu_floor", 0.0) or 0.0)
    if not floor:
        return []
    if report is None:
        from ..telemetry import costledger
        report = costledger.cost_report(resolve=resolve)
    findings: List[Finding] = []
    for lbl, rec in report.get("programs", {}).items():
        if rec.get("status") != "ok":
            continue
        attained = rec.get("attained")
        if attained is None or attained >= floor:
            continue
        findings.append(Finding(
            "mfu-floor",
            f"program {lbl!r} attains {attained:.3f} of its calibrated "
            f"roofline prediction (measured {rec['measured_ms']:.3f} ms "
            f"vs predicted {rec['predicted_ms']:.3f} ms, "
            f"{rec.get('bound', '?')}-bound) — below the "
            f"mfu_floor={floor} floor",
            detail=(lbl, rec)))
    return findings


# ---------------------------------------------------------------------------
# combined dispatch for compiled train steps

def lint_compiled_step(compiled, args, *, mesh=None, dtype=False,
                       transfers=False, donation=False,
                       logits_vocab: Optional[int] = None,
                       logits_min_rows: Optional[int] = None):
    """Shared body of ShardedTrainStep.lint / OffloadPipelineStep.lint:
    trace the jitted `compiled` ONCE for the jaxpr-walking lints, lower
    separately for the donation check, all under the mesh context.
    Returns {category: [Finding, ...]} for the enabled categories.

    logits_vocab: enable lint_materialized_logits with this vocab size
    (the fused-CE no-full-logits contract); logits_min_rows additionally
    flags flattened 2-D [rows>=min_rows, V] fp32 buffers (the [B*S, V]
    evasion — callers that know the step's token count pass it)."""
    import contextlib
    out = {}
    with (mesh if mesh is not None else contextlib.nullcontext()):
        if dtype or transfers or logits_vocab is not None:
            jaxpr = jax.make_jaxpr(compiled)(*args)
            if dtype:
                out["dtype"] = lint_dtype_promotion(jaxpr)
            if transfers:
                out["transfers"] = lint_transfers(jaxpr)
            if logits_vocab is not None:
                out["logits"] = lint_materialized_logits(
                    jaxpr, vocab_size=int(logits_vocab),
                    min_rows=logits_min_rows)
        if donation:
            out["donation"] = lint_donation(compiled.lower(*args))
    return out


# ---------------------------------------------------------------------------
# recompile_guard

# model-level program-cache builds (inference.generation
# _model_program_cache) are announced here so a guard can also bound
# cache growth, not just raw XLA compiles
_BUILD_LISTENERS: List[Callable] = []


def note_program_build(key):
    """Called by program caches on a build miss (cold compile ahead)."""
    for cb in list(_BUILD_LISTENERS):
        cb(key)


def lint_serve_programs(batcher) -> List[Finding]:
    """Donation lint over BOTH of a ContinuousBatcher's step programs
    (decode — speculative draft/verify when armed — and admission):
    every carry buffer, including the paged KV pool, the page tables
    and the speculation draft cache, must alias an output in the
    lowered module.  The one call sites run after ISSUE 11 grew the
    carry set — a forgotten donate_argnum on a new carry silently
    doubles the dominant HBM buffer.  Uses the batcher's side-effect-
    free `lower_step` probe (no program/timing bookkeeping)."""
    findings: List[Finding] = []
    for mixed in (False, True):
        findings.extend(lint_donation(batcher.lower_step(mixed=mixed)))
    return findings


_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")
# the installed jax logs "Compiling jit(<name>) with global shapes ..."
_COMPILE_PAT = re.compile(r"Compiling jit\((.+?)\) with ")


class _CompileLogHandler(logging.Handler):
    def __init__(self, sink):
        super().__init__(level=logging.DEBUG)
        self._sink = sink

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self._sink(msg)


class recompile_guard:
    """Bound the number of XLA compilations inside a `with` block.

        with recompile_guard(max_programs=2, match="serve_step") as g:
            batcher.run()
        assert g.count <= 2

    Replaces hand-rolled "exactly N compiled programs" counting: on
    exit, if more than `max_programs` compilations matched, raises
    RecompileError listing each offending compile — jax's compile log
    line carries the jitted function's name AND the argument avals, so
    the report names the shapes that caused the recompile.

    match    substring the compiled function's name must contain
             (None = count every compile, including jax-internal
             helper jits like convert_element_type)
    The guard also records model-level program-cache builds
    (`note_program_build`) in `.cache_builds` — the serving batcher and
    generate() announce their cache misses there.
    """

    def __init__(self, max_programs: int, match: Optional[str] = None,
                 label: str = ""):
        self.max_programs = int(max_programs)
        self.match = match
        self.label = label
        self.compiles: List[str] = []
        self.cache_builds: List = []

    # -- sinks -------------------------------------------------------------
    def _on_compile(self, msg):
        name_m = _COMPILE_PAT.match(msg)
        name = name_m.group(1) if name_m else "<unknown>"
        if self.match is None or self.match in name:
            self.compiles.append(msg)

    def _on_build(self, key):
        self.cache_builds.append(key)

    @property
    def count(self) -> int:
        return len(self.compiles)

    # -- context -----------------------------------------------------------
    def __enter__(self):
        self._handler = _CompileLogHandler(self._on_compile)
        self._prev_log = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._loggers = []
        for name in _COMPILE_LOGGERS:
            lg = logging.getLogger(name)
            self._loggers.append((lg, lg.level, lg.propagate))
            if lg.level > logging.WARNING:
                lg.setLevel(logging.WARNING)
            # the records exist only because the guard turned the
            # compile log on — keep them out of the user's terminal
            lg.propagate = False
            lg.addHandler(self._handler)
        _BUILD_LISTENERS.append(self._on_build)
        return self

    def __exit__(self, exc_type, exc, tb):
        jax.config.update("jax_log_compiles", self._prev_log)
        for lg, lvl, prop in self._loggers:
            lg.removeHandler(self._handler)
            lg.setLevel(lvl)
            lg.propagate = prop
        _BUILD_LISTENERS.remove(self._on_build)
        if exc_type is None and self.count > self.max_programs:
            raise RecompileError(self.compiles, self.max_programs,
                                 label=self.label or (self.match or ""))
        return False
