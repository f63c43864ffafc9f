"""Tape verifier — PIR-style structural invariants over the OpDesc tape.

Reference: `paddle/pir/core/operation.cc` `Operation::Verify` (every op
checks its signature/types after each pass) and the legacy
`framework/ir/graph_helper` sanity walks.  The recorded-tape analog of
"verifiable IR" is:

  V1 def-before-use   every `in_vid` of op[i] resolves to a placeholder,
                      a registered leaf, a live named var, or an out_vid
                      of some op[j<i].  An out_vid of op[j>i] is a
                      use-before-def (a reordering pass bug: replay
                      would KeyError or silently read a stale leaf).
  V2 single-def (SSA) no vid is written twice: by two ops (WAW), by an
                      op and its own input set (WAR self-alias), or by
                      an op over a leaf/placeholder vid (a recorded
                      in-place mutation that skipped the
                      `on_inplace_retag` protocol — replay would apply
                      the mutation on top of the live post-mutation
                      value, i.e. apply it twice).
  V3 leaf liveness    every leaf entry must carry a live weakref OR a
                      build-time snapshot; (dead, None) is a dangling
                      leaf that can only KeyError at replay.
  V4 name table       every `var_names` entry resolves to a vid the
                      program knows (placeholder / leaf / op output /
                      tracked var).
  V5 arity (full)     abstract-evaluating op.fn over the input avals
                      yields exactly len(out_vids) arrays — `replay`'s
                      zip would silently DROP extra outputs or leave
                      out_vids unbound.  Needs input avals, so it runs
                      only at level="full" (used by the conftest
                      fixture and the CLI; apply_pass/Executor.run use
                      the zero-trace "structural" level).

`verify_program` returns findings; `check_program` raises
ProgramVerifyError.  Both are cold-path: the replay hot path never
calls them unless FLAGS_check_program is set.
"""
from __future__ import annotations

from typing import List

from .base import Finding, ProgramVerifyError

__all__ = ["verify_program", "check_program", "VERIFY_CALLS"]

# invocation counter: it does NOT move on the flags-off replay hot path
# (tests/test_program_verifier.py
# `test_hot_path_runs_zero_verifications_with_flag_off`)
VERIFY_CALLS = 0


def _op_name(op, i):
    return f"'{getattr(op, 'type', '?')}'#{i}"


def verify_program(prog, level: str = "structural") -> List[Finding]:
    """Verify the OpDesc tape of `prog`.  Returns a list of findings
    (empty == verifier-clean).  level: "structural" (no tracing) or
    "full" (adds the V5 abstract-eval arity check)."""
    global VERIFY_CALLS
    VERIFY_CALLS += 1
    findings: List[Finding] = []
    ops = list(getattr(prog, "ops", ()))
    leaves = dict(getattr(prog, "leaves", {}))
    known = set(getattr(prog, "_known_vids", ()) or ())
    refs = getattr(prog, "_var_refs", None) or {}
    placeholders = getattr(prog, "placeholders", {}) or {}
    ph_vids = {getattr(ph, "_static_vid", None)
               for ph in placeholders.values()}
    ph_vids.discard(None)

    produced_by = {}            # vid -> first defining op index
    for i, op in enumerate(ops):
        for v in op.out_vids:
            produced_by.setdefault(v, i)

    # V3: dangling leaves
    for vid, entry in leaves.items():
        ref, snapshot = entry
        alive = ref is not None and ref() is not None
        if not alive and snapshot is None:
            findings.append(Finding(
                "dangling-leaf",
                f"leaf var {vid} has a dead weakref and no build-time "
                f"snapshot — replay can only KeyError on it",
                detail=vid))

    # V4: name table integrity
    for name, vid in (getattr(prog, "var_names", {}) or {}).items():
        if vid not in known and vid not in produced_by \
                and vid not in leaves and vid not in ph_vids:
            findings.append(Finding(
                "unknown-named-var",
                f"var_names[{name!r}] = {vid} resolves to no known vid "
                f"of this program (not a placeholder, leaf, tracked "
                f"var, or op output)",
                detail=(name, vid)))

    # V1 + V2 in one ordered walk
    defined = set(ph_vids) | set(leaves)
    live_named = {v for v, r in refs.items() if r() is not None}
    seen_out = {}
    for i, op in enumerate(ops):
        in_set = set(op.in_vids)
        for v in op.in_vids:
            if v in defined or v in seen_out:
                continue
            later = produced_by.get(v)
            if later is not None and later > i:
                findings.append(Finding(
                    "use-before-def",
                    f"op {_op_name(op, i)} reads var {v}, which is only "
                    f"defined later by op "
                    f"{_op_name(ops[later], later)} — a reordering "
                    f"pass broke topological order",
                    op_index=i, detail=v))
            elif v in live_named:
                # create_var()-style tracked var: replay resolves it
                # through the live object (Program.find_tensor)
                pass
            else:
                findings.append(Finding(
                    "undefined-var",
                    f"op {_op_name(op, i)} reads var {v}, which no "
                    f"placeholder, leaf, live var, or earlier op "
                    f"defines",
                    op_index=i, detail=v))
        for v in op.out_vids:
            if v in seen_out:
                j = seen_out[v]
                findings.append(Finding(
                    "ssa-double-def",
                    f"var {v} is defined twice: by op "
                    f"{_op_name(ops[j], j)} and op {_op_name(op, i)} "
                    f"(WAW hazard — the tape is not SSA)",
                    op_index=i, detail=v))
            elif v in in_set:
                findings.append(Finding(
                    "inplace-self-alias",
                    f"op {_op_name(op, i)} writes var {v} that it also "
                    f"reads (WAR hazard: an in-place op recorded "
                    f"without the on_inplace_retag rename)",
                    op_index=i, detail=v))
            elif v in leaves:
                findings.append(Finding(
                    "leaf-overwrite",
                    f"op {_op_name(op, i)} writes var {v}, which is a "
                    f"registered leaf — a recorded mutation of a "
                    f"parameter/constant that skipped on_inplace_retag "
                    f"(replay would apply it on top of the live value, "
                    f"i.e. twice)",
                    op_index=i, detail=v))
            elif v in ph_vids:
                findings.append(Finding(
                    "placeholder-overwrite",
                    f"op {_op_name(op, i)} writes var {v}, which is a "
                    f"data() placeholder — feeds for it would be "
                    f"silently shadowed",
                    op_index=i, detail=v))
            seen_out.setdefault(v, i)

    if level == "full":
        findings.extend(_check_arity(prog, ops, leaves, refs, ph_vids))
    elif level != "structural":
        raise ValueError(f"unknown verify level {level!r} "
                         f"(use 'structural' or 'full')")
    return findings


def _check_arity(prog, ops, leaves, refs, ph_vids):
    """V5: abstract-eval each op.fn and compare output count with
    out_vids.  Ops whose input avals are unrecoverable (released
    interior tensors) or whose fn cannot be abstractly traced are
    skipped — the check is best-effort by design."""
    import jax
    import jax.numpy as jnp

    findings = []
    avals = {}
    for name, ph in (getattr(prog, "placeholders", {}) or {}).items():
        vid = getattr(ph, "_static_vid", None)
        if vid is not None:
            avals[vid] = jax.ShapeDtypeStruct(ph._value.shape,
                                              ph._value.dtype)
    for vid, (ref, snapshot) in leaves.items():
        t = ref() if ref is not None else None
        val = t._value if t is not None else snapshot
        if val is not None:
            avals[vid] = jax.ShapeDtypeStruct(jnp.shape(val),
                                              jnp.result_type(val))
    for vid, r in refs.items():
        t = r()
        if t is not None and vid not in avals:
            avals[vid] = jax.ShapeDtypeStruct(t._value.shape,
                                              t._value.dtype)

    for i, op in enumerate(ops):
        ins = [avals.get(v) for v in op.in_vids]
        if any(a is None for a in ins):
            continue
        try:
            out = jax.eval_shape(op.fn, *ins)
        except Exception:
            continue                      # not abstractly traceable
        outs = (out,) if not isinstance(out, (tuple, list)) \
            else tuple(out)
        if len(outs) != len(op.out_vids):
            findings.append(Finding(
                "arity-mismatch",
                f"op {_op_name(op, i)}: fn produces {len(outs)} "
                f"output(s) {[str(getattr(o, 'shape', '?')) for o in outs]} "
                f"but the op declares {len(op.out_vids)} out_vids "
                f"{list(op.out_vids)} — replay's zip would silently "
                f"drop/unbind the difference",
                op_index=i, detail=(len(outs), len(op.out_vids))))
        else:
            for v, o in zip(op.out_vids, outs):
                avals.setdefault(v, o)
    return findings


def check_program(prog, level: str = "structural",
                  title: str = "program verification failed"):
    """verify_program + raise ProgramVerifyError on any finding."""
    findings = verify_program(prog, level=level)
    if findings:
        raise ProgramVerifyError(findings, title=title)
    return prog
