"""paddle_tpu.analysis — program verification and jaxpr lint passes.

Reference: the PIR layer's `Operation::Verify` contract — every pass must
leave the IR verifiable (`paddle/pir/core/operation.cc`, and
`VerifySig/VerifyType` hooks on each op) — plus the debugging passes
under `paddle/fluid/framework/ir/` (graph_viz, check ops).  Here the
same discipline is applied to this framework's two program forms:

  * the recorded **OpDesc tape** (`static/program.py`) — structural
    invariants: def-before-use, single definition (SSA) per vid,
    WAR/WAW in-place hazards against the `on_inplace_retag` protocol,
    leaf liveness, name-table integrity, and (level="full") per-op
    output arity via abstract evaluation.  `verify_program` runs
    automatically after every `apply_pass`, and — gated on
    `FLAGS_check_program` — at `Executor.run` entry, so a buggy tape
    pass can never ship a structurally broken program;

  * **traced/compiled jax programs** — lint analyses over jaxprs and
    lowered modules: silent dtype promotion (fp32 upcasts inside
    bf16/AMP regions, x64 creep), unexpected host<->device transfers
    inside a jitted step, declared-donated buffers the executable did
    not actually alias, a `recompile_guard` context manager that
    bounds compilation count and reports the offending avals, and a
    cross-rank collective-order checker (`collectives.py`) — the
    static deadlock detector for the NCCL-hang-equivalent failure
    mode (a collective misorder across mesh ranks).

  * the **Program Sentinel** (`passes.py` + `sharding_census.py`) —
    the PIR-equivalent registered pass manager unifying the lints as
    passes (severity ladder, per-pass flags, baseline suppression)
    plus two whole-program analyzers: the HLO **collective census**
    (parse `compiled.as_text()` for every all-reduce / all-gather /
    reduce-scatter / all-to-all / collective-permute with replica
    groups and byte counts, diff per traffic class against the modeled
    `CollectiveEvent` schedule — an implicit resharding XLA inserted
    is a named error finding) and the **replication audit** (large
    tensors the strategy shards but the partitioned module holds at
    full global shape).  Wired behind FLAGS_static_sentinel into the
    build paths of ShardedTrainStep / PipelineEngine /
    HybridParallelEngine / ContinuousBatcher (build-level), with the
    full catalog on each engine's `.preflight(...)`.

CLI: `python tools/verify_program.py` (JSON mode + non-zero exit on
findings, like tools/op_audit.py) and `python tools/static_check.py`
(the sentinel catalog over the standard program zoo, diffed against
tools/static_baseline.json).  All checks are cold-path: with the
flags off the replay hot path pays one dict lookup and keeps its
replay-cache keys (tests/test_program_verifier.py
`test_hot_path_runs_zero_verifications_with_flag_off`).
"""
from __future__ import annotations

from .base import Finding, ProgramVerifyError, LintError, \
    CollectiveOrderError, RecompileError
from .verifier import verify_program, check_program
from .lints import lint_dtype_promotion, lint_transfers, lint_donation, \
    lint_materialized_logits, lint_peak_hbm, lint_mfu_floor, \
    lint_serve_programs, recompile_guard, note_program_build
from .collectives import CollectiveEvent, collective_schedule, \
    check_collective_order
from .passes import Pass, PassContext, PassManager, SentinelError, \
    SentinelReport, register_pass, registered_passes, sentinel_preflight
from .sharding_census import HloCollective, parse_hlo_collectives, \
    census_diff, replication_audit

__all__ = [
    "Finding", "ProgramVerifyError", "LintError", "CollectiveOrderError",
    "RecompileError",
    "verify_program", "check_program",
    "lint_dtype_promotion", "lint_transfers", "lint_donation",
    "lint_materialized_logits", "lint_peak_hbm", "lint_mfu_floor",
    "lint_serve_programs",
    "recompile_guard", "note_program_build",
    "CollectiveEvent", "collective_schedule", "check_collective_order",
    "Pass", "PassContext", "PassManager", "SentinelError",
    "SentinelReport", "register_pass", "registered_passes",
    "sentinel_preflight",
    "HloCollective", "parse_hlo_collectives", "census_diff",
    "replication_audit",
]
