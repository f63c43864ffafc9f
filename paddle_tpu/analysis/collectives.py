"""Cross-rank collective-order checker — the static deadlock detector.

Reference failure mode: NCCL collectives hang the fleet when two ranks
of one communicator enter DIFFERENT collectives (or the same ones in a
different order) — `ProcessGroupNCCL` has no ordering protection, the
reference relies on every rank tracing the same program.  The TPU
analog is identical: a mis-scheduled psum/ppermute/all_gather across
mesh ranks, or a pipeline stage consuming micro-batch transfers in an
order its peer never sends, is a silent whole-mesh hang.

Model: a `CollectiveEvent` is one communication op with

  kind    primitive/channel kind ("psum", "ppermute", "act", "grad"...)
  key     payload identity that must agree across participants
          (axis names + perm + shape for jaxpr collectives;
          (src_chunk, dst_chunk, micro) for pipeline transfers)
  domain  the ORDERING DOMAIN — the communicator analog.  Events in
          one domain execute in issue order on every member rank, so
          all ranks listing events of a domain must list them in the
          SAME order.  For named-axis collectives the domain is the
          axis-name tuple; for pipeline point-to-point it is the
          directed channel (kind, src_stage, dst_stage).

`check_collective_order({rank: [events...]})` proves, per domain, an
identical total order across every rank that participates — exactly
the property whose violation deadlocks rendezvous communication.  The
proof is static: it needs only the schedules, never runs the programs.

`collective_schedule(fn, *args)` extracts the event sequence from a
traced jax program (recursing through scan/while/pjit bodies in
program order — one scan iteration represents the per-iteration order,
which is what rendezvous matching depends on).  SPMD programs yield
one schedule shared by every rank; per-rank/per-stage host-driven
systems (PipelineEngine) build their own per-rank event lists.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple, Sequence, Tuple

from .base import Finding, CollectiveOrderError
from .lints import as_jaxpr, iter_eqns

__all__ = ["CollectiveEvent", "COLLECTIVE_PRIMS", "collective_schedule",
           "check_collective_order", "assert_collective_order",
           "estimate_exposed_comm"]


class CollectiveEvent(NamedTuple):
    kind: str
    key: tuple
    domain: tuple
    # payload accounting (ISSUE 16): bytes moved and the grad-bucket id
    # the event drains, so order checks AND overlap-efficiency
    # estimates ride one event stream.  Defaulted so every existing
    # 3-field construction site and (kind, key)-only order comparison
    # is untouched — bytes/bucket are cost metadata, not identity.
    bytes: int = 0
    bucket: int = -1

    def describe(self) -> str:
        s = f"{self.kind}{list(self.key)} on domain {self.domain}"
        if self.bytes:
            s += f" [{self.bytes / 2**20:.2f}MB" + (
                f", bucket {self.bucket}]" if self.bucket >= 0 else "]")
        return s


# jaxpr primitives that lower to cross-rank communication.
# psum_invariant is what jax.lax.psum traces to inside a shard_map that
# checks varying-ness (the default); pvary is shard_map's device-local
# varying-ness MARKER, deliberately excluded.
COLLECTIVE_PRIMS = {
    "psum": "psum", "psum_invariant": "psum", "pmax": "pmax",
    "pmin": "pmin",
    "ppermute": "ppermute", "pgather": "pgather",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",
    "reduce_scatter": "reduce_scatter", "all_to_all": "all_to_all",
}


def _event_of(eqn) -> CollectiveEvent:
    kind = COLLECTIVE_PRIMS[eqn.primitive.name]
    axes = eqn.params.get("axis_name",
                          eqn.params.get("axes", eqn.params.get(
                              "axis_index_groups")))
    if not isinstance(axes, tuple):
        axes = (axes,)
    shape = None
    for v in eqn.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            shape = tuple(aval.shape)
            break
    extras: Tuple = ()
    if "perm" in eqn.params:
        extras = (tuple(map(tuple, eqn.params["perm"])),)
    return CollectiveEvent(kind, (axes, shape) + extras, tuple(axes))


def collective_schedule(fn_or_jaxpr, *args) -> List[CollectiveEvent]:
    """The ordered collective-event sequence of a traced program
    (one shared jaxpr walker — lints.iter_eqns — so the lints and this
    checker can never disagree on which sub-jaxprs are visited)."""
    return [_event_of(eqn)
            for eqn in iter_eqns(as_jaxpr(fn_or_jaxpr, *args))
            if eqn.primitive.name in COLLECTIVE_PRIMS]


def _degenerate_domain(domain) -> bool:
    """True for a domain carrying no real communication axis: the empty
    tuple (a CommOverlapPlan over zero live axes — every mesh axis size
    1) or an all-None tuple (a psum whose axis collapsed to size 1 and
    traced as an unnamed/device-local reduction).  Such events are
    device-local copies, not rendezvous — the order checker must treat
    them as no-ops, never as a divergence between the one rank that
    lists them and a peer that doesn't."""
    if not isinstance(domain, tuple):
        return domain is None
    return all(x is None for x in domain)


def _domain_participants(domain, all_ranks):
    """Ranks expected to take part in `domain`.  Pipeline channels
    encode their endpoints as the ints in the domain tuple (("act", 0,
    1) → stages 0 and 1); axis-name domains have no rank info in the
    events, so EVERY scheduled rank is presumed a member — the sound
    default for the one-rank-skips-the-collective hang (a rank that
    genuinely sits outside the communicator should not be in
    `schedules`, or pass an explicit `participants=`)."""
    ints = [x for x in domain if isinstance(x, int)]
    if ints and len(ints) == len(domain) - 1:
        return set(ints) & set(all_ranks)
    return set(all_ranks)


def check_collective_order(
        schedules: Dict[object, Sequence[CollectiveEvent]],
        participants=None, composed: bool = False) -> List[Finding]:
    """Statically prove an identical per-domain total order across all
    participating ranks.  Returns findings (empty == deadlock-free
    ordering); each finding names the domain, the diverging ranks, and
    the first position where their orders disagree.  A participant
    with ZERO events of a domain its peers use is a divergence too —
    the classic one-rank-never-enters-the-collective hang.

    participants: optional callable domain -> set(ranks) overriding
    `_domain_participants`.

    composed=True additionally proves the CROSS-domain issue order
    (the hybrid-engine contract): ranks that touch the same SET of
    domains — e.g. every rank of one SPMD stage program, which issues
    all of its mesh axes' collectives in one program order — must
    interleave those domains identically.  Per-domain checking alone
    cannot see a sharding reduce-scatter swapped with an mp
    all-gather on one rank (each domain still holds a consistent
    order of ONE event); with every rank blocking on its first
    collective, the swap is still a rendezvous deadlock."""
    findings: List[Finding] = []
    all_ranks = list(schedules)
    if participants is None:
        raw_part = lambda d: _domain_participants(d, all_ranks)  # noqa: E731
    elif callable(participants):
        raw_part = participants
    else:                       # a mapping domain -> ranks
        raw_part = participants.__getitem__

    def part(d):
        # a participants mapping (dict / __getitem__) may not know
        # degenerate/one-off domains — a size-1 axis's domain is a
        # no-op, not a KeyError
        try:
            return raw_part(d)
        except (KeyError, LookupError):
            return _domain_participants(d, all_ranks)

    domains = {ev.domain for events in schedules.values()
               for ev in events if not _degenerate_domain(ev.domain)}
    by_domain: Dict[tuple, List] = {}
    for d in sorted(domains, key=repr):
        members = part(d)
        if len(members) < 2:
            # single-rank domain: one participant can't diverge from a
            # peer — nothing to prove (the size-1-axis no-op contract)
            continue
        for rank in all_ranks:
            if rank not in members:
                continue
            seq = [(ev.kind, ev.key) for ev in schedules[rank]
                   if ev.domain == d]
            by_domain.setdefault(d, []).append((rank, seq))
    for domain, rank_seqs in by_domain.items():
        ref_rank, ref = rank_seqs[0]
        for rank, seq in rank_seqs[1:]:
            if seq == ref:
                continue
            pos = next((i for i, (a, b) in enumerate(zip(ref, seq))
                        if a != b), min(len(ref), len(seq)))
            a = ref[pos] if pos < len(ref) else "<nothing — sequence ends>"
            b = seq[pos] if pos < len(seq) else "<nothing — sequence ends>"
            findings.append(Finding(
                "collective-order-divergence",
                f"domain {domain}: rank {ref_rank!r} and rank {rank!r} "
                f"disagree at position {pos}: {a!r} vs {b!r} — ranks "
                f"would enter different collectives and hang "
                f"(lengths {len(ref)} vs {len(seq)})",
                op_index=pos,
                detail=(domain, ref_rank, rank, pos)))
    if composed:
        # degenerate (size-1 / unnamed-axis) events are device-local:
        # they neither define a rank's domain signature nor participate
        # in the cross-domain issue order
        groups: Dict[frozenset, List] = {}
        for rank in all_ranks:
            sig = frozenset(ev.domain for ev in schedules[rank]
                            if not _degenerate_domain(ev.domain))
            groups.setdefault(sig, []).append(rank)
        for sig, ranks in groups.items():
            if len(ranks) < 2 or not sig:
                continue
            ref_rank = ranks[0]
            ref = [(ev.kind, ev.key, ev.domain)
                   for ev in schedules[ref_rank]
                   if not _degenerate_domain(ev.domain)]
            for rank in ranks[1:]:
                seq = [(ev.kind, ev.key, ev.domain)
                       for ev in schedules[rank]
                       if not _degenerate_domain(ev.domain)]
                if seq == ref:
                    continue
                pos = next((i for i, (a, b) in enumerate(zip(ref, seq))
                            if a != b), min(len(ref), len(seq)))
                a = ref[pos] if pos < len(ref) \
                    else "<nothing — sequence ends>"
                b = seq[pos] if pos < len(seq) \
                    else "<nothing — sequence ends>"
                findings.append(Finding(
                    "composed-order-divergence",
                    f"composed issue order: rank {ref_rank!r} and rank "
                    f"{rank!r} share domains {sorted(sig, key=repr)} "
                    f"but interleave them differently at position "
                    f"{pos}: {a!r} vs {b!r} — one program order per "
                    f"SPMD group, or the first divergent collective "
                    f"rendezvous hangs the mesh",
                    op_index=pos,
                    detail=(sorted(sig, key=repr), ref_rank, rank, pos)))
    return findings


def assert_collective_order(schedules, title="collective order check "
                            "failed", composed: bool = False):
    findings = check_collective_order(schedules, composed=composed)
    if findings:
        raise CollectiveOrderError(findings, title=title)


def estimate_exposed_comm(schedule, compute_ms: float = 0.0, *,
                          bytes_per_sec: float = None,
                          overlap: bool = True) -> dict:
    """Exposed-comm estimate from the SAME event stream the order
    checker consumes — one walker for deadlock proofs and
    overlap-efficiency predictions (ISSUE 16 satellite).

    Model: the backward that produces n buckets' grads is split into n
    equal compute segments; bucket k's collective (bytes_k at the ICI
    peak) can start once segment k completes — i.e. at (k+1)·s with
    s = compute_ms / n — and buckets are totally ordered among
    themselves (the barrier chain), so

        finish_k = max(finish_{k-1}, (k+1)·s) + bytes_k / bw
        exposed  = max(0, finish_{n-1} − compute_ms)

    With `overlap=False` (the monolithic baseline) nothing hides:
    exposed = Σ bytes_k / bw.  For n ≥ 2 buckets and compute_ms > 0
    the overlapped figure is strictly below the monolithic one — the
    acceptance inequality perf_report gates.

    `schedule` is a sequence of CollectiveEvents (zero-byte events are
    skipped) or plain per-bucket byte counts.  Returns {"comm_ms",
    "exposed_ms", "overlap_efficiency", "bytes", "buckets"}."""
    if bytes_per_sec is None:
        from ..telemetry.costledger import interconnect_bytes_per_sec
        bytes_per_sec = interconnect_bytes_per_sec()
    sizes = [int(getattr(ev, "bytes", ev)) for ev in schedule]
    sizes = [b for b in sizes if b > 0]
    total = sum(sizes)
    comm = [b / bytes_per_sec * 1e3 for b in sizes]
    comm_ms = sum(comm)
    if not sizes:
        return {"comm_ms": 0.0, "exposed_ms": 0.0,
                "overlap_efficiency": 1.0, "bytes": 0, "buckets": 0}
    if overlap and compute_ms > 0:
        seg = compute_ms / len(sizes)
        t = 0.0
        for k, c in enumerate(comm):
            t = max(t, (k + 1) * seg) + c
        exposed = max(0.0, t - compute_ms)
    else:
        exposed = comm_ms
    return {"comm_ms": comm_ms, "exposed_ms": exposed,
            "overlap_efficiency": 1.0 - exposed / comm_ms,
            "bytes": total, "buckets": len(sizes)}
