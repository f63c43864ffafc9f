"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

Read with `jax.profiler.ProfileData` alone.  A TPU trace has one plane per
chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one event per executed
HLO instruction (a Pallas kernel is one such event, named after its
`pallas_call(name=...)` plus a number), and a plane `/host:CPU` whose thread lines hold the
benchmark's own `TraceAnnotation` spans (`bench.*`).  All on one clock.

    python3 benchmark/trace_reduce.py <file.xplane.pb>     # look at a trace
"""
import collections
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

Trace = collections.namedtuple("Trace", "device_ops host_spans")
# device_ops: {plane name: [(name, start_ns, duration_ns), ...] by start}
# host_spans: [(name, start_ns, duration_ns), ...] by start


def _profile(path=None, text_proto=None):
    from jax.profiler import ProfileData
    if text_proto is not None:
        return ProfileData.from_serialized_xspace(
            ProfileData.text_proto_to_serialized_xspace(text_proto))
    return ProfileData.from_file(path)


def op_name(event_name):
    """An XLA Ops event is named by its instruction's whole text,
    `%fused_adamw.49 = (f32[...]...) custom-call(...)`: keep the name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(path=None, text_proto=None):
    device_ops, host_spans = {}, []
    for plane in _profile(path, text_proto).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name] = sorted(
                        ((op_name(e.name), float(e.start_ns),
                          float(e.duration_ns))
                         for e in line.events), key=lambda e: e[1])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans.extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    host_spans.sort(key=lambda e: e[1])
    return Trace(device_ops, host_spans)


def _extent(trace):
    starts = [ops[0][1] for ops in trace.device_ops.values() if ops]
    ends = [max(s + d for _, s, d in ops)
            for ops in trace.device_ops.values() if ops]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def window_seconds(trace):
    """From the first device operation's start to the last one's end."""
    lo, hi = _extent(trace)
    return (hi - lo) * 1e-9


def _busy_intervals(ops):
    """Union of the operations' intervals: [(start, end)] by start.  Nested
    events (a while loop and the operations of its body) count once."""
    merged = []
    for _, start, dur in ops:
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def busy_seconds(trace):
    """Seconds in which an operation ran, averaged over the chips traced."""
    per_chip = [sum(e - s for s, e in _busy_intervals(ops))
                for ops in trace.device_ops.values()]
    return sum(per_chip) * 1e-9 / len(per_chip) if per_chip else 0.0


def idle_share(trace):
    window = window_seconds(trace)
    return None if window <= 0 else 1.0 - busy_seconds(trace) / window


def _leaf_ops(ops):
    """Operations that hold no other operation: a while loop's event spans
    its body's events and would count their time twice."""
    leaves, stack = [], []
    for op in ops:
        _, start, dur = op
        while stack and start >= stack[-1][1] + stack[-1][2] - 1e-3:
            done = stack.pop()
            if not done[3]:
                leaves.append(done[:3])
        if stack:
            stack[-1][3] = True
        stack.append([op[0], start, dur, False])
    leaves.extend(tuple(s[:3]) for s in stack if not s[3])
    return leaves


def kernel_seconds(trace, pattern):
    """Summed device time of the operations whose name matches `pattern`
    (a regular expression, searched), averaged over the chips; None where
    none matches."""
    rx = re.compile(pattern)
    per_chip, found = [], False
    for ops in trace.device_ops.values():
        hits = [d for n, _, d in _leaf_ops(ops) if rx.search(n)]
        found = found or bool(hits)
        per_chip.append(sum(hits))
    return sum(per_chip) * 1e-9 / len(per_chip) if found else None


def top_device_ops(trace, count=10):
    """[[name, seconds], ...]: leaf operations by summed time over the chips'
    mean; names that differ only by a trailing .<number> are one entry."""
    total = collections.Counter()
    for ops in trace.device_ops.values():
        for name, _, dur in _leaf_ops(ops):
            total[re.sub(r"[.\d]+$", "", name)] += dur
    chips = max(1, len(trace.device_ops))
    return [[name, ns * 1e-9 / chips] for name, ns in total.most_common(count)]


def longest_idle_gaps(trace, count=10):
    """[[what the host was doing, seconds], ...]: the idle gaps of the first
    chip, each named by the benchmark's host span that covers most of it,
    summed by that name, longest first."""
    if not trace.device_ops:
        return []
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = _busy_intervals(ops)
    total = collections.Counter()
    for (_, end), (start, _) in zip(busy, busy[1:]):
        best, cover = "no benchmark span", 0.0
        for name, s, d in trace.host_spans:
            if s >= start:
                break
            overlap = min(start, s + d) - max(end, s)
            if overlap > cover:
                best, cover = name, overlap
        total[best] += start - end
    return [[name, ns * 1e-9] for name, ns in total.most_common(count)]


def dump(path, out=sys.stdout):
    """What a trace holds: planes, lines, and the names that take most time."""
    for plane in _profile(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            total = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
            span = (max(e.start_ns + e.duration_ns for e in events)
                    - min(e.start_ns for e in events))
            print(f"  LINE {line.name!r}: {len(events)} events over "
                  f"{span * 1e-6:.3f} ms", file=out)
            for name, ns in total.most_common(25):
                first = next(e for e in events if e.name == name)
                stats = {k: v for k, v in list(first.stats)[:8]}
                print(f"    {ns * 1e-6:10.3f} ms  {name[:90]!r}  "
                      f"{str(stats)[:300]}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1])
