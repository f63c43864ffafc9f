"""The program's own spans, and the scopes of its step program, from the
traced stretch's `.xplane.pb`.

`trace_reduce.load` keeps only the benchmark's `bench.*` host spans and the
drivers hand no program state to the readers, so the per-layer metrics that
read what the PROGRAM records get it here, from the same file:

* `/host:CPU` events named `serve.*` / `train.*` are `telemetry.span`s
  (`jax.profiler.TraceAnnotation`s) with their ids as the event's stats, on
  the clock of the device's operations.  Nesting is by time on one thread.
* An `XLA Ops` event names its HLO instruction; the instruction's `op_name`
  (`jit(step)/transpose(jvp(llama.layer3))/mlp/dot_general`, with the
  program's `jax.named_scope`s in it) is the stat `tf_op` of the event's
  METADATA, which `jax.profiler.ProfileData` does not show: the few fields
  needed are read from the file's bytes.

A program without such spans or scopes (the parent of the PR that brought
them) gives None everywhere, never 0.

    python3 benchmark/program_spans.py <file.xplane.pb>    # look at both
"""
import collections
import json
import os
import statistics
import sys

import harness
import trace_reduce

PREFIXES = ("serve.", "train.")

Span = collections.namedtuple("Span", "name start_ns end_ns ids parent")
# parent: index of the span it lies in (same thread), or None
Program = collections.namedtuple("Program", "spans device_leaves")
# spans: [Span] by start; device_leaves: [(op_name or "", duration_ns)] of
# the first chip's leaf operations


# -- the file's bytes: instruction -> op_name ------------------------------

def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for anything with a length or a fixed width."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _map_value(entry):
    return next((v for n, v in _fields(entry) if n == 2), b"")


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def op_names(serialized_xspace, plane_name):
    """{event name: op_name} of one plane: XSpace.planes[1] -> XPlane
    {name 2, event_metadata 4, stat_metadata 5} -> XEventMetadata {name 2,
    stats 5} -> XStat {metadata_id 1, str_value 5, ref_value 7}."""
    for n, plane in _fields(memoryview(serialized_xspace)):
        if n != 1:
            continue
        name, events, stat_names = None, [], {}
        for m, value in _fields(plane):
            if m == 2:
                name = _text(value)
            elif m == 4:
                events.append(_map_value(value))
            elif m == 5:
                meta = dict(_fields(_map_value(value)))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        if name != plane_name:
            continue
        out = {}
        for event in events:
            event_name, found = "", None
            for m, value in _fields(event):
                if m == 2:
                    event_name = _text(value)
                elif m == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        found = _text(stat[5]) if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            if found:
                out[event_name] = found
        return out
    return {}


# -- loading ---------------------------------------------------------------

def _nested(events):
    """[Span] of one thread's (name, start, end, ids) by start, each with
    the index (into the returned list) of the span it lies in."""
    out, stack = [], []
    for name, start, end, ids in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and end > out[stack[-1]].end_ns:
            stack.pop()
        out.append(Span(name, start, end, ids,
                        stack[-1] if stack else None))
        stack.append(len(out) - 1)
    return out


def load(path=None, text_proto=None):
    from jax.profiler import ProfileData
    if text_proto is not None:
        raw = ProfileData.text_proto_to_serialized_xspace(text_proto)
    else:
        with open(path, "rb") as f:
            raw = f.read()
    spans, device = [], {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                thread = _nested(
                    (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns), dict(e.stats))
                    for e in line.events if e.name.startswith(PREFIXES))
                base = len(spans)
                spans.extend(s._replace(parent=None if s.parent is None
                                        else s.parent + base)
                             for s in thread)
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    device[plane.name] = sorted(
                        ((e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events), key=lambda e: e[1])
    leaves = []
    if device:
        first = sorted(device)[0]
        names = op_names(raw, first)
        leaves = [(names.get(name, ""), dur)
                  for name, _, dur in trace_reduce._leaf_ops(device[first])]
    return Program(spans, leaves)


_LOADED = {}


def for_cell(trace, cell):
    """The Program of this run's trace: the cell is the one entry of
    BENCHMARK.json whose config, traffic and chips are the workload
    file's, its trace the one under harness.TRACE_ROOT/<cell>.  None in an
    untraced run or where no such file is found.  Parsed once."""
    if trace is None:
        return None
    work = cell["workload"]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]
                 if (w["config"], w["traffic"], w["chips"])
                 == (work["config"], work["traffic"], work["chips"])]
    if len(names) != 1:
        return None
    path = harness.Tracer(names[0], True).xplane_path()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = load(path)
    return _LOADED[key]


# -- what the readers ask --------------------------------------------------

def durations_ms(program, name):
    return [(s.end_ns - s.start_ns) * 1e-6 for s in program.spans
            if s.name == name]


def self_ns(program, index):
    """A span's duration minus what the spans lying directly in it cover
    (their union: children of one span may overlap)."""
    span = program.spans[index]
    covered, reach = 0.0, span.start_ns
    for child in program.spans:
        if child.parent == index:
            lo, hi = max(child.start_ns, reach), min(child.end_ns,
                                                     span.end_ns)
            if hi > lo:
                covered, reach = covered + hi - lo, hi
    return span.end_ns - span.start_ns - covered


def _segments(spans):
    """[(start, end, name of the innermost span over it or None)] covering
    the time line from the first span's start to the last one's end."""
    cuts = sorted({t for s in spans for t in (s.start_ns, s.end_ns)})
    out = []
    for lo, hi in zip(cuts, cuts[1:]):
        over = [s for s in spans if s.start_ns <= lo and hi <= s.end_ns]
        out.append((lo, hi, max(over, key=lambda s: (s.start_ns, -s.end_ns))
                    .name if over else None))
    return out


def idle_by_span(trace, program):
    """{span name or None: idle ns}: every idle interval of the first chip
    (between the busy intervals of `trace`'s device operations, which share
    the spans' clock), cut where a program span starts or ends, each piece
    to the innermost span that lies over it, None where none does."""
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = trace_reduce._busy_intervals(ops)
    segments = _segments(program.spans)
    out, k = collections.Counter(), 0
    for (_, a), (b, _) in zip(busy, busy[1:]):
        while k < len(segments) and segments[k][1] <= a:
            k += 1
        at, j = a, k
        while at < b:
            if j >= len(segments) or segments[j][0] >= b:
                name, upto = None, b
            elif segments[j][0] > at:
                name, upto = None, segments[j][0]
            else:
                name, upto = segments[j][2], min(b, segments[j][1])
                j += 1
            out[name] += upto - at
            at = upto
    return out


SERVE_IDLE = {"harvest": ("serve.harvest", "serve.deliver"),
              "admit": ("serve.evict", "serve.admit"),
              "dispatch": ("serve.dispatch",)}
# "other": under serve.device_wait, under serve.step itself, outside any
# serve.step (the caller's own code) or under no span at all


def serve_idle_share(trace, cell, part):
    """Percent of the traced window in which the first chip was idle while
    the host was in `part` of ContinuousBatcher.step().  The four parts
    add up to the device's idle share."""
    program = for_cell(trace, cell)
    if program is None or not trace.device_ops \
            or not any(s.name == "serve.step" for s in program.spans):
        return None
    window = trace_reduce.window_seconds(trace) * 1e9
    if window <= 0:
        return None
    idle = idle_by_span(trace, program)
    named = {p: sum(idle.get(n, 0.0) for n in names)
             for p, names in SERVE_IDLE.items()}
    named["other"] = sum(idle.values()) - sum(named.values())
    return 100.0 * named[part] / window


def serve_host_self_ms_p50(trace, cell):
    """Median over the traced chunks of serve.step minus the
    serve.device_wait in it: what the host adds to a chunk."""
    program = for_cell(trace, cell)
    if program is None:
        return None
    waits = collections.Counter()
    for s in program.spans:
        if s.name == "serve.device_wait" and s.parent is not None:
            waits[s.parent] += s.end_ns - s.start_ns
    own = [(s.end_ns - s.start_ns - waits[i]) * 1e-6
           for i, s in enumerate(program.spans)
           if s.name == "serve.step" and i in waits]
    return statistics.median(own) if own else None


def span_ms_p50(trace, cell, name):
    program = for_cell(trace, cell)
    values = durations_ms(program, name) if program else []
    return statistics.median(values) if values else None


def scope_of(op_name):
    """forward / backward / optimizer of one operation of the train step,
    from its op_name: the trainer's scopes first, then what jax writes
    (`transpose(jvp(..))` is the backward pass)."""
    if "train.optimizer" in op_name or "train.guard" in op_name:
        return "optimizer"
    if "transpose(" in op_name or "train.grad_reduce" in op_name:
        return "backward"
    return "forward"


def train_device_share(trace, cell, part):
    """Percent of the first chip's busy time spent in leaf operations of
    `part` of the train step.  None where the trace names no operation by
    the trainer's optimizer scope (a program without the scopes)."""
    program = for_cell(trace, cell)
    if program is None or not trace.device_ops or not any(
            "train.optimizer" in name for name, _ in program.device_leaves):
        return None
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = sum(e - s for s, e in trace_reduce._busy_intervals(ops))
    time = sum(dur for name, dur in program.device_leaves
               if scope_of(name) == part)
    return 100.0 * time / busy if busy > 0 else None


def dump(path, out=sys.stdout):
    program = load(path)
    print(f"{len(program.spans)} program spans", file=out)
    depth = {}
    for i, s in enumerate(program.spans):
        depth[i] = 0 if s.parent is None else depth[s.parent] + 1
        print(f"  {s.start_ns * 1e-6:12.3f} ms {'  ' * depth[i]}{s.name} "
              f"{(s.end_ns - s.start_ns) * 1e-6:.3f} ms (self "
              f"{self_ns(program, i) * 1e-6:.3f}) {s.ids}", file=out)
    by_scope, by_name = collections.Counter(), collections.Counter()
    for name, dur in program.device_leaves:
        by_scope[scope_of(name) if name else "no op_name"] += dur
        by_name[name.split(":")[0]] += dur
    print("device leaf time by scope: "
          + ", ".join(f"{k} {v * 1e-6:.3f} ms"
                      for k, v in by_scope.most_common()), file=out)
    for name, dur in by_name.most_common(25):
        print(f"  {dur * 1e-6:10.3f} ms  {name[:150]!r}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1])
