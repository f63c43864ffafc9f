"""The serve step's device time by layer, in ms a scan step, from the
program's own scopes (`serve_step_device_ms.<part>`), and the share of the
lanes it computed that carried a token (`serve_lane_valid_share`).

Each of the first chip's leaf operations in the traced chunks goes to the
FIRST part of `PARTS` whose pattern its `op_name` holds (the grouped product
of the expert layers, a kernel the TPU compiler names `ragged-dot-*`, has
that name for its `op_name`); `other` is the chip's busy time less the five
named parts (copies, the layers' norms, the embedding, the slots' state and
whatever no scope names), so the six parts sum to the busy time.  The
denominator is the scan steps of the traced chunks, the sum of the `steps`
id of their `serve.dispatch` spans.  A program whose spans carry no `steps`
(or no `lanes`) reads None, never 0."""
import collections

import program_spans
import trace_reduce

PARTS = (
    ("experts", ("/moe.", "ragged-dot")),
    ("attention", ("attn.cache_write", "attn.attend", "mla.cache_write",
                   "mla.attend", "attn.window", "attn.full")),
    ("projections", ("attn.project", "mla.project")),
    ("mlp", ("/mlp",)),
    ("head", ("llama.norm", "llama.lm_head", "serve.sample",
              "diffusion.sample")),
)


def part_of(op_name):
    for part, patterns in PARTS:
        if any(p in op_name for p in patterns):
            return part
    return "other"


def device_ns(trace, program):
    """{part: ns} of the first chip over the traced chunks; the parts sum
    to its busy time."""
    out = collections.Counter({part: 0.0 for part, _ in PARTS})
    for op_name, dur in program.device_leaves:
        out[part_of(op_name)] += dur
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = sum(e - s for s, e in trace_reduce._busy_intervals(ops))
    out["other"] = busy - sum(out[part] for part, _ in PARTS)
    return out


def step_ms(trace, cell, part):
    """ms of the first chip's busy time in `part`, a traced scan step."""
    program = program_spans.for_cell(trace, cell)
    if program is None or not trace.device_ops:
        return None
    steps = sum(s.ids["steps"] for s in program.spans
                if s.name == "serve.dispatch" and "steps" in s.ids)
    if not steps:
        return None
    return device_ns(trace, program)[part] * 1e-6 / steps


def lane_valid_share(trace, cell):
    """100 x the lanes that carried a token over the lanes computed, summed
    over the traced chunks' `serve.harvest` spans."""
    program = program_spans.for_cell(trace, cell)
    if program is None:
        return None
    pairs = [(s.ids["lanes_valid"], s.ids["lanes"]) for s in program.spans
             if s.name == "serve.harvest"
             and "lanes" in s.ids and "lanes_valid" in s.ids]
    lanes = sum(n for _, n in pairs)
    return 100.0 * sum(v for v, _ in pairs) / lanes if lanes else None
