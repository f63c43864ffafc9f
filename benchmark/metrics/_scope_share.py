"""Share of the first chip's busy time, in the traced chunks, spent in leaf
operations whose `op_name` lies under one of the given `jax.named_scope`s of
the step program (read as `program_spans.train_device_share` reads the
trainer's).  None where the trace names no operation by such a scope: a
program without them."""
import program_spans
import trace_reduce


def device_share_percent(trace, cell, scopes):
    program = program_spans.for_cell(trace, cell)
    if program is None or not trace.device_ops:
        return None
    time = sum(dur for name, dur in program.device_leaves
               if any(scope in name for scope in scopes))
    if not time:
        return None
    ops = trace.device_ops[sorted(trace.device_ops)[0]]
    busy = sum(e - s for s, e in trace_reduce._busy_intervals(ops))
    return 100.0 * time / busy if busy > 0 else None
