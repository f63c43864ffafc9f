"""ms of the first chip's busy time a traced scan step spent in the mamba
layers' mixers: leaf operations whose `op_name` lies under one of the
mixer's scopes (`ssm.project`, `ssm.conv`, `ssm.scan`, `ssm.norm`,
`ssm.out`), over the scan steps of the traced chunks (the `steps` id of
their `serve.dispatch` spans).  None for a program without such scopes."""
import program_spans


def read(trace, counters, cell):
    program = program_spans.for_cell(trace, cell)
    if program is None or not trace.device_ops:
        return None
    steps = sum(s.ids["steps"] for s in program.spans
                if s.name == "serve.dispatch" and "steps" in s.ids)
    ns = sum(dur for name, dur in program.device_leaves if "/ssm." in name)
    if not steps or not ns:
        return None
    return ns * 1e-6 / steps
