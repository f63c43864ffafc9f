"""Roofline share of the `ssm_scan` kernel (the mamba layers' recurrence) in
the traced chunks: the work it must do, each slot's SSM state of each mamba
layer read and written once a call (the `ssm_state_bytes` id of the chunks'
`serve.dispatch` spans) and each VALID lane's x, B, C, dt and y moved and
its recurrence computed once in every mamba layer (the `lanes_valid` id of
`serve.harvest`; a pad lane is lost share), over the device time of the
operations named `ssm_scan`.  None where no such kernel ran or no span
carries the counts (a program without state-space layers).

At the cell's shapes the bytes bound: a call moves each slot's whole state
(2 x 64 x 2.1 MB in fp32, half that in bf16) for at most 32 valid lanes a
slot, whose recurrence is about 2.6 MFLOP a lane, so the flops' time is a
few percent of the bytes' time."""
import opcount_granite_hybrid
import program_spans
from metrics._common import kernel_roofline_percent

KERNELS = r"ssm_scan"


def read(trace, counters, cell):
    program = program_spans.for_cell(trace, cell)
    cfg = cell["config"]
    if program is None or "mamba" not in cfg.get("layer_types", ()):
        return None
    state = [s.ids["ssm_state_bytes"] for s in program.spans
             if s.name == "serve.dispatch" and "ssm_state_bytes" in s.ids]
    lanes = [s.ids["lanes_valid"] for s in program.spans
             if s.name == "serve.harvest" and "lanes_valid" in s.ids]
    if not state or not lanes:
        return None
    mamba, _ = opcount_granite_hybrid.layers_of(cfg)
    valid = mamba * sum(lanes)
    nbytes = sum(state) \
        + valid * opcount_granite_hybrid.scan_lane_bytes(cfg)
    flops = opcount_granite_hybrid.scan_flops(cfg, valid)
    return kernel_roofline_percent(trace, KERNELS, flops, nbytes, cell)
