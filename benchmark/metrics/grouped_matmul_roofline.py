"""Roofline share of the `grouped_matmul` kernel (the expert layers' two
grouped products) in the traced chunks: the bytes it must move, each
(layer, step, held expert) that got a token reading that expert's weights
once (`moe_expert_steps_hit` x (hidden x 2 x expert width + expert width x
hidden) x 2 B) and each held assignment's rows once in and once out of both
products (`moe_assignments_held` x (2 x hidden + 3 x expert width) x 2 B),
the counts summed over the traced chunks' `serve.harvest` ids; over the
device time of the operations named `grouped_matmul` and the HBM peak.
None where no such kernel ran (a program whose products are
`ragged_dot`'s) or no span carries the counts."""
import program_spans
from metrics._common import kernel_roofline_percent

KERNELS = r"grouped_matmul"


def read(trace, counters, cell):
    program = program_spans.for_cell(trace, cell)
    if program is None:
        return None
    ids = [s.ids for s in program.spans if s.name == "serve.harvest"
           and "moe_expert_steps_hit" in s.ids
           and "moe_assignments_held" in s.ids]
    if not ids:
        return None
    d = cell["config"]["hidden_size"]
    f = cell["config"]["moe_intermediate_size"]
    hit = sum(i["moe_expert_steps_hit"] for i in ids)
    held = sum(i["moe_assignments_held"] for i in ids)
    nbytes = 2.0 * (hit * 3 * d * f + held * (2 * d + 3 * f))
    return kernel_roofline_percent(trace, KERNELS, 0.0, nbytes, cell)
