"""Model FLOP/s utilization of the whole train step over the window: the
operations forward and backward require (opcount.train_step_flops) times the
steps finished, over the window and the chips' bf16 peak."""
import opcount


def read(trace, counters, cell):
    if not counters.get("steps"):
        return None
    mix = cell["mix"]
    flops = opcount.train_step_flops(cell["config"], mix["batch"],
                                     mix["sequence"]) * counters["steps"]
    peak = cell["peaks"]["bf16_flops_per_s"] * cell["chips"]
    return 100.0 * flops / counters["window_s"] / peak
