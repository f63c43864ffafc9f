#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for a serve cell of
the window-and-full-attention sparse-expert configuration, on the chip at the
cell's own size.  The benchmark's own runs never run this.

    python3 benchmark/control_window.py --workload <cell> --seed <n> [--seconds <s>]

A short window at the cell's own load gives prompts and served tokens (the
program's reading).  Then, at the same positions of the same prompts and
tokens, the CONTROL (the reference with its weight products one precision
below what the configuration states) and each planted FAULT of
`reference/window_moe_f32.FAULTS` (one departure from the equations each) are
judged as `control.py` judges a control: by the token that forward puts
first.  A fault that reads under the cell's limit is a hole in the check.

Prints one JSON object: {"program": [gap, share], "control": [gap, share],
"faults": {name: [gap, share]}}: the widest gap (`served_token_gap`) and the
percent of the judged tokens over the workload's `off_gap`
(`served_token_off_share`).
"""
import argparse
import json
import sys

import run as runmod
from control import LOWER


def fault_names():
    """The planted faults, by the reference's own names (imported late: the
    reference imports jax, and `run.prepare` names the compile cache's
    directory before anything does)."""
    from reference.window_moe_f32 import FAULTS
    return FAULTS


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--faults", default="all")
    args = ap.parse_args(argv)
    ctx, _, _ = runmod.prepare(args.workload, args.seed, args.seconds, 0)
    from drivers import serve_window_moe as serve_moe
    faults = fault_names() if args.faults == "all" \
        else tuple(f for f in args.faults.split(",") if f)
    got = serve_moe.run(ctx)
    lower = LOWER[ctx.config["torch_dtype"]]
    ref = serve_moe.reference_logits(ctx, got["evidence"])
    program = serve_moe.reference_gaps(ctx, got["evidence"], ref=ref)
    def reading(gaps):
        return [float(gaps.max()), serve_moe.off_share(ctx, gaps)]
    out = {"workload": args.workload, "seed": args.seed,
           "tokens": int(program.size), "limits": ctx.workload["correct"],
           "off_gap": ctx.workload["off_gap"],
           "program": reading(program),
           "program_widest": sorted(program.tolist())[-8:],
           "control_precision": lower,
           "control": reading(serve_moe.reference_gaps(
               ctx, got["evidence"], lower, ref=ref)), "faults": {}}
    for fault in faults:
        out["faults"][fault] = reading(serve_moe.reference_gaps(
            ctx, got["evidence"], fault=fault, ref=ref))
        print(f"benchmark: fault {fault} = {out['faults'][fault]}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
