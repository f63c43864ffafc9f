#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, on the chip at a
cell's own size.  The benchmark's own runs never run this.

    python3 benchmark/control.py --workload <cell> --seed <n> [--seconds <s>]

train   No window is needed: the float32 reference follows the first steps,
        then the CONTROL does (the reference one precision below what the
        configuration states, put in the program's place), then the planted
        fault "half of the batch left out, the mean taken over the rest".
        and "a step that returns its state unchanged" (the reference with a
        learning rate of nought).  Each is compared with the reference as a
        run's program is.  `--runs` picks among control,half,frozen.
serve   A short window at the cell's own load gives prompts and served
        tokens; the control is judged at the same positions of the same
        prompts and tokens by the token it puts first.

Prints one JSON object: {"control": {...}, "fault_half_batch": {...}} or
{"program": gap, "control": gap}.
"""
import argparse
import importlib
import json
import sys

import run as runmod

LOWER = {"float32": "bfloat16", "bfloat16": "int8", "float16": "int8"}


def readings(ctx, runs=("control", "half", "frozen")):
    lower = LOWER[ctx.config["torch_dtype"]]
    driver = importlib.import_module(f"drivers.{ctx.workload['driver']}")
    if ctx.workload["driver"] == "train":
        ref = driver.reference_numbers(ctx)
        out = {"control_precision": lower}
        if "control" in runs:
            out["control"], _ = driver.compare(
                driver.reference_numbers(ctx, lower), ref)
        if "half" in runs:
            rows = slice(0, int(ctx.mix["batch"]) // 2)
            out["fault_half_batch"], _ = driver.compare(
                driver.reference_numbers(ctx, rows=rows), ref)
        if "frozen" in runs:
            still = dict(ctx.workload["trainer"], learning_rate=0.0)
            out["fault_state_unchanged"], _ = driver.compare(
                driver.reference_numbers(ctx, trainer=still), ref)
        return out
    got = driver.run(ctx)
    program = driver.reference_gaps(ctx, got["evidence"])
    control = driver.reference_gaps(ctx, got["evidence"], lower)
    return {"control_precision": lower, "tokens": int(program.size),
            "program": float(program.max()), "control": float(control.max())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--runs", default="control,half,frozen")
    args = ap.parse_args(argv)
    ctx, _, _ = runmod.prepare(args.workload, args.seed, args.seconds, 0)
    print(json.dumps(dict(readings(ctx, args.runs.split(",")),
                          workload=args.workload,
                          seed=args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
