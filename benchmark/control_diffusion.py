#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for a serve cell of a
configuration that generates by diffusion over blocks, on the chip at the
cell's own size.  The benchmark's own runs never run this.

    python3 benchmark/control_diffusion.py --workload <cell> --seed <n> [--seconds <s>]
        [--faults all|none|<name>,..] [--schedule-fault <name>]

A short window at the cell's own load gives prompts, served tokens and the
pass at which each was fixed (the program's reading).  Then, in the same
passes of the same served states, the CONTROL (the reference with its weight
products one precision below what the configuration states) and each planted
FAULT of `reference/block_diffusion_moe_f32.FAULTS` (one departure from the
equations each) are judged as `control.py` judges a control: by the token and
the lanes that forward puts first.  A fault that reads under every limit of
the cell is a hole in the check.

A fault of the SCHEDULE (`SCHEDULE_FAULTS`) is no property of a forward: it is
planted in the served program itself, for this process only, and the run it
gives is judged as the benchmark's own run is (`"program"`; no control, no
fault of the forward beside it unless asked for).

Prints one JSON object: {"program": {...}, "control": {...}, "faults":
{name: {...}}}, each the numbers `drivers/serve_diffusion.numbers` gives.
"""
import argparse
import json
import sys

import run as runmod
from control import LOWER


def _plant_all_lanes_in_pass_0(serving):
    """Every masked lane fixed in a block's first pass: 2 passes a block."""
    serving._step_quotas = lambda L, S: [L] * S


def _plant_one_step_skipped(serving):
    """The third step fixes what the fourth should: S passes a block."""
    sound = serving._step_quotas

    def quotas(L, S):
        q = sound(L, S)
        return q[:-2] + [q[-2] + q[-1], 0]
    serving._step_quotas = quotas


def _plant_position_order(serving):
    """The quota's lanes taken in position order, not by confidence."""
    import jax.numpy as jnp
    sound = serving._unmask_choice

    def choice(conf, masked, quota, threshold):
        lane = jnp.arange(conf.shape[-1], dtype=conf.dtype)
        return sound(jnp.broadcast_to(-1.0 - lane, conf.shape), masked,
                     quota, threshold)
    serving._unmask_choice = choice


SCHEDULE_FAULTS = {"all_lanes_in_pass_0": _plant_all_lanes_in_pass_0,
                   "one_step_skipped": _plant_one_step_skipped,
                   "position_order": _plant_position_order}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--faults", default=None,
                    help="all (the default without --schedule-fault), "
                    "none, or names")
    ap.add_argument("--schedule-fault", choices=sorted(SCHEDULE_FAULTS))
    args = ap.parse_args(argv)
    if args.faults is None:
        args.faults = "none" if args.schedule_fault else "all"
    ctx, _, _ = runmod.prepare(args.workload, args.seed, args.seconds, 0)
    from drivers import serve_diffusion as driver
    from reference import block_diffusion_moe_f32 as reference
    faults = reference.FAULTS if args.faults == "all" \
        else () if args.faults == "none" \
        else tuple(f for f in args.faults.split(",") if f)
    if args.schedule_fault:
        from paddle_tpu.inference import serving
        SCHEDULE_FAULTS[args.schedule_fault](serving)
    got = driver.run(ctx)
    lower = LOWER[ctx.config["torch_dtype"]]
    program = driver.judgement(ctx, got["evidence"])
    out = {"workload": args.workload, "seed": args.seed,
           "schedule_fault": args.schedule_fault,
           "tokens": int(program["token"].size),
           "limits": ctx.workload["correct"],
           "off_gap": ctx.workload["off_gap"],
           "program": driver.numbers(ctx, program),
           "program_widest": {k: sorted(program[k].tolist())[-8:]
                              for k in ("token", "lane")},
           "end_to_end": got["end_to_end"], "faults": {}}
    print(f"benchmark: program = {out['program']}", file=sys.stderr,
          flush=True)
    if not args.schedule_fault:
        out["control_precision"] = lower
        out["control"] = driver.numbers(ctx, driver.judgement(
            ctx, got["evidence"], lower))
        print(f"benchmark: control = {out['control']}", file=sys.stderr,
              flush=True)
    for fault in faults:
        out["faults"][fault] = driver.numbers(ctx, driver.judgement(
            ctx, got["evidence"], fault=fault))
        print(f"benchmark: fault {fault} = {out['faults'][fault]}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
