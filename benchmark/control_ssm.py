#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from, for a serve cell of
the hybrid state-space configuration, on the chip at the cell's own size.
The benchmark's own runs never run this.

    python3 benchmark/control_ssm.py --workload <cell> --seed <n> [--seconds <s>]
        [--faults all|none|<name>,..]

A short window at the cell's own load gives prompts and served tokens (the
program's reading), judged as the benchmark's run is; at the same positions
of the same prompts and tokens the CONTROL (the reference with its weight
products one precision below what the configuration states) is judged as
`control.py` judges a control, by the token it puts first.  Then each FAULT
of `FAULTS` is planted in the served program itself, for this process only,
and a window of its own is served and judged as the benchmark's run is.  A
fault that reads under every limit of the cell is a hole in the check.

Prints one JSON object: {"program": [gap, share, state gap], "control":
[gap, share], "faults": {name: [gap, share, tokens judged, state gap]}}: the
widest gap (`served_token_gap`), the percent of the judged tokens over the
workload's `off_gap` (`served_token_off_share`) and the probed slot's
`served_state_gap`.
"""
import argparse
import contextlib
import json
import sys

import run as runmod
from control import LOWER


def _scan_with(change):
    """ops.ssm_scan with its arguments changed by `change(args) -> args`,
    its results by nothing."""
    from paddle_tpu import ops
    sound = ops.ssm_scan

    def scan(*args):
        return sound(*change(list(args)))
    return ops, "ssm_scan", scan


def _pad_lanes_advance():
    """(a) every lane of a step advances the state, pad lanes too."""
    import jax.numpy as jnp

    def change(args):
        x, n_valid = args[1], args[7]
        args[7] = jnp.full_like(n_valid, x.shape[1])
        return args
    return _scan_with(change)


def _conv_shift_by_width():
    """(b) the conv window moves on by the step's width, not by n."""
    import jax.numpy as jnp
    from paddle_tpu.models import llama
    sound = llama.conv_window_after

    def after(win, n_valid, taps):
        return sound(win, jnp.full_like(n_valid, win.shape[1] - taps + 1),
                     taps)
    return llama, "conv_window_after", after


def _reoccupied_keeps_state():
    """(c) a slot a new request takes keeps its predecessor's state."""
    from paddle_tpu.models.llama import LlamaMamba2Mixer
    sound = LlamaMamba2Mixer.forward_cached_paged

    def never_fresh(self, h, cache, pos, layer, lanes_valid=None):
        return sound(self, h, cache, pos + 1, layer, lanes_valid)
    return LlamaMamba2Mixer, "forward_cached_paged", never_fresh


def _no_d_skip():
    """(d) y_t = h_t C_t, the D x_t skip dropped."""
    import jax.numpy as jnp

    def change(args):
        args[6] = jnp.zeros_like(args[6])
        return args
    return _scan_with(change)


def _state_rounded_to(dtype):
    """(e) the SSM state stored in a narrower dtype than the
    configuration's: rounded each time it is written, "int8" to 8 bits a
    row of N with the row's own scale."""
    import jax.numpy as jnp
    from paddle_tpu import ops
    sound = ops.ssm_scan

    def narrow(h):
        if dtype != "int8":
            return h.astype(dtype).astype(h.dtype)
        h32 = h.astype(jnp.float32)
        scale = jnp.max(jnp.abs(h32), -1, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return (jnp.round(h32 / scale) * scale).astype(h.dtype)

    def scan(state, *args):
        layer = args[-1]
        y, state = sound(state, *args)
        return y, state.at[layer].set(narrow(state[layer]))
    return ops, "ssm_scan", scan


FAULTS = {"pad_lanes_advance": _pad_lanes_advance,
          "conv_shift_by_width": _conv_shift_by_width,
          "reoccupied_keeps_state": _reoccupied_keeps_state,
          "no_d_skip": _no_d_skip,
          "bf16_state": lambda: _state_rounded_to("bfloat16"),
          "int8_state": lambda: _state_rounded_to("int8")}


@contextlib.contextmanager
def planted(name):
    owner, attr, faulty = FAULTS[name]()
    sound = getattr(owner, attr)
    setattr(owner, attr, faulty)
    try:
        yield
    finally:
        setattr(owner, attr, sound)


def share_one_model(driver):
    """Every window of this process serves the SAME model, built once (the
    seed's weights do not change with the fault); the step programs it
    keeps are dropped before each window, so that each fault's window
    compiles its own."""
    build, made = driver.build_model, []

    def shared(cfg, seed, param_dtype):
        if not made:
            made.append(build(cfg, seed, param_dtype))
        made[0].__dict__.pop("_gen_compiled", None)
        return made[0]
    driver.build_model = shared


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="all")
    args = ap.parse_args(argv)
    ctx, _, _ = runmod.prepare(args.workload, args.seed, args.seconds, 0)
    from drivers import serve_granite_hybrid as driver
    share_one_model(driver)
    faults = tuple(FAULTS) if args.faults == "all" \
        else () if args.faults == "none" \
        else tuple(f for f in args.faults.split(",") if f)

    def reading(gaps):
        return [float(gaps.max()), driver.off_share(ctx, gaps)]
    got = driver.run(ctx)
    ref, states = driver.reference(ctx, got["evidence"])
    program = driver.reference_gaps(ctx, got["evidence"], ref=ref)
    lower = LOWER[ctx.config["torch_dtype"]]
    out = {"workload": args.workload, "seed": args.seed,
           "tokens": int(program.size), "limits": ctx.workload["correct"],
           "off_gap": ctx.workload["off_gap"],
           "end_to_end": got["end_to_end"],
           "program": reading(program)
           + [driver.state_gap(got["evidence"], states)],
           "program_widest": sorted(program.tolist())[-8:],
           "control_precision": lower,
           "control": reading(driver.reference_gaps(
               ctx, got["evidence"], lower, ref=ref)), "faults": {}}
    print(f"benchmark: program {out['program']} control {out['control']}",
          file=sys.stderr, flush=True)
    del got, ref, states
    for fault in faults:
        with planted(fault):
            got = driver.run(ctx)
        ref, states = driver.reference(ctx, got["evidence"])
        gaps = driver.reference_gaps(ctx, got["evidence"], ref=ref)
        out["faults"][fault] = reading(gaps) + [
            int(gaps.size), driver.state_gap(got["evidence"], states)]
        print(f"benchmark: fault {fault} = {out['faults'][fault]}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
