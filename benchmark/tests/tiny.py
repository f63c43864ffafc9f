"""Tiny cells for the CPU: the same drivers, readers and checks as a chip
run, on cells that exist only as data under tests/data/cells."""
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}   # no chip's


def load(name):
    with open(os.path.join(HERE, "data", "cells", f"{name}.json")) as f:
        return json.load(f)


def context(cell_name, seed=3, seconds=0.5, trace=False):
    """The ctx run.main builds, without its look for a chip."""
    import jax
    data, config = load(cell_name), load("tiny_config")
    workload = data["workload"]
    devices = jax.devices()[:workload["chips"]]
    notes = []
    ctx = types.SimpleNamespace(
        cell={"name": cell_name, "config": workload["config"],
              "traffic": workload["traffic"], "chips": workload["chips"]},
        config=config, workload=workload, mix=data["mix"], seed=seed,
        seconds=seconds, devices=devices, t_start=time.perf_counter(),
        peaks=CPU_PEAKS, spans=harness.Spans(),
        tracer=harness.Tracer(cell_name, trace),
        memory_peak_bytes=lambda: 0, note=notes.append, notes=notes)
    return ctx


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
