#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it builds the cell's model from the seed, warms up, measures for
`--seconds`, checks what the timed path produced against the plain reference
and prints one JSON object as the last line of standard output.  It runs on a
TPU only.  Everything that belongs to one configuration, one cell, one traffic
mix or one per-layer metric is a file of its own, found by the name in
BENCHMARK.json: `configs/`, `workloads/`, `traffic/`, `metrics/`.
"""
import time
T_START = time.perf_counter()       # set-up is counted from here

import argparse                      # noqa: E402
import importlib                     # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402
import types                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the program keeps its compile cache where this variable says; the path is
# part of the cache's key, so it is fixed inside the checkout, and it is the
# benchmark's own, so that no other run's entries stand in it
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")


def load_cell(name):
    """BENCHMARK.json's entries for the cell, with the files they name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json "
                         f"({sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "workloads", f"{name}.json")) as f:
        workload = json.load(f)
    if workload["config"] != cell["config"] \
            or workload["traffic"] != cell["traffic"] \
            or workload["chips"] != cell["chips"]:
        raise SystemExit(f"benchmark: workloads/{name}.json and "
                         "BENCHMARK.json disagree on config, traffic or chips")
    return bench, cell, config, workload


def metrics_of(bench, kind, cell_name):
    """The cell's metrics of one kind: those that list it, or list nothing."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell_name in m["workloads"]]


def metric_reader(name):
    """metrics/<name>.py, loaded by path: a metric's name may hold dots."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_per_layer(bench, cell_name, trace, counters, cell):
    out = {}
    for m in metrics_of(bench, "per_layer", cell_name):
        value = metric_reader(m["name"]).read(trace, counters, cell)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(ctx, bench):
    """Everything after the look for a chip: window, check, metrics.
    Returns the result object (without `device`)."""
    import trace_reduce
    driver = importlib.import_module(f"drivers.{ctx.workload['driver']}")
    got = driver.run(ctx)
    since = got["counters"]["window_t0"]
    got["counters"]["span_ms"] = {
        name: ctx.spans.durations_ms(name, since)
        for name in {n for n, _, _ in ctx.spans.records}}
    checks = driver.check(ctx, got["evidence"])
    correct = got["failed"] == 0 and bool(checks) and all(
        value <= limit for _, value, limit in checks)   # a NaN fails
    cell = {"config": ctx.config, "workload": ctx.workload, "mix": ctx.mix,
            "peaks": ctx.peaks, "chips": ctx.workload["chips"]}
    result = {"correct": correct, "attempted": got["attempted"],
              "failed": got["failed"]}
    device = {"memory_peak_bytes": got["memory_peak_bytes"]}
    if ctx.tracer.enabled:
        trace = trace_reduce.load(ctx.tracer.xplane_path())
        result["metrics"] = read_per_layer(bench, ctx.cell["name"], trace,
                                           got["counters"], cell)
        device["busy_s"] = trace_reduce.busy_seconds(trace)
        device["window_s"] = trace_reduce.window_seconds(trace)
        result["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(trace, 10),
            "idle_gaps": trace_reduce.longest_idle_gaps(trace, 10)}
    else:
        units = {m["name"]: m["unit"]
                 for m in metrics_of(bench, "end_to_end", ctx.cell["name"])}
        result["metrics"] = {n: {"value": float(v), "unit": units[n]}
                             for n, v in got["end_to_end"].items()
                             if n in units}
    result["device"] = device
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result


def prepare(workload, seed, seconds, trace):
    """The run's context, after the look for a chip; exits without a TPU."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    for path in (ROOT, HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    bench, cell, config, workload_file = load_cell(workload)

    import jax
    import harness
    import traffic
    # every program goes to the cache, also the small ones: a later run of
    # this cell in this checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device, devices = harness.device_info(cell["chips"])
    if device["platform"] != "tpu" or device["count"] < cell["chips"]:
        raise SystemExit(f"benchmark: {workload} needs {cell['chips']} TPU "
                         f"chip(s); jax reports {device}")
    ctx = types.SimpleNamespace(
        cell=cell, config=config, workload=workload_file,
        mix=traffic.load(cell["traffic"]), seed=seed, seconds=seconds,
        devices=devices, t_start=T_START,
        peaks=harness.load_peaks(device["kind"]), spans=harness.Spans(),
        tracer=harness.Tracer(cell["name"], bool(trace)),
        memory_peak_bytes=lambda: harness.memory_peak_bytes(devices),
        note=lambda text: print(f"benchmark: {text}", file=sys.stderr))
    return ctx, bench, device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ctx, bench, device = prepare(args.workload, args.seed, args.seconds,
                                 args.trace)
    result = execute(ctx, bench)
    result["device"] = dict(device, **result["device"])
    result["checks"] = result.pop("checks")   # the compared numbers come last
    for name, c in result["checks"].items():
        print(f"benchmark: check {name} = {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
