"""What both windows share: the benchmark's own host spans, the profiler's
start and stop, the device's description and the table of peaks."""
import contextlib
import json
import os
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_ROOT = os.path.join(ROOT, ".bench_trace")


class Spans:
    """Host spans on the benchmark's side of each call into the program.
    Each is kept in memory (name, start, end by time.perf_counter) and, while
    the profiler runs, also written into its trace as a TraceAnnotation, so
    that an idle gap on the device can be named by what the host was doing."""

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations_ms(self, name, since=0.0):
        return [(b - a) * 1e3 for n, a, b in self.records
                if n == name and a >= since]


class Tracer:
    """The profiler over a short steady part of the window (--trace 1)."""

    def __init__(self, cell_name, enabled):
        self.enabled = enabled
        self.dir = os.path.join(TRACE_ROOT, cell_name)
        self.running = False

    def start(self):
        import jax
        if not self.enabled or self.running:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self.running = True

    def stop(self):
        import jax
        if not self.running:
            return
        jax.profiler.stop_trace()
        self.running = False

    def xplane_path(self):
        for base, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        return None


def load_peaks(device_kind):
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json ({sorted(table)}): no peak, "
                         "no utilization")
    return table[device_kind]


def device_info(chips):
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, devs[:chips]


def memory_peak_bytes(devices):
    """Peak on the fullest chip, as the allocator reports it."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


def compile_requests():
    """Compile requests the process has made so far, served from the cache
    or not (the program's own compile_report())."""
    from paddle_tpu import telemetry
    c = telemetry.compile_report()["xla_cache"]
    return c["hits"] + c["misses"]


def percentile(values, q):
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))
