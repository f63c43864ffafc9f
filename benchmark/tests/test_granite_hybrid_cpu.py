"""The fifth serve driver end to end at a tiny size on the CPU: the copied
window over the hybrid state-space decoder, its reference, the planted
faults of `control_ssm.py` and the readers of the new metrics.  No device
number is asserted."""
import json
import os
import time
import types

import pytest

import tiny
import harness
import run as runmod

CELL = "graniteh3b_serve_chat_c64"
NEW_METRICS = ("ssm_scan_roofline", "ssm_mixer_device_ms",
               "ssm_serve_step_mfu", "ssm_serve_hbm_roofline")


def context(seed=3, seconds=0.5, trace=False):
    import jax
    with open(os.path.join(tiny.HERE, "data", "cells",
                           "tiny_granite_hybrid.json")) as f:
        data = json.load(f)
    workload = data["workload"]
    notes = []
    return types.SimpleNamespace(
        cell={"name": CELL, "config": workload["config"],
              "traffic": workload["traffic"], "chips": 1},
        config=data["config"], workload=workload, mix=data["mix"], seed=seed,
        seconds=seconds, devices=jax.devices()[:1],
        t_start=time.perf_counter(), peaks=tiny.CPU_PEAKS,
        spans=harness.Spans(), tracer=harness.Tracer(CELL, trace),
        memory_peak_bytes=lambda: 0, note=notes.append, notes=notes)


def test_serve_granite_hybrid_driver_end_to_end():
    ctx = context(seed=2**31 + 40, seconds=1.0)
    res = runmod.execute(ctx, tiny.bench_json())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    # in float32 the probed slot's state is the reference's to rounding
    assert res["checks"]["served_state_gap"]["value"] < 1e-4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}


def test_traced_run_reports_the_counter_metrics():
    res = runmod.execute(context(seconds=0.5, trace=True), tiny.bench_json())
    got = set(res["metrics"])
    assert {"ssm_serve_step_mfu", "ssm_serve_hbm_roofline",
            "serve_chunk_ms_p50", "prefill_token_share"} <= got
    assert all(res["metrics"][m]["value"] > 0 for m in
               ("ssm_serve_step_mfu", "ssm_serve_hbm_roofline"))


@pytest.mark.parametrize("fault", ["pad_lanes_advance", "conv_shift_by_width",
                                   "reoccupied_keeps_state", "no_d_skip",
                                   "int8_state"])
def test_each_planted_fault_fails_the_check(fault):
    import control_ssm
    from drivers import serve_granite_hybrid as driver
    ctx = context(seed=11, seconds=1.0)
    with control_ssm.planted(fault):
        got = driver.run(ctx)
    checks = driver.check(ctx, got["evidence"])
    assert any(value > limit for _, value, limit in checks), checks


def test_a_program_without_state_gives_nothing():
    """The parent cannot serve this configuration; a run without the state
    layers' spans or counts reads None and does not raise."""
    counters = {"prefill_tokens": 10, "decode_tokens": 5, "window_s": 1.0,
                "tokens_delivered": 5, "window_chunks": [(1, 0, [3, 4])],
                "traced_chunks": [(1, 0, [3, 4])], "prefill_chunk": 32,
                "admit_steps": 4, "chunk": 16, "slots": 2}
    cell = {"config": {"layer_types": ["attention"]},
            "peaks": tiny.CPU_PEAKS, "chips": 1}
    for name in NEW_METRICS:
        assert runmod.metric_reader(name).read(None, counters, cell) is None
