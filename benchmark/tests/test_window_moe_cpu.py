"""The fourth serve driver end to end at a tiny size on the CPU: the copied
window over the window-and-full-attention sparse-expert decoder, its
reference, its counters and the readers that take them.  No device number is
asserted."""
import json
import os
import time
import types

import numpy as np
import pytest

import tiny
import harness
import run as runmod

CELL = "kexaone236b_serve_mixed_c64"
NEW_METRICS = ("window_moe_serve_step_mfu", "window_moe_serve_hbm_roofline",
               "window_paged_attention_roofline",
               "window_attention_device_share", "full_attention_device_share",
               "kv_window_pages_walked_over_needed")


def context(seed=3, seconds=0.5, trace=False):
    import jax
    with open(os.path.join(tiny.HERE, "data", "cells",
                           "tiny_window_moe.json")) as f:
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


def test_serve_window_moe_driver_end_to_end():
    ctx = context(seed=2**31 + 34, seconds=1.0)
    res = runmod.execute(ctx, tiny.bench_json())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert set(res["checks"]) == {"served_token_gap",
                                  "served_token_off_share"}


def test_traced_run_reports_the_counter_metrics():
    res = runmod.execute(context(seconds=0.5, trace=True), tiny.bench_json())
    got = set(res["metrics"])
    assert {"window_moe_serve_step_mfu", "window_moe_serve_hbm_roofline",
            "moe_expert_load_max_over_mean", "serve_chunk_ms_p50",
            "prefill_token_share", "compiles_in_window.serve"} <= got
    # a CPU trace has no device plane, a tiny cell no entry in
    # BENCHMARK.json (program_spans.for_cell finds its trace by that); the
    # other models' counts are not this cell's
    assert not {"moe_device_share", "window_attention_device_share",
                "kv_window_pages_walked_over_needed",
                "full_attention_device_share",
                "window_paged_attention_roofline", "serve_step_mfu",
                "moe_serve_step_mfu", "paged_attention_roofline",
                "kv_pages_walked_over_live"} & got
    assert all(res["metrics"][m]["value"] > 0 for m in
               ("window_moe_serve_step_mfu", "window_moe_serve_hbm_roofline"))


def test_a_program_without_kinds_gives_nothing():
    """The parent's program has no such counters, spans or scopes: the
    readers return None and do not raise."""
    counters = {"prefill_tokens": 10, "decode_tokens": 5, "window_s": 1.0,
                "tokens_delivered": 5, "window_chunks": [(1, 0, [3, 4])],
                "traced_chunks": [(1, 0, [3, 4])], "prefill_chunk": 32,
                "admit_steps": 4, "chunk": 16}
    cell = {"config": context().config, "peaks": tiny.CPU_PEAKS, "chips": 1}
    for name in NEW_METRICS:
        assert runmod.metric_reader(name).read(None, counters, cell) is None
    dense = dict(cell, config=tiny.load("tiny_config"))
    assert runmod.metric_reader("window_paged_attention_roofline").read(
        None, counters, dense) is None


def test_walked_over_needed_reads_the_dispatch_spans(monkeypatch):
    """The sums of the two ids over the traced chunks' serve.dispatch spans;
    nothing (and no error) without a trace, such spans or the ids (the
    parent's program)."""
    import program_spans
    reader = runmod.metric_reader("kv_window_pages_walked_over_needed")

    def span(name, **ids):
        return program_spans.Span(name, 0.0, 1.0, ids, None)
    programs = {
        "ids": [span("serve.step", chunk=1),
                span("serve.dispatch", kind="admit", chunk=1,
                     kv_pages_live=40, kv_pages_walked=40,
                     kv_pages_walked_window=110, kv_pages_window_needed=90),
                span("serve.dispatch", kind="admit", chunk=2,
                     kv_pages_live=60, kv_pages_walked=60,
                     kv_pages_walked_window=115, kv_pages_window_needed=90)],
        "no ids": [span("serve.dispatch", kind="admit", chunk=1,
                        kv_pages_live=40, kv_pages_walked=40)],
        "no spans": []}
    monkeypatch.setattr(
        program_spans, "for_cell", lambda trace, cell: trace and
        program_spans.Program(programs[trace], []))
    assert reader.read("ids", {}, {}) == 1.25
    for trace in (None, "no ids", "no spans"):
        assert reader.read(trace, {}, {}) is None


@pytest.mark.parametrize("fault", ["window_ignored", "no_rope_on_sliding",
                                   "no_shared_expert"])
def test_a_planted_fault_reads_wider_than_the_program(fault):
    from drivers import serve_window_moe
    ctx = context(seed=9, seconds=0.3)
    got = serve_window_moe.run(ctx)
    ref = serve_window_moe.reference_logits(ctx, got["evidence"])
    sound = serve_window_moe.reference_gaps(ctx, got["evidence"],
                                            ref=ref).max()
    planted = serve_window_moe.reference_gaps(ctx, got["evidence"],
                                              fault=fault, ref=ref).max()
    assert planted > 2 * sound and planted > 0.1, (sound, planted)


def test_opcount_against_the_issue_s_count():
    """ISSUE 34's parameter count of the published widths."""
    import opcount_exaone_moe as oc
    import weights_exaone_moe
    with open(os.path.join(tiny.BENCH, "configs",
                           "k-exaone-236b-a23b.json")) as f:
        cfg = json.load(f)
    assert round(oc.attention_params(cfg) / 1e6, 2) == 113.25
    assert round(oc.expert_params(cfg) / 1e6, 2) == 37.75
    assert round(oc.router_params(cfg) / 1e6, 2) == 0.79
    assert round(oc.dense_mlp_params(cfg) / 1e6, 2) == 339.74
    assert oc.kv_row_bytes(cfg) == 4096
    assert oc.layers_of(cfg) == (4, 1, 1, 4)
    total = sum(int(np.prod(shape))
                for _, shape, _, _ in weights_exaone_moe.leaf_specs(cfg))
    assert abs(total / 1e9 - 3.712) < 0.001
    assert abs(oc.total_params(cfg) - total) < 1e6      # the norms
    # a slot 1.1 k deep: a full layer attends all of it, a sliding one 128
    assert oc.attended_rows(cfg, [1100, 40]) == (1140, 128 + 40)
    assert oc.attended_rows(cfg, [1100], lanes=32) == (1100, 159)
