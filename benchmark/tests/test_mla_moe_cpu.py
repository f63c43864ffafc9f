"""The second serve driver end to end at a tiny size on the CPU: the copied
window over the latent-attention, sparse-expert decoder, its reference, its
counters and the readers that take them.  No device number is asserted."""
import json
import os
import time
import types

import numpy as np
import pytest

import tiny
import harness
import run as runmod

CELL = "sarvam105b_serve_chat_c64"


def context(seed=3, seconds=0.5, trace=False):
    import jax
    with open(os.path.join(tiny.HERE, "data", "cells",
                           "tiny_mla_moe.json")) as f:
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


def test_serve_moe_driver_end_to_end():
    ctx = context(seed=2**31 + 12, seconds=1.0)
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
    assert {"moe_serve_step_mfu", "moe_serve_hbm_roofline",
            "moe_expert_load_max_over_mean", "serve_chunk_ms_p50",
            "prefill_token_share", "compiles_in_window.serve"} <= got
    # a CPU trace has no device plane; llama's counts are not this cell's
    assert not {"moe_device_share", "mla_attend_device_share",
                "serve_step_mfu", "serve_hbm_roofline",
                "paged_attention_roofline"} & got
    assert res["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
    assert all(res["metrics"][m]["value"] > 0 for m in
               ("moe_serve_step_mfu", "moe_serve_hbm_roofline"))


def test_a_program_without_routing_counts_gives_nothing():
    """The parent's program has no such counters and no such scopes: the
    readers return None and do not raise."""
    counters = {"prefill_tokens": 10, "decode_tokens": 5, "window_s": 1.0,
                "tokens_delivered": 5, "window_chunks": [(1, 0, [3, 4])],
                "admit_steps": 4, "chunk": 16}
    cell = {"config": context().config, "peaks": tiny.CPU_PEAKS, "chips": 1}
    for name in ("moe_serve_step_mfu", "moe_serve_hbm_roofline",
                 "moe_expert_load_max_over_mean", "moe_device_share",
                 "mla_attend_device_share"):
        assert runmod.metric_reader(name).read(None, counters, cell) is None


@pytest.mark.parametrize("fault", ["no_shared_expert", "top_k_minus_1",
                                   "no_routed_scaling"])
def test_a_planted_fault_reads_wider_than_the_program(fault):
    from drivers import serve_moe
    ctx = context(seed=9, seconds=0.3)
    got = serve_moe.run(ctx)
    ref = serve_moe.reference_logits(ctx, got["evidence"])
    sound = serve_moe.reference_gaps(ctx, got["evidence"], ref=ref).max()
    planted = serve_moe.reference_gaps(ctx, got["evidence"], fault=fault,
                                       ref=ref).max()
    assert planted > 2 * sound and planted > 0.1, (sound, planted)


def test_opcount_against_the_issue_s_count():
    """ISSUE 27's parameter count of the published widths."""
    import opcount_mla_moe as oc
    with open(os.path.join(tiny.BENCH, "configs", "sarvam-105b.json")) as f:
        cfg = json.load(f)
    assert round(oc.attention_params(cfg) / 1e6, 1) == 94.6
    assert round(oc.expert_params(cfg) / 1e6, 2) == 25.17
    assert round(oc.router_params(cfg) / 1e6, 2) == 0.52
    assert oc.latent_row_bytes(cfg) == 1152
    import weights_mla_moe
    total = sum(int(np.prod(shape))
                for _, shape, _, _ in weights_mla_moe.leaf_specs(cfg))
    assert abs(total / 1e9 - 4.535) < 0.001      # the issue rounds to 4.53
    assert oc.absorbed_attention_flops_per_pair(cfg) == 2 * 64 * (576 + 512)
