"""The third serve driver end to end at a tiny size on the CPU: the copied
window over the block-diffusion sparse-expert decoder, its reference's
replay, the block schedule's counters and the readers that take them.  No
device number is asserted."""
import json
import os
import time
import types

import numpy as np
import pytest

import tiny
import harness
import run as runmod

CELL = "sdar30b_serve_gen_c64"


def context(seed=3, seconds=0.5, trace=False):
    import jax
    data = tiny.load("tiny_sdar")
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


def test_serve_diffusion_driver_end_to_end():
    ctx = context(seed=2**31 + 12, seconds=1.0)
    res = runmod.execute(ctx, tiny.bench_json())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert set(res["checks"]) == {"served_token_gap", "served_token_off_share",
                                  "served_lane_off_share",
                                  "served_pass_count_off_share",
                                  "served_block_passes_max"}
    # the schedule as published: a lane a pass, five passes a whole block
    assert res["checks"]["served_pass_count_off_share"]["value"] == 0
    assert res["checks"]["served_block_passes_max"]["value"] == 5


def test_traced_run_reports_the_schedule_s_metrics():
    res = runmod.execute(context(seconds=0.5, trace=True), tiny.bench_json())
    got = set(res["metrics"])
    assert {"diffusion_passes_per_block", "diffusion_serve_step_mfu",
            "diffusion_serve_hbm_roofline", "moe_expert_load_max_over_mean",
            "serve_chunk_ms_p50", "prefill_token_share",
            "compiles_in_window.serve"} <= got
    # a CPU trace has no device plane; the other cells' counts are not
    # this cell's
    assert not {"diffusion_sample_device_share", "moe_device_share",
                "paged_attention_roofline", "moe_serve_step_mfu",
                "serve_step_mfu", "mla_attend_device_share"} & got
    per_block = res["metrics"]["diffusion_passes_per_block"]["value"]
    assert 4.0 < per_block <= 5.0     # 5 a whole block, fewer for a first
    assert all(res["metrics"][m]["value"] > 0 for m in
               ("diffusion_serve_step_mfu", "diffusion_serve_hbm_roofline"))


def test_a_program_without_the_block_schedule_gives_nothing():
    """The parent's program has no such counters and no such scopes: the
    readers return None and do not raise."""
    counters = {"prefill_tokens": 10, "decode_tokens": 5, "window_s": 1.0,
                "tokens_delivered": 5, "window_chunks": [(1, 0, [3, 4])],
                "admit_steps": 4, "chunk": 16, "moe_assignments": 40,
                "moe_expert_steps_hit": 8}
    cell = {"config": context().config, "peaks": tiny.CPU_PEAKS, "chips": 1}
    for name in ("diffusion_passes_per_block", "diffusion_serve_step_mfu",
                 "diffusion_serve_hbm_roofline",
                 "diffusion_sample_device_share"):
        assert runmod.metric_reader(name).read(None, counters, cell) is None


def test_replayed_logits_are_generate_s_own():
    """What decides `correct` is held to SDAR's loop as written: the replay
    of a generated request gives the logits generate() itself saw at every
    denoise pass of every whole block."""
    import jax.numpy as jnp
    import weights_sdar_moe
    from reference import block_diffusion_moe_f32 as ref
    cfg = context().config
    params = {n: v.astype(jnp.float32)
              for n, v in weights_sdar_moe.leaves(11, cfg, "float32")}
    prompt = np.random.RandomState(1).randint(0, 250, 13).astype(np.int32)
    g = ref.generate(params, prompt, 14, cfg)
    (plan, logits), = ref.replayed_logits(
        11, cfg, [(prompt, g["tokens"], g["passes"])], "float32", 32)
    # blocks 3 (a prompt token and three lanes), 4 and 5; block 6 is cut
    # by max_new_tokens and not judged
    assert len(plan["states"]) == logits.shape[0] == 3 + 4 + 4
    for s, (b, p, want) in enumerate(g["pass_logits"][:11]):
        assert plan["states"][s] == (b, p)
        np.testing.assert_allclose(np.asarray(logits[s]), want,
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("fault", ["causal_in_block", "next_token_shift",
                                   "no_qk_norm", "top_k_minus_1"])
def test_a_planted_fault_reads_wider_than_the_program(fault):
    from drivers import serve_diffusion
    ctx = context(seed=9, seconds=0.3)
    got = serve_diffusion.run(ctx)
    sound = serve_diffusion.numbers(
        ctx, serve_diffusion.judgement(ctx, got["evidence"]))
    planted = serve_diffusion.numbers(
        ctx, serve_diffusion.judgement(ctx, got["evidence"], fault=fault))
    assert planted["served_token_gap"] > 10 * sound["served_token_gap"] \
        and planted["served_token_gap"] > 0.05, (sound, planted)


@pytest.mark.parametrize("fault, caught_by", [
    ("all_lanes_in_pass_0", "served_pass_count_off_share"),
    ("one_step_skipped", "served_pass_count_off_share"),
    ("position_order", "served_lane_off_share")])
def test_a_fault_of_the_schedule_fails_correct(fault, caught_by, monkeypatch):
    """`correct` holds the served run to the block schedule, not only to
    the forward: a program that fixes another number of lanes a pass, or
    the right number in position order, fails a limit though every token
    it serves is the reference's best in its lane."""
    import control_diffusion
    from drivers import serve_diffusion
    from paddle_tpu.inference import serving
    for name in ("_step_quotas", "_unmask_choice"):       # restored after
        monkeypatch.setattr(serving, name, getattr(serving, name))
    control_diffusion.SCHEDULE_FAULTS[fault](serving)
    ctx = context(seed=9, seconds=0.3)
    got = serve_diffusion.run(ctx)
    checks = {name: (value, limit) for name, value, limit
              in serve_diffusion.check(ctx, got["evidence"])}
    value, limit = checks[caught_by]
    assert value > limit, checks
    # no token is wrong: the forward is sound
    assert checks["served_token_off_share"][0] \
        <= checks["served_token_off_share"][1], checks
    if fault == "one_step_skipped":     # one pass in three of a whole block
        assert 25.0 < value < 40.0 and \
            checks["served_block_passes_max"][0] == 4
    if fault == "all_lanes_in_pass_0":
        # every pass but a first block's that had one lane left to fix;
        # and the lanes' gap reads nothing, which is why the count is held
        assert value > 90.0 and checks["served_lane_off_share"][0] == 0 \
            and checks["served_block_passes_max"][0] == 2


def test_opcount_against_the_issue_s_count():
    """ISSUE 32's parameter count of the published widths."""
    import opcount
    import opcount_sdar_moe as oc
    import weights_sdar_moe
    with open(os.path.join(tiny.BENCH, "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        cfg = json.load(f)
    assert round(oc.attention_params(cfg) / 1e6, 2) == 18.87
    assert round(oc.router_params(cfg) / 1e6, 2) == 0.26
    assert round(cfg["num_experts"] * oc.expert_params(cfg) / 1e6, 2) == 603.98
    assert round(2 * oc.head_params(cfg) / 1e6, 1) == 622.3
    assert opcount.kv_bytes_per_token(cfg) == 2048
    assert oc.attention_flops_per_pair(cfg) == 4 * 128 * 32
    total = sum(int(np.prod(shape))
                for _, shape, _, _ in weights_sdar_moe.leaf_specs(cfg))
    assert abs(total / 1e9 - 4.984) < 0.001
    # one decode pass of 64 slots: every expert of every layer hit
    step = oc.serve_bytes(cfg, 1, 0, 7 * 128)
    assert abs(step / 1e9 - 9.35) < 0.01
