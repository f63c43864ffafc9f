"""The harness end to end at tiny sizes on the CPU: both drivers, both arrival
modes, a mesh that exists only as data, the per-layer readers, the refusal to
run without a TPU.  No device number is asserted: there is no device."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny
import run as runmod


def _execute(cell, real_cell, **kw):
    ctx = tiny.context(cell, **kw)
    ctx.cell["name"] = real_cell      # report what the real cell reports
    return ctx, runmod.execute(ctx, tiny.bench_json())


@pytest.mark.parametrize("cell", ["tiny_train", "tiny_train_4dev"])
def test_train_driver_end_to_end(cell):
    import jax
    if len(jax.devices()) < tiny.load(cell)["workload"]["chips"]:
        pytest.skip("needs four (virtual) devices")
    ctx, res = _execute(cell, "mistral7b_train_2k", seed=2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(ctx.workload["correct"])


@pytest.mark.parametrize("cell", ["tiny_serve_closed", "tiny_serve_open"])
def test_serve_driver_end_to_end(cell):
    ctx, res = _execute(cell, "dsllm7b_serve_chat_c24", seed=2**31 + 12,
                        seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_run_reports_per_layer_metrics_and_no_device_share():
    _, res = _execute("tiny_serve_closed", "dsllm7b_serve_chat_c24",
                      seconds=0.5, trace=True)
    got = set(res["metrics"])
    assert {"serve_chunk_ms_p50", "prefill_token_share", "serve_step_mfu",
            "serve_hbm_roofline", "compiles_in_window.serve"} <= got
    # a CPU trace has no device plane: the readers that need one say nothing
    assert not {"paged_attention_roofline", "device_idle_share.serve"} & got
    assert res["device"]["busy_s"] == 0.0
    assert res["metrics"]["compiles_in_window.serve"]["value"] == 0


def test_same_seed_same_evidence():
    from drivers import serve
    a = serve.run(tiny.context("tiny_serve_closed", seed=5, seconds=0.3))
    b = serve.run(tiny.context("tiny_serve_closed", seed=5, seconds=0.3))
    pa, ta = a["evidence"]["sample"][0]
    pb, tb = b["evidence"]["sample"][0]
    assert (pa == pb).all() and (ta[:4] == tb[:4]).all()


def test_no_tpu_no_result():
    """On this machine jax finds no TPU: the command fails and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(tiny.BENCH, "run.py"), "--workload",
         "mistral7b_train_2k", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_unknown_device_kind_has_no_peak():
    import harness
    with pytest.raises(SystemExit):
        harness.load_peaks("cpu")
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_benchmark_json_names_only_files_that_exist():
    bench = tiny.bench_json()
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(tiny.ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        _, cell, _, wl = runmod.load_cell(w["name"])
        assert os.path.exists(os.path.join(tiny.BENCH, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(tiny.BENCH, "drivers",
                                           wl["driver"] + ".py"))
    names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in names
        assert hasattr(runmod.metric_reader(m["name"]), "read")
