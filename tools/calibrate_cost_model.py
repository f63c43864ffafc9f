"""Calibrate the auto-tuner roofline cost model against measured steps.

Round-5 verdict (weak #7): the planner ranks strategies with
`auto_tuner.cost_model.estimate_step_time`, but no artifact compared a
prediction against a MEASURED step time.  This tool closes that loop on
the single real chip: it measures the full train-step wall time for the
llama-1B and bert-base bench configs (same phase-timing scaffold as
tools/profile_mfu.py), computes the model's prediction for the same
(model, strategy, batch) point, and reports measured/predicted ratios
plus the `mfu_assumption` each measurement implies.  Writes
CALIBRATION_r05.md at the repo root.

Reference analog: `auto_tuner` trial runs measure real step time per
candidate; this framework's planner is analytic, so calibration is the
honest substitute (`/root/reference/python/paddle/distributed/auto_tuner/
tuner.py` trial loop).

On CPU (no chip) the tool still runs the tiny configs and reports the
plumbing (ratios will be meaningless there; the artifact is only written
on TPU).
"""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _llama_point():
    import jax
    from tools.profile_mfu import profile_llama
    on_tpu = jax.default_backend() == "tpu"
    row = profile_llama()
    if on_tpu:
        model_cfg = dict(vocab_size=8192, hidden_size=2560,
                         intermediate_size=6912, num_hidden_layers=14,
                         num_attention_heads=20, num_key_value_heads=4,
                         seq_len=2048)
        batch = 4
        strategy = {"dp": 1, "mp": 1, "pp": 1, "sharding": 1,
                    "sharding_stage": 3, "micro_batch_size": batch,
                    "recompute": "selective"}
    else:
        model_cfg = dict(vocab_size=256, hidden_size=128,
                         intermediate_size=384, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=4,
                         seq_len=128)
        batch = 2
        strategy = {"dp": 1, "mp": 1, "pp": 1, "sharding": 1,
                    "sharding_stage": 3, "micro_batch_size": batch,
                    "recompute": "none"}
    return "llama-1B" if on_tpu else "llama-tiny", row, model_cfg, \
        strategy, batch


def _bert_point():
    import jax
    from tools.profile_mfu import profile_bert
    on_tpu = jax.default_backend() == "tpu"
    row = profile_bert()
    if on_tpu:
        model_cfg = dict(vocab_size=30522, hidden_size=768,
                         intermediate_size=3072, num_hidden_layers=12,
                         num_attention_heads=12, seq_len=512)
        batch = 64
    else:
        model_cfg = dict(vocab_size=128, hidden_size=64,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4, seq_len=32)
        batch = 2
    strategy = {"dp": 1, "mp": 1, "pp": 1, "sharding": 1,
                "sharding_stage": 1, "micro_batch_size": batch,
                "recompute": "none"}
    return "bert-base" if on_tpu else "bert-tiny", row, model_cfg, \
        strategy, batch


def _chip_name():
    """The generation the cost model is asked about: this device's row
    of the cost ledger's table (an unknown TPU raises there); off-TPU the
    v5e the r05 calibration rows were measured on."""
    from paddle_tpu.telemetry.costledger import backend_peaks
    return backend_peaks()["chip"] or "v5e"


def calibrate():
    from paddle_tpu.distributed.auto_tuner.cost_model import (
        estimate_step_time)
    chip = _chip_name()
    results = []
    for label, row, model_cfg, strategy, batch in (
            _llama_point(), _bert_point()):
        measured_s = row["t_full_ms"] / 1e3
        # estimate_step_time(m) = C/m + F (compute term over the mfu
        # assumption plus fixed HBM/comm/bubble terms); two evaluations
        # extract C and F, then the implied assumption solves
        # C/m + F = measured
        e06 = estimate_step_time(model_cfg, strategy, batch, chip=chip,
                                 mfu_assumption=0.6)
        e10 = estimate_step_time(model_cfg, strategy, batch, chip=chip,
                                 mfu_assumption=1.0)
        # e(m) = C/m + F  ->  C = (e06 - e10)/(1/0.6 - 1), F = e10 - C
        C = (e06 - e10) / (1 / 0.6 - 1.0)
        F = e10 - C
        implied = C / max(measured_s - F, 1e-9)
        results.append(dict(label=label, measured_ms=measured_s * 1e3,
                            predicted_ms=e06 * 1e3,
                            ratio=measured_s / e06,
                            implied_mfu=implied,
                            mfu_measured=row["mfu_full"]))
    return chip, results


def render(chip, results):
    lines = [
        "# Cost-model calibration (round 5, measured on the real chip)",
        "",
        "`auto_tuner.cost_model.estimate_step_time` predictions vs "
        "measured full-step times (median-of-reps, same scaffold as "
        "PROFILE_r05.md), single chip `%s`, default "
        "`mfu_assumption=0.6`.  `implied mfu` is the assumption that "
        "would make the prediction exact after subtracting the model's "
        "analytic HBM/comm/bubble terms — the number to feed back when "
        "the planner targets this chip+model family.  Regenerate: "
        "`python tools/calibrate_cost_model.py`." % chip,
        "",
        "| config | measured ms | predicted ms (mfu=0.6) | "
        "measured/predicted | implied mfu_assumption | measured MFU |",
        "|---|---|---|---|---|---|",
    ]
    for r in results:
        lines.append(
            f"| {r['label']} | {r['measured_ms']:.1f} "
            f"| {r['predicted_ms']:.1f} | {r['ratio']:.2f} "
            f"| {r['implied_mfu']:.3f} | {r['mfu_measured']:.3f} |")
    lines += [
        "",
        "Reading: ratio ≈ 1 means the roofline + fixed terms rank "
        "strategies on a truthful scale for this family; a consistent "
        "ratio ≠ 1 is a pure rescale (harmless for ARGMAX ranking, "
        "which is the planner's use) but the implied mfu per family is "
        "recorded so absolute step-time/ETA features can calibrate.",
    ]
    return "\n".join(lines) + "\n"


def main():
    import jax
    chip, results = calibrate()
    md = render(chip, results)
    print(md)
    if jax.default_backend() == "tpu":
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        with open(os.path.join(root, "CALIBRATION_r05.md"), "w") as f:
            f.write(md)


if __name__ == "__main__":
    main()
