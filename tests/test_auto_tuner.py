"""Auto-tuner: prune rules, memory model, ranked search.

Reference: python/paddle/distributed/auto_tuner/ (tuner.py, prune.py,
memory_cost_model.py interface).
"""
import numpy as np
import pytest

from paddle_tpu.distributed.auto_tuner import (AutoTuner, tune,
                                               estimate_memory_bytes,
                                               estimate_step_time)
from paddle_tpu.distributed.auto_tuner.prune import prune_candidate

LLAMA_1B = dict(hidden_size=2560, intermediate_size=6912,
                num_hidden_layers=14, num_attention_heads=20,
                num_key_value_heads=4, vocab_size=8192, seq_len=2048)


def _cand(**kw):
    c = dict(dp=1, mp=1, pp=1, vpp=1, sharding=8, sharding_stage=3,
             micro_batch_size=4, recompute="selective")
    c.update(kw)
    return c


class TestPrune:
    CFG = {"model_cfg": LLAMA_1B, "n_devices": 8,
           "global_batch_size": 64, "hbm_bytes": 95e9}

    def test_device_product(self):
        assert prune_candidate(self.CFG, _cand(dp=2)) is not None
        assert prune_candidate(self.CFG, _cand()) is None

    def test_mp_divisibility(self):
        # 20 heads: mp=8 does not divide
        bad = _cand(mp=8, sharding=1, sharding_stage=0)
        assert "mp" in prune_candidate(self.CFG, bad)

    def test_pp_layers(self):
        bad = _cand(pp=4, sharding=2)  # 14 % 4 != 0
        assert "layers" in prune_candidate(self.CFG, bad)

    def test_micro_divisibility(self):
        bad = _cand(micro_batch_size=16, sharding=8)  # 64/8=8 % 16
        assert "micro" in prune_candidate(self.CFG, bad)

    def test_sharding_stage_consistency(self):
        bad = _cand(sharding=1, dp=8, sharding_stage=3)
        assert "sharding" in prune_candidate(self.CFG, bad)

    def test_memory_prune(self):
        # 1B params fp32+moments replicated on a 16G chip, no recompute:
        # must be pruned by memory
        cfg = dict(self.CFG, hbm_bytes=16e9)
        bad = _cand(sharding=1, dp=8, sharding_stage=0,
                    recompute="none", micro_batch_size=8)
        assert "HBM" in prune_candidate(cfg, bad)


class TestMemoryModel:
    def test_bench_config_fits_v5e(self):
        """The actual round-3 bench point (1 chip, stage 3 no-op,
        selective recompute, b=8) must be estimated under 16G."""
        est = estimate_memory_bytes(
            LLAMA_1B, _cand(sharding=1, sharding_stage=0,
                            micro_batch_size=8),
            dtype_bytes=4.0, moment_bytes=2.0)
        assert 8e9 < est.total < 16e9, est

    def test_zero3_shards_params(self):
        full = estimate_memory_bytes(LLAMA_1B,
                                     _cand(sharding=1, dp=8,
                                           sharding_stage=0))
        sharded = estimate_memory_bytes(LLAMA_1B, _cand())
        assert sharded.params < full.params / 4
        assert sharded.optimizer < full.optimizer / 4

    def test_recompute_cuts_activations(self):
        none = estimate_memory_bytes(LLAMA_1B, _cand(recompute="none"))
        sel = estimate_memory_bytes(LLAMA_1B,
                                    _cand(recompute="selective"))
        full = estimate_memory_bytes(LLAMA_1B, _cand(recompute="full"))
        assert full.activations < sel.activations < none.activations


class TestTune:
    def test_ranked_output(self):
        ranked = tune(LLAMA_1B, n_devices=8, global_batch_size=64,
                      chip="v5p")
        assert len(ranked) > 10
        times = [c["est_step_time"] for c in ranked]
        assert times == sorted(times)
        for c in ranked[:3]:
            assert c["dp"] * c["mp"] * c["pp"] * c["sharding"] == 8
            assert c["est_memory_gb"] < 95

    def test_8dev_choice_for_1b_llama(self):
        """Pin the 8-device strategy for the 1B llama on v5p: plenty of
        HBM -> the tuner should avoid pp (bubble) and avoid recompute
        (replay flops), using pure data-parallel ZeRO or DP."""
        best = tune(LLAMA_1B, n_devices=8, global_batch_size=64,
                    chip="v5p")[0]
        assert best["pp"] == 1
        assert best["recompute"] == "none"
        assert best["dp"] * best["sharding"] == 8
        assert best["mp"] == 1

    def test_memory_constrained_prefers_zero3(self):
        """On 16G chips with the reference O2 scheme (bf16 params + fp32
        master + fp32 moments = 14 bytes/param) replicated state cannot
        fit: every surviving candidate shards state or the model."""
        ranked = tune(LLAMA_1B, n_devices=8, global_batch_size=64,
                      chip="v5e", hbm_bytes=16e9,
                      param_bytes=6.0, moment_bytes=4.0)
        assert ranked, "no feasible candidate found"
        assert all(c["sharding_stage"] >= 1 or c["pp"] > 1 or
                   c["mp"] > 1 for c in ranked)

    def test_compile_check_top_candidate(self):
        """The top candidate compiles through the real ShardedTrainStep
        on the 8-device virtual mesh."""
        ranked = tune(LLAMA_1B, n_devices=8, global_batch_size=64,
                      chip="v5p", compile_check=True, top_k=1)
        assert ranked


class TestCostModelForm:
    def test_step_time_is_linear_in_inverse_efficiency(self):
        """e(m) = C/m + F in the assumed matmul efficiency m: the form a
        calibration against measured steps solves for m."""
        def e(m):
            return estimate_step_time(LLAMA_1B, _cand(), 64, chip="v5p",
                                      mfu_assumption=m)
        C = (e(0.6) - e(1.0)) / (1 / 0.6 - 1.0)
        F = e(1.0) - C
        # two points fix the line; a third lies on it
        np.testing.assert_allclose(C / 0.8 + F, e(0.8), rtol=1e-9)
