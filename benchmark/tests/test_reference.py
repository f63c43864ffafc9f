"""The plain reference against LlamaForCausalLM at a tiny size (float32 on
both sides), and the controls and planted faults that `correct` has to
catch, at a size a test run can hold."""
import numpy as np
import pytest

import tiny
import weights as weights_mod
from drivers import serve, train
from drivers.llama_program import build_model
from reference import decoder_f32


def test_reference_agrees_with_the_program_in_float32():
    import jax
    import paddle_tpu as paddle
    cfg = dict(tiny.load("tiny_config"), torch_dtype="float32")
    model = build_model(cfg, 7, "float32")
    model.eval()
    ids = np.random.default_rng(0).integers(0, 512, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"), paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids)).value)
    seqs = [(row[:40], row[40:]) for row in ids]
    want = decoder_f32.teacher_forced_logits(7, cfg, seqs, "float32", 48)
    for r in range(2):
        np.testing.assert_allclose(got[r, 39:47], np.asarray(want[r]),
                                   rtol=2e-4, atol=2e-4)


def test_one_leaf_alone_is_what_the_whole_model_got():
    cfg = tiny.load("tiny_config")
    whole = weights_mod.make_weights(2**31 + 3, cfg, "bfloat16")
    for name in ("embed", "layers.1.k_proj", "final_norm"):
        alone = weights_mod.make_leaf(2**31 + 3, cfg, name, "bfloat16")
        assert (np.asarray(whole[name]) == np.asarray(alone)).all()
    assert weights_mod.change_norm(whole["embed"], 2**31 + 3, cfg, "embed",
                                   "bfloat16") == 0.0


@pytest.fixture(scope="module")
def train_truth():
    ctx = tiny.context("tiny_train", seed=21)
    return ctx, train.reference_numbers(ctx)


def _fails(ctx, values):
    return [n for n, limit in ctx.workload["correct"].items()
            if not values[n] <= limit]


def test_sound_training_run_passes(train_truth):
    ctx, ref = train_truth
    values, _ = train.compare(train.run(ctx)["evidence"], ref)
    assert _fails(ctx, values) == []


def test_fault_state_left_unchanged_reads_one(train_truth, monkeypatch):
    """A step that returns its state unchanged."""
    from paddle_tpu.parallel import ShardedTrainStep
    ctx, ref = train_truth
    real = ShardedTrainStep.__call__

    def frozen(self, *batch):
        before, _ = self.train_state()
        keep = {k: v.copy() for k, v in before.items()
                if k.startswith("model.")}
        loss = real(self, *batch)
        sd = self.model.state_dict()
        for k, v in keep.items():
            sd[k[len("model."):]]._value = v
        return loss
    monkeypatch.setattr(ShardedTrainStep, "__call__", frozen)
    values, _ = train.compare(train.run(ctx)["evidence"], ref)
    assert values["change_norm_gap"] == pytest.approx(1.0)
    assert "change_norm_gap" in _fails(ctx, values)


def test_fault_half_of_the_batch_left_out(train_truth, monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    import paddle_tpu as paddle
    from paddle_tpu.parallel import ShardedTrainStep
    ctx, ref = train_truth
    real = ShardedTrainStep.__call__

    def half(self, x, y):
        n = x.shape[0] // 2
        return real(self, paddle.to_tensor(np.asarray(x.value)[:n]),
                    paddle.to_tensor(np.asarray(y.value)[:n]))
    monkeypatch.setattr(ShardedTrainStep, "__call__", half)
    values, _ = train.compare(train.run(ctx)["evidence"], ref)
    assert "grad_norm_gap" in _fails(ctx, values)
    # planted in the reference instead, it reads the same
    planted, _ = train.compare(
        train.reference_numbers(ctx, rows=slice(0, 2)), ref)
    assert planted["grad_norm_gap"] == pytest.approx(
        values["grad_norm_gap"], rel=0.1)


def test_fault_token_altered_where_it_is_produced(monkeypatch):
    from paddle_tpu.inference import ContinuousBatcher
    ctx = tiny.context("tiny_serve_closed", seed=22, seconds=0.5)
    real = ContinuousBatcher._deliver

    def altered(self, req, done):
        if req.req_id % 2 and len(req.tokens) >= 3:
            req.tokens[2] = (req.tokens[2] + 1) % 512
        return real(self, req, done)
    monkeypatch.setattr(ContinuousBatcher, "_deliver", altered)
    checks = serve.check(ctx, serve.run(ctx)["evidence"])
    assert [name for name, value, limit in checks if not value <= limit] == \
        ["served_token_gap"]


def test_lower_precision_control_fails_training(train_truth):
    """The reference one precision below (int8 products for a bf16
    configuration), put in the program's place, is not correct."""
    ctx, ref = train_truth
    values, _ = train.compare(train.reference_numbers(ctx, "int8"), ref)
    assert _fails(ctx, values) != []
