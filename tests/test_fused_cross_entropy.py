"""Fused chunked linear+cross-entropy (ISSUE 5 tentpole).

What is being validated:
  * kernel/grad parity: every fused-CE variant (jnp twin, Pallas
    interpret, online vocab-chunked, vocab-sharded psum) produces the
    reference loss AND gradients to fp32 tolerance;
  * the dedup satellite: llama/gpt/bert's compute_loss — now all routed
    through nn.functional.fused_cross_entropy — pins the exact values
    of the old hand-rolled per-model formulas;
  * the no-materialization acceptance bar: with FLAGS_fused_ce on, the
    jitted llama train step contains NO [B, S, V] fp32 intermediate
    (lint_materialized_logits clean) while the legacy path trips the
    same lint;
  * fused-vs-legacy loss/training parity within fp32-accumulation
    tolerance, eager-tape backward included.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.pallas.fused_cross_entropy import (
    fused_linear_cross_entropy)

_rng = np.random.RandomState(0)


@pytest.fixture
def fused_ce_flag():
    set_flags({"FLAGS_fused_ce": True})
    yield
    set_flags({"FLAGS_fused_ce": False})


def _data(n=30, h=16, v=64, ignore=0):
    h_ = jnp.asarray(_rng.randn(n, h).astype(np.float32))
    w = jnp.asarray(_rng.randn(h, v).astype(np.float32) * 0.1)
    b = jnp.asarray(_rng.randn(v).astype(np.float32) * 0.1)
    lbl = _rng.randint(0, v, n).astype(np.int32)
    if ignore:
        lbl[:ignore] = -1
    return h_, w, b, jnp.asarray(lbl)


def _ref_loss(h, w, b, lbl):
    lg = jnp.dot(h, w, preferred_element_type=jnp.float32)
    if b is not None:
        lg = lg + b.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    safe = jnp.maximum(lbl, 0)
    picked = jnp.take_along_axis(lg, safe[:, None], axis=-1)[:, 0]
    mask = (lbl >= 0).astype(jnp.float32)
    return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


class TestKernelParity:
    @pytest.mark.parametrize("variant", ["jnp", "pallas", "online"])
    @pytest.mark.parametrize("ignore", [0, 5])
    def test_loss_and_grads(self, variant, ignore):
        h, w, b, lbl = _data(ignore=ignore)
        kw = {"jnp": {}, "pallas": {"use_pallas": True},
              "online": {"vocab_chunk": 16}}[variant]

        def fused(h, w, b):
            return fused_linear_cross_entropy(h, w, lbl, bias=b,
                                              chunk_rows=8, **kw)

        def ref(h, w, b):
            return _ref_loss(h, w, b, lbl)

        np.testing.assert_allclose(float(fused(h, w, b)),
                                   float(ref(h, w, b)), rtol=1e-6)
        gf = jax.jit(jax.grad(fused, argnums=(0, 1, 2)))(h, w, b)
        gr = jax.grad(ref, argnums=(0, 1, 2))(h, w, b)
        for a, c in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=2e-6, rtol=2e-5)

    def test_transpose_weight_tied_embedding_layout(self):
        h, w, _, lbl = _data()

        def fused(h, wT):
            return fused_linear_cross_entropy(h, wT, lbl,
                                              transpose_weight=True,
                                              chunk_rows=8)

        def ref(h, wT):
            return _ref_loss(h, wT.T, None, lbl)

        wT = w.T
        np.testing.assert_allclose(float(fused(h, wT)),
                                   float(ref(h, wT)), rtol=1e-6)
        gf = jax.grad(fused, argnums=(0, 1))(h, wT)
        gr = jax.grad(ref, argnums=(0, 1))(h, wT)
        for a, c in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       atol=2e-6, rtol=2e-5)

    def test_ragged_rows_pad_and_ignore_index(self):
        # 30 rows with chunk 8 → padded to 32; pad rows must not leak
        # into loss, dh, or the valid-count denominator
        h, w, b, lbl = _data(n=30)
        l1 = float(fused_linear_cross_entropy(h, w, lbl, bias=b,
                                              chunk_rows=8))
        l2 = float(fused_linear_cross_entropy(h, w, lbl, bias=b,
                                              chunk_rows=30))
        np.testing.assert_allclose(l1, l2, rtol=1e-6)
        # ignore_index remap: labels equal to it drop from the mean
        lbl_ig = jnp.where(jnp.arange(30) < 4, 63, lbl)
        li = float(fused_linear_cross_entropy(h, w, lbl_ig, bias=b,
                                              ignore_index=63,
                                              chunk_rows=8))
        ref = float(_ref_loss(h, w, b, jnp.where(lbl_ig == 63, -1,
                                                 lbl_ig)))
        np.testing.assert_allclose(li, ref, rtol=1e-6)

    def test_vocab_sharded_psum_path(self):
        """ParallelCrossEntropy contract: each shard holds a [H, V/n]
        weight slice; per-shard max/denominator/picked merge with one
        pmax + psum, dh is a psum of per-shard partials.  Gradients to
        hidden AND the local weight shard must match the unsharded
        reference."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        n_shards = 4
        h, w, _, lbl = _data(n=16, h=8, v=64)
        devs = np.array(jax.devices()[:n_shards])
        mesh = Mesh(devs, ("mp",))

        # grads taken INSIDE the shard_map — the TP-layer contract:
        # each shard differentiates its replicated-h / local-w-slice
        # loss; the kernel's internal psum makes dh full and replicated,
        # dw stays the local shard's slice
        def local(h_, w_, lbl_):
            def loss(h__, w__):
                return fused_linear_cross_entropy(
                    h__, w__, lbl_, chunk_rows=8, axis_name="mp")
            l, (dh, dw) = jax.value_and_grad(
                loss, argnums=(0, 1))(h_, w_)
            return l, dh, dw

        loss, dh, dw = jax.jit(shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(None, "mp"), P()),
            out_specs=(P(), P(), P(None, "mp")),
            check_vma=False))(h, w, lbl)

        def ref(h, w):
            return _ref_loss(h, w, None, lbl)

        np.testing.assert_allclose(float(loss), float(ref(h, w)),
                                   rtol=1e-6)
        rh, rw = jax.grad(ref, argnums=(0, 1))(h, w)
        np.testing.assert_allclose(np.asarray(dh), np.asarray(rh),
                                   atol=2e-6, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(dw), np.asarray(rw),
                                   atol=2e-6, rtol=2e-5)


# ---------------------------------------------------------------------------
# dedup satellite: the shared functional pins the old per-model values

def _llama():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    paddle.seed(0)
    return LlamaForCausalLM(llama_tiny_config()), 512


def _gpt():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny_config
    paddle.seed(0)
    return GPTForCausalLM(gpt_tiny_config()), 256


def _bert():
    from paddle_tpu.models.bert import BertForMaskedLM, bert_tiny_config
    paddle.seed(0)
    return BertForMaskedLM(bert_tiny_config()), 128


class TestModelLossDedup:
    def test_llama_pins_old_formula(self):
        m, vocab = _llama()
        ids = paddle.to_tensor(_rng.randint(0, vocab, (2, 16))
                               .astype(np.int32))
        logits = m(ids)
        new = float(np.asarray(m.compute_loss(logits, ids).value))
        lgf = logits.value[:, :-1].astype(jnp.float32)
        tgt = ids.value[:, 1:].astype(jnp.int32)
        logp = jax.nn.log_softmax(lgf, axis=-1)
        old = float(-jnp.mean(jnp.take_along_axis(
            logp, tgt[..., None], axis=-1)[..., 0]))
        np.testing.assert_allclose(new, old, rtol=1e-6)

    def test_gpt_pins_old_formula(self):
        m, vocab = _gpt()
        ids = paddle.to_tensor(_rng.randint(0, vocab, (2, 12))
                               .astype(np.int32))
        logits = m(ids)
        new = float(np.asarray(m.compute_loss(logits, ids).value))
        lgf = logits.value[:, :-1].astype(jnp.float32)
        tgt = ids.value[:, 1:].astype(jnp.int32)
        logp = jax.nn.log_softmax(lgf, axis=-1)
        old = float(-jnp.mean(jnp.take_along_axis(
            logp, tgt[..., None], axis=-1)[..., 0]))
        np.testing.assert_allclose(new, old, rtol=1e-6)

    def test_bert_pins_old_formula(self):
        m, vocab = _bert()
        ids_np = _rng.randint(0, vocab, (2, 16)).astype(np.int32)
        lbl = ids_np.copy()
        lbl[0, :8] = -100                       # unmasked positions
        ids = paddle.to_tensor(ids_np)
        logits = m(ids)
        new = float(np.asarray(
            m.compute_loss(logits, paddle.to_tensor(lbl)).value))
        lg = logits.value
        tgt = jnp.maximum(jnp.asarray(lbl).astype(jnp.int32), 0)
        picked = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
        lse = jax.nn.logsumexp(lg.astype(jnp.float32), axis=-1)
        mask = (jnp.asarray(lbl) != -100).astype(jnp.float32)
        old = float(jnp.sum((lse - picked.astype(jnp.float32)) * mask)
                    / jnp.maximum(jnp.sum(mask), 1.0))
        np.testing.assert_allclose(new, old, rtol=1e-6)


class TestFusedModelPath:
    @pytest.mark.parametrize("make", [_llama, _gpt, _bert],
                             ids=["llama", "gpt", "bert"])
    def test_fused_matches_legacy_loss(self, make, fused_ce_flag):
        m, vocab = make()
        ids = paddle.to_tensor(_rng.randint(0, vocab, (2, 16))
                               .astype(np.int32))
        set_flags({"FLAGS_fused_ce": False})
        legacy = float(np.asarray(m.compute_loss(m(ids), ids).value))
        set_flags({"FLAGS_fused_ce": True})
        out = m(ids)
        assert out.shape[-1] != vocab, \
            "fused-mode training forward must return hidden states"
        fused = float(np.asarray(m.compute_loss(out, ids).value))
        np.testing.assert_allclose(fused, legacy, atol=5e-4, rtol=1e-5)

    def test_eval_mode_keeps_logits(self, fused_ce_flag):
        m, vocab = _llama()
        ids = paddle.to_tensor(_rng.randint(0, vocab, (2, 8))
                               .astype(np.int32))
        m.eval()
        assert m(ids).shape[-1] == vocab

    def test_eager_tape_backward(self, fused_ce_flag):
        m, vocab = _llama()
        ids = paddle.to_tensor(_rng.randint(0, vocab, (2, 8))
                               .astype(np.int32))
        loss = m.compute_loss(m(ids), ids)
        loss.backward()
        head = m.lm_head if not m.config.tie_word_embeddings \
            else m.llama.embed_tokens
        assert head.grad is not None
        assert float(jnp.sum(jnp.abs(head.grad.value))) > 0


class TestNoMaterializedLogits:
    """Acceptance bar: jaxpr inspection of the jitted llama train step."""

    def _step(self):
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh
        m, vocab = _llama()
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters(),
                                     weight_decay=0.1)
        step = ShardedTrainStep(m, opt,
                                build_mesh(devices=jax.devices()[:1]),
                                sharding_stage=0)
        ids = paddle.to_tensor(_rng.randint(0, vocab, (2, 16))
                               .astype(np.int32))
        return step, ids

    def test_fused_step_has_no_full_logits(self, fused_ce_flag):
        step, ids = self._step()
        float(np.asarray(step(ids, ids).value))   # build + run
        findings = step.lint(ids, ids, donation=False, transfers=False,
                             logits=True)["logits"]
        assert not findings, [f.message for f in findings]

    def test_legacy_step_trips_the_lint(self):
        step, ids = self._step()
        float(np.asarray(step(ids, ids).value))
        findings = step.lint(ids, ids, donation=False, transfers=False,
                             logits=True)["logits"]
        assert findings, "legacy fp32 log_softmax must be flagged"
        assert any("512" in f.message for f in findings)

    def test_fused_training_tracks_legacy(self, fused_ce_flag):
        set_flags({"FLAGS_fused_ce": False})
        step_l, ids = self._step()
        legacy = [float(np.asarray(step_l(ids, ids).value))
                  for _ in range(4)]
        set_flags({"FLAGS_fused_ce": True})
        step_f, _ = self._step()
        fused = [float(np.asarray(step_f(ids, ids).value))
                 for _ in range(4)]
        np.testing.assert_allclose(fused, legacy, atol=5e-3)
