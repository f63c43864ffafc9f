"""Jaxpr lints + recompile_guard + collective-order deadlock detector.

Each lint gets a planted-defect test (the defect MUST be flagged) and a
clean-program test (no false positive on the intended pattern).  The
collective checker gets both the jaxpr extraction path and the pipeline
schedule path, including a deliberately misordered schedule caught
statically — before any device work.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.analysis import (
    lint_dtype_promotion, lint_transfers, lint_donation,
    lint_materialized_logits,
    recompile_guard, RecompileError, CollectiveOrderError,
    CollectiveEvent, collective_schedule, check_collective_order)


def _codes(findings):
    return {f.code for f in findings}


class TestDtypeLint:
    def test_silent_fp32_upcast_flagged(self):
        def amp_region(x):
            return x * np.float32(2.0)      # f32 constant promotes bf16
        f = lint_dtype_promotion(amp_region,
                                 jnp.ones((4,), jnp.bfloat16))
        assert "fp32-upcast" in _codes(f)
        assert any("bfloat16" in g.message and "float32" in g.message
                   for g in f)

    def test_clean_bf16_region_passes(self):
        def clean(x):
            y = x * jnp.bfloat16(2.0)
            return jnp.tanh(y) + x
        assert lint_dtype_promotion(clean,
                                    jnp.ones((4,), jnp.bfloat16)) == []

    def test_x64_creep_flagged(self):
        def creep(x):
            return x.astype(jnp.float64).sum()
        f = lint_dtype_promotion(creep, jnp.ones((4,), jnp.float32))
        assert "x64-creep" in _codes(f)

    def test_x64_input_flagged(self):
        f = lint_dtype_promotion(lambda x: x + 1,
                                 jnp.ones((4,), jnp.float64))
        assert "x64-input" in _codes(f)

    def test_ignore_prims_suppresses_intentional_cast(self):
        def loss_cast(x):
            return x.astype(jnp.float32).sum()
        assert "fp32-upcast" in _codes(
            lint_dtype_promotion(loss_cast, jnp.ones((4,), jnp.bfloat16)))
        assert lint_dtype_promotion(
            loss_cast, jnp.ones((4,), jnp.bfloat16),
            ignore_prims=("convert_element_type", "reduce_sum")) == []


class TestIterEqnsDedupe:
    def test_shared_subjaxpr_walked_once(self):
        # two jit call sites of one jitted fn reference the SAME
        # ClosedJaxpr: the walk must yield its body once (r22 dedupe)
        from paddle_tpu.analysis.lints import iter_eqns
        inner = jax.jit(lambda x: jnp.sin(x) * 2.0)

        def outer(x):
            return inner(x) + inner(x)

        jaxpr = jax.make_jaxpr(outer)(jnp.float32(1.0))
        eqns = list(iter_eqns(jaxpr))
        assert len([e for e in eqns
                    if e.primitive.name == "jit"]) == 2
        assert len([e for e in eqns
                    if e.primitive.name == "sin"]) == 1

    def test_lint_reports_shared_body_findings_once(self):
        inner = jax.jit(lambda x: x * np.float32(2.0))  # bf16 upcast
        x = jnp.ones((4,), jnp.bfloat16)
        once = lint_dtype_promotion(lambda v: inner(v), x)
        twice = lint_dtype_promotion(lambda v: inner(v) + inner(v), x)
        assert "fp32-upcast" in _codes(once)
        # each jit CALL SITE is still its own finding, but the shared
        # body's convert_element_type must not double
        def body_hits(findings):
            return [g for g in findings
                    if "convert_element_type" in g.message]
        assert len(body_hits(once)) == 1
        assert len(body_hits(twice)) == 1


class TestTransferLint:
    def test_in_step_device_put_flagged(self):
        def step(x):
            return jax.device_put(x, jax.devices()[0]) + 1
        f = lint_transfers(step, jnp.ones((2,), jnp.float32))
        assert "in-step-transfer" in _codes(f)

    def test_clean_step_passes(self):
        def step(x):
            return (x * x).sum()
        assert lint_transfers(step, jnp.ones((2,), jnp.float32)) == []

    def test_allow_predicate_whitelists(self):
        def step(x):
            return jax.device_put(x, jax.devices()[0]) + 1
        assert lint_transfers(step, jnp.ones((2,), jnp.float32),
                              allow=lambda eqn: True) == []


class TestDonationLint:
    def test_unaliasable_donation_flagged(self):
        def step(x, y):                  # x donated but never aliased
            return (y.sum(),)
        f = lint_donation(step, jnp.ones((4,), jnp.float32),
                          jnp.ones((3,), jnp.float32),
                          donate_argnums=(0,))
        assert "donation-unaliased" in _codes(f)
        assert any("float32[4]" in g.message for g in f)

    def test_aliased_donation_passes(self):
        def step(x, y):
            return x + y
        assert lint_donation(step, jnp.ones((4,), jnp.float32),
                             jnp.ones((4,), jnp.float32),
                             donate_argnums=(0,)) == []

    def test_accepts_prelowered(self):
        def step(x, y):
            return (y.sum(),)
        lowered = jax.jit(step, donate_argnums=(0,)).lower(
            jnp.ones((4,), jnp.float32), jnp.ones((3,), jnp.float32))
        assert "donation-unaliased" in _codes(lint_donation(lowered))


class TestRecompileGuard:
    def test_violation_reports_offending_avals(self):
        def stepfn_lint_probe(x):
            return x * 2
        j = jax.jit(stepfn_lint_probe)
        with pytest.raises(RecompileError) as ei:
            with recompile_guard(max_programs=1,
                                 match="stepfn_lint_probe"):
                j(jnp.ones((2, 2), jnp.float32))
                j(jnp.ones((3, 3), jnp.float32))    # second program
        msg = str(ei.value)
        assert "max_programs=1" in msg
        # the offending avals are in the report
        assert "ShapedArray" in msg and "float32[3,3]" in msg

    def test_within_budget_passes_and_counts(self):
        def stepfn_lint_probe2(x):
            return x + 1
        j = jax.jit(stepfn_lint_probe2)
        with recompile_guard(max_programs=2,
                             match="stepfn_lint_probe2") as g:
            j(jnp.ones((2,), jnp.float32))
            j(jnp.ones((2,), jnp.float32))     # cache hit — no compile
            j(jnp.ones((5,), jnp.float32))
        assert g.count == 2

    def test_match_filters_unrelated_compiles(self):
        def other_probe(x):
            return x - 1
        with recompile_guard(max_programs=0, match="no_such_name") as g:
            jax.jit(other_probe)(jnp.ones((2,), jnp.float32))
        assert g.count == 0

    def test_generation_cache_builds_recorded(self):
        """inference.generation announces program-cache misses; the
        guard records them in .cache_builds (and a warm cache adds
        none).  Every announced key ends with the KV-layout/decode-
        precision fingerprint plus the model's weight-only state
        (ISSUE 7/11: toggling FLAGS_kv_cache_dtype, the pool geometry
        or FLAGS_weight_only_dtype mid-process — or packing the
        model's weights — re-keys, and thus rebuilds, every cached
        program)."""
        from paddle_tpu.inference.generation import _model_program_cache

        class M:
            pass

        m = M()
        with recompile_guard(max_programs=10, label="cache") as g:
            _model_program_cache(m, ("k", 1), lambda: "prog")
            _model_program_cache(m, ("k", 1), lambda: "prog")  # warm
            _model_program_cache(m, ("k", 2), lambda: "prog")
        assert [k[:2] for k in g.cache_builds] == [("k", 1), ("k", 2)]
        assert all(k[-2][0] == "kvcfg" for k in g.cache_builds)
        assert all(k[-1][0] == "wo" for k in g.cache_builds)


class TestCollectiveOrder:
    def _mesh(self):
        return Mesh(np.array(jax.devices()[:4]).reshape(4), ("dp",))

    def test_schedule_extraction_in_program_order(self):
        from jax import shard_map
        mesh = self._mesh()

        def f(x):
            s = jax.lax.psum(x, "dp")
            t = jax.lax.ppermute(
                x, "dp", [(i, (i + 1) % 4) for i in range(4)])
            return s + t

        fm = shard_map(f, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"))
        sched = collective_schedule(fm, jnp.ones((8,), jnp.float32))
        assert [e.kind for e in sched] == ["psum", "ppermute"]
        assert all(e.domain == ("dp",) for e in sched)

    def test_identical_schedules_pass(self):
        from jax import shard_map
        mesh = self._mesh()
        fm = shard_map(lambda x: jax.lax.psum(x, "dp"), mesh=mesh,
                       in_specs=P("dp"), out_specs=P())
        sched = collective_schedule(fm, jnp.ones((8,), jnp.float32))
        assert check_collective_order(
            {r: sched for r in range(4)}) == []

    def test_misordered_ranks_flagged_with_divergence_point(self):
        a = [CollectiveEvent("psum", (("dp",), (8,)), ("dp",)),
             CollectiveEvent("all_gather", (("dp",), (8,)), ("dp",))]
        f = check_collective_order({0: a, 1: list(reversed(a))})
        assert "collective-order-divergence" in _codes(f)
        assert f[0].op_index == 0           # diverges at the first eqn
        assert "psum" in f[0].message and "all_gather" in f[0].message

    def test_rank_skipping_a_collective_is_flagged(self):
        """The classic hang: one rank never enters the collective its
        peers are blocked in.  Every scheduled rank is presumed a
        participant of an axis-name domain, so an empty schedule
        diverges instead of silently passing."""
        ev = CollectiveEvent("psum", (("dp",), (8,)), ("dp",))
        f = check_collective_order({0: [ev], 1: []})
        assert "collective-order-divergence" in _codes(f)
        assert "sequence ends" in f[0].message

    def test_disjoint_domains_do_not_cross_talk(self):
        """Events in different ordering domains (different
        communicators) are not order-constrained against each other."""
        s0 = [CollectiveEvent("psum", ("k1",), ("dp",)),
              CollectiveEvent("psum", ("k2",), ("mp",))]
        s1 = [CollectiveEvent("psum", ("k2",), ("mp",)),
              CollectiveEvent("psum", ("k1",), ("dp",))]
        assert check_collective_order({0: s0, 1: s1}) == []


class _Block(nn.Layer):
    def __init__(self, d):
        super().__init__()
        self.fc = nn.Linear(d, d)

    def forward(self, x):
        return nn.functional.gelu(self.fc(x))


def _engine(pp=2, vpp=1, depth=4):
    from paddle_tpu.distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer)
    from paddle_tpu.parallel.pipeline import PipelineEngine
    d = 4
    pl = PipelineLayer([LayerDesc(_Block, d) for _ in range(depth)],
                       loss_fn=lambda o, y: ((o - y) ** 2).mean(),
                       num_stages=pp)
    return PipelineEngine(pl, num_stages=pp,
                          num_virtual_stages=vpp)


class TestPipelineScheduleChecker:
    @pytest.mark.parametrize("schedule,vpp", [
        ("1F1B", 1), ("FThenB", 1), ("ZB", 1), ("1F1B", 2),
        ("ZBVPP", 2),
    ])
    def test_shipped_schedules_verify_clean(self, schedule, vpp):
        eng = _engine(pp=2, vpp=vpp)
        assert eng.verify_schedule(4, schedule) is eng

    def test_misordered_backwards_caught_statically(self):
        """Swap two backward micro-batches on the LAST stage: its grad
        sends to stage 0 now cross micro order.  The host dispatcher
        happens to tolerate this (async inboxes), but rendezvous
        send/recv semantics — the NCCL-equivalent — would block stage 0
        on micro 0's grad while stage 1 blocks sending micro 1's: a
        deadlock.  verify_schedule proves it without running anything."""
        eng = _engine(pp=2)
        orders = eng._orders(4, "1F1B")
        s = 1
        b_pos = [k for k, (kind, _, _) in enumerate(orders[s])
                 if kind == "b"]
        i, j = b_pos[0], b_pos[1]
        orders[s][i], orders[s][j] = orders[s][j], orders[s][i]
        with pytest.raises(CollectiveOrderError) as ei:
            eng.verify_schedule(4, "1F1B", orders=orders)
        msg = str(ei.value)
        assert "collective-order-divergence" in msg
        assert "grad" in msg

    def test_missing_op_caught_as_divergence_or_stall(self):
        eng = _engine(pp=2)
        orders = eng._orders(4, "1F1B")
        # drop stage 1's last backward: stage 0 waits for a grad that
        # is never produced
        drop = next(k for k in range(len(orders[1]) - 1, -1, -1)
                    if orders[1][k][0] == "b")
        del orders[1][drop]
        with pytest.raises(CollectiveOrderError):
            eng.verify_schedule(4, "1F1B", orders=orders)

    def test_stalled_dependency_caught(self):
        eng = _engine(pp=2)
        orders = eng._orders(4, "1F1B")
        # reverse stage 0 entirely: its first op needs a grad that can
        # only exist after its own forwards — the dispatcher stalls
        orders[0] = list(reversed(orders[0]))
        with pytest.raises(CollectiveOrderError) as ei:
            eng.verify_schedule(4, "1F1B", orders=orders)
        assert "schedule-stall" in str(ei.value) \
            or "collective-order-divergence" in str(ei.value)

    def test_flag_gates_train_batch_verification(self):
        """FLAGS_check_collective_order wires verify_schedule into
        train_batch — exercised through a schedule the static checker
        rejects (unknown to _orders, so pass orders directly)."""
        eng = _engine(pp=2)
        # sanity: the flag-gated path runs the verifier on the real
        # schedule without error (no device work: m must divide batch)
        paddle.set_flags({"FLAGS_check_collective_order": True})
        try:
            eng.verify_schedule(4, "1F1B")
            x = paddle.to_tensor(
                np.random.RandomState(0).randn(4, 4).astype("float32"))
            y = paddle.to_tensor(
                np.random.RandomState(1).randn(4, 4).astype("float32"))
            loss = eng.train_batch([x, y], 2, schedule="1F1B")
            assert np.isfinite(float(np.asarray(loss.value)))
        finally:
            paddle.set_flags({"FLAGS_check_collective_order": False})


class TestTrainerIntegration:
    def _step(self, stage=0):
        from paddle_tpu.parallel import ShardedTrainStep
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("dp", "sharding"))
        model = nn.Sequential(nn.Linear(6, 6), nn.Tanh(),
                              nn.Linear(6, 2))
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=model.parameters())
        loss = lambda o, y: ((o - y) ** 2).mean()   # noqa: E731
        return ShardedTrainStep(model, opt, mesh, loss_fn=loss,
                                sharding_stage=stage)

    def _batch(self):
        rng = np.random.RandomState(0)
        return (paddle.to_tensor(rng.randn(4, 6).astype("float32")),
                paddle.to_tensor(rng.randn(4, 2).astype("float32")))

    def test_collective_schedule_and_lint_on_clean_step(self):
        step = self._step()
        x, y = self._batch()
        sched = step.collective_schedule(x, y)
        assert isinstance(sched, list)      # 1-device mesh: no comm
        report = step.lint(x, y)
        assert report.get("transfers", []) == []
        # donated params/states/bufs must all be aliased by the module
        assert report.get("donation", []) == []

    def test_train_step_compiles_once_under_guard(self):
        """recompile_guard as the trainer's program-count assertion:
        repeat same-shape steps must reuse ONE compiled program."""
        step = self._step()
        x, y = self._batch()
        with recompile_guard(max_programs=1, match="step",
                             label="sharded train step") as g:
            step(x, y)
            step(x, y)
        assert g.count <= 1


class TestMaterializedLogitsLint:
    """lint_materialized_logits: the fused-CE contract checker — any
    [B, S, vocab] fp32 intermediate in a traced step is a full-logits
    materialization the chunked loss exists to eliminate."""

    V = 512

    def test_planted_defect_old_compute_loss(self):
        """The pre-dedup causal-LM loss (fp32 log_softmax over the full
        [B, S-1, V] logits) MUST trip the lint."""
        lbl = jnp.zeros((2, 16), jnp.int32)

        def legacy_loss(lg):
            lgf = lg[:, :-1].astype(jnp.float32)
            tgt = lbl[:, 1:]
            logp = jax.nn.log_softmax(lgf, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, tgt[..., None], axis=-1)[..., 0])

        lg = jnp.zeros((2, 16, self.V), jnp.bfloat16)
        findings = lint_materialized_logits(legacy_loss, lg,
                                            vocab_size=self.V)
        assert findings and _codes(findings) == {"materialized-logits"}
        assert any("(2, 15, 512)" in str(f.detail) for f in findings)

    def test_fused_chunked_loss_is_clean(self):
        """The chunked fused loss's per-chunk [chunk, V] slices are 2-D
        and must stay below the radar."""
        from paddle_tpu.ops.pallas.fused_cross_entropy import (
            fused_linear_cross_entropy)
        lbl = jnp.zeros((32,), jnp.int32)

        def fused(h, w):
            return fused_linear_cross_entropy(h, w, lbl, chunk_rows=8)

        h = jnp.zeros((32, 64), jnp.float32)
        w = jnp.zeros((64, self.V), jnp.float32)
        assert lint_materialized_logits(fused, h, w,
                                        vocab_size=self.V) == []
        # the gradient pass does its vocab work per chunk too
        assert lint_materialized_logits(
            jax.grad(lambda h, w: fused(h, w), argnums=(0, 1)), h, w,
            vocab_size=self.V) == []

    def test_min_rows_catches_flattened_2d(self):
        """min_rows flags a flattened [B*S, V] fp32 buffer that the 3-D
        rule alone would miss, without flagging small chunks."""
        def flat(lg):
            return jnp.sum(jax.nn.log_softmax(
                lg.astype(jnp.float32), axis=-1))

        lg = jnp.zeros((32, self.V), jnp.bfloat16)
        assert lint_materialized_logits(flat, lg,
                                        vocab_size=self.V) == []
        findings = lint_materialized_logits(flat, lg, vocab_size=self.V,
                                            min_rows=32)
        assert findings and _codes(findings) == {"materialized-logits"}

    def test_weight_grad_shape_not_flagged(self):
        # [H, V] fp32 lm-head gradients share the vocab last dim but are
        # 2-D below min_rows — not a logits materialization
        def wgrad(h, d):
            return jnp.dot(h.T, d, preferred_element_type=jnp.float32)

        h = jnp.zeros((32, 64), jnp.bfloat16)
        d = jnp.zeros((32, self.V), jnp.bfloat16)
        assert lint_materialized_logits(wgrad, h, d,
                                        vocab_size=self.V) == []

    def test_recurses_into_scan(self):
        lbl = jnp.zeros((4, 2, 16), jnp.int32)

        def stepped(lgs):
            def body(c, xs):
                lg, tg = xs
                logp = jax.nn.log_softmax(lg.astype(jnp.float32),
                                          axis=-1)
                return c - jnp.mean(jnp.take_along_axis(
                    logp, tg[..., None], axis=-1)), None
            out, _ = jax.lax.scan(body, jnp.float32(0), (lgs, lbl))
            return out

        lgs = jnp.zeros((4, 2, 16, self.V), jnp.bfloat16)
        findings = lint_materialized_logits(stepped, lgs,
                                            vocab_size=self.V)
        assert findings, "per-iteration [B, S, V] fp32 must be flagged"
