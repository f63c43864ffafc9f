"""Optimizer + LR scheduler + AMP tests."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework.tensor import Parameter


def make_param(val):
    p = Parameter(np.asarray(val, np.float32))
    return p


def set_grad(p, g):
    p.grad = paddle.to_tensor(np.asarray(g, np.float32))


class TestSGD:
    def test_step(self):
        p = make_param([1.0, 2.0])
        opt = paddle.optimizer.SGD(0.1, parameters=[p])
        set_grad(p, [1.0, 1.0])
        opt.step()
        np.testing.assert_allclose(p.numpy(), [0.9, 1.9], rtol=1e-6)

    def test_weight_decay(self):
        p = make_param([1.0])
        opt = paddle.optimizer.SGD(0.1, parameters=[p], weight_decay=0.5)
        set_grad(p, [0.0])
        opt.step()
        np.testing.assert_allclose(p.numpy(), [1.0 - 0.1 * 0.5], rtol=1e-6)


class TestMomentum:
    def test_velocity(self):
        p = make_param([0.0])
        opt = paddle.optimizer.Momentum(0.1, 0.9, parameters=[p])
        set_grad(p, [1.0])
        opt.step()
        np.testing.assert_allclose(p.numpy(), [-0.1])
        set_grad(p, [1.0])
        opt.step()
        # v2 = 0.9*1 + 1 = 1.9 → p = -0.1 - 0.19
        np.testing.assert_allclose(p.numpy(), [-0.29], rtol=1e-5)


class TestAdam:
    def test_first_step_size(self):
        p = make_param([1.0])
        opt = paddle.optimizer.Adam(0.001, parameters=[p])
        set_grad(p, [10.0])
        opt.step()
        # adam first step ≈ lr regardless of grad scale
        np.testing.assert_allclose(p.numpy(), [1.0 - 0.001], rtol=1e-4)

    def test_reference_sequence(self):
        # compare against a hand-rolled adam
        rng = np.random.RandomState(0)
        w = rng.rand(4).astype(np.float32)
        g_seq = [rng.rand(4).astype(np.float32) for _ in range(5)]
        p = make_param(w.copy())
        opt = paddle.optimizer.Adam(0.01, parameters=[p])
        m = np.zeros(4)
        v = np.zeros(4)
        ref = w.astype(np.float64).copy()
        for t, g in enumerate(g_seq, 1):
            set_grad(p, g)
            opt.step()
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            mh = m / (1 - 0.9 ** t)
            vh = v / (1 - 0.999 ** t)
            ref -= 0.01 * mh / (np.sqrt(vh) + 1e-8)
        np.testing.assert_allclose(p.numpy(), ref, rtol=1e-4)


class TestAdamW:
    def test_decoupled_decay(self):
        p = make_param([1.0])
        opt = paddle.optimizer.AdamW(0.1, parameters=[p], weight_decay=0.1)
        set_grad(p, [0.0])
        opt.step()
        # zero grad → pure decay: p -= lr * wd * p
        np.testing.assert_allclose(p.numpy(), [1.0 - 0.1 * 0.1 * 1.0],
                                   rtol=1e-5)

    def test_apply_decay_param_fun(self):
        p = make_param([1.0])
        p.name = "bias"
        opt = paddle.optimizer.AdamW(
            0.1, parameters=[p], weight_decay=0.5,
            apply_decay_param_fun=lambda n: "bias" not in n)
        set_grad(p, [0.0])
        opt.step()
        np.testing.assert_allclose(p.numpy(), [1.0])  # no decay applied


class TestMultiPrecision:
    def test_bf16_master_weights(self):
        p = Parameter(np.asarray([1.0], np.float32))
        p._value = p._value.astype("bfloat16")
        opt = paddle.optimizer.AdamW(1e-4, parameters=[p],
                                     multi_precision=True)
        for _ in range(10):
            set_grad(p, [0.01])
            opt.step()
        # master weights keep fp32 precision across tiny updates
        assert id(p) in opt._master_weights


class TestLRSchedulers:
    def test_scheduler_drives_optimizer(self):
        sched = paddle.optimizer.lr.StepDecay(0.1, step_size=2, gamma=0.5)
        p = make_param([1.0])
        opt = paddle.optimizer.SGD(sched, parameters=[p])
        assert opt.get_lr() == pytest.approx(0.1)
        sched.step()
        sched.step()
        assert opt.get_lr() == pytest.approx(0.05)

    def test_cosine(self):
        s = paddle.optimizer.lr.CosineAnnealingDecay(1.0, T_max=10)
        assert s() == pytest.approx(1.0)
        s.step(10)
        assert s() == pytest.approx(0.0, abs=1e-9)

    def test_warmup(self):
        s = paddle.optimizer.lr.LinearWarmup(0.1, 10, 0.0, 0.1)
        s.step(5)
        assert s() == pytest.approx(0.05)
        s.step(20)
        assert s() == pytest.approx(0.1)

    def test_piecewise(self):
        s = paddle.optimizer.lr.PiecewiseDecay([3, 6], [0.1, 0.01, 0.001])
        s.step(0)
        assert s() == pytest.approx(0.1)
        s.step(4)
        assert s() == pytest.approx(0.01)
        s.step(100)
        assert s() == pytest.approx(0.001)

    def test_reduce_on_plateau(self):
        s = paddle.optimizer.lr.ReduceOnPlateau(0.1, patience=1, factor=0.5)
        s.step(1.0)
        s.step(1.0)
        s.step(1.0)
        assert s() == pytest.approx(0.05)


class TestGradClipIntegration:
    def test_clip_in_optimizer(self):
        p = make_param([0.0])
        clip = nn.ClipGradByGlobalNorm(0.5)
        opt = paddle.optimizer.SGD(1.0, parameters=[p], grad_clip=clip)
        set_grad(p, [10.0])
        opt.step()
        np.testing.assert_allclose(p.numpy(), [-0.5], rtol=1e-5)


class TestAMP:
    def test_auto_cast_matmul_bf16(self):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            a = paddle.ones([2, 2])
            out = paddle.matmul(a, a)
        assert out.dtype == paddle.bfloat16

    def test_black_list_stays_fp32(self):
        with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
            x = paddle.ones([4], "bfloat16")
            out = paddle.mean(x)
        assert out.dtype == paddle.float32

    def test_decorate_o2(self):
        net = nn.Linear(2, 2)
        net2 = paddle.amp.decorate(net, level="O2", dtype="bfloat16")
        assert net2.weight.dtype == paddle.bfloat16

    def test_grad_scaler_noop_path(self):
        p = make_param([1.0])
        opt = paddle.optimizer.SGD(0.1, parameters=[p])
        scaler = paddle.amp.GradScaler(use_dynamic_loss_scaling=False)
        loss = paddle.to_tensor(1.0)
        scaled = scaler.scale(loss)
        assert float(scaled) == 1.0
        set_grad(p, [1.0])
        scaler.step(opt)
        scaler.update()
        np.testing.assert_allclose(p.numpy(), [0.9], rtol=1e-6)


class TestStateDict:
    def test_optimizer_state_roundtrip(self):
        p = make_param([1.0, 2.0])
        p.name = "w0"
        opt = paddle.optimizer.Adam(0.01, parameters=[p])
        set_grad(p, [0.1, 0.1])
        opt.step()
        sd = opt.state_dict()
        p2 = make_param([1.0, 2.0])
        p2.name = "w0"
        opt2 = paddle.optimizer.Adam(0.01, parameters=[p2])
        opt2.set_state_dict(sd)
        assert opt2._step_count == 1
        np.testing.assert_allclose(
            opt2._accumulators[id(p2)]["moment1"],
            opt._accumulators[id(p)]["moment1"])


class TestFusedAdamW:
    """Pallas fused kernel vs the pure Adam update rule (interpret mode),
    and the master-weight path inside the jitted trainers."""

    @pytest.mark.parametrize("n", [1000, 512 * 1024 + 3])
    def test_kernel_matches_pure_rule(self, n):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
        from paddle_tpu.optimizer.optimizer import Adam

        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(n).astype(np.float32)).astype(jnp.bfloat16)
        m = jnp.asarray(rng.randn(n).astype(np.float32)) * 0.1
        v = jnp.abs(jnp.asarray(rng.randn(n).astype(np.float32))) * 0.01
        master = jnp.asarray(rng.randn(n).astype(np.float32))
        lr, step, wd = 1e-3, 3, 0.1

        p_f, m_f, v_f, mst_f = fused_adamw(
            g, m, v, master, lr, step, b1=0.9, b2=0.999, eps=1e-8,
            wd=wd, decoupled=True, out_dtype=jnp.bfloat16)
        ref_mst, ref_state = Adam._update(
            master, g.astype(jnp.float32),
            {"moment1": m, "moment2": v}, lr, wd, step,
            b1=0.9, b2=0.999, eps=1e-8, decoupled=True)
        np.testing.assert_allclose(np.asarray(mst_f), np.asarray(ref_mst),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(m_f),
                                   np.asarray(ref_state["moment1"]),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(v_f),
                                   np.asarray(ref_state["moment2"]),
                                   atol=1e-6, rtol=1e-6)
        # p is the bf16 cast of the (1e-6-tolerance) master: values near a
        # rounding boundary may flip one bf16 ulp
        np.testing.assert_allclose(
            np.asarray(p_f.astype(jnp.float32)),
            np.asarray(ref_mst.astype(jnp.bfloat16).astype(jnp.float32)),
            atol=1e-2, rtol=1e-2)

    def test_trainstep_master_weights(self):
        """bf16 model + multi_precision: the fp32 master accumulates
        updates a bf16-only parameter would lose."""
        import jax.numpy as jnp
        from paddle_tpu.jit import TrainStep

        paddle.seed(0)
        lin = nn.Linear(8, 8)
        lin.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(1e-5, parameters=lin.parameters(),
                                     multi_precision=True)

        def loss_fn(out, y):
            return ((out - y) ** 2).mean()

        step = TrainStep(lin, loss_fn, opt)
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(4, 8).astype(np.float32))
        x = paddle.cast(x, "bfloat16")
        losses = [float(np.asarray(step(x, x).value)) for _ in range(5)]
        # master state exists and is fp32
        assert all("master" in s for s in step._opt_states)
        assert all(s["master"].dtype == jnp.float32
                   for s in step._opt_states)
        # tiny lr: bf16-only updates would round away; the fp32 master
        # must still drift from its starting point
        drift = float(np.abs(np.asarray(
            step._opt_states[0]["master"]).astype(np.float64)
            - np.asarray(lin.weight.value.astype(jnp.float32))).max())
        assert drift > 0, "fp32 master must hold sub-bf16-ulp updates"
        assert losses[-1] <= losses[0]

    def test_sharded_trainer_master_sharded_stage1(self):
        """ZeRO-1: master shards land on the sharding axis with the
        moments."""
        import jax
        from jax.sharding import Mesh
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh

        paddle.seed(0)
        lin = nn.Linear(16, 16)
        lin.to(dtype="bfloat16")
        opt = paddle.optimizer.AdamW(1e-3, parameters=lin.parameters(),
                                     multi_precision=True)
        mesh = build_mesh(sharding=4,
                          devices=jax.devices()[:4])
        st = ShardedTrainStep(lin, opt, mesh, sharding_stage=1,
                              loss_fn=lambda o, y: ((o - y) ** 2).mean())
        x = paddle.to_tensor(
            np.random.RandomState(0).randn(8, 16).astype(np.float32))
        x = paddle.cast(x, "bfloat16")
        l0 = float(np.asarray(st(x, x).value))
        for s in st._opt_states:
            assert "master" in s
            spec = s["master"].sharding.spec
            assert any(ax == "sharding" for ax in spec if ax), spec
        l1 = float(np.asarray(st(x, x).value))
        assert np.isfinite(l0) and np.isfinite(l1)


class TestFusedAdamWFp32Params:
    """fp32-param ("param is the master", flax param_dtype idiom) fused
    kernel mode + bf16 moment storage + shard_map wrapping."""

    def test_fp32_mode_matches_pure_rule(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
        from paddle_tpu.optimizer.optimizer import Adam

        rng = np.random.RandomState(0)
        n = 4096
        g = jnp.asarray(rng.randn(n).astype(np.float32))
        m = jnp.asarray(rng.randn(n).astype(np.float32)) * 0.1
        v = jnp.abs(jnp.asarray(rng.randn(n).astype(np.float32))) * 0.01
        p = jnp.asarray(rng.randn(n).astype(np.float32))
        lr, step, wd = 1e-3, 3, 0.1

        p_f, m_f, v_f, mst_f = fused_adamw(
            g, m, v, p, lr, step, b1=0.9, b2=0.999, eps=1e-8,
            wd=wd, decoupled=True, out_dtype=jnp.float32)
        ref_p, ref_state = Adam._update(
            p, g, {"moment1": m, "moment2": v}, lr, wd, step,
            b1=0.9, b2=0.999, eps=1e-8, decoupled=True)
        np.testing.assert_allclose(np.asarray(p_f), np.asarray(ref_p),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(mst_f), np.asarray(p_f))
        np.testing.assert_allclose(np.asarray(m_f),
                                   np.asarray(ref_state["moment1"]),
                                   atol=1e-6, rtol=1e-6)

    def test_bf16_moments_match_pure_rule(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
        from paddle_tpu.optimizer.optimizer import Adam

        rng = np.random.RandomState(1)
        n = 2048
        g = jnp.asarray(rng.randn(n).astype(np.float32))
        m = (jnp.asarray(rng.randn(n).astype(np.float32)) * 0.1
             ).astype(jnp.bfloat16)
        v = (jnp.abs(jnp.asarray(rng.randn(n).astype(np.float32)))
             * 0.01).astype(jnp.bfloat16)
        p = jnp.asarray(rng.randn(n).astype(np.float32))

        p_f, m_f, v_f, _ = fused_adamw(
            g, m, v, p, 1e-3, 2, b1=0.9, b2=0.999, eps=1e-8,
            wd=0.0, decoupled=True, out_dtype=jnp.float32)
        assert m_f.dtype == jnp.bfloat16 and v_f.dtype == jnp.bfloat16
        ref_p, ref_state = Adam._update(
            p, g, {"moment1": m, "moment2": v}, 1e-3, 0.0, 2,
            b1=0.9, b2=0.999, eps=1e-8, decoupled=True)
        np.testing.assert_allclose(np.asarray(p_f), np.asarray(ref_p),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(m_f.astype(jnp.float32)),
            np.asarray(ref_state["moment1"].astype(jnp.float32)))

    def test_adam_moment_dtype_state(self):
        """moment_dtype plumbs into accumulator init + pure update."""
        import jax.numpy as jnp

        p = paddle.to_tensor(np.ones(8, np.float32))
        p.stop_gradient = False
        opt = paddle.optimizer.Adam(0.01, parameters=[p],
                                    moment_dtype="bfloat16")
        loss = (p ** 2).sum()
        loss.backward()
        opt.step()
        st = opt._accumulators[id(p)]
        assert st["moment1"].dtype == jnp.bfloat16
        assert st["moment2"].dtype == jnp.bfloat16

    def test_sharded_trainer_fused_shard_map(self):
        """The fused kernel runs shard_map-wrapped on a >1-device mesh
        (Pallas interpret mode on CPU) and matches the unfused path."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh

        def run_once(force_fused):
            paddle.set_flags({"FLAGS_fused_adamw_interpret": force_fused,
                              "FLAGS_use_fused_adamw": force_fused})
            try:
                paddle.seed(0)
                lin = nn.Linear(16, 16)
                # fp32 params + bf16 moments: the fp32-param kernel mode
                opt = paddle.optimizer.AdamW(
                    1e-2, parameters=lin.parameters(),
                    moment_dtype="bfloat16")
                mesh = build_mesh(sharding=4, devices=jax.devices()[:4])
                st = ShardedTrainStep(
                    lin, opt, mesh, sharding_stage=3,
                    loss_fn=lambda o, y: ((o - y) ** 2).mean())
                x = paddle.to_tensor(np.random.RandomState(0)
                                     .randn(8, 16).astype(np.float32))
                return [float(np.asarray(st(x, x).value))
                        for _ in range(3)]
            finally:
                paddle.set_flags({"FLAGS_fused_adamw_interpret": False,
                                  "FLAGS_use_fused_adamw": True})

        fused = run_once(True)
        plain = run_once(False)
        np.testing.assert_allclose(fused, plain, rtol=2e-2, atol=2e-2)
        assert fused[-1] < fused[0]


_ADAMW_VARIANTS = [(False, False), (False, True), (True, False),
                   (True, True)]
_ADAMW_VARIANT_IDS = ["fp32_param", "fp32_param_ef", "master", "master_ef"]
# (shape, moment dtype, path).  bf16 moments pack 16 rows a tile, so
# [24, 128] tiles only with fp32 moments.
_ADAMW_SHAPES = [((32, 256), "bfloat16", "own"),
                 ((48, 384), "bfloat16", "own"),
                 ((24, 128), "float32", "own"),
                 ((2, 32, 256), "bfloat16", "own"),
                 ((37, 130), "bfloat16", "flat"),
                 ((5,), "bfloat16", "flat"),
                 ((4096,), "bfloat16", "flat"),
                 ((3, 5, 7), "bfloat16", "flat"),
                 ((2, 24, 128), "bfloat16", "flat"),
                 ((24, 128), "bfloat16", "flat")]


def _adamw_operands(shape, master, ef, moment_dtype, seed=0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    g_dtype = jnp.bfloat16 if master else jnp.float32
    g = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1, g_dtype)
    p = jnp.asarray(rng.randn(*shape).astype(np.float32))
    zeros = jnp.zeros(shape, moment_dtype)
    return g, [zeros, zeros, p] + ([zeros] if ef else [])


def _adamw_two_steps(rule, shape, master, ef, moment_dtype, view=None):
    """[(param, m, v, master[, ef]) after step 1, after step 2] of `rule`
    (`fused_adamw` or its host-side twin), the state carried; `view`
    reshapes the operands first (the same elements through another
    block plan)."""
    import jax.numpy as jnp
    g, state = _adamw_operands(shape, master, ef, moment_dtype)
    if view is not None:
        g, state = g.reshape(view), [a.reshape(view) for a in state]
    outs = []
    for step in (1, 2):
        out = rule(g, state[0], state[1], state[2], 1e-2, step, wd=0.1,
                   out_dtype=jnp.bfloat16 if master else jnp.float32,
                   ef=state[3] if ef else None)
        outs.append([np.asarray(a.astype(jnp.float32)).reshape(shape)
                     for a in out])
        state = list(out[1:])
    return outs


class TestFusedAdamWOwnShape:
    """ISSUE 31: the kernel blocks a leaf in the leaf's own shape
    (`block_plan`); the update is elementwise, so how a leaf is blocked
    may not change it."""

    @pytest.mark.parametrize("master,ef", _ADAMW_VARIANTS,
                             ids=_ADAMW_VARIANT_IDS)
    @pytest.mark.parametrize("shape,moment_dtype,path", _ADAMW_SHAPES,
                             ids=lambda v: "x".join(map(str, v))
                             if isinstance(v, tuple) else str(v))
    def test_every_plan_gives_the_same_update(self, shape, moment_dtype,
                                              path, master, ef):
        """All four kernel bodies, on shapes that tile and shapes that
        must fall to the flat path, over two steps: the leaf in its own
        shape, the same elements raveled (the flat path, the parent's
        arithmetic for every such leaf) and `adamw_hostside` agree to a
        unit or two in the last place of each result's storage.  Not to
        the bit here: XLA's CPU code contracts the products differently
        from one block shape to the next, and the twin takes its bias
        corrections in double (it reads a unit off the kernel at the
        parent too).  On the chip the blocking changes no bit (PERF.md
        section 6, PR 31: own shape against the same elements flat)."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.fused_adamw import (
            adamw_hostside, block_plan, fused_adamw, moved_dtypes)
        g_dtype = jnp.bfloat16 if master else jnp.float32
        ins, outs = moved_dtypes(
            g_dtype, moment_dtype, moment_dtype,
            jnp.bfloat16 if master else jnp.float32,
            moment_dtype if ef else None)
        if path == "own" and moment_dtype == "float32" and master:
            path = "flat"       # the bf16 gradient packs 16 rows a tile
        assert block_plan(shape, ins + outs)[0] == path
        n = int(np.prod(shape))
        assert block_plan((n,), ins + outs)[0] == "flat"
        args = (shape, master, ef, moment_dtype)
        own = _adamw_two_steps(fused_adamw, *args)
        flat = _adamw_two_steps(fused_adamw, *args, view=(n,))
        host = _adamw_two_steps(adamw_hostside, *args)
        full = dict(rtol=1e-6, atol=3e-7)
        # a value stored in bf16 may land one bf16 step from the other's
        half = full if moment_dtype == "float32" else \
            dict(rtol=2 ** -7, atol=1e-12)
        for got, others in zip(own, zip(flat, host)):
            p, m, v, mst = got[:4]
            for other in others:
                np.testing.assert_allclose(m, other[1], **half)
                np.testing.assert_allclose(mst, other[3], **full)
                if master:
                    np.testing.assert_allclose(p, other[0], rtol=2 ** -7,
                                               atol=1e-7)
                else:
                    np.testing.assert_array_equal(p, mst)
                if ef:  # the pair IS the second moment: compare the sum
                    np.testing.assert_allclose(v + got[4],
                                               other[2] + other[4],
                                               rtol=1e-4, atol=1e-12)
                else:
                    np.testing.assert_allclose(v, other[2], **half)

    def test_block_plan_blocks_fit_and_tile(self):
        """The plan's own-shape blocks: full-width where the row fits the
        budget, sublane-aligned rows, a grid that covers the leaf, leading
        dimensions squeezed; and the flat path's padding."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import fused_adamw as fa
        f32, bf = jnp.float32, jnp.bfloat16
        cell = sum(fa.moved_dtypes(f32, bf, bf, f32), ())
        for shape in [(4096, 14336), (14336, 4096), (4096, 4096),
                      (4096, 1024), (32768, 4096), (2048, 14336),
                      (4096, 7168), (4096, 11008), (8, 4096, 2048),
                      (2, 3, 32, 128)]:
            path, grid, block = fa.block_plan(shape, cell)
            assert path == "own"
            lead = len(shape) - 2
            assert block[:lead] == (None,) * lead
            assert grid[:lead] == shape[:lead]
            br, bc = block[lead:]
            assert br % 16 == 0 and bc % 128 == 0
            assert (grid[-2] * br, grid[-1] * bc) == shape[-2:]
            per_elem = sum(jnp.dtype(d).itemsize for d in cell)
            assert 2 * br * bc * per_elem <= fa._VMEM_BUDGET
            # the widest block that fits: the whole row, or no wider
            # divisor of the row would fit at the packing's 16 rows
            wider = [d for d in range(bc + 128, shape[-1] + 1, 128)
                     if shape[-1] % d == 0
                     and 2 * 16 * d * per_elem <= fa._VMEM_BUDGET]
            assert not wider, (shape, block, wider)
        # the cell's widest row is one block, a contiguous run of tiles
        assert fa.block_plan((4096, 14336), cell)[2] == (16, 14336)
        assert fa.block_plan((4096, 4096), cell)[2] == (64, 4096)
        # fp32 moments: 8 rows a tile
        assert fa.block_plan((24, 128), (f32,) * 7) == ("own", (1, 1),
                                                        (24, 128))
        assert fa.block_plan((5,), cell) == ("flat", (1,), (2048,))
        assert fa.block_plan((64 * 1024 + 1,), cell) == \
            ("flat", (2,), (64 * 1024,))

    def test_cell_config_has_no_two_dim_leaf_on_the_flat_path(self):
        """Every leaf of `mistral-7b-v0.3` as `mistral7b_train_2k` trains
        it (fp32 parameters, bf16 moments; the benchmark's own list of
        them), whole and as the shards ZeRO (rows halved) and `mp`
        (columns halved) make of it: every 2-D leaf is blocked in its own
        shape; only the norms' vectors take the flat path."""
        import json
        import os
        import sys
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.fused_adamw import (block_plan,
                                                       moved_dtypes)
        bench = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark")
        sys.path.insert(0, bench)
        try:
            import weights
        finally:
            sys.path.remove(bench)
        with open(os.path.join(bench, "configs",
                               "mistral-7b-v0.3.json")) as f:
            specs = weights.leaf_specs(json.load(f))
        dtypes = sum(moved_dtypes(jnp.float32, jnp.bfloat16, jnp.bfloat16,
                                  jnp.float32), ())
        for _, shape, _, _ in specs:
            views = [shape]
            if len(shape) == 2:
                views += [(shape[0] // 2, shape[1]),
                          (shape[0], shape[1] // 2)]
            for view in views:
                want = "own" if len(view) == 2 else "flat"
                assert block_plan(view, dtypes)[0] == want, view
        assert {(4096, 14336), (14336, 4096), (4096, 4096), (4096, 1024),
                (32768, 4096), (4096, 32768), (4096,)} == \
            {shape for _, shape, _, _ in specs}

    @pytest.mark.parametrize("master,ef", _ADAMW_VARIANTS,
                             ids=_ADAMW_VARIANT_IDS)
    def test_apply_update_under_shard_map_matches_one_chip(self, master,
                                                           ef):
        """`apply_update` with the state sharded: each device's kernel sees
        its LOCAL shard, [32, 256] of [64, 512] on a 2 x 2 mesh (own
        shape) and [10, 130] of [20, 260] (flat), and the gathered result
        is the one-chip kernel's."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.distributed.topology import build_mesh
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.optimizer.jit_update import apply_update
        from paddle_tpu.optimizer.optimizer import Adam
        mesh = build_mesh(sharding=2, mp=2, devices=jax.devices()[:4])
        spec = P("sharding", "mp")
        hp = dict(b1=0.9, b2=0.999, eps=1e-8, decoupled=True)
        set_flags({"fused_adamw_interpret": True})
        try:
            for shape in [(64, 512), (20, 260)]:
                g, (m, v, mst, *rest) = _adamw_operands(
                    shape, master, ef, jnp.bfloat16)
                p = mst.astype(jnp.bfloat16) if master else mst
                s = {"moment1": m, "moment2": v}
                if master:
                    s["master"] = mst
                if ef:
                    s["ef"] = rest[0]
                one_p, one_s = jax.jit(
                    lambda p, g, s: apply_update(
                        Adam._update, p, g, s, 1e-2, 0.1, 1, hp))(p, g, s)
                put = lambda a: jax.device_put(a, NamedSharding(mesh, spec))
                many_p, many_s = jax.jit(
                    lambda p, g, s: apply_update(
                        Adam._update, p, g, s, 1e-2, 0.1, 1, hp,
                        fused_ok=False, mesh=mesh, spec=spec))(
                    put(p), put(g), {k: put(a) for k, a in s.items()})
                def close(a, b):    # to a step of the result's storage
                    tol = dict(rtol=1e-6, atol=3e-7) \
                        if a.dtype == jnp.float32 else \
                        dict(rtol=2 ** -7, atol=1e-7)
                    assert a.shape == b.shape and a.dtype == b.dtype
                    np.testing.assert_allclose(
                        np.asarray(a.astype(jnp.float32)),
                        np.asarray(b.astype(jnp.float32)), **tol)
                close(many_p, one_p)
                assert set(many_s) == set(one_s)
                for k in one_s:
                    close(many_s[k], one_s[k])
        finally:
            set_flags({"fused_adamw_interpret": False})


class TestMultiTensorAdamW:
    """Opt-in multi-tensor grouping (FLAGS_multi_tensor_adamw): small
    params flatten into ONE fused call; must match the per-param path
    bit-for-bit semantics-wise.  Default OFF (round 5, pre-ledger:
    neutral on llama, -4.3% on bert; not measured since)."""

    def test_grouped_matches_per_param(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.jit import TrainStep

        def run(mt):
            set_flags({"fused_adamw_interpret": True,
                       "multi_tensor_adamw": mt})
            try:
                paddle.seed(7)
                m = nn.Sequential(nn.Linear(16, 32), nn.LayerNorm(32),
                                  nn.Linear(32, 4))
                opt = paddle.optimizer.AdamW(
                    1e-2, parameters=m.parameters(), weight_decay=0.01)
                step = TrainStep(
                    m, lambda o, t: ((o - t) ** 2).mean(), opt)
                x = np.random.RandomState(0).randn(8, 16).astype(
                    np.float32)
                y = np.random.RandomState(1).randn(8, 4).astype(
                    np.float32)
                for _ in range(3):
                    step(paddle.to_tensor(x), paddle.to_tensor(y))
                return [np.asarray(p.value) for p in m.parameters()]
            finally:
                set_flags({"fused_adamw_interpret": False,
                           "multi_tensor_adamw": False})

        for a, b in zip(run(False), run(True)):
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)

    def test_grouping_key_separates_weight_decay(self):
        """Params with different wd must not land in one flat group."""
        import jax.numpy as jnp
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.optimizer.jit_update import apply_updates
        from paddle_tpu.optimizer.optimizer import Adam

        rng = np.random.RandomState(3)
        params = [jnp.asarray(rng.randn(8).astype(np.float32))
                  for _ in range(4)]
        grads = [jnp.asarray(rng.randn(8).astype(np.float32))
                 for _ in range(4)]
        states = [{"moment1": jnp.zeros(8, jnp.float32),
                   "moment2": jnp.zeros(8, jnp.float32)}
                  for _ in range(4)]
        hp = dict(b1=0.9, b2=0.999, eps=1e-8, decoupled=True)
        wds = [0.1, 0.0, 0.1, 0.0]
        set_flags({"multi_tensor_adamw": True,
                   "fused_adamw_interpret": True})
        try:
            new_p, _ = apply_updates(Adam._update, params, grads,
                                     states, 1e-2, wds, 1, hp)
        finally:
            set_flags({"multi_tensor_adamw": False,
                       "fused_adamw_interpret": False})
        for i in range(4):
            ref_p, _ = Adam._update(
                params[i], grads[i],
                {"moment1": jnp.zeros(8, jnp.float32),
                 "moment2": jnp.zeros(8, jnp.float32)},
                1e-2, wds[i], 1, **hp)
            np.testing.assert_allclose(np.asarray(new_p[i]),
                                       np.asarray(ref_p),
                                       rtol=1e-5, atol=1e-6)
