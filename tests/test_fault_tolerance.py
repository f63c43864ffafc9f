"""Fault-tolerant training runtime (distributed/{fault,checkpoint,guard}).

Every recovery path is exercised through a PLANTED fault driven by the
deterministic injection registry (`paddle_tpu.distributed.fault`):

  * torn shard (truncate), bit-rot (corrupt), writer IO error (error),
    missing manifest / missing `latest` commit — checkpoint hardening;
  * async writer fail-fast at the next save (satellite);
  * NaN step — compiled skip-step guard + consecutive-bad budget + AMP
    loss-scale backoff;
  * transient KV connection blips — bounded retry (satellite);
  * watchdog task leak on a raising body (satellite);

plus the acceptance-bar bit-exact resume parity: N steps of
ShardedTrainStep / OffloadPipelineStep / hapi fit ≡ N/2 steps + save +
restore-into-fresh-state + N/2 steps.
"""
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed import fault
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.guard import (StepAnomalyGuard,
                                          BadStepBudgetExceeded)
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.parallel import ShardedTrainStep, OffloadPipelineStep


# ---------------------------------------------------------------------------
# shared tiny models / data
# ---------------------------------------------------------------------------

class MLP(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = paddle.nn.Linear(8, 16)
        self.fc2 = paddle.nn.Linear(16, 1)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


class Block(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = paddle.nn.Linear(16, 16)

    def forward(self, x):
        return paddle.nn.functional.relu(self.fc(x))


class StackedNet(paddle.nn.Layer):
    """Block-stacked net for the offload pipeline."""

    def __init__(self, L=3):
        super().__init__()
        self.inp = paddle.nn.Linear(8, 16)
        self.layers = paddle.nn.LayerList([Block() for _ in range(L)])
        self.head = paddle.nn.Linear(16, 1)

    def forward(self, x):
        h = self.inp(x)
        for b in self.layers:
            h = b(h)
        return self.head(h)


def _mse(out, y):
    return paddle.nn.functional.mse_loss(out, y)


def _batch(i, n=4):
    rng = np.random.RandomState(100 + i)
    return (paddle.to_tensor(rng.randn(n, 8).astype(np.float32)),
            paddle.to_tensor(rng.randn(n, 1).astype(np.float32)))


def _sharded(seed=7, lr_sched=False, **kw):
    paddle.seed(seed)
    m = MLP()
    lr = paddle.optimizer.lr.StepDecay(1e-2, step_size=2, gamma=0.5) \
        if lr_sched else 1e-2
    opt = paddle.optimizer.AdamW(lr, parameters=m.parameters(),
                                 weight_decay=0.1)
    mesh = build_mesh(devices=jax.devices()[:1])
    return m, ShardedTrainStep(m, opt, mesh, loss_fn=_mse, **kw)


def _offload(seed=7):
    paddle.seed(seed)
    m = StackedNet()
    opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters(),
                                 weight_decay=0.1)
    mesh = build_mesh(devices=jax.devices()[:1])
    return m, OffloadPipelineStep(m, opt, mesh, loss_fn=_mse,
                                  cast_dtype=None)


@pytest.fixture(autouse=True)
def _no_leftover_faults():
    yield
    paddle.set_flags({"FLAGS_fault_injection": ""})
    fault.reset()


# ---------------------------------------------------------------------------
# injection registry
# ---------------------------------------------------------------------------

class TestFaultSpecs:
    def test_grammar(self):
        specs = fault.parse_specs(
            "ckpt.write:step=3:mode=truncate;"
            "kv.request:times=2;step.data:mode=nan:times=*")
        assert [s.point for s in specs] == ["ckpt.write", "kv.request",
                                           "step.data"]
        assert specs[0].step == 3 and specs[0].mode == "truncate"
        assert specs[1].times == 2 and specs[1].mode == "error"
        assert specs[2].times == -1

    def test_bad_specs_fail_loudly(self):
        with pytest.raises(fault.FaultSpecError):
            fault.parse_specs("nonexistent.point:mode=error")
        with pytest.raises(fault.FaultSpecError):
            fault.parse_specs("ckpt.write:mode=frobnicate")
        with pytest.raises(fault.FaultSpecError):
            fault.parse_specs("ckpt.write:stepthree")

    def test_deterministic_nth_hit(self):
        with fault.scope("kv.request:step=2:mode=error"):
            assert fault.hit("kv.request") is None
            with pytest.raises(fault.FaultError):
                fault.hit("kv.request")
            assert fault.hit("kv.request") is None  # times=1 consumed

    def test_times_and_match(self):
        with fault.scope("ckpt.write:times=2:mode=corrupt:match=special"):
            assert fault.hit("ckpt.write", key="other") is None
            assert fault.hit("ckpt.write", key="special-1").mode \
                == "corrupt"
            assert fault.hit("ckpt.write", key="special-2") is not None
            assert fault.hit("ckpt.write", key="special-3") is None

    def test_step_with_times_fires_consecutively(self):
        """step=N:times=k fires at hits N..N+k-1 (the docstring's own
        `kv.request:step=1:times=2` example means TWO blips)."""
        with fault.scope("kv.request:step=2:times=2:mode=error"):
            fired = []
            for _ in range(4):
                try:
                    fault.hit("kv.request")
                    fired.append(False)
                except fault.FaultError:
                    fired.append(True)
            assert fired == [False, True, True, False]

    def test_unknown_point_raises_even_when_armed(self):
        with fault.scope("kv.request:mode=error"):
            with pytest.raises(fault.FaultSpecError, match="unregist"):
                fault.hit("ckpt.writ")      # typo'd call site

    def test_unset_is_inert(self):
        assert not fault.is_active()
        assert fault.hit("step.begin") is None
        assert fault.hit_counts() == {}


# ---------------------------------------------------------------------------
# checkpoint hardening — one planted defect per feature
# ---------------------------------------------------------------------------

def _w(val):
    return {"w": paddle.to_tensor(np.full((4, 4), val, np.float32))}


def _load_w(root):
    tgt = _w(0.0)
    got = ckpt.load_checkpoint(tgt, root)
    if got is None:
        return None
    return got[0], float(np.asarray(tgt["w"].value)[0, 0])


class TestCheckpointHardening:
    def test_commit_and_load_latest(self, tmp_path):
        root = str(tmp_path)
        for s in (1, 2, 3):
            ckpt.save_checkpoint(_w(s), root, s)
        assert (tmp_path / "latest").read_text() == "step_00000003"
        assert _load_w(root) == (3, 3.0)

    def test_torn_shard_falls_back(self, tmp_path):
        """Planted torn write (truncate): the save fails verification at
        commit, `latest` stays put, load falls back to the previous
        complete step."""
        root = str(tmp_path)
        ckpt.save_checkpoint(_w(1), root, 1)
        with fault.scope("ckpt.write:step=1:mode=truncate"):
            with pytest.raises(IOError, match="verification"):
                ckpt.save_checkpoint(_w(2), root, 2)
        assert _load_w(root) == (1, 1.0)

    def test_bad_crc_detected_and_skipped(self, tmp_path):
        """Planted bit-rot (corrupt): the sidecar CRC catches it; the
        torn dir is skipped on load."""
        root = str(tmp_path)
        ckpt.save_checkpoint(_w(1), root, 1)
        with fault.scope("ckpt.write:step=1:mode=corrupt"):
            with pytest.raises(IOError):
                ckpt.save_checkpoint(_w(2), root, 2)
        step2 = str(tmp_path / "step_00000002")
        assert not ckpt.is_complete(step2)
        assert ckpt.is_complete(str(tmp_path / "step_00000001"))
        assert _load_w(root) == (1, 1.0)

    def test_missing_manifest_is_torn(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint(_w(1), root, 1)
        with fault.scope("ckpt.manifest:mode=skip"):
            with pytest.raises(IOError, match="verification"):
                ckpt.save_checkpoint(_w(2), root, 2)
        assert _load_w(root) == (1, 1.0)

    def test_uncommitted_latest_still_recovered(self, tmp_path):
        """Crash between shard landing and the `latest` commit (the
        emergency-drain window): the complete-but-unpointed step is
        found by the verification scan and preferred."""
        root = str(tmp_path)
        ckpt.save_checkpoint(_w(1), root, 1)
        with fault.scope("ckpt.latest:mode=skip"):
            ckpt.save_checkpoint(_w(2), root, 2)
        assert (tmp_path / "latest").read_text() == "step_00000001"
        assert _load_w(root) == (2, 2.0)

    def test_transient_write_error_retried(self, tmp_path):
        """Two injected IO errors are absorbed by the bounded
        retry-with-backoff; the third attempt lands the shard."""
        root = str(tmp_path)
        with fault.scope("ckpt.write:times=2:mode=error"):
            ckpt.save_checkpoint(_w(5), root, 5)
        assert _load_w(root) == (5, 5.0)

    def test_persistent_write_error_raises(self, tmp_path):
        with fault.scope("ckpt.write:times=*:mode=error"):
            with pytest.raises(IOError):
                ckpt.save_checkpoint(_w(1), str(tmp_path), 1)

    def test_retention_gc(self, tmp_path):
        root = str(tmp_path)
        for s in range(1, 6):
            ckpt.save_checkpoint(_w(s), root, s, keep=2)
        dirs = sorted(d for d in os.listdir(root)
                      if d.startswith("step_"))
        assert dirs == ["step_00000004", "step_00000005"]
        assert _load_w(root) == (5, 5.0)

    def test_async_writer_fail_fast(self, tmp_path):
        """Satellite: a failed async save surfaces at the NEXT
        save_state_dict immediately (and is cleared), not only at
        synchronize_async_saves."""
        with fault.scope("ckpt.write:times=*:mode=error"):
            fut = ckpt.save_state_dict(_w(1), str(tmp_path / "a"),
                                       async_save=True)
            with pytest.raises(Exception):
                fut.result()          # writer job has failed
            with pytest.raises(IOError):
                ckpt.save_state_dict(_w(2), str(tmp_path / "b"))
        # error observed + cleared: the next save succeeds
        ckpt.save_state_dict(_w(3), str(tmp_path / "c"))
        ckpt.synchronize_async_saves()

    def test_async_save_checkpoint_commits_in_order(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint(_w(1), root, 1, async_save=True)
        ckpt.save_checkpoint(_w(2), root, 2, async_save=True)
        ckpt.synchronize_async_saves()
        assert _load_w(root) == (2, 2.0)

    def test_sync_save_behind_inflight_async(self, tmp_path):
        """A sync save issued while an async save is still writing (the
        SIGTERM emergency-drain shape) must not let its commit's GC
        reap the in-flight older step as a torn leftover: the sync save
        rides the writer queue, and both steps land complete."""
        root = str(tmp_path)
        with fault.scope("ckpt.write:step=1:mode=delay:secs=0.8"):
            ckpt.save_checkpoint(_w(1), root, 1, async_save=True)
            got = ckpt.save_checkpoint(_w(2), root, 2)     # sync
        assert got == os.path.join(root, "step_00000002")
        ckpt.synchronize_async_saves()     # no stored writer error
        assert ckpt.is_complete(os.path.join(root, "step_00000001"))
        assert (tmp_path / "latest").read_text() == "step_00000002"
        assert _load_w(root) == (2, 2.0)

    def test_mixed_path_training_warns_keeps_jit_capture(self):
        """An eager fallthrough AFTER jitted steps must not silently
        flip checkpoints to near-fresh eager accumulators: it warns,
        and train_state keeps capturing the jit TrainStep side."""
        from paddle_tpu.hapi.model import Model

        def loss(out, y, w=None):
            l = paddle.nn.functional.mse_loss(out, y)
            return l if w is None else l * w.mean()

        paddle.seed(5)
        m = Model(MLP())
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        m.prepare(opt, loss)
        x, y = _batch(0)
        m.train_batch([x], [y])            # jit path
        ones = paddle.to_tensor(np.ones((4, 1), np.float32))
        with pytest.warns(RuntimeWarning, match="split"):
            m.train_batch([x], [y, ones])  # eager fallthrough
        arrays, meta = m.train_state()
        assert meta["hapi_path"] == "jit"

    def test_partial_restore_warns(self, tmp_path):
        """Restoring into a trainer whose key set no longer matches the
        checkpoint (renamed/resized net) must warn loudly instead of
        silently resuming half-fresh with a late-schedule LR."""
        root = str(tmp_path)
        _, s_a = _sharded()
        for i in range(2):
            s_a(*_batch(i))
        ckpt.save_train_checkpoint(s_a, root)
        paddle.seed(11)
        m2 = StackedNet()
        opt = paddle.optimizer.AdamW(1e-2, parameters=m2.parameters())
        s_b = ShardedTrainStep(m2, opt,
                               build_mesh(devices=jax.devices()[:1]),
                               loss_fn=_mse)
        with pytest.warns(RuntimeWarning, match="PARTIAL"):
            ckpt.restore_train_checkpoint(s_b, root)

    def test_stale_wider_world_shards_ignored(self, tmp_path):
        """Elastic world shrink: a re-save into a step dir can leave
        higher-rank shards from the wider pre-resize incarnation
        behind; load must read exactly the ranks the manifest's
        __world__ declares, not mix stale values back in."""
        root = str(tmp_path)
        ckpt.save_checkpoint(_w(9), root, 1)      # the "stale" payload
        step1 = os.path.join(root, "step_00000001")
        import shutil
        stale = os.path.join(step1, "3.distcp")
        shutil.copy(os.path.join(step1, "0.distcp"), stale)
        shutil.copy(os.path.join(step1, "0.distcp.shard.json"),
                    stale + ".shard.json")
        # overwrite rank 0 in place (the post-shrink re-save)
        ckpt.save_state_dict(_w(1), step1)
        assert ckpt.is_complete(step1)            # stale rank-3 ignored
        assert _load_w(root) == (1, 1.0)          # ... by the load too

    def test_sync_behind_async_failure_not_reraised(self, tmp_path):
        """A sync save queued behind a healthy async save whose OWN
        write fails raises once at the call — synchronize_async_saves
        must not surface the same error again."""
        root = str(tmp_path)
        with fault.scope("ckpt.write:after=1:times=*:mode=error"):
            ckpt.save_checkpoint(_w(1), root, 1, async_save=True)
            with pytest.raises(IOError):
                ckpt.save_checkpoint(_w(2), root, 2)   # sync, fails
        ckpt.synchronize_async_saves()     # first save landed, no raise
        assert _load_w(root) == (1, 1.0)

    def test_failed_async_error_surfaces_exactly_once(self, tmp_path):
        """The fail-fast raise consumes the failure: the dead save's
        chained commit must not re-raise the same error a second time
        at synchronize_async_saves."""
        root = str(tmp_path)
        with fault.scope("ckpt.write:times=*:mode=error"):
            fut = ckpt.save_checkpoint(_w(1), root, 1, async_save=True)
            # the chained commit settles only after the write job: a
            # reliable barrier — and it must swallow the write failure
            assert fut.result() is None
            with pytest.raises(IOError):   # fail-fast observes it once
                ckpt.save_state_dict(_w(2), str(tmp_path / "b"))
        ckpt.synchronize_async_saves()     # ... and exactly once


# ---------------------------------------------------------------------------
# bit-exact resume parity (acceptance bar)
# ---------------------------------------------------------------------------

class TestBitExactResume:
    def _run(self, step, lo, hi):
        out = []
        for i in range(lo, hi):
            x, y = _batch(i)
            out.append(float(np.asarray(step(x, y).value)))
        return out

    def test_sharded_trainer_resume_parity(self, tmp_path):
        """8 steps ≡ 4 steps + save + restore-into-fresh-state + 4
        steps: losses identical, LR schedule and RNG restored."""
        _, s_ref = _sharded(lr_sched=True)
        ref = self._run(s_ref, 0, 8)
        _, s_a = _sharded(lr_sched=True)
        first = self._run(s_a, 0, 4)
        ckpt.save_train_checkpoint(s_a, str(tmp_path))
        paddle.seed(999)                  # clobber process RNG ...
        _, s_b = _sharded(seed=31337, lr_sched=True)  # ... and init
        meta = ckpt.restore_train_checkpoint(s_b, str(tmp_path))
        assert meta["step_count"] == 4
        rest = self._run(s_b, 4, 8)
        assert ref == first + rest        # bit-exact, not allclose

    def test_offload_pipeline_resume_parity(self, tmp_path):
        """Same bar for the streamed ZeRO-3 pipeline: host-parked
        param/state STACKS captured and restored exactly."""
        _, s_ref = _offload()
        ref = self._run(s_ref, 0, 6)
        _, s_a = _offload()
        first = self._run(s_a, 0, 3)
        ckpt.save_train_checkpoint(s_a, str(tmp_path))
        paddle.seed(999)
        _, s_b = _offload(seed=31337)
        meta = ckpt.restore_train_checkpoint(s_b, str(tmp_path))
        assert meta["step_count"] == 3
        rest = self._run(s_b, 3, 6)
        assert ref == first + rest

    def test_resume_survives_torn_newest_step(self, tmp_path):
        """Kill-anywhere guarantee: the newest checkpoint is torn (the
        crash hit mid-save) — resume transparently falls back to the
        previous complete step and stays bit-exact from there."""
        _, s_ref = _sharded()
        ref = self._run(s_ref, 0, 6)
        _, s_a = _sharded()
        first = self._run(s_a, 0, 3)
        ckpt.save_train_checkpoint(s_a, str(tmp_path))     # step 3, good
        self._run(s_a, 3, 4)
        with fault.scope("ckpt.write:step=1:mode=truncate"):
            with pytest.raises(IOError):
                ckpt.save_train_checkpoint(s_a, str(tmp_path))  # torn
        _, s_b = _sharded(seed=31337)
        meta = ckpt.restore_train_checkpoint(s_b, str(tmp_path))
        assert meta["step_count"] == 3    # fell back past the torn dir
        rest = self._run(s_b, 3, 6)
        assert ref == first + rest

    def test_hapi_eager_path_resume_parity(self, tmp_path):
        """jit=True with a multi-label loss falls through to hapi's
        EAGER train path; train_state must capture the eager optimizer
        accumulators (not a never-used TrainStep's fresh zeros) and the
        restore must follow the same branch — bit-exact."""
        from paddle_tpu.hapi.model import Model

        def loss2(out, y, w):
            return paddle.nn.functional.mse_loss(out * w, y * w)

        def make(seed=7):
            paddle.seed(seed)
            m = Model(MLP())
            opt = paddle.optimizer.AdamW(
                1e-2, parameters=m.parameters(), weight_decay=0.1)
            m.prepare(opt, loss2)          # jit=True (the default)
            return m

        ones = paddle.to_tensor(np.ones((4, 1), np.float32))

        def run(m, lo, hi):
            out = []
            for i in range(lo, hi):
                x, y = _batch(i)
                out.append(m.train_batch([x], [y, ones])[0])
            return out

        ref = run(make(), 0, 6)
        m_a = make()
        first = run(m_a, 0, 3)
        ckpt.save_train_checkpoint(m_a, str(tmp_path))
        paddle.seed(999)
        m_b = make(seed=31337)
        meta = ckpt.restore_train_checkpoint(m_b, str(tmp_path))
        assert meta["hapi_path"] == "eager"
        rest = run(m_b, 3, 6)
        assert ref == first + rest


# ---------------------------------------------------------------------------
# nonfinite step guard
# ---------------------------------------------------------------------------

@pytest.fixture
def _guard_flags():
    paddle.set_flags({"FLAGS_skip_nonfinite_steps": True})
    yield
    paddle.set_flags({"FLAGS_skip_nonfinite_steps": False,
                      "FLAGS_max_consecutive_bad_steps": 8})


class TestNonfiniteGuard:
    def test_nan_step_skipped_params_untouched(self, _guard_flags):
        """Planted NaN batch: the step's loss is nonfinite, params and
        optimizer state stay EXACTLY as before, training continues."""
        m, s = _sharded()
        x, y = _batch(0)
        s(x, y)
        snap = {n: np.asarray(t.value).copy()
                for n, t in m.state_dict().items()}
        states = [{k: np.asarray(v).copy() for k, v in st.items()}
                  for st in s._opt_states]
        with fault.scope("step.data:step=1:mode=nan"):
            x, y = _batch(1)
            bad = float(np.asarray(s(x, y).value))
        assert not np.isfinite(bad)
        for n, t in m.state_dict().items():
            np.testing.assert_array_equal(np.asarray(t.value), snap[n])
        for st, st0 in zip(s._opt_states, states):
            for k in st0:
                np.testing.assert_array_equal(np.asarray(st[k]), st0[k])
        x, y = _batch(2)
        assert np.isfinite(float(np.asarray(s(x, y).value)))

    def test_offload_pipeline_nan_step_skipped(self, _guard_flags):
        m, s = _offload()
        x, y = _batch(0)
        s(x, y)
        snap = {k: np.asarray(v).copy() for k, v in s._stk_param.items()}
        with fault.scope("step.data:step=1:mode=nan"):
            x, y = _batch(1)
            bad = float(np.asarray(s(x, y).value))
        assert not np.isfinite(bad)
        for k in snap:
            np.testing.assert_array_equal(np.asarray(s._stk_param[k]),
                                          snap[k])
        x, y = _batch(2)
        assert np.isfinite(float(np.asarray(s(x, y).value)))

    def test_budget_abort_with_diagnostics_and_backoff(self, _guard_flags):
        scaler = paddle.amp.GradScaler(init_loss_scaling=1024.0,
                                       use_dynamic_loss_scaling=True)
        _, s = _sharded(grad_scaler=scaler)
        paddle.set_flags({"FLAGS_max_consecutive_bad_steps": 3})
        with fault.scope("step.data:mode=nan:times=*"):
            with pytest.raises(BadStepBudgetExceeded,
                               match="consecutive nonfinite"):
                for i in range(10):
                    x, y = _batch(i)
                    s(x, y)
        # one backoff per bad step: 1024 * 0.5^3
        assert scaler._scale == 1024.0 * 0.5 ** 3

    def test_transient_spike_resets_budget(self, _guard_flags):
        _, s = _sharded()
        paddle.set_flags({"FLAGS_max_consecutive_bad_steps": 2})
        with fault.scope("step.data:step=2:mode=nan;"
                         "step.data:step=4:mode=nan"):
            for i in range(6):      # bad steps 2 and 4, never 2 in a row
                x, y = _batch(i)
                s(x, y)
        assert s._guard.total_bad == 2
        assert s._guard.consecutive_bad == 0

    def test_flags_off_compiles_no_guard_ops(self):
        _, s = _sharded()
        x, y = _batch(0)
        hlo = s.compiled_hlo(x, y, optimized=False)
        assert "is_finite" not in hlo
        paddle.set_flags({"FLAGS_skip_nonfinite_steps": True})
        try:
            _, s2 = _sharded()
            assert "is_finite" in s2.compiled_hlo(x, y, optimized=False)
        finally:
            paddle.set_flags({"FLAGS_skip_nonfinite_steps": False})

    def test_guard_unit(self):
        g = StepAnomalyGuard(budget=2, name="unit")
        assert g.record(1.0) is False
        assert g.record(float("nan")) is True
        assert g.record(2.0) is False          # streak reset
        g.record(float("inf"))
        with pytest.raises(BadStepBudgetExceeded):
            g.record(float("nan"))


# ---------------------------------------------------------------------------
# KV client retry (satellite)
# ---------------------------------------------------------------------------

class TestKVRetry:
    def test_transient_blips_absorbed(self):
        from paddle_tpu.distributed.launch.master import KVServer, KVClient
        srv = KVServer(0).start()
        try:
            kv = KVClient(f"127.0.0.1:{srv.port}")
            with fault.scope("kv.request:times=2:mode=error"):
                assert kv.put("ft/x", "1") is True   # 3rd attempt lands
            assert kv.get("ft/x") == "1"
            with fault.scope("kv.request:times=*:mode=error"):
                assert kv.put("ft/y", "1") is False  # exhausted: old
                assert kv.get("ft/y") is None        # contract holds
        finally:
            srv.stop()

    def test_heartbeat_rides_retry(self):
        from paddle_tpu.distributed.launch.master import KVServer, KVClient
        srv = KVServer(0).start()
        try:
            kv = KVClient(f"127.0.0.1:{srv.port}")
            with fault.scope("kv.request:step=1:mode=error"):
                assert kv.stamp("hb/pod0") is True
            assert kv.time() is not None
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# watchdog leak (satellite)
# ---------------------------------------------------------------------------

class TestWatchdogLeak:
    def test_raising_body_deregisters(self):
        from paddle_tpu.distributed.watchdog import (watched,
                                                     get_comm_task_manager)
        mgr = get_comm_task_manager()
        paddle.set_flags({"FLAGS_stop_check_timeout": 30})
        try:
            with pytest.raises(ValueError):
                with watched("raises mid-flight"):
                    raise ValueError("boom")
            assert "raises mid-flight" not in mgr.active_tasks()
        finally:
            paddle.set_flags({"FLAGS_stop_check_timeout": 0})

    def test_reentrant_instance_leaks_nothing(self):
        from paddle_tpu.distributed.watchdog import (watched,
                                                     get_comm_task_manager)
        mgr = get_comm_task_manager()
        paddle.set_flags({"FLAGS_stop_check_timeout": 30})
        try:
            w = watched("reused")
            with w:
                with w:
                    pass
            assert "reused" not in mgr.active_tasks()
        finally:
            paddle.set_flags({"FLAGS_stop_check_timeout": 0})

    def test_failed_arming_leaves_no_ghost(self):
        from paddle_tpu.distributed.watchdog import CommTaskManager
        mgr = CommTaskManager()

        def boom():
            raise RuntimeError("thread limit")
        mgr._ensure_thread = boom
        with pytest.raises(RuntimeError):
            mgr.start_task("ghost", timeout=5)
        assert mgr.active_tasks() == []


# ---------------------------------------------------------------------------
# SIGTERM drain protocol — fast in-process twins of the slow e2e test
# ---------------------------------------------------------------------------

class TestSigtermDrainProtocol:
    def _controller(self, tmp_path, cmd):
        import argparse
        from paddle_tpu.distributed.launch.controller import (
            CollectiveController, ProcEntry)
        args = argparse.Namespace(
            master=None, rank=-1, nnodes=1, nnodes_min=1, nnodes_max=1,
            nproc_per_node=1, log_dir=str(tmp_path / "log"),
            job_id="drain-unit", devices=None, max_restart=0,
            elastic_timeout=5, training_script="x.py",
            training_script_args=[])
        c = CollectiveController(args)
        p = ProcEntry(cmd, dict(os.environ),
                      str(tmp_path / "log" / "w.log"), 0)
        p.start()
        c.procs = [p]
        return c

    def test_drain_propagates_elastic_exit(self, tmp_path):
        """begin_drain forwards SIGTERM; a child that checkpoints and
        exits ELASTIC_EXIT_CODE makes the controller exit with it."""
        from paddle_tpu.distributed.launch.controller import \
            ELASTIC_EXIT_CODE
        c = self._controller(
            tmp_path, ["bash", "-c",
                       f"trap 'exit {ELASTIC_EXIT_CODE}' TERM; "
                       "sleep 30 & wait"])
        time.sleep(0.3)
        c.begin_drain()
        deadline = time.time() + 20
        rc = None
        while rc is None and time.time() < deadline:
            time.sleep(0.1)
            rc = c._watch_drain([p.poll() for p in c.procs])
        assert rc == ELASTIC_EXIT_CODE

    def test_drain_grace_expiry_terminates(self, tmp_path):
        """A child that ignores SIGTERM is terminated once the grace
        window lapses; the controller reports the signal death."""
        c = self._controller(
            tmp_path, ["bash", "-c", "trap '' TERM; sleep 30 & wait"])
        time.sleep(0.3)
        c.begin_drain()
        c._drain_deadline = time.time() - 1     # grace already over
        rc = c._watch_drain([p.poll() for p in c.procs])
        assert rc == 128 + 15
        assert c.procs[0].poll() is not None

    def test_drain_flag_roundtrip(self):
        from paddle_tpu.distributed import guard
        assert not guard.drain_requested()
        guard._drain.set()
        try:
            assert guard.drain_requested()
        finally:
            guard.clear_drain()
        assert not guard.drain_requested()

    def test_stale_drain_cleared_on_new_fit(self, tmp_path):
        """The drain event is a sticky process-global: a SIGTERM that
        landed after a PREVIOUS fit finished must not make a fresh fit
        with FaultTolerantCheckpoint emergency-exit at its first
        batch."""
        from paddle_tpu.distributed import guard
        from paddle_tpu.hapi.callbacks import FaultTolerantCheckpoint
        from paddle_tpu.hapi.model import Model

        class DS(paddle.io.Dataset):
            def __len__(self):
                return 8

            def __getitem__(self, i):
                rng = np.random.RandomState(i)
                return (rng.randn(8).astype(np.float32),
                        rng.randn(1).astype(np.float32))

        guard._drain.set()          # stale SIGTERM from an earlier run
        try:
            paddle.seed(3)
            m = Model(MLP())
            opt = paddle.optimizer.AdamW(1e-2,
                                         parameters=m.parameters())
            m.prepare(opt, paddle.nn.MSELoss())
            # pre-fix this dies with SystemExit(ELASTIC_EXIT_CODE) at
            # the first on_train_batch_end
            m.fit(DS(), batch_size=4, epochs=1, shuffle=False,
                  verbose=0,
                  callbacks=[FaultTolerantCheckpoint(str(tmp_path))])
            assert not guard.drain_requested()
        finally:
            guard.clear_drain()


# ---------------------------------------------------------------------------
# flags-off zero overhead
# ---------------------------------------------------------------------------

class TestZeroOverhead:
    def test_flags_off_no_ckpt_io_no_fault_hits(self, tmp_path):
        """The flags-off step path performs zero checkpoint IO and
        never consults the armed-fault machinery."""
        assert not fault.is_active()
        writes = ckpt.WRITE_CALLS
        hits_before = fault.hit_counts()
        _, s = _sharded()
        for i in range(2):
            x, y = _batch(i)
            s(x, y)
        assert ckpt.WRITE_CALLS == writes
        assert fault.hit_counts() == hits_before
