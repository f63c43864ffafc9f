"""Compute cost ledger + roofline drift tests (ISSUE 12).

Covers: per-program FLOP/byte resolution for every trainer and both
serve programs riding the memory ledger's providers (zero extra
compiles, probe contract pinned), measured-wall feeds from the live
train.step/serve.chunk events, the FLAGS_mfu_floor drift check
(perf.drift events + analysis.lint_mfu_floor), the named_scope
per-layer attribution census, the shared FLOP-accounting derivations
(paddle.flops / model_train_flops regression pins), and the
memory_report share=None graceful degrade (satellite bugfix).
"""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.telemetry import costledger

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_plane():
    telemetry.reset()
    yield
    telemetry.reset()


def _mlp_step():
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    model = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
        paddle.nn.Linear(16, 8))
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = TrainStep(
        model, lambda o, y: paddle.nn.functional.mse_loss(o, y), opt)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    return step, x


def _tiny_llama(n_layers=1):
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(3)
    cfg = llama_tiny_config(num_hidden_layers=n_layers, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    return LlamaForCausalLM(cfg)


# ---------------------------------------------------------------------------
# shared derivations (satellite 1: one FLOP accounting, pinned)

class TestSharedDerivations:
    def test_model_train_flops_accounting_pinned(self):
        """The analytic accounting — 2N/4N/6N per token, remat added to
        the backward — must come back out of the shared helper
        unchanged."""
        n, tok, remat = 1.5e9, 8192.0, 3.0e6
        f = costledger.model_train_flops
        assert f(n, tok, "fwd") == 2.0 * n * tok
        assert f(n, tok, "bwd") == 4.0 * n * tok
        assert f(n, tok, "full") == 6.0 * n * tok
        assert f(n, tok, "bwd", remat_flops_per_token=remat) \
            == (4.0 * n + remat) * tok
        # remat replays buy nothing in the forward
        assert f(n, tok, "fwd", remat_flops_per_token=remat) \
            == 2.0 * n * tok
        with pytest.raises(KeyError):
            f(n, tok, "warp")

    def test_cost_of_matches_raw_cost_analysis(self):
        import jax
        import jax.numpy as jnp
        compiled = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((16, 16)), jnp.ones((16, 16))).compile()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        got = costledger.cost_of(compiled)
        assert got["flops"] == float(ca.get("flops", 0.0)) > 0
        assert got["bytes_accessed"] \
            == float(ca.get("bytes accessed", 0.0)) > 0

    def test_paddle_flops_unchanged_by_unification(self):
        """paddle.flops() now reads through costledger.cost_of — the
        value must equal the old ad-hoc extraction (regression pin)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.jit import _swapped_state
        from paddle_tpu.framework.tensor import Tensor
        paddle.seed(0)
        net = paddle.nn.Linear(8, 16)
        total = paddle.flops(net, [4, 8])
        # the old derivation, inline
        sd = net.state_dict()
        names = list(sd)
        vals = [sd[n].value for n in names]

        def fwd(params, x):
            with _swapped_state(net, names, list(params)):
                out = net(Tensor(x))
            return out.value if isinstance(out, Tensor) else out

        compiled = jax.jit(fwd).lower(
            vals, jnp.zeros((4, 8), jnp.float32)).compile()
        cost = compiled.cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        assert total == float((cost or {}).get("flops", 0.0)) > 0


# ---------------------------------------------------------------------------
# ledger resolution: every trainer + both serve programs

class TestLedgerResolution:
    def test_trainstep_cost_resolved_with_roofline_fields(self):
        step, x = _mlp_step()
        step(x, x)
        rep = telemetry.cost_report()
        rec = rep["programs"]["jit.TrainStep.step"]
        assert rec["status"] == "ok"
        assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
        assert rec["intensity"] == pytest.approx(
            rec["flops"] / rec["bytes_accessed"], rel=1e-2)
        assert rec["bound"] in ("compute", "memory")
        assert rec["predicted_ms"] == max(
            rec["predicted_compute_ms"], rec["predicted_memory_ms"]) > 0
        peaks = rep["peaks"]
        assert peaks["flops_per_sec"] > 0 \
            and peaks["hbm_bytes_per_sec"] > 0
        assert peaks["ridge_intensity"] == pytest.approx(
            peaks["flops_per_sec"] / peaks["hbm_bytes_per_sec"])

    def test_sharded_trainer_cost_resolved(self):
        import jax
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh
        paddle.seed(0)
        m = paddle.nn.Sequential(paddle.nn.Linear(8, 8))
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = ShardedTrainStep(
            m, opt, build_mesh(devices=jax.devices()[:1]),
            loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        step(x, x)
        rep = telemetry.cost_report()
        rec = rep["programs"][f"ShardedTrainStep.step.s{step.stage}"]
        assert rec["status"] == "ok" and rec["flops"] > 0

    def test_serve_programs_cost_resolved_probe_contract(self):
        """Both serve-step programs resolve through the side-effect-
        free lower_step probe: cost_report() must not inflate
        compiled_programs or defeat first-use timing (the memledger
        probe contract, pinned for the cost twin)."""
        from paddle_tpu.inference import ContinuousBatcher
        model = _tiny_llama()
        bat = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                                chunk=4, prefill_chunk=4)
        rep = telemetry.cost_report()
        for label in ("serve_step.decode", "serve_step.admit"):
            rec = rep["programs"][label]
            assert rec["status"] == "ok", rec
            assert rec["flops"] > 0 and rec["bytes_accessed"] > 0
        assert bat.compiled_programs == 0
        rng = np.random.RandomState(0)
        bat.submit(rng.randint(1, 64, 4).astype(np.int32), 4)
        bat.run()
        assert bat.stats()["compiled_programs"] <= 2

    def test_one_resolution_fills_both_ledgers_no_extra_compiles(self):
        """ONE provider resolution serves memory AND cost: after
        memory_report() the cost entries are already ok, and a
        subsequent cost_report(resolve=True) compiles nothing."""
        from paddle_tpu.analysis import recompile_guard
        step, x = _mlp_step()
        step(x, x)
        telemetry.memory_report(top_buffers=0)
        snap = costledger.snapshot()
        assert snap["programs"]["jit.TrainStep.step"]["status"] == "ok"
        with recompile_guard(0, label="cost resolve"):
            rep = telemetry.cost_report()
        assert rep["programs"]["jit.TrainStep.step"]["status"] == "ok"

    def test_cost_report_alone_resolves_memory_too(self):
        step, x = _mlp_step()
        step(x, x)
        assert telemetry.memledger.snapshot()["programs"][
            "jit.TrainStep.step"]["status"] == "pending"
        telemetry.cost_report()
        assert telemetry.memledger.snapshot()["programs"][
            "jit.TrainStep.step"]["status"] == "ok"

    def test_cost_program_events_published_on_resolve(self):
        step, x = _mlp_step()
        step(x, x)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            telemetry.cost_report()
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records if r["event"] == "cost.program"]
        assert evs and evs[0]["label"] == "jit.TrainStep.step"
        assert evs[0]["flops"] > 0

    def test_dump_embeds_cost_snapshot_without_resolving(self):
        step, x = _mlp_step()
        step(x, x)
        d = telemetry.dump()
        assert "cost" not in d        # nothing ingested yet: dump
        #                               never compiles
        telemetry.cost_report()
        d = telemetry.dump(compact=True)
        assert d["cost"]["programs"] >= 1
        assert d["cost"]["drifts"] == 0


# ---------------------------------------------------------------------------
# measured walls + drift

class TestMeasuredAndDrift:
    def test_step_events_feed_measured_walls_warm_only(self):
        step, x = _mlp_step()
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            for _ in range(3):
                step(x, x)
        finally:
            telemetry.remove_sink(sink)
        # 3 steps, first cold (may include the compile) -> 2 samples
        assert costledger._measured_total["jit.TrainStep.step"] == 2
        assert costledger.measured_ms("jit.TrainStep.step") > 0
        rep = telemetry.cost_report()
        rec = rep["programs"]["jit.TrainStep.step"]
        assert rec["measured_ms"] > 0 and rec["measured_n"] == 2
        assert rec["attained"] == pytest.approx(
            rec["predicted_ms"] / rec["measured_ms"], abs=1e-3)
        assert rec["achieved_flops_per_sec"] > 0

    def test_no_sink_no_measured_walls(self):
        step, x = _mlp_step()
        for _ in range(2):
            step(x, x)
        assert costledger.measured_ms("jit.TrainStep.step") is None
        rec = telemetry.cost_report()["programs"][
            "jit.TrainStep.step"]
        assert "measured_ms" not in rec and "attained" not in rec

    def test_serve_chunks_feed_measured_walls(self):
        from paddle_tpu.inference import ContinuousBatcher
        model = _tiny_llama()
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            bat = ContinuousBatcher(model, max_batch_size=1,
                                    max_len=32, chunk=4,
                                    prefill_chunk=4)
            rng = np.random.RandomState(0)
            bat.submit(rng.randint(1, 64, 4).astype(np.int32), 10)
            bat.run()
        finally:
            telemetry.remove_sink(sink)
        # >=3 decode chunks ran (10 tokens / chunk=4); the first is
        # first_use (compile wall) and excluded
        assert costledger.measured_ms("serve_step.decode") > 0

    def test_drift_event_and_counter_below_floor(self):
        from paddle_tpu.framework.flags import set_flags
        step, x = _mlp_step()
        step(x, x)
        telemetry.cost_report()               # resolve (no drift yet)
        costledger.observe("jit.TrainStep.step", 1e9)  # planted crawl
        before = telemetry.counter("perf.drift").value
        sink = telemetry.add_sink(telemetry.MemorySink())
        set_flags({"FLAGS_mfu_floor": 0.99})
        try:
            rep = telemetry.cost_report()
        finally:
            set_flags({"FLAGS_mfu_floor": 0.0})
            telemetry.remove_sink(sink)
        rec = rep["programs"]["jit.TrainStep.step"]
        assert rec["drift"] is True and rec["attained"] < 0.99
        assert rep["mfu_floor"] == 0.99
        evs = [r for r in sink.records if r["event"] == "perf.drift"]
        assert len(evs) == 1
        assert evs[0]["label"] == "jit.TrainStep.step"
        assert evs[0]["floor"] == 0.99
        assert evs[0]["measured_ms"] == rec["measured_ms"]
        assert telemetry.counter("perf.drift").value == before + 1

    def test_drift_edge_triggered_not_per_poll(self):
        """A monitoring loop polling cost_report() while one program
        sits below the floor counts ONE detection, not one per poll;
        recovery re-arms the edge."""
        from paddle_tpu.framework.flags import set_flags
        step, x = _mlp_step()
        step(x, x)
        telemetry.cost_report()
        before = telemetry.counter("perf.drift").value
        sink = telemetry.add_sink(telemetry.MemorySink())
        set_flags({"FLAGS_mfu_floor": 0.99})
        try:
            slow = {"jit.TrainStep.step": 1e9}
            for _ in range(3):                 # sustained drift: 1 event
                telemetry.cost_report(measured=slow)
            assert telemetry.counter("perf.drift").value == before + 1
            # recovery (attained >= floor) re-arms the edge
            telemetry.cost_report(
                measured={"jit.TrainStep.step": 1e-9})
            telemetry.cost_report(measured=slow)   # relapse: fires again
            assert telemetry.counter("perf.drift").value == before + 2
        finally:
            set_flags({"FLAGS_mfu_floor": 0.0})
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records if r["event"] == "perf.drift"]
        assert len(evs) == 2

    def test_no_floor_no_drift(self):
        step, x = _mlp_step()
        step(x, x)
        costledger.observe("jit.TrainStep.step", 1e9)
        rep = telemetry.cost_report()
        rec = rep["programs"]["jit.TrainStep.step"]
        assert "drift" not in rec and rep["mfu_floor"] is None

    def test_explicit_measured_overrides_window(self):
        step, x = _mlp_step()
        step(x, x)
        rep = telemetry.cost_report(
            measured={"jit.TrainStep.step": 123.0})
        assert rep["programs"]["jit.TrainStep.step"][
            "measured_ms"] == 123.0

    def test_lint_mfu_floor_planted_and_clean(self):
        from paddle_tpu.analysis import lint_mfu_floor
        step, x = _mlp_step()
        step(x, x)
        costledger.observe("jit.TrainStep.step", 1e9)
        findings = lint_mfu_floor(floor=0.99)
        assert findings
        assert all(f.code == "mfu-floor" for f in findings)
        assert any("jit.TrainStep.step" in f.message for f in findings)
        # floor=0 (the default flag value) disables the lint entirely
        assert lint_mfu_floor() == []
        # a generous floor on a fast program stays clean
        assert lint_mfu_floor(
            report=telemetry.cost_report(
                measured={"jit.TrainStep.step": 1e-9}),
            floor=0.5) == []

    def test_cold_observations_excluded(self):
        costledger.observe("x", 5.0, cold=True)
        assert costledger.measured_ms("x") is None
        costledger.observe("x", 5.0)
        assert costledger.measured_ms("x") == 5.0

    def test_label_reuse_drops_stale_walls(self):
        """Ledger labels are class-constant: a SECOND trainer of the
        same class re-registers the label, and the first trainer's
        walls (a different program!) must not corrupt the new
        program's measured_ms/attained."""
        step, x = _mlp_step()
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            for _ in range(3):
                step(x, x)
            assert costledger.measured_ms("jit.TrainStep.step") > 0
            step2, x2 = _mlp_step()         # new program, same label
            step2(x2, x2)                   # re-registers on 1st call
            # old walls gone; the new program's first (cold) call
            # contributes nothing
            assert costledger.measured_ms(
                "jit.TrainStep.step") is None
            step2(x2, x2)
            assert costledger._measured_total[
                "jit.TrainStep.step"] == 1
        finally:
            telemetry.remove_sink(sink)

    def test_retrace_resets_walls_and_reregisters(self):
        """run_steps at a NEW K retraces the multi program mid-life:
        the ledger must re-register (entry describes the current
        program) and the old K's walls must not mix in — and the
        retrace call's own wall (it pays the compile) counts as
        cold."""
        from paddle_tpu.jit import TrainStep
        paddle.seed(0)
        model = paddle.nn.Sequential(paddle.nn.Linear(8, 8))
        opt = paddle.optimizer.AdamW(1e-3,
                                     parameters=model.parameters())
        step = TrainStep(
            model, lambda o, y: paddle.nn.functional.mse_loss(o, y),
            opt)
        label = "jit.TrainStep.multi"

        def stack(k):
            arr = np.ones((k, 4, 8), np.float32)
            return paddle.to_tensor(arr), paddle.to_tensor(arr)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            x2, y2 = stack(2)
            step.run_steps(x2, y2)             # cold (first use)
            step.run_steps(x2, y2)             # warm wall
            assert costledger._measured_total[label] == 1
            x8, y8 = stack(8)
            step.run_steps(x8, y8)             # retrace: resets, cold
            assert costledger.measured_ms(label) is None
            assert telemetry.memledger.snapshot()["programs"][
                label]["status"] == "pending"  # re-registered
            step.run_steps(x8, y8)             # the k=8 warm wall
            assert costledger._measured_total[label] == 1
            # flip BACK to k=2: alternation must also reset
            step.run_steps(x2, y2)
            assert costledger.measured_ms(label) is None
        finally:
            telemetry.remove_sink(sink)

    def test_attained_uses_unrounded_prediction(self):
        """A program whose predicted_ms displays as 0.0000 (sub-50ns)
        must not read attained == 0.0 — that would drift
        unconditionally under any floor."""
        class Fake:
            def cost_analysis(self):
                # 40k flops at 1e12 flop/s (eff 1.0) -> 4e-5 ms
                return [{"flops": 40000.0, "bytes accessed": 1.0}]

            def as_text(self):
                return ""
        costledger.ingest("tiny", Fake())
        costledger.configure_peaks(flops_per_sec=1e12,
                                   hbm_bytes_per_sec=1e12,
                                   efficiency=1.0)
        rec = telemetry.cost_report(
            resolve=False, measured={"tiny": 8e-5})["programs"]["tiny"]
        assert rec["predicted_ms"] == 0.0       # display rounds away
        assert rec["attained"] == pytest.approx(0.5, abs=1e-3)


# ---------------------------------------------------------------------------
# roofline verdicts under controlled peaks

class TestRooflineVerdict:
    def _ingest_matmul(self):
        import jax
        import jax.numpy as jnp
        compiled = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((32, 32)), jnp.ones((32, 32))).compile()
        return costledger.ingest("probe", compiled)

    def test_bound_flips_with_peak_ratio(self):
        entry = self._ingest_matmul()
        intensity = entry["flops"] / entry["bytes_accessed"]
        # ridge far above the program's intensity -> memory-bound
        costledger.configure_peaks(flops_per_sec=1e15,
                                   hbm_bytes_per_sec=1e9,
                                   efficiency=1.0)
        rec = telemetry.cost_report(resolve=False)["programs"]["probe"]
        assert rec["bound"] == "memory"
        assert rec["predicted_ms"] == rec["predicted_memory_ms"]
        # ridge far below -> compute-bound
        costledger.configure_peaks(flops_per_sec=1e9,
                                   hbm_bytes_per_sec=1e15)
        rec = telemetry.cost_report(resolve=False)["programs"]["probe"]
        assert rec["bound"] == "compute"
        assert rec["predicted_ms"] == rec["predicted_compute_ms"]
        assert intensity == pytest.approx(rec["intensity"], rel=1e-2)

    def test_efficiency_scales_prediction(self):
        self._ingest_matmul()
        # peaks low enough that predicted_ms survives 4-decimal
        # rounding on a 32x32 matmul
        costledger.configure_peaks(flops_per_sec=1e9,
                                   hbm_bytes_per_sec=1e9,
                                   efficiency=1.0)
        t1 = telemetry.cost_report(resolve=False)["programs"][
            "probe"]["predicted_ms"]
        costledger.configure_peaks(efficiency=0.5)
        t2 = telemetry.cost_report(resolve=False)["programs"][
            "probe"]["predicted_ms"]
        assert t2 == pytest.approx(2 * t1, rel=1e-3)

    def test_reset_clears_overrides(self):
        costledger.configure_peaks(flops_per_sec=123.0)
        costledger.reset()
        assert costledger.backend_peaks()["flops_per_sec"] != 123.0

    def test_peaks_come_from_one_table(self, monkeypatch):
        """ONE peak table, keyed by device_kind: the CPU placeholders
        are reached only through a CPU backend, and a device the table
        does not know raises."""
        import types
        import jax
        assert costledger.backend_peaks()["source"] == "default:cpu"
        assert costledger.chip_peak_flops() \
            == costledger.backend_peaks()["flops_per_sec"]

        def on_chip(kind):
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
            monkeypatch.setattr(
                jax, "devices",
                lambda *a: [types.SimpleNamespace(device_kind=kind)])

        on_chip("TPU v5 lite")
        assert costledger.chip_peak_flops() \
            == costledger.CHIP_PEAKS["TPU v5 lite"]["flops"] == 197e12
        assert costledger.backend_peaks()["chip"] == "v5e"
        on_chip("TPU v9 imaginary")
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            costledger.chip_peak_flops()
        with pytest.raises(ValueError, match="CHIP_PEAKS"):
            costledger.backend_peaks()


# ---------------------------------------------------------------------------
# named_scope per-layer attribution

class TestNamedScopeAttribution:
    def test_llama_train_program_carries_layer_scopes(self):
        from paddle_tpu.jit import TrainStep
        model = _tiny_llama(n_layers=2)
        opt = paddle.optimizer.AdamW(1e-3,
                                     parameters=model.parameters())
        step = TrainStep(model,
                         lambda o, y: model.compute_loss(o, y), opt)
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, 64, (2, 8)).astype(np.int32))
        step(ids, ids)
        rec = telemetry.cost_report()["programs"][
            "jit.TrainStep.step"]
        scopes = rec.get("scopes", {})
        for name in ("llama.embed", "llama.layer0", "llama.layer1",
                     "llama.norm"):
            assert scopes.get(name, 0) > 0, (name, scopes)

    def test_serve_decode_program_carries_layer_scopes(self):
        from paddle_tpu.inference import ContinuousBatcher
        model = _tiny_llama()
        # keep the batcher alive: the ledger's serve providers are
        # weakrefs
        bat = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                                chunk=4, prefill_chunk=4)
        rec = telemetry.cost_report()["programs"]["serve_step.decode"]
        assert bat.compiled_programs == 0
        assert rec.get("scopes", {}).get("llama.layer0", 0) > 0

    def test_census_ignores_source_file_paths(self):
        # ".../models/llama.py" appears in op metadata source
        # locations; the census must only count the scope vocabulary
        text = ('op_name="jit(f)/llama.layer0/dot" '
                'source_file="/repo/paddle_tpu/models/llama.py"')

        class Fake:
            def as_text(self):
                return text
        assert costledger.scope_census(Fake()) == {"llama.layer0": 1}


# ---------------------------------------------------------------------------
# the report CLI's cost/roofline section (satellite 4)

class TestReportCostSection:
    def _analyze(self, events):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report as cli
        finally:
            sys.path.pop(0)
        return cli.analyze(events)

    def test_latest_measure_state_wins(self):
        """perf.drift is the edge alarm; cost.measure carries the
        drift STATE — a recovery after a drift episode must clear the
        rendered flag, a persisting drift must keep it."""
        mk = lambda ev, **kw: dict(event=ev, label="p", **kw)
        events = [
            mk("cost.program", flops=10.0, bytes_accessed=20.0),
            mk("cost.measure", predicted_ms=1.0, measured_ms=10.0,
               attained=0.1, bound="compute", drift=True),
            mk("perf.drift", predicted_ms=1.0, measured_ms=10.0,
               attained=0.1, floor=0.5),
            mk("cost.measure", predicted_ms=1.0, measured_ms=1.1,
               attained=0.9, bound="compute", drift=False),
        ]
        rep = self._analyze(events)
        p = rep["cost"]["programs"]["p"]
        assert p["flops"] == 10.0 and p["attained"] == 0.9
        assert "drift" not in p            # recovered: latest wins
        assert rep["cost"]["drifts"] == 1  # the episode still counted
        # persisting drift: the latest measure keeps the flag
        rep = self._analyze(events + [
            mk("cost.measure", predicted_ms=1.0, measured_ms=10.0,
               attained=0.1, bound="compute", drift=True)])
        assert rep["cost"]["programs"]["p"]["drift"] is True


# ---------------------------------------------------------------------------
# satellite bugfix: memory_report share degrades gracefully

class TestMemoryShareGraceful:
    def test_share_none_when_backend_lacks_memory_stats(self,
                                                        monkeypatch):
        import jax
        step, x = _mlp_step()
        step(x, x)
        telemetry.memory_report(top_buffers=0)   # resolve on real jax

        class _NoStatsDev:
            def memory_stats(self):
                raise NotImplementedError("no memory_stats here")

        monkeypatch.setattr(jax, "devices",
                            lambda *a, **kw: [_NoStatsDev()])
        rep = telemetry.memory_report(top_buffers=0)
        rec = rep["programs"]["jit.TrainStep.step"]
        assert rec["status"] == "ok" and rec["peak_share"] is None
        assert rep["device_hbm_bytes"] is None
        assert rep["peak_hbm_share"] is None
        assert rep["peak_hbm_bytes"] > 0

    def test_share_none_when_memory_stats_empty(self, monkeypatch):
        import jax
        step, x = _mlp_step()
        step(x, x)
        telemetry.memory_report(top_buffers=0)

        class _EmptyStatsDev:
            def memory_stats(self):
                return {}            # CPU backends often report {}
        monkeypatch.setattr(jax, "devices",
                            lambda *a, **kw: [_EmptyStatsDev()])
        rep = telemetry.memory_report(top_buffers=0)
        assert rep["programs"]["jit.TrainStep.step"][
            "peak_share"] is None
        assert rep["peak_hbm_share"] is None

    def test_share_present_with_bytes_limit(self, monkeypatch):
        import jax
        step, x = _mlp_step()
        step(x, x)
        telemetry.memory_report(top_buffers=0)

        class _Dev:
            def memory_stats(self):
                return {"bytes_limit": 10 ** 12}
        monkeypatch.setattr(jax, "devices", lambda *a, **kw: [_Dev()])
        rep = telemetry.memory_report(top_buffers=0)
        rec = rep["programs"]["jit.TrainStep.step"]
        assert rec["peak_share"] == pytest.approx(
            rec["peak_bytes"] / 10 ** 12, abs=1e-4)
        assert rep["peak_hbm_share"] == pytest.approx(
            rep["peak_hbm_bytes"] / 10 ** 12, abs=1e-4)
