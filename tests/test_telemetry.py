"""Telemetry plane (paddle_tpu/telemetry) + persistent compile/AOT
cache — ISSUE 6.

The contracts under test:

  * a 3-step jit.TrainStep run with a JSONL sink attached emits
    per-step events (acceptance criterion); where the step's time goes
    is the `train.step` span's and its children's to say, on the
    profiler's clock (tests/test_program_spans.py);
  * a SECOND process pointed at the same FLAGS_compile_cache_dir
    reports a cache hit — no recompile — via telemetry.compile_report()
    (acceptance criterion);
  * with no sink attached the plane is free: emit() is a no-op, span()
    is the profiler's annotation and nothing else (no record, no clock
    read), programs are byte-identical (tests/test_program_contracts.py
    holds the HLO half; here the host half);
  * every producer (trainers, serving batcher, watchdog, fault
    registry, checkpoint runtime, io prefetcher) publishes its events;
  * ContinuousBatcher.stats() counters SURVIVE a forced program
    recompile, and the pre-recompile snapshot rides the
    serve.recompile event;
  * io.prefetch_to_device never hands a step a cold buffer when the
    producer outruns the consumer;
  * the profiler facade stays import-compatible;
  * tools/telemetry_report.py --selftest validates the schema (tier-1
    wiring, like verify_program --selftest).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane():
    """Every test starts and ends with no sinks attached and the
    compile cache disarmed (the plane is process-global)."""
    from paddle_tpu.framework.flags import set_flags
    for s in telemetry.sinks():
        telemetry.remove_sink(s)
    yield
    for s in telemetry.sinks():
        telemetry.remove_sink(s)
    set_flags({"FLAGS_compile_cache_dir": ""})


def _mlp_step():
    class _MLP(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = paddle.nn.Linear(8, 8)

        def forward(self, x):
            return self.fc(x)

    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    m = _MLP()
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda o, y: paddle.nn.functional.mse_loss(o, y),
                     opt)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    return step, x


# ---------------------------------------------------------------------------
# registry + bus

class TestRegistry:
    def test_instruments(self):
        r = telemetry.MetricsRegistry()
        r.counter("a").inc()
        r.counter("a").inc(2)
        r.gauge("g").set(1.5)
        for v in (1.0, 2.0, 3.0, 4.0):
            r.histogram("h").observe(v)
        d = r.dump()
        assert d["counters"]["a"] == 3
        assert d["gauges"]["g"] == 1.5
        h = d["histograms"]["h"]
        assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
        assert h["p50"] in (2.0, 3.0)

    def test_histogram_window_bounded(self):
        h = telemetry.Histogram("h", window=8)
        for v in range(100):
            h.observe(float(v))
        assert h.count == 100
        assert len(h._window) == 8          # ring, not unbounded

    def test_without_sink_span_builds_no_record(self, monkeypatch):
        # no sink: emit returns without touching anything; a span is the
        # profiler's annotation alone: no record built, no clock read,
        # nothing pushed on the thread's stack, no sink called
        import importlib
        registry = importlib.import_module("paddle_tpu.telemetry.registry")

        class _NoClock:
            def __getattr__(self, name):
                raise AssertionError(f"time.{name} read with no sink")

        built, number = [], registry._OPEN.number
        monkeypatch.setattr(registry, "emit",
                            lambda *a, **k: built.append(a))
        monkeypatch.setattr(registry, "time", _NoClock())
        with telemetry.span("x", a=1) as outer:
            with telemetry.span("y"):
                pass
            outer.set(b=2)
            telemetry.mark("z", req=3)
        assert built == [] and outer._rec is None
        assert registry._OPEN.stack == [] \
            and registry._OPEN.number == number

    def test_span_records_start_parent_and_running_number(self):
        sink = telemetry.add_sink(telemetry.MemorySink())
        with telemetry.span("outer", chunk=7) as outer:
            with telemetry.span("first"):
                time.sleep(0.002)
            telemetry.mark("instant", req=5)
            with telemetry.span("second") as second:
                second.set(count=3)
                with telemetry.span("inner"):
                    pass
            outer.set(admitted=2)
        with telemetry.span("sibling"):
            pass
        telemetry.remove_sink(sink)
        recs = {r["event"]: r for r in sink.records}
        assert [r["event"] for r in sink.records] == [
            "first", "instant", "inner", "second", "outer", "sibling"]
        out = recs["outer"]
        assert out["chunk"] == 7 and out["admitted"] == 2
        assert "parent" not in out and "parent" not in recs["sibling"]
        for name in ("first", "second", "instant"):
            assert recs[name]["parent"] == "outer"
            assert recs[name]["parent_span"] == out["span"]
        assert recs["inner"]["parent"] == "second"
        assert recs["inner"]["parent_span"] == recs["second"]["span"]
        assert recs["second"]["count"] == 3
        # the running number rises in the order the spans were OPENED
        order = ["outer", "first", "second", "inner", "sibling"]
        numbers = [recs[n]["span"] for n in order]
        assert numbers == sorted(numbers) and len(set(numbers)) == 5
        for r in sink.records:
            if "dur_ms" in r:
                assert r["t0"] <= r["ts"]
                assert r["ts"] - r["t0"] == pytest.approx(
                    r["dur_ms"] / 1e3, abs=0.05)
        assert recs["first"]["dur_ms"] >= 1.5
        assert out["t0"] <= recs["first"]["t0"] \
            <= recs["second"]["t0"] <= out["ts"]

    def test_span_numbers_are_per_thread(self):
        import threading
        sink = telemetry.add_sink(telemetry.MemorySink())

        def work():
            with telemetry.span("t.outer"):
                with telemetry.span("t.inner"):
                    pass

        with telemetry.span("main.outer"):
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
        telemetry.remove_sink(sink)
        recs = {r["event"]: r for r in sink.records}
        # the other thread's spans do not lie in this thread's span
        assert "parent" not in recs["t.outer"]
        assert recs["t.inner"]["parent"] == "t.outer"
        assert recs["t.outer"]["span"] == 1

    def test_raising_span_keeps_error_and_unwinds(self):
        sink = telemetry.add_sink(telemetry.MemorySink())
        with pytest.raises(KeyError):
            with telemetry.span("outer"):
                with telemetry.span("bad", tag="t"):
                    raise KeyError("x")
        with telemetry.span("after"):
            pass
        telemetry.remove_sink(sink)
        recs = {r["event"]: r for r in sink.records}
        assert recs["bad"]["error"] == "KeyError" \
            and recs["bad"]["tag"] == "t"
        assert recs["outer"]["error"] == "KeyError"
        assert "parent" not in recs["after"] and "error" not in recs["after"]

    def test_chrome_sink_draws_spans_from_their_start(self):
        sink = telemetry.add_sink(telemetry.ChromeTraceSink())
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                time.sleep(0.002)
            time.sleep(0.002)
        telemetry.remove_sink(sink, close=False)
        ev = {e["name"]: e for e in sink.trace_events}
        o, i = ev["outer"], ev["inner"]
        assert o["ph"] == i["ph"] == "X"
        # nested, not stacked at their end
        assert o["ts"] <= i["ts"] and \
            i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1.0
        assert "t0" not in i["args"] and i["args"]["parent"] == "outer"

    def test_sink_receives_and_broken_sink_detached(self):
        good = telemetry.add_sink(telemetry.MemorySink())

        class Bad:
            def record(self, rec):
                raise RuntimeError("disk full")

        bad = telemetry.add_sink(Bad())
        telemetry.emit("ev", a=1)
        telemetry.emit("ev", a=2)
        telemetry.remove_sink(good)
        assert [r["a"] for r in good.records] == [1, 2]
        assert bad not in telemetry.sinks()  # detached, loop survived

    def test_span_emits_duration(self):
        sink = telemetry.add_sink(telemetry.MemorySink())
        with telemetry.span("work", tag="t"):
            time.sleep(0.01)
        telemetry.remove_sink(sink)
        (rec,) = sink.records
        assert rec["event"] == "work" and rec["tag"] == "t"
        assert rec["dur_ms"] >= 5

    def test_configure_rejects_unknown_key(self):
        with pytest.raises(KeyError):
            telemetry.configure(not_a_switch=True)

    def test_reset_restores_config_defaults(self):
        telemetry.configure(sync_steps=True)
        telemetry.reset()
        assert telemetry.config("sync_steps") is False
        # the one switch left: the probe's went with the probe
        assert telemetry.configure() == {"sync_steps": False}


# ---------------------------------------------------------------------------
# train-step events (acceptance: 3-step run + JSONL sink -> per-step
# events; the phase split is the spans', not the event's)

class TestStepEvents:
    def test_three_step_trainstep_jsonl(self, tmp_path):
        log = str(tmp_path / "steps.jsonl")
        sink = telemetry.attach_jsonl(log)
        try:
            step, x = _mlp_step()
            for _ in range(3):
                step(x, x)
        finally:
            telemetry.remove_sink(sink)
        events = [json.loads(l) for l in open(log)]
        steps = [e for e in events if e["event"] == "train.step"]
        assert len(steps) == 3
        assert [e["step"] for e in steps] == [1, 2, 3]
        for e in steps:
            assert e["trainer"] == "jit" and e["k"] == 1
            assert e["wall_ms"] >= 0 and e["step_ms"] >= 0
            # no probe: nothing beside the program was compiled or
            # timed to fill the event
            assert "phases" not in e
        assert steps[0].get("cold") is True
        assert "cold" not in steps[1]

    def test_sharded_step_and_run_steps_events(self):
        import jax
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh

        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            class _MLP(paddle.nn.Layer):
                def __init__(self):
                    super().__init__()
                    self.fc = paddle.nn.Linear(8, 8)

                def forward(self, x):
                    return self.fc(x)

            paddle.seed(0)
            m = _MLP()
            opt = paddle.optimizer.AdamW(1e-3,
                                         parameters=m.parameters())
            step = ShardedTrainStep(
                m, opt, build_mesh(devices=jax.devices()[:1]),
                loss_fn=lambda o, y:
                paddle.nn.functional.mse_loss(o, y))
            x = paddle.to_tensor(np.ones((4, 8), np.float32))
            step(x, x)
            sx = paddle.to_tensor(np.ones((2, 4, 8), np.float32))
            step.run_steps(sx, sx)
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records if r["event"] == "train.step"]
        assert [e["k"] for e in evs] == [1, 2]
        assert all(e["trainer"] == "sharded" for e in evs)
        assert evs[1]["step"] == 3          # 1 single + 2 fused

    def test_sharded_step_spans_split_the_call(self):
        # what the probe's fwd/bwd/opt columns stood for on the host's
        # side: the call's own spans, children of train.step, covering
        # it in order (the device's side is the step program's scopes)
        import jax
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh
        paddle.seed(0)
        m = paddle.nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = ShardedTrainStep(
            m, opt, build_mesh(devices=jax.devices()[:1]),
            loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        step(x, x)                          # no sink: nothing recorded
        sink = telemetry.add_sink(telemetry.MemorySink())
        step(x, x)
        telemetry.remove_sink(sink)
        spans = [r for r in sink.records if "span" in r]
        assert [r["event"] for r in spans] == [
            "train.prepare", "train.dispatch", "train.writeback",
            "train.step"]
        call = spans[-1]
        assert call["step"] == 2 and call["k"] == 1
        for child in spans[:-1]:
            assert child["parent"] == "train.step"
            assert child["parent_span"] == call["span"]
            assert call["t0"] <= child["t0"]
        assert sum(c["dur_ms"] for c in spans[:-1]) <= call["dur_ms"]


# ---------------------------------------------------------------------------
# compile cache (acceptance: second process reports a cache hit)

_CACHE_SCRIPT = r"""
import json
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.jit import TrainStep

class MLP(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = paddle.nn.Linear(8, 8)
    def forward(self, x):
        return self.fc(x)

paddle.seed(0)
m = MLP()
opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
step = TrainStep(m, lambda o, y: paddle.nn.functional.mse_loss(o, y),
                 opt)
x = paddle.to_tensor(np.ones((4, 8), np.float32))
for _ in range(2):
    loss = step(x, x)
print("RESULT " + json.dumps({
    "loss": float(np.asarray(loss.value)),
    "report": telemetry.compile_report(),
}))
"""


class TestCompileCache:
    def _run(self, cache_dir):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=cache_dir,
                   FLAGS_compile_cache_dir="1",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT],
                             env=env, text=True, capture_output=True,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        line = next(l for l in out.stdout.splitlines()
                    if l.startswith("RESULT "))
        return json.loads(line[len("RESULT "):])

    def test_second_process_reports_cache_hit(self, tmp_path):
        cache = str(tmp_path / "cache")
        first = self._run(cache)
        progs = first["report"]["programs"]
        assert progs and all(p["cache"] == "miss" for p in progs)
        assert first["report"]["aot_misses"] >= 1
        second = self._run(cache)
        progs2 = second["report"]["programs"]
        # the SAME program key resolves to a hit: no recompile
        assert progs2 and all(p["cache"] == "hit" for p in progs2)
        assert second["report"]["hit_rate"] == 1.0
        assert all(p["compile_ms"] == 0.0 for p in progs2)
        assert {p["key"] for p in progs2} == {p["key"] for p in progs}
        # and the cached executable computes the same training step
        assert second["loss"] == pytest.approx(first["loss"])

    def test_aot_in_process_flags_off_identical(self, tmp_path,
                                                monkeypatch):
        """Arming + disarming the AOT store leaves the flags-off path
        untouched, and the armed path really serves from the store —
        which lives in the cache directory in force, not in one the
        flag names."""
        from paddle_tpu.framework.flags import set_flags
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        step, x = _mlp_step()
        l_off = float(np.asarray(step(x, x).value))
        telemetry.clear_report()
        set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "flag")})
        try:
            paddle.seed(0)
            step2, x2 = _mlp_step()
            l_on = float(np.asarray(step2(x2, x2).value))
            rep = telemetry.compile_report()
            assert rep["programs"], "armed flag produced no AOT records"
            assert os.path.isdir(str(tmp_path / "c" / "aot"))
            assert not os.path.exists(str(tmp_path / "flag"))
        finally:
            set_flags({"FLAGS_compile_cache_dir": ""})
        assert l_on == pytest.approx(l_off)

    def test_flag_moves_nothing_in_jax(self, tmp_path):
        """The flag only arms the AOT store: setting and clearing it
        leaves the directory in force, and jax's own cache settings,
        where they were (it used to re-point the XLA cache and zero
        jax's thresholds, and had to restore both)."""
        import jax
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.telemetry import compile_cache as cc
        assert cc.cache_dir() == cc.DEFAULT_DIR \
            == os.path.join(REPO, ".jax_cache")
        assert cc._aot_dir() is None

        def settings():
            return (jax.config.jax_compilation_cache_dir,
                    jax.config.jax_persistent_cache_min_compile_time_secs,
                    jax.config.jax_persistent_cache_min_entry_size_bytes)
        before = settings()
        assert before[0] == cc.DEFAULT_DIR
        set_flags({"FLAGS_compile_cache_dir": str(tmp_path / "c")})
        try:
            assert cc.maybe_enable_persistent_cache() == cc.DEFAULT_DIR
            assert cc._aot_dir() == os.path.join(cc.DEFAULT_DIR, "aot")
            assert settings() == before
        finally:
            set_flags({"FLAGS_compile_cache_dir": ""})
        assert cc._aot_dir() is None and settings() == before

    def test_env_dir_wins_over_flag_and_default_is_fixed(self, tmp_path):
        """Where JAX_COMPILATION_CACHE_DIR is set the program uses that
        directory and sets no other in code — FLAGS_compile_cache_dir
        only arms the AOT store, INSIDE the environment's directory.
        Without either, every process uses the one fixed path inside
        the checkout."""
        script = (
            "import json, jax, paddle_tpu\n"
            "from paddle_tpu import telemetry\n"
            "from paddle_tpu.telemetry import compile_cache as cc\n"
            "print('RESULT ' + json.dumps({\n"
            "    'dir': telemetry.cache_dir(),\n"
            "    'jax': jax.config.jax_compilation_cache_dir,\n"
            "    'aot': cc._aot_dir(),\n"
            "    'report': telemetry.compile_report()['dir']}))\n")

        def run(**extra):
            env = {k: v for k, v in os.environ.items()
                   if k not in ("JAX_COMPILATION_CACHE_DIR",
                                "FLAGS_compile_cache_dir")}
            env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)
            out = subprocess.run([sys.executable, "-c", script], env=env,
                                 text=True, capture_output=True,
                                 timeout=300)
            assert out.returncode == 0, out.stderr[-2000:]
            line = next(l for l in out.stdout.splitlines()
                        if l.startswith("RESULT "))
            return json.loads(line[len("RESULT "):])

        env_dir, flag_dir = str(tmp_path / "env"), str(tmp_path / "flag")
        both = run(JAX_COMPILATION_CACHE_DIR=env_dir,
                   FLAGS_compile_cache_dir=flag_dir)
        assert both["dir"] == both["jax"] == both["report"] == env_dir
        assert both["aot"] == os.path.join(env_dir, "aot")
        assert not os.path.exists(flag_dir)
        default = os.path.join(REPO, ".jax_cache")
        plain = run()
        assert plain["dir"] == plain["jax"] == plain["report"] == default
        assert plain["aot"] is None
        assert run() == plain                 # the same path every time


# ---------------------------------------------------------------------------
# io.prefetch_to_device

class TestPrefetch:
    def test_never_cold_buffer(self):
        """Producer (instant) outruns consumer (sleeping): after the
        priming get, every step must find a WARM device-resident
        buffer."""
        from paddle_tpu.io import prefetch_to_device
        batches = [np.full((2, 4), i, np.float32) for i in range(8)]
        pf = prefetch_to_device(iter(batches), depth=2)
        # deterministic priming: wait for the pipeline to fill before
        # the first get (scheduling noise on a loaded box must not
        # masquerade as a cold buffer)
        deadline = time.time() + 10
        while pf._q.qsize() < 2 and time.time() < deadline:
            time.sleep(0.005)
        seen = []
        for b in pf:
            time.sleep(0.03)            # consumer slower than producer
            seen.append(float(np.asarray(b.value)[0, 0]))
        assert seen == [float(i) for i in range(8)]
        st = pf.stats()
        assert st["steps"] == 8
        assert st["cold_gets"] == 0, st

    def test_emits_host_wait_events_and_structure(self):
        from paddle_tpu.io import prefetch_to_device
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            batches = [(np.ones((2, 4), np.float32),
                        np.zeros((2,), np.int64)) for _ in range(3)]
            out = list(prefetch_to_device(iter(batches), depth=2))
        finally:
            telemetry.remove_sink(sink)
        assert len(out) == 3
        xb, yb = out[0]
        import jax
        assert isinstance(xb.value, jax.Array)     # device-resident
        evs = [r for r in sink.records if r["event"] == "io.step"]
        assert len(evs) == 3
        assert all("host_wait_ms" in e and "buffered" in e
                   for e in evs)

    def test_sharding_aware_with_mesh(self):
        import jax
        from paddle_tpu.io import prefetch_to_device
        from paddle_tpu.distributed.topology import build_mesh
        mesh = build_mesh(dp=4, devices=jax.devices()[:4])
        batches = [np.ones((8, 4), np.float32) for _ in range(2)]
        out = list(prefetch_to_device(iter(batches), depth=2,
                                      mesh=mesh))
        sh = out[0].value.sharding
        # batch dim sharded over the data axes
        assert sh.spec[0] is not None

    def test_slow_loader_host_wait_accounted(self):
        """Satellite (ISSUE 10): a loader slower than its consumer
        must show up as host-wait — io.step events carry growing
        host_wait_ms and the io.host_wait_ms histogram AND gauge are
        visible in telemetry.dump()."""
        from paddle_tpu.io import prefetch_to_device

        def slow_gen():
            for i in range(4):
                time.sleep(0.03)        # deliberately slow producer
                yield np.full((2,), i, np.float32)

        telemetry.registry().reset()    # instrument counts start clean
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            out = list(prefetch_to_device(slow_gen(), depth=2))
        finally:
            telemetry.remove_sink(sink)
        assert len(out) == 4
        evs = [r for r in sink.records if r["event"] == "io.step"]
        assert len(evs) == 4
        waits = [e["host_wait_ms"] for e in evs]
        # past the priming get, the consumer keeps blocking on the
        # slow producer — the wait accounting must show it
        assert sum(w > 10 for w in waits[1:]) >= 2, waits
        d = telemetry.dump()
        h = d["histograms"]["io.host_wait_ms"]
        assert h["count"] == 4 and h["max"] > 10
        assert "io.host_wait_ms" in d["gauges"]
        assert d["gauges"]["io.host_wait_ms"] \
            == pytest.approx(waits[-1], abs=0.001)

    def test_loader_error_propagates(self):
        from paddle_tpu.io import prefetch_to_device

        def gen():
            yield np.zeros((2,), np.float32)
            raise ValueError("planted")

        pf = prefetch_to_device(gen(), depth=2)
        next(pf)
        with pytest.raises(ValueError, match="planted"):
            for _ in pf:
                pass

    def test_close_on_abandon_stops_producer(self):
        """An abandoned iterator must release its producer thread and
        the parked device batches via close() (regression: the thread
        used to stay parked on the full queue forever)."""
        from paddle_tpu.io import prefetch_to_device

        def gen():
            for i in range(1000):
                yield np.full((2,), i, np.float32)

        pf = prefetch_to_device(gen(), depth=2)
        next(pf)                        # consume one, then abandon
        pf.close()
        pf._thread.join(timeout=2.0)
        assert not pf._thread.is_alive()
        # parked DATA batches dropped (at most the wake-up sentinel
        # remains), and further iteration raises instead of hanging
        assert pf._q.qsize() <= 1
        with pytest.raises(StopIteration):
            next(pf)
        # context-manager form does the same
        with prefetch_to_device(gen(), depth=2) as pf2:
            next(pf2)
        pf2._thread.join(timeout=2.0)
        assert not pf2._thread.is_alive()


# ---------------------------------------------------------------------------
# serving batcher: counters survive a forced recompile; snapshot event

@pytest.fixture(scope="module")
def serve_model():
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            intermediate_size=128,
                            num_attention_heads=4,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _serve_workload(model, force_recompile_at=None):
    from paddle_tpu.inference import ContinuousBatcher
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (4, 7, 5)]
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=32,
                            chunk=4)
    for p in prompts[:2]:
        bat.submit(p, 6)
    bat.step()
    bat.submit(prompts[2], 6)
    n = 0
    while bat.queued or bat.active:
        n += 1
        if force_recompile_at is not None and n == force_recompile_at:
            # forced program-cache miss: the next chunk re-traces
            model.__dict__.get("_gen_compiled", {}).clear()
        bat.step()
    return bat


class TestServeTelemetry:
    def test_stats_survive_forced_recompile(self, serve_model):
        """Regression (ISSUE 6 satellite): a program-cache miss
        mid-life must not lose the batcher's counters — counts across
        a forced recompile equal the undisturbed run's."""
        base = _serve_workload(serve_model)
        forced = _serve_workload(serve_model, force_recompile_at=2)
        b, f = base.stats(), forced.stats()
        for k in ("chunks", "decode_chunks", "admit_chunks",
                  "prefill_tokens", "decode_tokens", "tokens_produced"):
            assert f[k] == b[k], (k, f, b)
        # and the outputs are unchanged by the recompile
        assert {r: list(base._finished[r].tokens)
                for r in base._finished} \
            == {r: list(forced._finished[r].tokens)
                for r in forced._finished}

    def test_recompile_event_snapshots_stats(self, serve_model):
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            _serve_workload(serve_model, force_recompile_at=2)
        finally:
            telemetry.remove_sink(sink)
        recs = [r for r in sink.records
                if r["event"] == "serve.recompile"]
        assert recs, "forced recompile emitted no serve.recompile"
        snap = recs[0]
        # the snapshot carries the PRE-recompile counters
        assert snap["chunks"] >= 1
        assert "prefill_tokens" in snap and "decode_tokens" in snap
        chunks = [r for r in sink.records
                  if r["event"] == "serve.chunk"]
        assert len(chunks) >= snap["chunks"]
        assert any(c["first_use"] for c in chunks)

    def test_chunk_events(self, serve_model):
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            bat = _serve_workload(serve_model)
        finally:
            telemetry.remove_sink(sink)
        chunks = [r for r in sink.records if r["event"] == "serve.chunk"]
        assert len(chunks) == bat.stats()["chunks"]
        kinds = {c["kind"] for c in chunks}
        assert kinds <= {"admit", "decode"} and "admit" in kinds
        assert sum(c["prefill_tokens"] for c in chunks) \
            == bat.stats()["prefill_tokens"]


# ---------------------------------------------------------------------------
# runtime producers: watchdog, fault, checkpoint, pipeline/collectives

class TestRuntimeProducers:
    def test_watchdog_timeout_event(self):
        from paddle_tpu.distributed.watchdog import CommTaskManager
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            mgr = CommTaskManager(poll_interval=0.02)
            task = mgr.start_task("test hang", timeout=0.05)
            try:
                deadline = time.time() + 5
                while not mgr.timeout_log and time.time() < deadline:
                    time.sleep(0.02)
            finally:
                task.done()
                mgr.shutdown()
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records
               if r["event"] == "watchdog.timeout"]
        assert evs and evs[0]["task"] == "test hang"
        assert evs[0]["age_s"] >= 0.05

    def test_fault_hit_event(self):
        from paddle_tpu.distributed import fault
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            with fault.scope("step.begin:mode=delay:secs=0"):
                fault.hit("step.begin", key="probe")
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records if r["event"] == "fault.hit"]
        assert evs and evs[0]["point"] == "step.begin"
        assert evs[0]["mode"] == "delay"

    def test_checkpoint_commit_and_gc_events(self, tmp_path):
        from paddle_tpu.distributed import checkpoint as ckpt
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            root = str(tmp_path)
            for s in (1, 2, 3):
                ckpt.save_checkpoint(
                    {"w": paddle.to_tensor(
                        np.full((2, 2), s, np.float32))},
                    root, s, keep=2)
        finally:
            telemetry.remove_sink(sink)
        commits = [r for r in sink.records if r["event"] == "ckpt.commit"]
        gcs = [r for r in sink.records if r["event"] == "ckpt.gc"]
        assert [c["step"] for c in commits] == [1, 2, 3]
        assert gcs and gcs[-1]["removed"] == ["step_00000001"]

    def test_collective_schedule_event(self):
        import jax
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh

        class _MLP(paddle.nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = paddle.nn.Linear(8, 8)

            def forward(self, x):
                return self.fc(x)

        paddle.seed(0)
        m = _MLP()
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = ShardedTrainStep(
            m, opt, build_mesh(dp=4, devices=jax.devices()[:4]),
            loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
        x = paddle.to_tensor(np.ones((8, 8), np.float32))
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            events = step.collective_schedule(x, x)
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records
               if r["event"] == "collective.schedule"]
        assert evs and evs[0]["total"] == len(events)
        assert sum(evs[0]["kinds"].values()) == len(events)


# ---------------------------------------------------------------------------
# exporters + profiler facade + report CLI

class TestExportersAndFacade:
    def test_chrome_trace_sink(self, tmp_path):
        path = str(tmp_path / "trace.json")
        sink = telemetry.attach_chrome_trace(path)
        try:
            with telemetry.span("slice"):
                time.sleep(0.002)
            telemetry.emit("instant", a=1)
        finally:
            telemetry.remove_sink(sink)   # close() writes the doc
        doc = json.load(open(path))
        phs = {e["ph"] for e in doc["traceEvents"]}
        assert phs == {"X", "i"}
        sl = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert sl["name"] == "slice" and sl["dur"] > 0

    def test_profiler_facade_names_and_record(self, tmp_path):
        # import-compat surface (deprecation shim over telemetry)
        from paddle_tpu.profiler import (Profiler, ProfilerState,
                                         ProfilerTarget, RecordEvent,
                                         make_scheduler,
                                         export_chrome_tracing,
                                         load_profiler_result,
                                         SummaryView, benchmark)
        assert ProfilerState.RECORD and ProfilerTarget.TPU \
            and SummaryView.OverView
        assert "deprecat" in sys.modules["paddle_tpu.profiler"] \
            .__doc__.lower()
        prof = Profiler(timer_only=True)
        with prof:
            with RecordEvent("my_op"):
                time.sleep(0.002)
            benchmark().step(4)
        out = str(tmp_path / "prof.json")
        prof.export(out)
        doc = load_profiler_result(out)
        names = [e["name"] for e in doc["traceEvents"]]
        assert "my_op" in names
        assert "my_op" in prof.summary()
        sched = make_scheduler(closed=1, ready=1, record=2)
        assert sched(0) == ProfilerState.CLOSED
        assert export_chrome_tracing(str(tmp_path))  # handler builds
        # the window detached its sink
        assert not telemetry.active()

    def test_record_event_outside_window_is_free(self):
        from paddle_tpu.profiler import RecordEvent
        with RecordEvent("noop"):
            pass                # no sink attached -> no-op span

    def test_profiler_scheduled_second_window_records(self):
        """Regression: a scheduled profiler's second RECORD window must
        attach a fresh sink (the first fix left self._sink set, so
        window 2 silently recorded nothing), and on_trace_ready fires
        once per closed window, not again at stop()."""
        from paddle_tpu.profiler import (Profiler, RecordEvent,
                                         make_scheduler)
        fired = []
        prof = Profiler(timer_only=True,
                        scheduler=make_scheduler(closed=1, ready=0,
                                                 record=1, repeat=2),
                        on_trace_ready=lambda p: fired.append(
                            len(p._events())))
        prof.start()                    # step 0: CLOSED
        for _ in range(4):              # steps 1..4: R, C, R, C
            with RecordEvent("op"):
                pass
            prof.step()
        prof.stop()
        assert len(fired) == 2          # one per closed window
        # windows ACCUMULATE: summary()/export() cover every window
        # since start(), and window 2 really recorded
        assert fired == [1, 2], fired
        assert not telemetry.active()

    def test_report_cli_selftest(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report as cli
        finally:
            sys.path.pop(0)
        assert cli.main(["--selftest"]) == 0

    def test_report_analyze(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report as cli
        finally:
            sys.path.pop(0)
        log = str(tmp_path / "s.jsonl")
        sink = telemetry.attach_jsonl(log)
        try:
            step, x = _mlp_step()
            for _ in range(4):
                step(x, x)
        finally:
            telemetry.remove_sink(sink)
        rep = cli.analyze(cli.load_events(log))
        assert rep["train_steps"] == 4 and rep["cold_steps"] == 1
        assert "phases" not in rep and rep["step_ms"]["p50"] >= 0
        assert cli.render(rep)

    def test_report_span_table(self):
        # the sink-side reader of the program's spans: per name count,
        # p50 / max of the duration and the SELF time (duration minus
        # what the span's children cover)
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report as cli
        finally:
            sys.path.pop(0)
        events = []
        for k, (step_ms, wait_ms) in enumerate([(10.0, 6.0), (20.0, 8.0)]):
            base = 100 * k
            events += [
                {"event": "serve.device_wait", "span": base + 2,
                 "parent": "serve.step", "parent_span": base + 1,
                 "dur_ms": wait_ms, "t0": 1.0, "ts": 2.0},
                {"event": "serve.step", "span": base + 1,
                 "dur_ms": step_ms, "t0": 0.5, "ts": 2.5, "chunk": k}]
        rep = cli.analyze(events)
        assert rep["spans"]["serve.step"] == {
            "count": 2, "p50_ms": 10.0, "max_ms": 20.0,
            "self_p50_ms": 4.0, "self_max_ms": 12.0}
        assert rep["spans"]["serve.device_wait"]["self_max_ms"] == 8.0
        assert "serve.step" in cli.render(rep)

    def test_dump_compact_snapshot(self):
        telemetry.counter("x").inc(5)
        d = telemetry.dump(compact=True)
        assert d["counters"]["x"] >= 5
        assert "programs" not in d["compile"]
