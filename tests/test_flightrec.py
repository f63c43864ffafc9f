"""Incident flight recorder + in-step numerics telemetry — ISSUE 14.

The contracts under test:

  * the FlightRecorder ring is bounded; a trigger event dumps a
    complete incident bundle (trigger/events/trace/memory/cost/
    fingerprint/manifest) via tmp+rename (no half bundle ever has a
    final name); bundles are rate-limited PER TRIGGER KIND and
    retention-bounded (keep=N); a dump failure never detaches the
    recorder;
  * EVERY trigger kind produces exactly one rate-limited bundle when
    planted for real: perf.drift (configure_peaks + FLAGS_mfu_floor),
    fleet.straggler / fleet.desync (the r14 2-rank KV harness),
    train.anomaly (FLAGS_fault_injection step.data:mode=nan under the
    numerics plane), serve.hung (delay-injected chunk under the serve
    watchdog), watchdog.timeout — with the trigger event inside the
    bundle's JSONL;
  * FLAGS_numerics_stats: the compiled step returns per-layer-bundle
    grad/param/update norms + a first-nonfinite index; train.numerics
    events carry them; a nan step names the first bad layer and the
    StepAnomalyGuard abort report repeats it;
  * JsonlSink size-capped rotation (FLAGS_telemetry_max_log_mb):
    events.jsonl -> .1 -> .2 shifting, drain-flush preserved,
    merge_jsonl_traces reads segments oldest-first;
  * telemetry.span() marks a raising body with error=<type> and
    re-raises (clean spans are unmarked);
  * summary_of is the one shared window derivation (true min/max
    beside the percentiles) and the report CLIs pick it up;
  * tools/incident_report.py renders bundles; --selftest passes
    (tier-1 wiring, like telemetry_report --selftest).
"""
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.telemetry import flightrec
from paddle_tpu.telemetry.flightrec import FlightRecorder

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane():
    telemetry.reset()
    yield
    from paddle_tpu.framework.flags import set_flags
    telemetry.reset()
    set_flags({"FLAGS_mfu_floor": 0.0, "FLAGS_numerics_stats": False,
               "FLAGS_telemetry_max_log_mb": 0.0,
               "FLAGS_skip_nonfinite_steps": False,
               "FLAGS_stop_check_timeout": 0,
               "FLAGS_max_consecutive_bad_steps": 8})


def _mlp_step():
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    m = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                             paddle.nn.Linear(16, 8))
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = TrainStep(m, lambda o, y: paddle.nn.functional.mse_loss(o, y),
                     opt)
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    return step, x


def _bundle_events(bundle):
    out = []
    with open(os.path.join(bundle, "events.jsonl")) as f:
        for line in f:
            if line.strip():
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# recorder mechanics

class TestRecorder:
    def test_ring_bounded_and_no_dump_without_trigger(self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), ring=16))
        for i in range(100):
            telemetry.emit("train.step", step=i)
        assert len(rec._ring) == 16
        assert rec.bundles() == []

    def test_trigger_dumps_complete_bundle(self, tmp_path):
        rec = telemetry.add_sink(FlightRecorder(str(tmp_path / "inc")))
        for i in range(5):
            telemetry.emit("train.step", step=i, wall_ms=1.0)
        telemetry.emit("perf.drift", label="prog", attained=0.1,
                       floor=0.5)
        bundles = rec.bundles()
        assert len(bundles) == 1
        b = bundles[0]
        assert os.path.basename(b).endswith("perf-drift")
        for f in ("manifest.json", "trigger.json", "events.jsonl",
                  "trace.json", "memory.json", "cost.json",
                  "fingerprint.json"):
            assert os.path.isfile(os.path.join(b, f)), f
        trig = json.load(open(os.path.join(b, "trigger.json")))
        assert trig["event"] == "perf.drift" and trig["label"] == "prog"
        evs = _bundle_events(b)
        assert any(e["event"] == "perf.drift" for e in evs)
        assert sum(1 for e in evs if e["event"] == "train.step") == 5
        trace = json.load(open(os.path.join(b, "trace.json")))
        assert len(trace["traceEvents"]) == len(evs)
        man = json.load(open(os.path.join(b, "manifest.json")))
        assert man["kind"] == "perf.drift" and man["events"] == len(evs)
        fp = json.load(open(os.path.join(b, "fingerprint.json")))
        # resolved FLAGS + the r16 capture-id fingerprint ride along
        assert "FLAGS_numerics_stats" in fp["flags"]
        assert fp["capture_id"]
        # tmp+rename: no half-written directory left behind
        assert not [n for n in os.listdir(tmp_path / "inc")
                    if n.startswith(".tmp-")]

    def test_rate_limit_per_kind_and_distinct_kinds(self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=60.0))
        telemetry.emit("perf.drift", label="a")
        telemetry.emit("perf.drift", label="b")   # same kind: limited
        telemetry.emit("serve.hung", kind="decode")  # new kind: dumps
        names = [os.path.basename(b) for b in rec.bundles()]
        assert len(names) == 2, names
        assert sum("perf-drift" in n for n in names) == 1
        assert sum("serve-hung" in n for n in names) == 1
        assert rec.suppressed == {"perf.drift": 1}
        assert telemetry.registry().dump()["counters"][
            "flightrec.suppressed"] == 1

    def test_interval_zero_dumps_every_trigger(self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0))
        telemetry.emit("perf.drift", label="a")
        telemetry.emit("perf.drift", label="b")
        assert len(rec.bundles()) == 2

    def test_retention_keeps_newest(self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0,
                           keep=2))
        for i in range(5):
            telemetry.emit("perf.drift", label=f"p{i}")
        bundles = rec.bundles()
        assert len(bundles) == 2
        # newest survive: seq 4 and 5
        assert [os.path.basename(b)[:15] for b in bundles] == \
            ["incident-000004", "incident-000005"]
        trig = json.load(open(os.path.join(bundles[-1], "trigger.json")))
        assert trig["label"] == "p4"

    def test_seq_resumes_past_existing_bundles(self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0))
        telemetry.emit("perf.drift", label="first")
        telemetry.remove_sink(rec)
        rec2 = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0))
        telemetry.emit("perf.drift", label="second")
        names = [os.path.basename(b) for b in rec2.bundles()]
        assert names[0].startswith("incident-000001")
        assert names[1].startswith("incident-000002")

    def test_dump_failure_never_detaches_recorder(self, tmp_path):
        target = tmp_path / "inc"
        rec = telemetry.add_sink(FlightRecorder(str(target),
                                                interval_s=0.0))
        # make the incidents dir an unwritable FILE: every dump fails
        with open(target, "w") as f:
            f.write("not a dir")
        telemetry.emit("perf.drift", label="x")
        assert rec.errors == 1
        assert rec in telemetry.sinks()     # still attached
        # and the bus keeps delivering to it
        telemetry.emit("train.step", step=1)
        assert rec._ring[-1]["event"] == "train.step"

    def test_bundle_names_carry_rank_and_collision_falls_back(
            self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0))
        # a same-named NON-EMPTY bundle already on disk (another
        # same-rank process won the rename; empty dirs are replaced by
        # rename): the dump falls back to a pid-suffixed name instead
        # of silently dropping the incident
        decoy = tmp_path / "inc" / "incident-000001-r0-perf-drift"
        os.makedirs(decoy)
        (decoy / "manifest.json").write_text("{}")
        telemetry.emit("perf.drift", label="x")
        assert rec.errors == 0
        names = sorted(os.path.basename(b) for b in rec.bundles())
        assert names[0] == "incident-000001-r0-perf-drift"
        assert names[1] == \
            f"incident-000001-r0-perf-drift-p{os.getpid()}"
        # the fleet identity rides the NAME once announced
        telemetry.set_rank(3, 4)
        telemetry.emit("perf.drift", label="y")
        assert any("-r3-" in os.path.basename(b)
                   for b in rec.bundles())

    def test_detach_returns_recorder_and_restore_reattaches(
            self, tmp_path):
        rec = flightrec.attach(str(tmp_path / "inc"))
        assert flightrec.detach() is rec
        assert flightrec.attached() is None and rec not in \
            telemetry.sinks()
        assert flightrec.restore(rec) is rec
        assert flightrec.attached() is rec and rec in telemetry.sinks()
        assert flightrec.restore(None) is None    # no-op
        flightrec.detach()

    def test_post_trigger_profile_window(self, tmp_path):
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0,
                           profile_steps=2))
        telemetry.emit("perf.drift", label="x")
        if not rec._profile_ok:     # capability-gated: no-op backend
            pytest.skip("jax.profiler unsupported on this backend")
        assert rec._profile_active and rec._profile_left == 2
        telemetry.emit("train.step", step=1)
        telemetry.emit("train.step", step=2)
        # window closed after K step events; the trace landed in the
        # bundle's profile/ dir
        assert not rec._profile_active
        (b,) = rec.bundles()
        assert os.path.isdir(os.path.join(b, "profile"))

    def test_attach_idempotent_and_flag_armed(self, tmp_path):
        from paddle_tpu.framework.flags import set_flags
        r1 = flightrec.attach(str(tmp_path / "a"))
        assert flightrec.attach(str(tmp_path / "b")) is r1
        flightrec.detach()
        assert flightrec.attached() is None
        set_flags({"FLAGS_flightrec_dir": str(tmp_path / "auto")})
        try:
            r2 = flightrec.maybe_attach()
            assert r2 is not None and r2.dir == str(tmp_path / "auto")
        finally:
            set_flags({"FLAGS_flightrec_dir": ""})
            flightrec.detach()
        assert flightrec.maybe_attach() is None


# ---------------------------------------------------------------------------
# every trigger kind, planted for real (the ISSUE 14 coverage matrix).
# Each plant returns the expected bundle kind; the shared assertion is
# "exactly ONE rate-limited bundle of that kind, trigger event inside".

def _plant_drift():
    """perf.drift via configure_peaks + FLAGS_mfu_floor against a real
    compiled program with an absurd measured wall."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.telemetry import costledger
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((32, 32), jnp.float32)).compile()
    costledger.ingest("flightrec.test", compiled)
    costledger.observe("flightrec.test", 250.0)
    costledger.configure_peaks(flops_per_sec=1e15,
                               hbm_bytes_per_sec=1e15)
    set_flags({"FLAGS_mfu_floor": 0.5})
    telemetry.cost_report()
    telemetry.cost_report()         # drift persists: edge, no re-fire
    return "perf.drift"


def _plant_straggler(kv):
    """fleet.straggler via the r14 2-rank harness: rank 1's step-3
    wall 10x the fleet's."""
    from paddle_tpu.telemetry.fleet import FleetAggregator, FleetSink
    for step in (1, 2, 3):
        for rank in (0, 1):
            wall = 100.0 if (rank == 1 and step == 3) else 10.0
            s = FleetSink(kv, job_id="fr", rank=rank, world=2, every=1)
            s.record({"event": "train.step", "step": step,
                      "ts": float(step), "wall_ms": wall,
                      "step_ms": wall, "k": 1})
            s.close()
    FleetAggregator(kv, job_id="fr", world=2, skew_ms=50.0).poll()
    return "fleet.straggler"


def _plant_desync(kv):
    """fleet.desync via rank step-counter spread past the threshold."""
    from paddle_tpu.telemetry.fleet import FleetAggregator, FleetSink
    for rank, step in ((0, 1), (1, 40)):
        s = FleetSink(kv, job_id="fr2", rank=rank, world=2, every=1)
        s.record({"event": "train.step", "step": step,
                  "ts": float(step), "wall_ms": 10.0, "step_ms": 10.0,
                  "k": 1})
        s.close()
    agg = FleetAggregator(kv, job_id="fr2", world=2, desync_steps=8)
    agg.poll()
    agg.poll()                      # edge-triggered: no second event
    return "fleet.desync"


def _plant_nan():
    """train.anomaly via FLAGS_fault_injection step.data:mode=nan under
    the numerics plane."""
    from paddle_tpu.distributed import fault
    from paddle_tpu.framework.flags import set_flags
    set_flags({"FLAGS_numerics_stats": True})
    step, x = _mlp_step()
    step(x, x)                      # clean step: ring has history
    with fault.scope("step.data:mode=nan"):
        step(x, x)
    return "train.anomaly"


def _plant_hung_chunk():
    """serve.hung via a delay-injected chunk aging past the serve
    watchdog deadline."""
    from paddle_tpu.distributed import fault
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    model = LlamaForCausalLM(cfg)
    set_flags({"FLAGS_stop_check_timeout": 0.05})
    try:
        with fault.scope("serve.chunk:step=1:mode=delay:secs=0.6"):
            bat = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                                    chunk=4, prefill_chunk=4)
            bat.submit(np.arange(1, 5, dtype=np.int32), 4)
            bat.run()
    finally:
        set_flags({"FLAGS_stop_check_timeout": 0})
    return "serve.hung"


def _plant_watchdog():
    """watchdog.timeout via a watched block aging past its deadline."""
    from paddle_tpu.distributed.watchdog import watched
    with watched("flightrec probe", timeout=0.05):
        time.sleep(0.6)             # monitor polls at 0.25s
    return "watchdog.timeout"


_PLANTS = {
    "drift": (_plant_drift, False),
    "straggler": (_plant_straggler, True),
    "desync": (_plant_desync, True),
    "nan": (_plant_nan, False),
    "hung_chunk": (_plant_hung_chunk, False),
    "watchdog": (_plant_watchdog, False),
}


class TestTriggerKinds:
    @pytest.mark.parametrize("name", sorted(_PLANTS))
    def test_planted_trigger_lands_one_bundle(self, name, tmp_path):
        plant, needs_kv = _PLANTS[name]
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=60.0))
        if needs_kv:
            from paddle_tpu.distributed.launch.master import (KVClient,
                                                              KVServer)
            server = KVServer(0, host="127.0.0.1").start()
            try:
                kind = plant(KVClient(f"127.0.0.1:{server.port}"))
            finally:
                server.stop()
        else:
            kind = plant()
        # async emitters (watchdog monitor thread): wait for the dump
        deadline = time.monotonic() + 3.0
        want = kind.replace(".", "-")
        while time.monotonic() < deadline:
            if any(want in b for b in rec.bundles()):
                break
            time.sleep(0.05)
        matching = [b for b in rec.bundles() if want in b]
        assert len(matching) == 1, (kind, rec.bundles())
        evs = _bundle_events(matching[0])
        assert any(e.get("event") == kind for e in evs), kind
        if name == "nan":
            # the numerics plane named the first bad layer, inside the
            # SAME bundle (the acceptance criterion's nan case)
            nums = [e for e in evs if e.get("event") == "train.numerics"
                    and e.get("first_nonfinite", -1) >= 0]
            assert nums and nums[0]["first_nonfinite_layer"]


class TestPlantedAnomalyE2E:
    def test_step_begin_nan_spec_produces_named_bundle(self, tmp_path):
        """The acceptance wording verbatim: a run under
        FLAGS_fault_injection=step.begin:mode=nan produces exactly one
        rate-limited bundle per fired trigger kind, each with the
        trigger event and a non-empty ring inside, and the nonfinite
        bundle carries a train.numerics event naming the first bad
        layer."""
        from paddle_tpu.distributed import fault
        from paddle_tpu.framework.flags import set_flags
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=60.0))
        set_flags({"FLAGS_numerics_stats": True})
        step, x = _mlp_step()
        step(x, x)
        with fault.scope("step.begin:mode=nan"):
            loss = step(x, x)
        assert np.isnan(float(loss))    # begin-point nan really plants
        for kind in ("fault.hit", "train.anomaly"):
            matching = [b for b in rec.bundles()
                        if kind.replace(".", "-") in b]
            assert len(matching) == 1, (kind, rec.bundles())
            evs = _bundle_events(matching[0])
            assert evs                  # non-empty ring window
            assert any(e.get("event") == kind for e in evs)
        (anom,) = [b for b in rec.bundles() if "train-anomaly" in b]
        nums = [e for e in _bundle_events(anom)
                if e.get("event") == "train.numerics"
                and e.get("first_nonfinite", -1) >= 0]
        assert nums and nums[0]["first_nonfinite_layer"] == "0"


# ---------------------------------------------------------------------------
# numerics plane

class TestNumerics:
    def test_bundles_of_grouping(self):
        from paddle_tpu.telemetry.numerics import bundles_of
        labels, assign = bundles_of(
            ["layers.0.attn.q.weight", "layers.0.mlp.w", "layers.1.w",
             "embed.weight", "weight"])
        assert labels == ["layers.0", "layers.1", "embed", "weight"]
        assert assign == [0, 0, 1, 2, 3]

    def test_graph_stats_values(self):
        import jax.numpy as jnp
        from paddle_tpu.telemetry.numerics import graph_stats
        params = [jnp.ones((2,)), jnp.ones((2,))]
        grads = [jnp.asarray([3.0, 4.0]), jnp.asarray([0.0, 0.0])]
        new = [jnp.asarray([1.1, 1.0]), jnp.ones((2,))]
        st = graph_stats([0, 1], 2, params, grads, new)
        assert np.allclose(np.asarray(st["grad_norm"]), [5.0, 0.0])
        assert np.allclose(np.asarray(st["param_norm"]),
                           [np.sqrt(2)] * 2)
        assert int(st["first_nonfinite"]) == -1
        grads[1] = jnp.asarray([np.nan, 0.0])
        st = graph_stats([0, 1], 2, params, grads, new)
        assert int(st["first_nonfinite"]) == 1

    def test_trainstep_emits_numerics_events(self):
        from paddle_tpu.framework.flags import set_flags
        set_flags({"FLAGS_numerics_stats": True})
        step, x = _mlp_step()
        probe = telemetry.add_sink(telemetry.MemorySink())
        step(x, x)
        xs = paddle.to_tensor(np.ones((3, 4, 8), np.float32))
        step.run_steps(xs, xs)
        evs = [r for r in probe.records
               if r["event"] == "train.numerics"]
        # one per compiled call (the window's trend sample)
        assert len(evs) == 2
        # the positional bundle labels ride the FIRST event per
        # trainer only (they are identical every step)
        assert len(evs[0]["bundles"]) == len(evs[0]["grad_norm"])
        e = evs[-1]
        assert "bundles" not in e
        assert e["trainer"] == "jit" and e["step"] == 4
        assert len(e["grad_norm"]) == len(evs[0]["bundles"]) \
            == len(e["param_norm"]) == len(e["update_ratio"])
        assert e["first_nonfinite"] == -1
        assert all(v >= 0 for v in e["update_ratio"])
        # registry histograms accumulate sink or not
        d = telemetry.registry().dump()
        assert d["histograms"]["numerics.grad_norm"]["count"] >= 2

    def test_record_window_emits_first_bad_and_last(self):
        # fused window where steps 0 AND 2 go nonfinite: the first bad
        # step is emitted for attribution and the LAST step is still
        # emitted as the trend sample (regression: the last-step emit
        # used to be skipped whenever the last step was bad at all)
        from paddle_tpu.telemetry import numerics
        probe = telemetry.add_sink(telemetry.MemorySink())
        stats = {"grad_norm": np.array([[1.0], [2.0], [3.0]]),
                 "param_norm": np.ones((3, 1)),
                 "update_ratio": np.ones((3, 1)),
                 "first_nonfinite": np.array([0, -1, 0])}
        bad = numerics.record("jit", 3, 3, ["fc"], stats)
        assert bad == "fc"
        nums = [r for r in probe.records
                if r["event"] == "train.numerics"]
        assert [e["step"] for e in nums] == [1, 3]
        assert all(e["first_nonfinite_layer"] == "fc" for e in nums)
        anoms = [r for r in probe.records
                 if r["event"] == "train.anomaly"]
        assert len(anoms) == 1 and anoms[0]["step"] == 1

    def test_flags_off_step_returns_plain_tuple(self):
        # numerics off: the compiled call keeps its historic 4-tuple
        # (tests/test_program_contracts.py holds the HLO half)
        step, x = _mlp_step()
        step(x, x)
        assert not getattr(step, "_numerics", True)

    def test_sharded_guard_abort_names_layer(self):
        import jax
        from paddle_tpu.distributed import fault, guard
        from paddle_tpu.distributed.topology import build_mesh
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.parallel import ShardedTrainStep
        set_flags({"FLAGS_numerics_stats": True,
                   "FLAGS_skip_nonfinite_steps": True,
                   "FLAGS_max_consecutive_bad_steps": 1})
        paddle.seed(0)
        m = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                                 paddle.nn.ReLU(),
                                 paddle.nn.Linear(16, 8))
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = ShardedTrainStep(
            m, opt, build_mesh(devices=jax.devices()[:1]),
            loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        with pytest.raises(guard.BadStepBudgetExceeded) as ei:
            with fault.scope("step.data:mode=nan:times=*"):
                step(x, x)
        assert "first nonfinite layer: 0" in str(ei.value)

    def test_offload_pipeline_per_layer_bundles(self):
        import jax
        from paddle_tpu.distributed.topology import build_mesh
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig
        from paddle_tpu.parallel import OffloadPipelineStep
        set_flags({"FLAGS_numerics_stats": True})
        paddle.seed(7)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=3,
                          num_attention_heads=2, num_key_value_heads=2,
                          max_position_embeddings=32, dtype="float32")
        m = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters())
        st = OffloadPipelineStep(m, opt,
                                 build_mesh(devices=jax.devices()[:1]))
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 64, (2, 16))
            .astype(np.int32))
        probe = telemetry.add_sink(telemetry.MemorySink())
        st(ids, ids)
        evs = [r for r in probe.records
               if r["event"] == "train.numerics"]
        assert len(evs) == 1
        e = evs[0]
        # one bundle per scanned layer + the pre/post tail
        assert e["bundles"] == ["layer0", "layer1", "layer2", "tail"]
        assert e["first_nonfinite"] == -1
        assert all(v > 0 for v in e["grad_norm"])


# ---------------------------------------------------------------------------
# satellites: rotation, span error, summary_of

class TestJsonlRotation:
    def test_rotation_shifts_segments_and_merge_reads_in_order(
            self, tmp_path):
        from paddle_tpu.telemetry import JsonlSink
        from paddle_tpu.telemetry.fleet import (load_jsonl, log_segments,
                                                merge_jsonl_traces)
        path = str(tmp_path / "events.jsonl")
        sink = JsonlSink(path, max_mb=0.0003)   # ~300 bytes per segment
        n = 40
        for i in range(n):
            sink.record({"ts": float(i), "event": "train.step", "i": i})
        sink.close()
        assert os.path.exists(path + ".1") and os.path.exists(path + ".2")
        # oldest-first segment order, every record present exactly once,
        # in emit order across the rotation boundaries
        segs = log_segments(path)
        assert segs[-1] == path
        recs = [r for s in segs for r in load_jsonl(s)]
        assert [r["i"] for r in recs] == list(range(n))
        doc = merge_jsonl_traces([path])
        data = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        assert len(data) == n

    def test_flag_drives_rotation_and_default_off(self, tmp_path):
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.telemetry import JsonlSink
        p1 = str(tmp_path / "a.jsonl")
        sink = JsonlSink(p1)                    # flag at default: off
        for i in range(50):
            sink.record({"event": "x", "pad": "y" * 64})
        sink.close()
        assert not os.path.exists(p1 + ".1")
        set_flags({"FLAGS_telemetry_max_log_mb": 0.0003})
        try:
            p2 = str(tmp_path / "b.jsonl")
            sink = JsonlSink(p2)
            for i in range(50):
                sink.record({"event": "x", "pad": "y" * 64})
            sink.close()
            assert os.path.exists(p2 + ".1")
        finally:
            set_flags({"FLAGS_telemetry_max_log_mb": 0.0})

    def test_file_object_sink_never_rotates(self, tmp_path):
        import io
        from paddle_tpu.telemetry import JsonlSink
        buf = io.StringIO()
        sink = JsonlSink(buf, max_mb=0.0001)    # not owned: cap ignored
        for i in range(50):
            sink.record({"event": "x", "pad": "y" * 64})
        assert len(buf.getvalue().splitlines()) == 50


class TestSpanError:
    def test_raising_span_marked_and_reraises(self):
        probe = telemetry.add_sink(telemetry.MemorySink())
        with pytest.raises(ValueError):
            with telemetry.span("phase.x", step=1):
                raise ValueError("boom")
        with telemetry.span("phase.x", step=2):
            pass
        bad, clean = probe.records
        assert bad["error"] == "ValueError" and bad["step"] == 1
        assert "dur_ms" in bad
        assert "error" not in clean and clean["step"] == 2


class TestSummaryOf:
    def test_true_min_max_beside_percentiles(self):
        s = telemetry.summary_of([5.0, 1.0, 3.0, 100.0])
        assert s["count"] == 4
        assert s["min"] == 1.0 and s["max"] == 100.0
        assert s["p50"] == 3.0 or s["p50"] == 5.0
        assert telemetry.summary_of([]) == {
            "count": 0, "min": 0.0, "max": 0.0, "p50": 0.0,
            "p90": 0.0, "p99": 0.0}

    def test_histogram_summary_has_true_min_max(self):
        h = telemetry.histogram("fr.test", window=4)
        for v in (50.0, 1.0, 2.0, 3.0, 4.0, 5.0):
            h.observe(v)            # 50.0 rotated out of the window
        s = h.summary()
        assert s["min"] == 1.0 and s["max"] == 50.0   # lifetime-true

    def test_report_cli_step_ms_min_max(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import telemetry_report as cli
        finally:
            sys.path.pop(0)
        events = [{"ts": float(i), "event": "train.step", "step": i,
                   "wall_ms": w, "step_ms": w, "k": 1}
                  for i, w in enumerate((9.0, 1.0, 2.0, 2.5))]
        events[0]["cold"] = True    # excluded from the summary
        rep = cli.analyze(events)
        assert rep["step_ms"]["min"] == 1.0
        assert rep["step_ms"]["max"] == 2.5
        assert cli.render(rep)

    def test_serving_latency_block_carries_min_max(self):
        # the stats() block reads the shared derivation — synthesize
        # the window rather than running a server
        from paddle_tpu.telemetry import summary_of
        s = summary_of([2.0, 40.0, 3.0])
        assert set(s) >= {"count", "min", "max", "p50", "p90", "p99"}
        assert s["max"] == 40.0


# ---------------------------------------------------------------------------
# incident report CLI

class TestIncidentReportCLI:
    def _cli(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import incident_report as cli
        finally:
            sys.path.pop(0)
        return cli

    def test_selftest(self):
        # tier-1 wiring (acceptance): plants a drift AND a nan fault,
        # asserts one bundle each with the right trigger, renders both
        assert self._cli().main(["--selftest"]) == 0

    def test_render_bundle_and_directory(self, tmp_path, capsys):
        cli = self._cli()
        rec = telemetry.add_sink(
            FlightRecorder(str(tmp_path / "inc"), interval_s=0.0))
        telemetry.emit("train.step", step=1, wall_ms=2.0)
        telemetry.emit("train.numerics", trainer="jit", step=1,
                       bundles=["fc"], grad_norm=[1.5],
                       param_norm=[2.0], update_ratio=[0.001],
                       first_nonfinite=-1)
        telemetry.emit("perf.drift", label="prog", attained=0.1)
        (b,) = rec.bundles()
        rep = cli.analyze(b)
        assert rep["kind"] == "perf.drift"
        assert rep["numerics"]["samples"] == 1
        assert rep["timeline"][-1]["event"] == "perf.drift"
        out = cli.render(rep)
        assert "perf.drift" in out and "numerics" in out
        # directory mode renders every bundle; missing path errors
        assert cli.main([str(tmp_path / "inc")]) == 0
        assert "incident:" in capsys.readouterr().out
        assert cli.main([str(tmp_path / "nothing")]) == 1
