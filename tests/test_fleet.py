"""Fleet-wide observability (ISSUE 10): rank-aware telemetry, the
coordinator aggregator + straggler/desync detector, the HBM memory
ledger, per-request serve spans, and the sink drain-flush contract.

The contracts under test:

  * every emitted event carries (rank, world) once the fleet identity
    is announced — trainers, watchdog, fault registry, checkpoint
    runtime all inherit it from the bus (satellite: they were
    anonymous);
  * FleetSink publishes per-rank step summaries into the launch KV
    store; FleetAggregator judges per-step cross-rank wall/arrival
    skew, emits fleet.straggler naming the slow rank, arms/disarms
    the comm watchdog, and emits fleet.desync on step-counter spread
    or per-step collective-kind mismatch;
  * 2-process e2e (acceptance): per-rank JSONL logs merge into ONE
    chrome trace with a lane per rank, and a mode=delay fault injected
    into rank 1 makes the coordinator fire fleet.straggler while both
    ranks complete bit-exact;
  * telemetry.memory_report() returns non-empty per-program byte
    accounting for every trainer and the serve step (XLA's own
    memory_analysis, not hand-derived), and lint_peak_hbm flags a
    planted over-budget program;
  * ContinuousBatcher stamps queue→admit→first-token→finish per
    request: stats() carries TTFT/TPOT/e2e/queue percentiles and
    per-SLO attainment, serve.request events feed the report CLI;
  * JSONL/chrome sinks flush on interpreter exit, so a SIGTERM drain
    loses nothing (subprocess kill mid-run, tail asserted on disk);
  * tools/fleet_report.py --selftest (tier-1 wiring, like
    telemetry_report --selftest).
"""
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.telemetry.fleet import (FleetSink, FleetAggregator,
                                        merge_jsonl_traces, load_jsonl)
from paddle_tpu.distributed.launch.master import KVServer, KVClient

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_plane():
    """Sinks detached, rank identity dropped, memory ledger empty on
    both sides of every test (the plane is process-global)."""
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture()
def kv_store():
    server = KVServer(0, host="127.0.0.1").start()
    try:
        yield KVClient(f"127.0.0.1:{server.port}")
    finally:
        server.stop()


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


# ---------------------------------------------------------------------------
# rank-aware records

class TestRankTagging:
    def test_events_carry_rank_and_world(self):
        telemetry.set_rank(3, 4)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            telemetry.emit("probe", a=1)
        finally:
            telemetry.remove_sink(sink)
        (rec,) = sink.records
        assert rec["rank"] == 3 and rec["world"] == 4

    def test_single_process_world_omits_world_field(self):
        telemetry.set_rank(0, 1)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            telemetry.emit("probe")
        finally:
            telemetry.remove_sink(sink)
        (rec,) = sink.records
        assert rec["rank"] == 0 and "world" not in rec

    def test_uninitialized_stays_untagged(self):
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            telemetry.emit("probe")
        finally:
            telemetry.remove_sink(sink)
        assert "rank" not in sink.records[0]

    def test_runtime_producers_inherit_rank(self, tmp_path):
        """Satellite: watchdog, fault-registry and checkpoint events
        were anonymous — with the identity announced they all carry
        the rank label without any call-site change."""
        from paddle_tpu.distributed import fault
        from paddle_tpu.distributed import checkpoint as ckpt
        from paddle_tpu.distributed.watchdog import CommTaskManager
        telemetry.set_rank(2, 4)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            with fault.scope("step.begin:mode=delay:secs=0"):
                fault.hit("step.begin", key="probe")
            ckpt.save_checkpoint(
                {"w": paddle.to_tensor(np.ones((2, 2), np.float32))},
                str(tmp_path), 1)
            mgr = CommTaskManager(poll_interval=0.02)
            task = mgr.start_task("rank probe hang", timeout=0.05)
            try:
                deadline = time.time() + 5
                while not mgr.timeout_log and time.time() < deadline:
                    time.sleep(0.02)
            finally:
                task.done()
                mgr.shutdown()
        finally:
            telemetry.remove_sink(sink)
        by_event = {}
        for r in sink.records:
            by_event.setdefault(r["event"], r)
        for ev in ("fault.hit", "ckpt.commit", "watchdog.timeout"):
            assert ev in by_event, sorted(by_event)
            assert by_event[ev]["rank"] == 2, by_event[ev]
            assert by_event[ev]["world"] == 4

    def test_train_step_events_tagged(self):
        telemetry.set_rank(1, 2)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            step, x = _mlp_step()
            step(x, x)
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records if r["event"] == "train.step"]
        assert evs and evs[0]["rank"] == 1 and evs[0]["world"] == 2

    def test_init_parallel_env_announces_rank(self):
        from paddle_tpu.distributed import env as denv
        prev = denv._initialized
        denv._initialized = False
        try:
            denv.init_parallel_env()
            assert telemetry.rank_info() == (0, 1)
        finally:
            denv._initialized = prev

    def test_dump_carries_identity(self):
        telemetry.set_rank(5, 8)
        d = telemetry.dump()
        assert d["rank"] == {"rank": 5, "world": 8}


# ---------------------------------------------------------------------------
# histogram percentiles (satellite)

class TestPercentiles:
    def test_histogram_percentiles_and_summary(self):
        h = telemetry.histogram("lat")
        for v in range(1, 101):
            h.observe(float(v))
        pct = h.percentiles((50, 90, 99))
        assert pct["p50"] == pytest.approx(50, abs=1)
        assert pct["p90"] == pytest.approx(90, abs=1)
        assert pct["p99"] == pytest.approx(99, abs=1)
        s = h.summary()
        assert {"p50", "p90", "p99"} <= set(s)
        d = telemetry.dump()
        assert d["histograms"]["lat"]["p90"] == s["p90"]

    def test_percentiles_of_empty(self):
        assert telemetry.percentiles_of([], (50, 99)) \
            == {"p50": 0.0, "p99": 0.0}


# ---------------------------------------------------------------------------
# fleet sink + aggregator

def _publish(kv, rank, step, wall_ms, ts=None, world=2, job="j",
             kinds=None, cold=False):
    s = FleetSink(kv, job_id=job, rank=rank, world=world, every=1)
    if kinds is not None:
        s.record({"event": "collective.schedule", "kinds": kinds})
    rec = {"event": "train.step", "step": step,
           "ts": float(step) if ts is None else ts,
           "wall_ms": wall_ms, "step_ms": wall_ms, "k": 1}
    if cold:
        rec["cold"] = True
    s.record(rec)
    s.close()       # synchronous drain: the summary is in the store


class TestAggregator:
    def test_straggler_detected_and_attributed(self, kv_store):
        for step in (1, 2, 3):
            for rank in (0, 1):
                wall = 100.0 if (rank == 1 and step == 3) else 10.0
                _publish(kv_store, rank, step, wall)
        probe = telemetry.add_sink(telemetry.MemorySink())
        try:
            agg = FleetAggregator(kv_store, job_id="j", world=2,
                                  skew_ms=50.0)
            rep = agg.poll()
        finally:
            telemetry.remove_sink(probe)
        evs = [r for r in probe.records
               if r["event"] == "fleet.straggler"]
        assert len(evs) == 1
        assert evs[0]["straggler"] == 1 and evs[0]["step"] == 3
        assert evs[0]["skew_ms"] == pytest.approx(90.0)
        assert rep["max_skew_ms"] == pytest.approx(90.0)
        assert rep["stragglers"] == {1: 1}
        # steps are judged exactly once: a second poll is silent
        probe2 = telemetry.add_sink(telemetry.MemorySink())
        try:
            agg.poll()
        finally:
            telemetry.remove_sink(probe2)
        assert not [r for r in probe2.records
                    if r["event"] == "fleet.straggler"]

    def test_below_threshold_records_skew_silently(self, kv_store):
        for rank in (0, 1):
            _publish(kv_store, rank, 1, 10.0 + rank)
        probe = telemetry.add_sink(telemetry.MemorySink())
        try:
            rep = FleetAggregator(kv_store, job_id="j", world=2,
                                  skew_ms=50.0).poll()
        finally:
            telemetry.remove_sink(probe)
        assert rep["skews"] and not rep["stragglers"]
        assert not [r for r in probe.records
                    if r["event"] == "fleet.straggler"]

    def test_cold_steps_not_judged(self, kv_store):
        for rank in (0, 1):
            _publish(kv_store, rank, 1, 1000.0 if rank else 1.0,
                     cold=True)
        rep = FleetAggregator(kv_store, job_id="j", world=2,
                              skew_ms=10.0).poll()
        assert not rep["skews"] and not rep["stragglers"]

    def test_straggler_arms_and_disarms_watchdog(self, kv_store):
        from paddle_tpu.framework.flags import set_flags
        from paddle_tpu.distributed.watchdog import get_comm_task_manager
        set_flags({"FLAGS_stop_check_timeout": 600})
        try:
            agg = FleetAggregator(kv_store, job_id="j", world=2,
                                  skew_ms=50.0)
            for rank in (0, 1):
                _publish(kv_store, rank, 1, 100.0 if rank else 10.0)
            rep = agg.poll()
            assert rep["watchdog_armed"] == [1]
            assert "fleet.straggler rank1" in \
                get_comm_task_manager().active_tasks()
            # rank 1 catches up -> disarmed
            for rank in (0, 1):
                _publish(kv_store, rank, 2, 10.0)
            rep = agg.poll()
            assert rep["watchdog_armed"] == []
            assert "fleet.straggler rank1" not in \
                get_comm_task_manager().active_tasks()
        finally:
            set_flags({"FLAGS_stop_check_timeout": 0})
            agg.close()

    def test_tombstoned_rank_never_reads_as_straggler(self, kv_store):
        """ISSUE 19 satellite: a rank retired by a scale-in tombstones
        itself — its stale summaries leave the judged set, the
        effective world shrinks so the survivors' steps keep being
        judged, and no spurious fleet.straggler ever fires."""
        from paddle_tpu.telemetry.fleet import tombstone_rank
        for step in (1, 2):
            for rank in (0, 1):
                _publish(kv_store, rank, step, 10.0)
        # rank 1 retires mid-run through the sink's own retire() path
        s = FleetSink(kv_store, job_id="j", rank=1, world=2, every=1)
        s.retire()
        assert kv_store.get("j/fleet/1/tombstone") is not None
        # the survivor keeps stepping alone; rank 1's stale summaries
        # are still on the plane
        for step in (3, 4):
            _publish(kv_store, 0, step, 10.0)
        probe = telemetry.add_sink(telemetry.MemorySink())
        try:
            agg = FleetAggregator(kv_store, job_id="j", world=2,
                                  skew_ms=50.0)
            agg.straggler_counts[1] = 3       # stale verdicts clear too
            rep = agg.poll()
        finally:
            telemetry.remove_sink(probe)
        assert not [r for r in probe.records
                    if r["event"] == "fleet.straggler"]
        assert rep["tombstoned"] == [1]
        assert rep["world_effective"] == 1
        assert rep["ranks"] == [0]
        assert rep["stragglers"] == {}
        # the survivor's solo steps WERE judged (world shrank — the
        # aggregator isn't waiting forever for the retired rank)
        assert rep["steps_judged"] == 4
        # idempotent across polls and across a re-retire
        assert tombstone_rank(kv_store, "j", 1)
        rep2 = agg.poll()
        assert rep2["tombstoned"] == [1] and rep2["stragglers"] == {}

    def test_desync_on_step_spread(self, kv_store):
        _publish(kv_store, 0, 30, 10.0)
        _publish(kv_store, 1, 1, 10.0)
        probe = telemetry.add_sink(telemetry.MemorySink())
        try:
            agg = FleetAggregator(kv_store, job_id="j", world=2,
                                  skew_ms=0.0, desync_steps=8)
            agg.poll()
            agg.poll()              # edge-triggered: no second event
        finally:
            telemetry.remove_sink(probe)
        evs = [r for r in probe.records if r["event"] == "fleet.desync"]
        assert len(evs) == 1
        assert evs[0]["reason"] == "step-spread"
        assert evs[0]["spread"] == 29

    def test_desync_on_collective_mismatch(self, kv_store):
        _publish(kv_store, 0, 1, 10.0, kinds={"psum": 2})
        _publish(kv_store, 1, 1, 10.0, kinds={"psum": 3})
        probe = telemetry.add_sink(telemetry.MemorySink())
        try:
            FleetAggregator(kv_store, job_id="j", world=2,
                            skew_ms=0.0).poll()
        finally:
            telemetry.remove_sink(probe)
        evs = [r for r in probe.records if r["event"] == "fleet.desync"]
        assert evs and evs[0]["reason"] == "collectives"

    def test_collective_kinds_ride_one_summary_only(self, kv_store):
        """Regression: a probe's kind counts attach to the NEXT
        summary only — a stale mix smeared onto every later step
        would read as a permanent desync."""
        s = FleetSink(kv_store, job_id="c1", rank=0, world=1, every=1)
        s.record({"event": "collective.schedule",
                  "kinds": {"psum": 2}})
        for step in (1, 2):
            s.record({"event": "train.step", "step": step,
                      "ts": float(step), "wall_ms": 1.0,
                      "step_ms": 1.0, "k": 1})
        s.close()
        one = json.loads(kv_store.get("c1/fleet/0/s00000001"))
        two = json.loads(kv_store.get("c1/fleet/0/s00000002"))
        assert one["collectives"] == {"psum": 2}
        assert "collectives" not in two

    def test_sink_prunes_its_window(self, kv_store):
        s = FleetSink(kv_store, job_id="w", rank=0, world=1, every=1,
                      window=4)
        for step in range(1, 11):
            s.record({"event": "train.step", "step": step,
                      "ts": float(step), "wall_ms": 1.0,
                      "step_ms": 1.0, "k": 1})
        s.close()
        keys = set(kv_store.prefix("w/fleet"))
        step_keys = {k for k in keys if not k.endswith("/latest")}
        assert len(step_keys) == 4          # rolling window
        assert "w/fleet/0/s00000010" in step_keys
        latest = json.loads(kv_store.get("w/fleet/0/latest"))
        assert latest["step"] == 10

    def test_sink_prunes_strided_steps(self, kv_store):
        """Regression: fused multi-step trainers publish steps k, 2k,
        3k... — the window must prune the keys actually published,
        not `step - window` (which is never a published key when the
        stride doesn't divide the window)."""
        s = FleetSink(kv_store, job_id="ws", rank=0, world=1, every=1,
                      window=3)
        for step in range(5, 55, 5):        # stride 5, 10 publishes
            s.record({"event": "train.step", "step": step,
                      "ts": float(step), "wall_ms": 1.0,
                      "step_ms": 1.0, "k": 5})
        s.close()
        step_keys = {k for k in kv_store.prefix("ws/fleet")
                     if not k.endswith("/latest")}
        assert len(step_keys) == 3, step_keys
        assert "ws/fleet/0/s00000050" in step_keys

    def test_sink_never_blocks_on_a_stalled_coordinator(self, kv_store):
        """Regression: the publisher is decoupled behind a bounded
        queue — with the coordinator stalled (each publish slow),
        record() returns immediately and overflow is counted as
        dropped, never stalling the step loop."""
        s = FleetSink(kv_store, job_id="stall", rank=0, world=1,
                      every=1)
        s._publish = lambda msg: time.sleep(0.02)   # stalled KV
        t0 = time.perf_counter()
        for step in range(1, 61):
            s.record({"event": "train.step", "step": step,
                      "ts": float(step), "wall_ms": 1.0,
                      "step_ms": 1.0, "k": 1})
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.5, elapsed       # 60 records, no KV waits
        assert s.dropped > 0                # bounded queue overflowed
        s.close()


# ---------------------------------------------------------------------------
# trace merge

class TestMerge:
    def test_one_lane_per_rank(self, tmp_path):
        logs = []
        for rank in (0, 1):
            p = str(tmp_path / f"r{rank}.jsonl")
            with open(p, "w") as f:
                for step in (1, 2):
                    f.write(json.dumps(
                        {"ts": time.time(), "event": "train.step",
                         "rank": rank, "step": step, "wall_ms": 1.0,
                         "dur_ms": 1.0}) + "\n")
            logs.append(p)
        out = str(tmp_path / "merged.json")
        doc = merge_jsonl_traces(logs, out_path=out)
        lanes = {e["pid"] for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
        assert lanes == {0, 1}
        names = {e["pid"]: e["args"]["name"]
                 for e in doc["traceEvents"]
                 if e.get("name") == "process_name"}
        assert names == {0: "rank 0", 1: "rank 1"}
        assert json.load(open(out))["traceEvents"]

    def test_untagged_log_uses_positional_rank(self, tmp_path):
        p = str(tmp_path / "solo.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"ts": 1.0, "event": "x"}) + "\n")
        doc = merge_jsonl_traces([p], ranks=[7])
        evs = [e for e in doc["traceEvents"] if e.get("ph") != "M"]
        assert evs[0]["pid"] == 7

    def test_torn_tail_line_dropped(self, tmp_path):
        p = str(tmp_path / "torn.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"ts": 1.0, "event": "x"}) + "\n")
            f.write('{"ts": 2.0, "event": "tr')     # crash mid-write
        assert len(load_jsonl(p)) == 1


# ---------------------------------------------------------------------------
# 2-process e2e (acceptance criterion)

_WORKER = r"""
import json
import os
import numpy as np
import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.telemetry.fleet import FleetSink, init_from_env
from paddle_tpu.distributed.launch.master import KVClient

rank, world = init_from_env()
kv = KVClient(os.environ["KV_ENDPOINT"])
sink = telemetry.attach_jsonl(os.environ["FLEET_LOG"])
telemetry.add_sink(FleetSink(kv, job_id="e2e", every=1))

from paddle_tpu.jit import TrainStep
paddle.seed(0)
m = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                         paddle.nn.Linear(16, 8))
opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
step = TrainStep(m, lambda o, y: paddle.nn.functional.mse_loss(o, y),
                 opt)
x = paddle.to_tensor(np.ones((4, 8), np.float32))
loss = None
for _ in range(6):
    loss = step(x, x)
print("RESULT " + json.dumps(
    {"rank": rank, "loss": float(np.asarray(loss.value))}))
"""


class TestTwoProcessE2E:
    def test_delay_fault_fires_straggler_and_merge_lanes(
            self, kv_store, tmp_path):
        """The acceptance e2e: two ranks train the same 6 steps; rank 1
        runs under an injected per-step delay fault.  The coordinator's
        aggregator must fire fleet.straggler naming rank 1, both ranks
        must finish bit-exact (the delay changes no math), and the two
        JSONL logs must merge into one trace with a lane per rank."""
        procs, logs = [], []
        for rank in (0, 1):
            log = str(tmp_path / f"rank{rank}.jsonl")
            logs.append(log)
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       PYTHONPATH=REPO + os.pathsep
                       + os.environ.get("PYTHONPATH", ""),
                       PADDLE_TRAINER_ID=str(rank),
                       PADDLE_TRAINERS_NUM="2",
                       KV_ENDPOINT=kv_store.endpoint,
                       FLEET_LOG=log)
            if rank == 1:
                env["FLAGS_fault_injection"] = \
                    "step.begin:mode=delay:secs=0.15:times=*"
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _WORKER], env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE))
        results = {}
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-2000:]
            line = next(l for l in out.splitlines()
                        if l.startswith("RESULT "))
            rec = json.loads(line[len("RESULT "):])
            results[rec["rank"]] = rec["loss"]

        # bit-exact completion: the straggler's math is unchanged
        assert results[0] == results[1]

        # coordinator detects the planted straggler from the KV
        # summaries (arrival skew accumulates ~0.15s/step on rank 1)
        probe = telemetry.add_sink(telemetry.MemorySink())
        try:
            agg = FleetAggregator(kv_store, job_id="e2e", world=2,
                                  skew_ms=100.0)
            rep = agg.poll()
            agg.close()
        finally:
            telemetry.remove_sink(probe)
        evs = [r for r in probe.records
               if r["event"] == "fleet.straggler"]
        assert evs, rep
        assert all(e["straggler"] == 1 for e in evs), evs
        assert rep["stragglers"].get(1, 0) >= 1

        # per-rank logs are rank-tagged and merge into rank lanes
        for rank, log in enumerate(logs):
            steps = [e for e in load_jsonl(log)
                     if e["event"] == "train.step"]
            assert len(steps) == 6
            assert all(e["rank"] == rank and e["world"] == 2
                       for e in steps)
        doc = merge_jsonl_traces(logs)
        lanes = {e["pid"] for e in doc["traceEvents"]
                 if e.get("ph") != "M"}
        assert lanes == {0, 1}


# ---------------------------------------------------------------------------
# HBM memory ledger

class TestMemoryLedger:
    def test_trainstep_accounted(self):
        step, x = _mlp_step()
        step(x, x)
        rep = telemetry.memory_report()
        rec = rep["programs"]["jit.TrainStep.step"]
        assert rec["status"] == "ok"
        assert rec["argument_bytes"] > 0
        assert rec["peak_bytes"] > 0
        assert rep["peak_hbm_bytes"] >= rec["peak_bytes"]

    def test_sharded_trainer_accounted(self):
        import jax
        from paddle_tpu.parallel import ShardedTrainStep
        from paddle_tpu.distributed.topology import build_mesh

        paddle.seed(0)
        m = paddle.nn.Linear(8, 8)
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = ShardedTrainStep(
            m, opt, build_mesh(devices=jax.devices()[:1]),
            sharding_stage=3,
            loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        step(x, x)
        rep = telemetry.memory_report()
        rec = rep["programs"]["ShardedTrainStep.step.s3"]
        assert rec["status"] == "ok" and rec["peak_bytes"] > 0

    def test_offload_pipeline_accounted(self):
        import jax
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             LlamaConfig)
        from paddle_tpu.parallel import OffloadPipelineStep
        from paddle_tpu.distributed.topology import build_mesh

        paddle.seed(7)
        m = LlamaForCausalLM(LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=32,
            dtype="float32"))
        opt = paddle.optimizer.AdamW(1e-2,
                                     parameters=m.parameters())
        step = OffloadPipelineStep(
            m, opt, build_mesh(devices=jax.devices()[:1]))
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randint(0, 64, (2, 16))
                             .astype(np.int32))
        step(x, x)
        rep = telemetry.memory_report()
        rec = rep["programs"]["OffloadPipelineStep.step"]
        assert rec["status"] == "ok" and rec["peak_bytes"] > 0

    def test_serve_step_accounted(self):
        from paddle_tpu.inference import ContinuousBatcher
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(3)
        cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                                intermediate_size=64,
                                num_attention_heads=2,
                                num_key_value_heads=2, vocab_size=64)
        model = LlamaForCausalLM(cfg)
        bat = ContinuousBatcher(model, max_batch_size=2, max_len=32,
                                chunk=4, prefill_chunk=4)
        rep = telemetry.memory_report()
        for label in ("serve_step.decode", "serve_step.admit"):
            rec = rep["programs"][label]
            assert rec["status"] == "ok", rec
            # the KV pool rides the carry: arguments dominate
            assert rec["argument_bytes"] > bat.kv_cache_bytes()

    def test_resolution_is_side_effect_free_for_serve(self):
        """The ledger resolves through lower_step(record=False): it
        must not inflate compiled_programs or defeat the first-use
        timing exclusion (the r12 probe contract)."""
        from paddle_tpu.inference import ContinuousBatcher
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             llama_tiny_config)
        paddle.seed(3)
        cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                                intermediate_size=64,
                                num_attention_heads=2,
                                num_key_value_heads=2, vocab_size=64)
        model = LlamaForCausalLM(cfg)
        bat = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                                chunk=4, prefill_chunk=4)
        telemetry.memory_report()
        assert bat.compiled_programs == 0
        rng = np.random.RandomState(0)
        bat.submit(rng.randint(1, 64, 4).astype(np.int32), 4)
        bat.run()
        assert bat.stats()["compiled_programs"] <= 2

    def test_dump_never_resolves(self):
        step, x = _mlp_step()
        step(x, x)
        d = telemetry.dump()
        assert d["memory"]["programs"]["jit.TrainStep.step"][
            "status"] == "pending"
        assert d["memory"]["peak_hbm_bytes"] == 0

    def test_lint_peak_hbm_flags_planted_over_budget(self):
        from paddle_tpu.analysis import lint_peak_hbm
        step, x = _mlp_step()
        step(x, x)
        findings = lint_peak_hbm(budget_bytes=1)
        assert findings
        assert all(f.code == "peak-hbm-over-budget" for f in findings)
        assert any("jit.TrainStep.step" in f.message
                   for f in findings)
        assert lint_peak_hbm(budget_bytes=10 ** 15) == []

    def test_lint_peak_hbm_single_compiled(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.analysis import lint_peak_hbm
        lowered = jax.jit(lambda a, b: a @ b).lower(
            jnp.ones((64, 64)), jnp.ones((64, 64)))
        assert lint_peak_hbm(lowered, budget_bytes=1,
                             label="planted")[0].code \
            == "peak-hbm-over-budget"
        assert lint_peak_hbm(lowered.compile(),
                             budget_bytes=10 ** 12) == []

    def test_aot_capture_not_clobbered_and_matches_lazy(self, tmp_path,
                                                        monkeypatch):
        """With FLAGS_compile_cache_dir armed the AOT path captures
        stats for FREE at its own compile — note_jit (registered
        before aot_for) must not clobber them back to pending, and the
        lazy provider's numbers must agree with the captured ones."""
        from paddle_tpu.framework.flags import set_flags
        step, x = _mlp_step()
        step(x, x)
        lazy = telemetry.memory_report()["programs"][
            "jit.TrainStep.step"]
        assert lazy["status"] == "ok"
        telemetry.reset()
        # the store goes to the cache directory in force: keep it out
        # of the checkout's
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        set_flags({"FLAGS_compile_cache_dir": "1"})
        try:
            paddle.seed(0)
            step2, x2 = _mlp_step()
            step2(x2, x2)
        finally:
            set_flags({"FLAGS_compile_cache_dir": ""})
        snap = telemetry.memledger.snapshot()["programs"][
            "jit.TrainStep.step"]
        assert snap["status"] == "ok", snap     # free capture survived
        for k in ("argument_bytes", "output_bytes", "temp_bytes"):
            assert snap[k] == lazy[k], (k, snap, lazy)

    def test_mem_program_events_published_on_resolve(self):
        step, x = _mlp_step()
        step(x, x)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            telemetry.memory_report()
        finally:
            telemetry.remove_sink(sink)
        evs = [r for r in sink.records if r["event"] == "mem.program"]
        assert evs and evs[0]["label"] == "jit.TrainStep.step"
        assert evs[0]["peak_bytes"] > 0


# ---------------------------------------------------------------------------
# per-request serve spans

@pytest.fixture(scope="module")
def span_model():
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    paddle.seed(13)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    return LlamaForCausalLM(cfg)


class TestServeSpans:
    def test_stats_latency_block(self, span_model):
        from paddle_tpu.inference import ContinuousBatcher
        rng = np.random.RandomState(2)
        bat = ContinuousBatcher(span_model, max_batch_size=2,
                                max_len=32, chunk=4, prefill_chunk=4)
        bat.submit(rng.randint(1, 64, 4).astype(np.int32), 5,
                   slo="interactive", deadline_ms=60000)
        bat.submit(rng.randint(1, 64, 6).astype(np.int32), 5,
                   slo="batch")
        bat.run()
        st = bat.stats()
        lat = st["latency"]
        assert lat["e2e_ms"]["count"] == 2
        assert lat["ttft_ms"]["count"] == 2
        assert lat["queue_ms"]["count"] == 2
        # spans nest: queue <= ttft <= e2e at matching percentiles
        assert lat["queue_ms"]["p50"] <= lat["ttft_ms"]["p99"]
        assert lat["ttft_ms"]["p99"] <= lat["e2e_ms"]["p99"]
        assert lat["tpot_ms"]["count"] == 2
        att = st["slo_attainment"]
        assert att["interactive"]["with_deadline"] == 1
        assert att["interactive"]["deadline_met"] == 1
        assert att["interactive"]["attainment"] == 1.0
        assert att["batch"]["completed"] == 1

    def test_request_events_and_ordering(self, span_model):
        from paddle_tpu.inference import ContinuousBatcher
        rng = np.random.RandomState(4)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            bat = ContinuousBatcher(span_model, max_batch_size=2,
                                    max_len=32, chunk=4,
                                    prefill_chunk=4)
            for L in (4, 7):
                bat.submit(rng.randint(1, 64, L).astype(np.int32), 5)
            bat.run()
        finally:
            telemetry.remove_sink(sink)
        reqs = [r for r in sink.records
                if r["event"] == "serve.request"]
        assert len(reqs) == 2
        for e in reqs:
            for k in ("req", "slo", "tokens", "queue_ms", "ttft_ms",
                      "e2e_ms"):
                assert k in e, e
            assert e["queue_ms"] <= e["ttft_ms"] <= e["e2e_ms"]
        # timing histograms observed while the sink was live
        d = telemetry.dump()
        assert d["histograms"]["serve.ttft_ms"]["count"] == 2
        assert d["histograms"]["serve.e2e_ms"]["count"] == 2

    def test_shed_requests_take_no_latency_sample(self, span_model):
        from paddle_tpu.inference import ContinuousBatcher
        rng = np.random.RandomState(5)
        bat = ContinuousBatcher(span_model, max_batch_size=1,
                                max_len=32, chunk=4, prefill_chunk=4)
        bat.submit(rng.randint(1, 64, 4).astype(np.int32), 4,
                   slo="batch", deadline_ms=0.001)
        bat.submit(rng.randint(1, 64, 4).astype(np.int32), 4,
                   slo="batch", deadline_ms=0.001)
        time.sleep(0.01)
        bat.run()
        st = bat.stats()
        served = st["requests_completed"]
        assert st["requests_shed"] >= 1
        assert st["latency"]["e2e_ms"]["count"] == served
        att = st["slo_attainment"]["batch"]
        assert att["shed"] == st["requests_shed"]

    def test_requeued_request_spans_describe_final_decode(
            self, span_model):
        """A faulted slot's re-decode restarts admit/first-token: the
        delivered spans describe the decode the user got, with
        e2e still measured from the original submit."""
        from paddle_tpu.inference import ContinuousBatcher
        from paddle_tpu.distributed import fault
        rng = np.random.RandomState(6)
        sink = telemetry.add_sink(telemetry.MemorySink())
        try:
            with fault.scope("serve.decode:step=1:times=1"
                             ":mode=corrupt"):
                bat = ContinuousBatcher(span_model, max_batch_size=1,
                                        max_len=32, chunk=4,
                                        prefill_chunk=4)
                bat.submit(rng.randint(1, 64, 4).astype(np.int32), 5)
                bat.run()
        finally:
            telemetry.remove_sink(sink)
        st = bat.stats()
        assert st["requests_requeued"] == 1
        reqs = [r for r in sink.records
                if r["event"] == "serve.request"]
        assert len(reqs) == 1 and reqs[0]["requeues"] == 1
        assert reqs[0]["ttft_ms"] <= reqs[0]["e2e_ms"]


# ---------------------------------------------------------------------------
# sink drain flush (satellite)

_FLUSH_WORKER = r"""
import os
import signal
import sys
import time
from paddle_tpu import telemetry

signal.signal(signal.SIGTERM, lambda *a: sys.exit(1))
telemetry.attach_jsonl(os.environ["LOG"], flush_every=100000)
telemetry.attach_chrome_trace(os.environ["TRACE"])
for i in range(25):
    telemetry.emit("step.mark", step=i)
with open(os.environ["READY"], "w") as f:
    f.write("ready")
while True:
    time.sleep(0.05)
"""


class TestSinkDrainFlush:
    def test_sigterm_mid_run_keeps_the_tail(self, tmp_path):
        """Kill a worker mid-run: the buffered JSONL tail (flush_every
        huge) and the chrome trace must still land on disk via the
        atexit drain path — the last emitted step is recoverable."""
        log = str(tmp_path / "steps.jsonl")
        trace = str(tmp_path / "trace.json")
        ready = str(tmp_path / "ready")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=REPO + os.pathsep
                   + os.environ.get("PYTHONPATH", ""),
                   LOG=log, TRACE=trace, READY=ready)
        proc = subprocess.Popen([sys.executable, "-c", _FLUSH_WORKER],
                                env=env, text=True,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE)
        try:
            deadline = time.time() + 120
            while not os.path.exists(ready) \
                    and time.time() < deadline:
                assert proc.poll() is None, \
                    proc.communicate()[1][-2000:]
                time.sleep(0.05)
            assert os.path.exists(ready), "worker never came up"
            proc.send_signal(signal.SIGTERM)
            proc.communicate(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
        events = load_jsonl(log)
        marks = [e for e in events if e["event"] == "step.mark"]
        assert len(marks) == 25
        assert marks[-1]["step"] == 24      # the TAIL survived
        doc = json.load(open(trace))
        assert len([e for e in doc["traceEvents"]
                    if e["name"] == "step.mark"]) == 25

    def test_close_unregisters_atexit(self, tmp_path):
        import atexit
        sink = telemetry.JsonlSink(str(tmp_path / "s.jsonl"))
        sink.close()
        # double-unregister must not raise; closed sink's drain is a
        # no-op
        atexit.unregister(sink._drain_flush)
        sink._drain_flush()


# ---------------------------------------------------------------------------
# CLI wiring (satellite: tier-1 runs the fleet selftest)

class TestFleetReportCLI:
    def test_selftest(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import fleet_report as cli
        finally:
            sys.path.pop(0)
        assert cli.main(["--selftest"]) == 0

    def test_offline_report_and_trace(self, tmp_path):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import fleet_report as cli
        finally:
            sys.path.pop(0)
        logs = []
        for rank in (0, 1):
            p = str(tmp_path / f"r{rank}.jsonl")
            with open(p, "w") as f:
                for step in (1, 2, 3):
                    wall = 80.0 if (rank == 1 and step == 2) else 8.0
                    f.write(json.dumps(
                        {"ts": float(step), "event": "train.step",
                         "rank": rank, "step": step,
                         "wall_ms": wall}) + "\n")
                f.write(json.dumps(
                    {"ts": 4.0, "event": "mem.program",
                     "rank": rank, "label": f"prog{rank}",
                     "argument_bytes": 10, "output_bytes": 4,
                     "temp_bytes": 6, "alias_bytes": 0,
                     "generated_code_bytes": 0,
                     "peak_bytes": 20 + rank}) + "\n")
            logs.append(p)
        rep = cli.analyze_fleet([load_jsonl(p) for p in logs],
                                skew_ms=50.0)
        assert rep["steps_compared"] == 3
        assert rep["stragglers"] == {"1": 1}
        top = rep["skew_table"][0]
        assert top["step"] == 2 and top["flagged"]
        assert rep["memory"]["peak_hbm_bytes"] == 21
        assert cli.render(rep)
        trace = str(tmp_path / "m.json")
        assert cli.main(logs + ["--trace", trace, "--json"]) == 0
        assert json.load(open(trace))["traceEvents"]

    def test_offline_rank_collision_reassigned(self, tmp_path):
        """Regression: an untagged log whose positional index matches
        a tagged rank must get a free lane (and a warning), never
        silently replace the tagged rank's steps."""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import fleet_report as cli
        finally:
            sys.path.pop(0)
        tagged = str(tmp_path / "tagged.jsonl")
        with open(tagged, "w") as f:
            f.write(json.dumps({"ts": 1.0, "event": "train.step",
                                "rank": 1, "step": 1,
                                "wall_ms": 5.0}) + "\n")
        untagged = str(tmp_path / "untagged.jsonl")
        with open(untagged, "w") as f:
            f.write(json.dumps({"ts": 1.0, "event": "train.step",
                                "step": 1, "wall_ms": 7.0}) + "\n")
        rep = cli.analyze_fleet([load_jsonl(tagged),
                                 load_jsonl(untagged)])
        assert set(rep["ranks"]) == {"1", "2"}
        (c,) = rep["rank_collisions"]
        assert c["claimed"] == 1 and c["assigned"] == 2
        assert "WARNING" in cli.render(rep)
