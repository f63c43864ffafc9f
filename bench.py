"""Benchmarks: all five BASELINE.md configs + serving decode + offload.

Default run (no BENCH_CONFIG) measures EVERY config and prints one JSON
line per config — llama, offload-llama, bert, resnet, unet, decode — so
the driver-captured BENCH file records the full matrix, not just llama
(round-5 verdict item 3).  Each metric is the MEDIAN of BENCH_REPS
(default 3) timed repetitions of the same compiled program, with the
relative spread (max-min)/median reported alongside; compilation happens
once per config, outside the reps.

BENCH_CONFIG=llama|offload|bert|resnet|unet|decode|serve|longctx runs
one config; `python bench.py --only llama_serve_mixed` (metric OR
config name) re-measures a single metric in isolation with the same
reps>=3 + spread discipline.  Reference throughput instrumentation
analog: python/paddle/profiler/timer.py:351 (ips Benchmark).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

def chip_peak_flops():
    """Canonical bf16 peak — ONE table for the whole repo
    (telemetry.costledger owns it, keyed by device_kind; the cost
    ledger's roofline and these MFU lines can never quote different
    peaks).  PEAK_FLOPS env overrides; a device the table does not
    know raises."""
    from paddle_tpu.telemetry.costledger import chip_peak_flops as _cpf
    return _cpf()


def _reps():
    return max(1, int(os.environ.get("BENCH_REPS", "3")))


_ENV_FP = None


def _env_fingerprint():
    """Environment fingerprint for this capture (ISSUE 12): jax/jaxlib
    versions, backend + device kind, and the bench-relevant flags/envs.
    The perf sentry (tools/perf_report.py) compares metric lines only
    between captures whose fingerprints match — a library bump or a
    flag flip must read as 'incomparable', never as a regression.
    THE derivation lives in telemetry.flightrec (ISSUE 14: incident
    bundles carry the same identity, so a rendered incident matches
    the BENCH baselines it drifted from)."""
    global _ENV_FP
    if _ENV_FP is None:
        from paddle_tpu.telemetry.flightrec import env_fingerprint
        _ENV_FP = env_fingerprint()
    return _ENV_FP


def _capture_id():
    """Stable id of the env fingerprint (BENCH_CAPTURE_ID overrides):
    the sentry's match key."""
    from paddle_tpu.telemetry.flightrec import capture_id
    return capture_id(_env_fingerprint())


def _measure(rep_fn):
    """rep_fn() -> throughput for one timed repetition of the already-
    compiled program.  Returns (median, rel_spread, all_values)."""
    vals = [float(rep_fn()) for _ in range(_reps())]
    med = float(np.median(vals))
    spread = (max(vals) - min(vals)) / med if med > 0 else 0.0
    return med, spread, vals


def _emit(metric, value, unit, vs_baseline, spread, vals, extra=None):
    rec = {
        "metric": metric,
        "value": round(value, 1) if value >= 10 else round(value, 3),
        "unit": unit,
        "vs_baseline": round(vs_baseline, 3),
        "reps": len(vals),
        "spread": round(spread, 3),
        # env fingerprint + capture id (ISSUE 12): the perf sentry's
        # cross-environment refusal key
        "capture_id": _capture_id(),
        "env": _env_fingerprint(),
    }
    if len(vals) < 2:
        # a one-shot line has no spread to judge a regression against
        # — the sentry skips it instead of false-firing
        rec["comparable"] = False
    if extra:
        rec.update(extra)
    # the telemetry snapshot rides every metric line: lifetime counters
    # (train.steps, serve.chunks, pp.train_batches, fault/watchdog/ckpt
    # — incremented sink or not) plus compile-cache totals of THIS
    # config's process (each config runs in its own subprocess).  The
    # step/chunk TIMING histograms stay empty here by design — observed
    # only while a sink is attached, and bench runs sink-less (the
    # zero-overhead assert).
    try:
        from paddle_tpu import telemetry
        rec["telemetry"] = telemetry.dump(compact=True)
    except Exception:
        pass
    print(json.dumps(rec), flush=True)


def _peak_hbm_fields():
    """Measured peak HBM of this config's step program(s) — XLA's own
    `memory_analysis()` via the telemetry memory ledger (ISSUE 10),
    replacing hand-derived peak claims.  Resolution may recompile the
    step once (same cost class as the phase probes); BENCH_MEM=0
    skips it."""
    if os.environ.get("BENCH_MEM", "1") == "0":
        return {}
    try:
        from paddle_tpu import telemetry
        mem = telemetry.memory_report(top_buffers=0)
        if mem["peak_hbm_bytes"]:
            out = {"peak_hbm_bytes": int(mem["peak_hbm_bytes"])}
            if mem["device_hbm_bytes"]:
                out["peak_hbm_share"] = round(
                    mem["peak_hbm_bytes"] / mem["device_hbm_bytes"], 3)
            return out
    except Exception:
        pass
    return {}


def _cost_fields():
    """Cost-ledger roofline fields for this config's step program(s)
    (ISSUE 12): FLOPs/bytes/intensity + the roofline bound and the
    predicted step time at the calibrated peaks, from the same
    resolution pass _peak_hbm_fields already paid for.  Bench runs
    sink-less, so no measured walls ride along (the drift check lives
    in the live telemetry plane).  BENCH_MEM=0 skips (shared gate: the
    ledgers resolve together)."""
    if os.environ.get("BENCH_MEM", "1") == "0":
        return {}
    try:
        from paddle_tpu import telemetry
        rep = telemetry.cost_report()
        rows = {}
        for label, rec in rep["programs"].items():
            if rec.get("status") != "ok":
                continue
            rows[label] = {"flops": rec["flops"],
                           "bytes_accessed": rec["bytes_accessed"],
                           "intensity": rec.get("intensity"),
                           "bound": rec.get("bound"),
                           "predicted_ms": rec.get("predicted_ms")}
        if rows:
            return {"cost": rows}
    except Exception:
        pass
    return {}


def _phase_fields(model, step, batch, seq, n_params, label,
                  remat_flops=0.0):
    """fwd/bwd/opt phase decomposition (the PROFILE_r05 method, shared
    with tools/profile_mfu.py) as JSON-ready fields, so BENCH_r* tracks
    the gap items the kernel fusions target — not just tokens/s.
    BENCH_PHASES=0 skips the extra phase compiles."""
    if os.environ.get("BENCH_PHASES", "1") == "0":
        return None
    repo = os.path.dirname(os.path.abspath(__file__))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from tools.profile_mfu import _profile
        r = _profile(model, step, batch, seq, n_params, label,
                     remat_flops)
    except Exception as e:  # phases are telemetry, never a bench failure
        return {"phases_error": str(e)[:120]}
    return {"phases": {
        "fwd_ms": round(r["t_fwd_ms"], 1),
        "bwd_ms": round(r["t_bwd_ms"], 1),
        "opt_ms": round(r["t_opt_ms"], 1),
        "full_ms": round(r["t_full_ms"], 1),
        "fwd_util": round(r["fwd_util"], 3),
        "bwd_util": round(r["bwd_util"], 3),
        "bwd_util_hw": round(r["bwd_util_hw"], 3),
        "step_mfu": round(r["mfu_full"], 3),
    }}


def bench_llama(offload=False):
    """BASELINE.md config 3: llama pretraining tokens/s/chip + MFU.
    offload=True is the ZeRO-3 host-offload config (params beyond the
    fp32-resident ceiling; fp32 master + moments in pinned host)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    requested_offload = offload      # metric name tracks the REQUEST
    offload = offload and on_tpu
    if on_tpu:
        # 1.0B-param GQA llama sized for v5e 16G HBM.  Mixed precision
        # the TPU-idiomatic way: fp32 params (the param IS the master —
        # no separate copy) + bf16 compute + bf16 AdamW moments via the
        # fused Pallas kernel → resident state 8.0G, leaving ~6G for
        # activations.  r4 sweep: 3 selective-remat layers is the
        # throughput/gap sweet spot (mfu 0.538, hw_util-mfu 0.019).
        n_sel = int(os.environ.get("BENCH_RECOMPUTE_LAYERS", "3"))
        if offload:
            size = os.environ.get("BENCH_OFFLOAD_SIZE", "4b")
            if size == "4b":
                # 4.0B params — ~4x the fp32-resident ceiling (verdict
                # item 5): bf16 params resident (8.1G), fp32 master +
                # moments (48G) parked in pinned host, streamed per-
                # block through HBM inside the step
                cfg = LlamaConfig(vocab_size=8192, hidden_size=4608,
                                  intermediate_size=12544,
                                  num_hidden_layers=20,
                                  num_attention_heads=36,
                                  num_key_value_heads=4,
                                  max_position_embeddings=2048,
                                  dtype="bfloat16",
                                  recompute=True, recompute_layers=None,
                                  recompute_granularity="full")
            else:
                cfg = LlamaConfig(vocab_size=8192, hidden_size=3584,
                                  intermediate_size=9600,
                                  num_hidden_layers=14,
                                  num_attention_heads=28,
                                  num_key_value_heads=4,
                                  max_position_embeddings=2048,
                                  dtype="bfloat16",
                                  recompute=True, recompute_layers=None,
                                  recompute_granularity="full")
            batch = int(os.environ.get("BENCH_BATCH", "2"))
        else:
            cfg = LlamaConfig(vocab_size=8192, hidden_size=2560,
                              intermediate_size=6912,
                              num_hidden_layers=14,
                              num_attention_heads=20,
                              num_key_value_heads=4,
                              max_position_embeddings=2048,
                              dtype="bfloat16", param_dtype="float32",
                              recompute=n_sel > 0,
                              recompute_layers=n_sel,
                              recompute_granularity="selective")
            batch = int(os.environ.get("BENCH_BATCH", "4"))
        seq, steps = 2048, 8
    else:  # CPU smoke path so the script always runs
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256, dtype="float32")
        batch, seq, steps = 2, 128, 3
        n_sel = 0

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.value.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1,
                                 multi_precision=offload,
                                 moment_dtype="bfloat16" if on_tpu
                                 else None)
    mesh = build_mesh(devices=jax.devices()[:1])
    if requested_offload:
        # explicit double-buffered streaming pipeline (parallel/
        # offload_pipeline.py): per-layer prefetch windows forward AND
        # backward, in-backward fused AdamW on each streamed slice —
        # replaces the scheduler-overlapped param_stream path that
        # measured 0.188x baseline in r5.  The CPU smoke run exercises
        # the same scanned program minus placement annotations.
        prefetch = int(os.environ.get("BENCH_OFFLOAD_PREFETCH", "1"))
        step = ShardedTrainStep(
            model, opt, mesh, sharding_stage=3, rematerialize=False,
            offload="stream", offload_prefetch_depth=prefetch,
            offload_cast_dtype="bfloat16" if on_tpu else None)
    else:
        step = ShardedTrainStep(model, opt, mesh, sharding_stage=3,
                                rematerialize=False)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    tokens_per_sec, spread, vals, floss = _timed_train_tokens(
        step, x, batch, seq, steps)
    final_loss = [floss]
    from paddle_tpu.telemetry.costledger import model_train_flops
    model_flops = model_train_flops(n_params, tokens_per_sec)
    peak = chip_peak_flops()
    mfu = model_flops / peak
    # hardware utilization: selective remat replays only gate/up MLP
    # matmuls; the offload pipeline full-remats every layer (the
    # backward scan recomputes each block from its input residual)
    if requested_offload:
        recompute_per_tok = 2.0 * n_params
    else:
        recompute_per_tok = n_sel * (4.0 * cfg.hidden_size
                                     * cfg.intermediate_size)
    hw_util = mfu * (6.0 * n_params + recompute_per_tok) / (6.0 * n_params)
    name = "llama_offload_train_tokens_per_sec_per_chip" \
        if requested_offload else "llama_train_tokens_per_sec_per_chip"
    unit = (f"tokens/s/chip (mfu={mfu:.3f}, hw_util={hw_util:.3f}, "
            f"params={n_params/1e6:.0f}M, loss={final_loss[0]:.3f}")
    if requested_offload:
        # achieved-overlap telemetry (ISSUE 2): analytic DMA bytes, a
        # measured streaming-only probe, and its share of the step wall
        # — dma_share→1 reads bandwidth-bound (the pipeline is doing
        # its job; buy bandwidth or shrink bytes), dma_share≪1 with
        # low MFU reads schedule-bound (overlap is broken; fix the
        # program)
        pipe = step._pipeline
        sb = pipe.stream_bytes_per_step()
        step_wall = batch * seq / tokens_per_sec
        dma_s = pipe.dma_probe()
        unit += (f", h2d={sb['h2d_bytes'] / 1e9:.2f}G/step, "
                 f"d2h={sb['d2h_bytes'] / 1e9:.2f}G/step, "
                 f"dma_share={min(dma_s / step_wall, 9.99):.2f}, "
                 f"prefetch_depth={sb['prefetch_depth']}")
    extra = {}
    if not requested_offload:
        extra = _phase_fields(model, step, batch, seq, n_params,
                              "llama", recompute_per_tok) or {}
    extra.update(_peak_hbm_fields())
    extra.update(_cost_fields())
    _emit(name, tokens_per_sec, unit + ")", mfu / 0.40, spread, vals,
          extra=extra or None)


def _timed_train_tokens(step, x, batch, seq, steps):
    """Shared train-bench timing harness: warmup/compile, then timed
    reps.  The host transfer (`float(np.asarray(...))`) of the last
    loss ends each timed region in a real completion."""
    loss = step(x, x)
    _ = float(np.asarray(loss.value))
    final_loss = [0.0]

    def rep():
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, x)
        final_loss[0] = float(np.asarray(loss.value))
        return batch * seq * steps / (time.perf_counter() - t0)

    tokens_per_sec, spread, vals = _measure(rep)
    return tokens_per_sec, spread, vals, final_loss[0]


def bench_llama_overlap():
    """llama_sharded_overlap (ISSUE 16): the ZeRO-3 sharded trainer
    with bucketed gradient collectives overlapped with the backward
    (FLAGS_comm_overlap / parallel/comm_overlap.py).

    On TPU the step shards over every chip with the overlap engine
    armed; the exposed-comm column comes from the trainer's own plan
    through the cost ledger.  The CPU smoke run has one device (the
    plan is inactive by design — nothing to overlap), so the column is
    quoted from an 8-way MODELED plan over the same parameter list —
    the same estimator, same ledger path, no chip time.  Either way
    the leg emits `exposed_comm.on_ms` / `off_ms`, and perf_report.py
    gates on_ms < off_ms: the overlap engine must never PREDICT more
    exposed communication than the monolithic baseline."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.parallel.comm_overlap import CommOverlapPlan
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu import telemetry
    from paddle_tpu.telemetry import costledger

    if on_tpu:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=2560,
                          intermediate_size=6912,
                          num_hidden_layers=14,
                          num_attention_heads=20,
                          num_key_value_heads=4,
                          max_position_embeddings=2048,
                          dtype="bfloat16", param_dtype="float32",
                          recompute=True, recompute_layers=3,
                          recompute_granularity="selective")
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        seq, steps = 2048, 8
        bucket_mb = float(os.environ.get("BENCH_BUCKET_MB", "32"))
        n_shard = len(jax.devices())
    else:  # CPU smoke: tiny model, small buckets so the modeled plan
        #    still exercises the multi-bucket (n>=2) overlap shape
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256, dtype="float32")
        batch, seq, steps = 2, 128, 3
        bucket_mb = float(os.environ.get("BENCH_BUCKET_MB", "0.25"))
        n_shard = len(jax.devices())

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.value.shape))
                   for p in model.parameters())
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1)
    mesh = build_mesh(sharding=n_shard) if n_shard > 1 \
        else build_mesh(devices=jax.devices()[:1])
    step = ShardedTrainStep(model, opt, mesh, sharding_stage=3,
                            rematerialize=False, comm_overlap=True,
                            comm_bucket_mb=bucket_mb)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    tokens_per_sec, spread, vals, floss = _timed_train_tokens(
        step, x, batch, seq, steps)

    label = "ShardedTrainStep.step.s3"
    plan = step._overlap_plan
    if plan is None:
        # single-device smoke: model the 8-way plan over the same
        # param list and attach it to the ledger exactly as the
        # trainer would (verify() first — same static pre-flight)
        names = [n for n, _ in model.named_parameters()]
        shapes = [tuple(p.value.shape)
                  for _, p in model.named_parameters()]
        dts = [str(p.value.dtype) for _, p in model.named_parameters()]
        plan = CommOverlapPlan.modeled(
            names, shapes, dts, world=8, stage=3, bucket_mb=bucket_mb)
        plan.verify()
        costledger.note_comm(label, plan.comm_profile())

    exposed = {}
    try:
        rec = telemetry.cost_report()["programs"].get(label) or {}
        if "exposed_comm_ms" in rec:
            exposed = {
                "on_ms": rec["exposed_comm_ms"],
                "off_ms": rec["exposed_comm_ms_monolithic"],
                "comm_ms": rec["comm_ms"],
                "buckets": rec["comm_buckets"],
                "bytes": rec["comm_bytes"],
                "overlap_efficiency": rec["overlap_efficiency"],
                "modeled": step._overlap_plan is None,
            }
    except Exception as e:  # the column is telemetry, not the metric
        exposed = {"error": str(e)[:120]}

    from paddle_tpu.telemetry.costledger import model_train_flops
    mfu = model_train_flops(n_params, tokens_per_sec) \
        / chip_peak_flops()
    unit = (f"tokens/s/chip (mfu={mfu:.3f}, "
            f"params={n_params / 1e6:.0f}M, loss={floss:.3f}, "
            f"buckets={len(plan.buckets)}, shard={n_shard})")
    extra = {"exposed_comm": exposed,
             "comm_overlap": step._overlap_plan is not None,
             "bucket_mb": bucket_mb}
    extra.update(_peak_hbm_fields())
    extra.update(_cost_fields())
    _emit("llama_sharded_overlap_tokens_per_sec_per_chip",
          tokens_per_sec, unit, mfu / 0.40, spread, vals, extra=extra)


def _parse_hybrid_mesh(spec):
    """'dp2xmp2xsharding2' → {'dp_degree': 2, 'mp_degree': 2, ...}."""
    import re
    out = {}
    for m in re.finditer(r"(dp|mp|pp|sep|sharding)(\d+)", spec or ""):
        out[m.group(1) + "_degree"] = int(m.group(2))
    return out


def bench_llama_hybrid():
    """llama_hybrid (ISSUE 17): ONE strategy point of the composed N-D
    hybrid engine (parallel/hybrid_engine.py) — measured tokens/s/chip
    next to the cost ledger's per-axis exposed-comm columns and the
    roofline's predicted step time, so the record carries measured-vs-
    predicted MFU PER MESH SHAPE.

    On TPU the engine composes over every chip; BENCH_HYBRID_MESH
    ("dp2xmp4", "dp2xmp2xsharding2", ...) picks the point, default
    dp×mp over all chips.  The CPU smoke run has one device, so the
    measured wall comes from the engine's single-axis program (which
    the zero-overhead assert proves byte-identical to the plain
    trainer) and the quoted per-axis columns come from
    modeled_axis_profiles for the dp2×mp2×sharding2 8-way point over
    the SAME parameter list — same estimator, same ledger join that a
    real mesh would use, no chip time.  Either way the static
    pre-flight (engine.verify: composed collective-order check) runs
    before any timing, and perf_report.py gates the per-axis columns:
    they must sum to the program totals (no double-counting) and
    overlapped exposure must never exceed monolithic."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.parallel import HybridParallelEngine
    from paddle_tpu.parallel.hybrid_engine import modeled_axis_profiles
    from paddle_tpu import telemetry
    from paddle_tpu.telemetry import costledger

    n_dev = len(jax.devices())
    if on_tpu:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=2560,
                          intermediate_size=6912,
                          num_hidden_layers=14,
                          num_attention_heads=20,
                          num_key_value_heads=4,
                          max_position_embeddings=2048,
                          dtype="bfloat16", param_dtype="float32",
                          recompute=True, recompute_layers=3,
                          recompute_granularity="selective")
        batch = int(os.environ.get("BENCH_BATCH", "4"))
        seq, steps = 2048, 8
        default = f"dp{max(1, n_dev // 2)}xmp{2 if n_dev >= 2 else 1}"
        degrees = _parse_hybrid_mesh(
            os.environ.get("BENCH_HYBRID_MESH", default))
    else:  # CPU smoke: one device — engine runs single-axis, columns
        #    are modeled for the quoted 8-way point below
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256, dtype="float32")
        batch, seq, steps = 2, 128, 3
        degrees = {}

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.value.shape))
                   for p in model.parameters())
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1)
    engine = HybridParallelEngine(model, opt, **degrees)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    engine.verify(x, x)  # static pre-flight before any chip time
    tokens_per_sec, spread, vals, floss = _timed_train_tokens(
        engine, x, batch, seq, steps)

    label = engine.cost_label()
    quoted = degrees
    if engine.mesh.size == 1:
        # quote the 8-way modeled point through the same ledger path
        quoted = {"dp_degree": 2, "mp_degree": 2, "sharding_degree": 2}
        params = [(tuple(p.value.shape), str(p.value.dtype))
                  for _, p in model.named_parameters()]
        dq = {k.replace("_degree", ""): v for k, v in quoted.items()}
        for prof in modeled_axis_profiles(params, cfg, dq,
                                          (batch, seq), stage=1):
            costledger.note_comm(label, prof)

    exposed = {}
    predicted_ms = None
    try:
        rec = telemetry.cost_report()["programs"].get(label) or {}
        predicted_ms = rec.get("predicted_ms")
        if "exposed_comm_ms" in rec:
            exposed = {
                "on_ms": rec["exposed_comm_ms"],
                "off_ms": rec["exposed_comm_ms_monolithic"],
                "comm_ms": rec["comm_ms"],
                "buckets": rec["comm_buckets"],
                "bytes": rec["comm_bytes"],
                "per_axis": rec.get("exposed_comm_by_axis"),
                "overlap_efficiency": rec["overlap_efficiency"],
                "modeled": engine.mesh.size == 1,
            }
    except Exception as e:  # the column is telemetry, not the metric
        exposed = {"error": str(e)[:120]}

    from paddle_tpu.telemetry.costledger import model_train_flops
    mfu = model_train_flops(n_params, tokens_per_sec) \
        / chip_peak_flops()
    measured_ms = batch * seq * 1e3 / tokens_per_sec
    mesh_name = "x".join(f"{k.replace('_degree', '')}{v}"
                         for k, v in quoted.items()) or "single"
    unit = (f"tokens/s/chip (mfu={mfu:.3f}, mesh={mesh_name}, "
            f"params={n_params / 1e6:.0f}M, loss={floss:.3f})")
    extra = {"exposed_comm": exposed, "mesh": mesh_name,
             "degrees": {k.replace("_degree", ""): v
                         for k, v in quoted.items()},
             "measured_step_ms": round(measured_ms, 3)}
    if predicted_ms is not None:
        extra["predicted_step_ms"] = predicted_ms
    extra.update(_peak_hbm_fields())
    extra.update(_cost_fields())
    _emit("llama_hybrid_tokens_per_sec_per_chip",
          tokens_per_sec, unit, mfu / 0.40, spread, vals, extra=extra)


def bench_longctx():
    """Long-context training (SURVEY §5.7): the same 1.0B llama at
    seq 16384 (8x the headline config), batch 1, through the Pallas
    flash-attention path — flash's O(seq) memory is what makes a 16k
    context FIT next to 8G of resident fp32+moment state on the 16G
    chip.  MFU here uses attention-INCLUSIVE model FLOPs per token:
    6N dense + 6·L·h·seq attention (PaLM's 12·L·h·seq causal-halved);
    at 16k the attention matmuls are 37% of the work, so the
    dense-only 6N basis would overstate utilization."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    if on_tpu:
        seq = int(os.environ.get("BENCH_LONGCTX_SEQ", "16384"))
        remat = os.environ.get("BENCH_LONGCTX_REMAT", "full")
        cfg = LlamaConfig(vocab_size=8192, hidden_size=2560,
                          intermediate_size=6912, num_hidden_layers=14,
                          num_attention_heads=20, num_key_value_heads=4,
                          max_position_embeddings=seq,
                          dtype="bfloat16", param_dtype="float32",
                          recompute=remat != "none",
                          recompute_layers=None,
                          recompute_granularity=remat
                          if remat != "none" else "full")
        batch, steps = 1, 4
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=512, dtype="float32")
        batch, seq, steps = 1, 512, 2

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.value.shape))
                   for p in model.parameters())
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1,
                                 moment_dtype="bfloat16" if on_tpu
                                 else None)
    mesh = build_mesh(devices=jax.devices()[:1])
    step = ShardedTrainStep(model, opt, mesh, sharding_stage=3,
                            rematerialize=False)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    tokens_per_sec, spread, vals, floss = _timed_train_tokens(
        step, x, batch, seq, steps)
    # attention-inclusive train FLOPs/token: 6N dense + 6·L·h·seq
    # attention — PaLM's 12·L·h·seq (fwd 2 + bwd 4 passes over the
    # 2·seq·h QK^T/AV matmul pair per layer) halved for causal masking
    attn_per_tok = 6.0 * cfg.num_hidden_layers * cfg.hidden_size * seq
    model_flops = (6.0 * n_params + attn_per_tok) * tokens_per_sec
    mfu = model_flops / chip_peak_flops()
    _emit("llama_longctx_train_tokens_per_sec_per_chip",
          tokens_per_sec,
          f"tokens/s/chip (seq={seq}, b={batch}, mfu={mfu:.3f} "
          f"attention-inclusive, params={n_params/1e6:.0f}M, "
          f"attn_share={attn_per_tok/(6.0*n_params+attn_per_tok):.2f}, "
          f"loss={floss:.3f})",
          mfu / 0.40, spread, vals)


def _class_correlated_images(n, num_classes, rng, noise=0.6):
    """Learnable synthetic CIFAR stand-in (zero-egress environment):
    per-class template + gaussian noise — convergence on a held-out
    split is real evidence the training machinery optimizes."""
    templates = rng.randn(num_classes, 3, 32, 32).astype(np.float32)
    labels = rng.randint(0, num_classes, n)
    imgs = templates[labels] + noise * rng.randn(n, 3, 32, 32)
    return imgs.astype(np.float32), labels.astype(np.int64)


def bench_resnet():
    """BASELINE.md config 1: ResNet-50 on CIFAR-10-shaped data —
    images/sec + top-1 convergence on a held-out split."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.vision.models import resnet50, resnet18
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    rng = np.random.RandomState(0)
    if on_tpu:
        model = resnet50(num_classes=10)
        batch, n_train, n_test, epochs = 256, 4096, 1024, 3
    else:
        model = resnet18(num_classes=10)
        batch, n_train, n_test, epochs = 32, 64, 32, 1

    xs_all, ys_all = _class_correlated_images(n_train + n_test, 10, rng)
    xs, ys = xs_all[:n_train], ys_all[:n_train]
    xt, yt = xs_all[n_train:], ys_all[n_train:]
    opt = paddle.optimizer.Momentum(0.02, momentum=0.9,
                                    parameters=model.parameters(),
                                    weight_decay=1e-4)
    loss_fn = lambda o, y: nn.functional.cross_entropy(o, y)
    step = TrainStep(model, loss_fn, opt)

    steps_per_epoch = n_train // batch
    # pre-stage the whole epoch as [K, b, ...] and fuse the K steps into
    # ONE device program per epoch (TrainStep.run_steps lax.scan):
    # per-step dispatch latency would otherwise dominate CIFAR-sized
    # compute
    sx = paddle.to_tensor(
        xs[: steps_per_epoch * batch].reshape(steps_per_epoch, batch,
                                              *xs.shape[1:]))
    sy = paddle.to_tensor(
        ys[: steps_per_epoch * batch].reshape(steps_per_epoch, batch))
    _ = float(np.asarray(step.run_steps(sx, sy).value[-1]))  # compile
    final_loss = [0.0]

    def rep():
        t0 = time.perf_counter()
        for _ in range(epochs):
            losses = step.run_steps(sx, sy)
        final_loss[0] = float(np.asarray(losses.value[-1]))
        return epochs * steps_per_epoch * batch \
            / (time.perf_counter() - t0)

    images_per_sec, spread, vals = _measure(rep)

    # held-out top-1 (jitted eval — per-op eager would be host-bound)
    import jax.numpy as jnp
    from paddle_tpu.jit import to_static
    model.eval()
    eval_fwd = to_static(model)
    correct = tot = 0
    for i in range(0, n_test, batch):
        out = eval_fwd(paddle.to_tensor(xt[i:i + batch]))
        pred = np.asarray(jnp.argmax(out.value, axis=-1))
        correct += int((pred == yt[i:i + batch]).sum())
        tot += len(pred)
    top1 = correct / max(1, tot)

    _emit("resnet50_cifar_images_per_sec", images_per_sec,
          f"images/s (top1={top1:.3f} heldout after "
          f"{epochs * _reps()} epochs, loss={final_loss[0]:.3f})",
          top1 / 0.90, spread, vals)


def bench_bert():
    """BASELINE.md config 2: BERT-base pretraining, DP + sharding
    stage 1 — tokens/s/chip + MFU."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertForMaskedLM, BertConfig
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    paddle.seed(0)
    if on_tpu:
        # fp32 params ARE the masters (nn.set_compute_dtype flax idiom)
        # + bf16 compute; b=64 fits with bf16 logits (r4: 0.481 MFU)
        cfg = BertConfig(dtype="bfloat16")
        batch = int(os.environ.get("BENCH_BATCH", "64"))
        seq, steps = 512, 8
    else:
        cfg = BertConfig(vocab_size=128, hidden_size=64,
                         num_hidden_layers=2, num_attention_heads=4,
                         intermediate_size=128,
                         max_position_embeddings=64)
        batch, seq, steps = 2, 32, 2

    model = BertForMaskedLM(cfg)
    n_params = sum(int(np.prod(p.value.shape))
                   for p in model.parameters())
    # fp32 moments: at 110M params the update is cheap, and bf16
    # moments force tail-padding copies on the ragged tied embedding
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 weight_decay=0.01)
    mesh = build_mesh(sharding=1, devices=jax.devices()[:1])
    step = ShardedTrainStep(model, opt, mesh, sharding_stage=1,
                            batch_axes=("dp", "sharding"))

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (steps, batch, seq)).astype(np.int32)
    x = paddle.to_tensor(ids)
    # fuse the whole run into one scanned program (run_steps)
    losses = step.run_steps(x, x)
    _ = float(np.asarray(losses.value[-1]))
    final_loss = [0.0]

    def rep():
        t0 = time.perf_counter()
        losses = step.run_steps(x, x)
        final_loss[0] = float(np.asarray(losses.value[-1]))
        return batch * seq * steps / (time.perf_counter() - t0)

    tokens_per_sec, spread, vals = _measure(rep)
    from paddle_tpu.telemetry.costledger import model_train_flops
    mfu = model_train_flops(n_params, tokens_per_sec) \
        / chip_peak_flops()
    _emit("bert_base_train_tokens_per_sec_per_chip", tokens_per_sec,
          f"tokens/s/chip (mfu={mfu:.3f}, params={n_params/1e6:.0f}M, "
          f"loss={final_loss[0]:.3f})", mfu / 0.40, spread, vals,
          extra=_phase_fields(model, step, batch, seq, n_params, "bert"))


def bench_unet():
    """BASELINE.md config 5: SD-style conditional UNet —
    epsilon-prediction training samples/sec."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.unet import (UNet2DConditionModel,
                                        unet_sd_config, unet_tiny_config)
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    if on_tpu:
        cfg = unet_sd_config()
        # bf16 compute (fp32 masters): convs on the MXU at full rate
        cfg.dtype = os.environ.get("BENCH_UNET_DTYPE", "bfloat16")
        batch, hw, ctx_len, steps = 8, 64, 77, 6
    else:
        cfg = unet_tiny_config()
        batch, hw, ctx_len, steps = 2, 16, 8, 2

    model = UNet2DConditionModel(cfg)
    n_params = sum(int(np.prod(p.value.shape))
                   for p in model.parameters())
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    step = TrainStep(model, lambda o, y: model.compute_loss(o, y), opt)

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(batch, cfg.in_channels, hw,
                                   hw).astype(np.float32))
    t = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype(np.int32))
    ctx = paddle.to_tensor(rng.randn(batch, ctx_len,
                                     cfg.cross_attention_dim)
                           .astype(np.float32))
    eps = paddle.to_tensor(rng.randn(batch, cfg.out_channels, hw,
                                     hw).astype(np.float32))

    loss = step(x, t, ctx, eps)
    _ = float(np.asarray(loss.value))
    final_loss = [0.0]

    def rep():
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(x, t, ctx, eps)
        final_loss[0] = float(np.asarray(loss.value))
        return batch * steps / (time.perf_counter() - t0)

    samples_per_sec, spread, vals = _measure(rep)
    _emit("sd_unet_train_samples_per_sec", samples_per_sec,
          f"samples/s (params={n_params/1e6:.0f}M, latents {hw}x{hw}, "
          f"loss={final_loss[0]:.3f})", 1.0, spread, vals)


def _serving_model():
    """The shared serving llama (1B GQA bf16 on TPU; tiny on CPU).
    Returns (model, cfg, batch, n_params, roofline_tok_s)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM, LlamaConfig

    paddle.seed(0)
    if on_tpu:
        # serving-appropriate bf16 weights: the decode roofline assumes
        # 2 bytes/param, which must match what the step reads
        cfg = LlamaConfig(vocab_size=8192, hidden_size=2560,
                          intermediate_size=6912, num_hidden_layers=14,
                          num_attention_heads=20, num_key_value_heads=4,
                          max_position_embeddings=2048,
                          dtype="bfloat16")
        batch = int(os.environ.get("BENCH_BATCH", "8"))
    else:
        cfg = LlamaConfig(vocab_size=256, hidden_size=128,
                          intermediate_size=384, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4,
                          max_position_embeddings=256, dtype="float32")
        batch = 2
    model = LlamaForCausalLM(cfg)
    n_params = sum(int(np.prod(p.value.shape))
                   for p in model.parameters())
    # decode roofline: every token reads all params once (bf16 stream)
    roofline = batch * 0.82e12 / (2.0 * n_params)
    return model, cfg, batch, n_params, roofline


def bench_llama_decode():
    """Serving decode: KV-cached generate() on the 1B llama — whole
    generation is one jitted lax.scan program (inference/generation.py).
    Reports decode tokens/s/chip."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle

    model, cfg, batch, n_params, roofline = _serving_model()
    prompt_len, new_tokens = (128, 512) if on_tpu else (8, 16)
    rng = np.random.RandomState(0)
    prompt = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size,
                    (batch, prompt_len)).astype(np.int32))

    out = model.generate(prompt, max_new_tokens=new_tokens)  # compile
    _ = np.asarray(out.value)

    def rep():
        t0 = time.perf_counter()
        out = model.generate(prompt, max_new_tokens=new_tokens)
        _ = np.asarray(out.value)
        return batch * new_tokens / (time.perf_counter() - t0)

    tok_s, spread, vals = _measure(rep)
    _emit("llama_decode_tokens_per_sec_per_chip", tok_s,
          f"tokens/s/chip (b={batch}, new={new_tokens}, "
          f"params={n_params/1e6:.0f}M, "
          f"hbm_roofline={roofline:.0f} tok/s)",
          tok_s / max(roofline, 1e-9), spread, vals)


def bench_llama_serve():
    """Continuous batching at MIXED prompt lengths: 16 staggered
    requests through one ContinuousBatcher with CHUNKED PREFILL —
    admission consumes prompts in decode-shaped chunks through the
    same compiled scan as live decode (inference/serving.py), so the
    workload compiles exactly two programs and prefill never stalls
    the batch.  Median-of-reps aggregate tokens/s + spread, like every
    other metric; each rep replays the same staggered 16-request
    workload through a fresh batcher (programs cached on the model)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    from paddle_tpu.inference import ContinuousBatcher

    model, cfg, batch, n_params, roofline = _serving_model()
    rngm = np.random.RandomState(1)
    if on_tpu:
        lens = [64, 128, 256, 192] * 4      # 16 requests over 8 slots
        n_new, chunk, max_len, pchunk = 128, 64, 640, 32
    else:
        lens = [4, 8, 6, 10]
        n_new, chunk, max_len, pchunk = 8, 4, 32, 4
    prompts = [rngm.randint(0, cfg.vocab_size, L).astype(np.int32)
               for L in lens]
    last_stats = {}
    hold = []       # keep the last batcher alive: the memory ledger's
    #                 serve providers are weakrefs (peak-HBM resolution
    #                 at emit time needs a live batcher)

    def serve_once():
        bat = ContinuousBatcher(model, max_batch_size=batch,
                                max_len=max_len, chunk=chunk,
                                prefill_chunk=pchunk)
        hold[:] = [bat]
        for p_ in prompts[:batch]:
            bat.submit(p_, n_new)
        t0 = time.perf_counter()
        bat.step()
        # remaining requests arrive while the batch is running
        for p_ in prompts[batch:]:
            bat.submit(p_, n_new)
        bat.run()
        dt = time.perf_counter() - t0
        last_stats.clear()
        last_stats.update(bat.stats())
        return bat.tokens_produced / dt

    serve_once()                            # compile (2 programs)
    tok_s, spread, vals = _measure(serve_once)
    st = last_stats
    _emit("llama_serve_mixed_tokens_per_sec", tok_s,
          f"aggregate tok/s, {len(prompts)} staggered reqs, prompt "
          f"lens {sorted(set(lens))}, b={batch} slots, chunk={chunk}, "
          f"prefill_chunk={pchunk}; occupancy="
          f"{st.get('avg_occupancy', 0):.2f}, "
          f"prefill/decode tokens={st.get('prefill_tokens', 0)}/"
          f"{st.get('decode_tokens', 0)}, "
          f"programs={st.get('compiled_programs', 0)}, "
          f"kv={st.get('kv_layout')}:"
          f"{st.get('kv_bytes', 0) / 1e6:.0f}MB",
          tok_s / max(roofline, 1e-9), spread, vals,
          extra={"kv_layout": st.get("kv_layout"),
                 "kv_bytes": st.get("kv_bytes", 0),
                 # per-request latency spans (ISSUE 10): TTFT/TPOT/e2e
                 # percentiles over the last rep's delivered requests
                 "latency": st.get("latency"),
                 **_peak_hbm_fields()})


def bench_llama_serve_prefix_shared():
    """Prefix-shared serving (ISSUE 7): 16 staggered requests that all
    open with one LONG system prompt, through the PAGED KV pool with
    prefix sharing — the shared pages prefill once and every later
    admission maps them (prefix_hit_tokens), so admission work shrinks
    to the per-request tail.  Reports aggregate tok/s, the prefix-hit
    rate, KV HBM bytes (and the int8 pool's bytes for the same
    geometry), plus the dense-path tok/s on the SAME workload — the
    >=1.3x acceptance ratio.  Off-TPU the smoke run also asserts the
    sharing actually happened (hit tokens > 0, strictly less prefill
    work than dense)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    from paddle_tpu.inference import ContinuousBatcher

    model, cfg, batch, n_params, roofline = _serving_model()
    rngm = np.random.RandomState(2)
    if on_tpu:
        sys_len, n_req = 384, 16
        tail_lens = [16, 48, 32, 64] * 4
        n_new, chunk, max_len, pchunk, ps = 128, 64, 768, 32, 32
    else:
        sys_len, n_req = 24, 4
        tail_lens = [4, 8, 6, 5]
        n_new, chunk, max_len, pchunk, ps = 8, 4, 48, 4, 8
    sys_prompt = rngm.randint(0, cfg.vocab_size, sys_len) \
        .astype(np.int32)
    prompts = [np.concatenate(
        [sys_prompt, rngm.randint(0, cfg.vocab_size, L)
         .astype(np.int32)]) for L in tail_lens[:n_req]]
    total_prompt = sum(len(p) for p in prompts)
    last_stats = {}

    hold = []       # liveness for the ledger's weakref'd serve providers

    def serve_once(layout="paged", sharing=True):
        bat = ContinuousBatcher(model, max_batch_size=batch,
                                max_len=max_len, chunk=chunk,
                                prefill_chunk=pchunk, kv_layout=layout,
                                page_size=ps, prefix_sharing=sharing)
        hold[:] = [bat]
        for p_ in prompts[:batch]:
            bat.submit(p_, n_new)
        t0 = time.perf_counter()
        bat.step()
        for p_ in prompts[batch:]:
            bat.submit(p_, n_new)
        bat.run()
        dt = time.perf_counter() - t0
        last_stats.clear()
        last_stats.update(bat.stats())
        return bat.tokens_produced / dt

    serve_once()                                   # compile paged
    serve_once("dense")                            # compile dense
    tok_s, spread, vals = _measure(serve_once)
    st = dict(last_stats)
    # resolve peak-HBM NOW, while the ledger's serve entries still
    # describe the PAGED batcher (the dense reps below re-register)
    peak_fields = _peak_hbm_fields()
    dense_tok = _measure(lambda: serve_once("dense"))[0]
    st_dense = dict(last_stats)
    hit_rate = st["prefix_hit_tokens"] / max(total_prompt, 1)
    # int8 pool bytes at identical geometry (the halved-KV-HBM claim;
    # pool dtype vs the full-precision pool, scales included) — pure
    # shape arithmetic, no throwaway pools allocated on the chip
    kv_full = ContinuousBatcher.paged_kv_bytes(
        model, max_batch_size=batch, max_len=max_len,
        prefill_chunk=pchunk, page_size=ps, kv_dtype="bfloat16")
    kv_int8 = ContinuousBatcher.paged_kv_bytes(
        model, max_batch_size=batch, max_len=max_len,
        prefill_chunk=pchunk, page_size=ps, kv_dtype="int8")
    if not on_tpu:
        # CPU smoke: the sharing must be REAL, not just plumbed
        assert st["prefix_hit_tokens"] > 0, st
        assert st["prefill_tokens"] < st_dense["prefill_tokens"], \
            (st["prefill_tokens"], st_dense["prefill_tokens"])
        assert st["admit_chunks"] <= st_dense["admit_chunks"]
        assert kv_int8 < 0.6 * kv_full, (kv_int8, kv_full)
    _emit("llama_serve_prefix_shared_tokens_per_sec", tok_s,
          f"aggregate tok/s, {n_req} staggered reqs sharing a "
          f"{sys_len}-token system prompt, b={batch} slots, "
          f"page_size={ps}; prefix_hit_rate={hit_rate:.2f}, "
          f"kv={st.get('kv_bytes', 0) / 1e6:.0f}MB "
          f"(int8 pool {kv_int8 / 1e6:.0f}MB vs bf16 "
          f"{kv_full / 1e6:.0f}MB), vs_dense={tok_s / max(dense_tok, 1e-9):.2f}x",
          tok_s / max(roofline, 1e-9), spread, vals,
          extra={"prefix_hit_tokens": int(st["prefix_hit_tokens"]),
                 "prefix_hit_rate": round(hit_rate, 3),
                 "kv_bytes": int(st.get("kv_bytes", 0)),
                 "kv_bytes_int8": int(kv_int8),
                 "kv_bytes_bf16": int(kv_full),
                 "evictions": int(st.get("evictions", 0)),
                 "vs_dense": round(tok_s / max(dense_tok, 1e-9), 3),
                 "dense_tokens_per_sec": round(dense_tok, 1),
                 **peak_fields})


def bench_llama_serve_speculative():
    """Speculative decoding + weight-only sizing (ISSUE 11): the
    mixed-length serve workload through the draft/verify scan, vs the
    plain batcher on the SAME workload.  On TPU the draft is an
    early-exit self-draft (first quarter of the layers); the CPU smoke
    instead self-speculates with the target as its own draft — the
    acceptance plumbing is then deterministic (accept_rate == 1), so
    the smoke can ASSERT accept_rate > 0, accepted_per_step > 1 and
    greedy bit-exactness vs the non-speculative batcher, which is the
    contract that matters off-TPU (TPU accept rates with trained
    weights land at the next driver capture).  Also reports the
    int8/int4 weight-pool bytes for this model (pure shape
    arithmetic — no second copy of the weights is packed)."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.quantization.weight_only import (weight_pool_bytes,
                                                     packed_bytes)

    model, cfg, batch, n_params, roofline = _serving_model()
    rngm = np.random.RandomState(3)
    if on_tpu:
        lens = [64, 128, 256, 192] * 4
        n_new, chunk, max_len, pchunk = 128, 16, 640, 32
        spec_kw = dict(spec_tokens=4,
                       draft_layers=max(1, cfg.num_hidden_layers // 4))
    else:
        lens = [4, 8, 6, 10]
        n_new, chunk, max_len, pchunk = 8, 4, 48, 4
        spec_kw = dict(spec_tokens=3, draft_model=model)
    prompts = [rngm.randint(0, cfg.vocab_size, L).astype(np.int32)
               for L in lens]
    last_stats = {}
    hold = []

    def serve_once(speculative=True):
        bat = ContinuousBatcher(model, max_batch_size=batch,
                                max_len=max_len, chunk=chunk,
                                prefill_chunk=pchunk,
                                **(spec_kw if speculative else {}))
        hold[:] = [bat]
        rids = []
        for p_ in prompts[:batch]:
            rids.append(bat.submit(p_, n_new))
        t0 = time.perf_counter()
        bat.step()
        for p_ in prompts[batch:]:
            rids.append(bat.submit(p_, n_new))
        outs = bat.run()
        dt = time.perf_counter() - t0
        last_stats.clear()
        last_stats.update(bat.stats())
        return bat.tokens_produced / dt, rids, outs

    serve_once()                                # compile (2 programs)
    serve_once(False)                           # compile plain
    tok_s, spread, vals = _measure(lambda: serve_once()[0])
    _, rids, outs = serve_once()                # capture outputs
    st = dict(last_stats)
    peak_fields = _peak_hbm_fields()
    base_tok = _measure(lambda: serve_once(False)[0])[0]
    _, base_rids, base_outs = serve_once(False)
    accept = st.get("spec_accept_rate", 0.0)
    aps = st.get("spec_accepted_per_step", {})
    wb_now = weight_pool_bytes(model)
    if getattr(model, "_weight_only", None) is None:
        wb_int8 = packed_bytes(model, "int8")
        wb_int4 = packed_bytes(model, "int4")
    else:
        wb_int8 = wb_int4 = wb_now
    if not on_tpu:
        # CPU smoke: speculation must be REAL and bit-exact, not just
        # plumbed (the acceptance criteria of ISSUE 11)
        assert st["compiled_programs"] == 2, st
        assert accept > 0, st
        assert aps.get("mean", 0) > 1, st
        for a, b in zip(rids, base_rids):
            assert (outs[a] == base_outs[b]).all(), \
                "speculative output diverged from the plain batcher"
    _emit("llama_serve_speculative_tokens_per_sec", tok_s,
          f"aggregate tok/s, {len(prompts)} staggered reqs, "
          f"spec_tokens={st.get('spec_tokens')}, "
          f"accept_rate={accept:.2f}, accepted/step "
          f"p50={aps.get('p50', 0)}, vs_plain="
          f"{tok_s / max(base_tok, 1e-9):.2f}x; weight pool "
          f"{wb_now / 1e6:.0f}MB (int8 {wb_int8 / 1e6:.0f}MB / "
          f"int4 {wb_int4 / 1e6:.0f}MB)",
          tok_s / max(roofline, 1e-9), spread, vals,
          extra={"spec_tokens": st.get("spec_tokens"),
                 "accept_rate": accept,
                 "accepted_per_step": aps,
                 "vs_plain": round(tok_s / max(base_tok, 1e-9), 3),
                 "plain_tokens_per_sec": round(base_tok, 1),
                 "weight_pool_bytes": wb_now,
                 "weight_pool_bytes_int8": wb_int8,
                 "weight_pool_bytes_int4": wb_int4,
                 "weight_only": st.get("weight_only"),
                 **peak_fields})


def bench_llama_serve_fleet():
    """Serve-fleet router (ISSUE 15): a staggered shared-prefix
    workload through TWO in-process ContinuousBatcher replicas behind
    the prefix-aware SLO-aware ServeRouter, vs ONE replica of the same
    per-replica capacity on the same workload.  Reports aggregate
    tok/s, the prefix-ROUTE hit rate (routes whose chosen replica
    already held the prompt's prefix) and the vs_single_replica
    multiplier.  The router is HOST-plane only: the CPU smoke asserts
    both replicas actually served traffic, the run was requeue-free
    and complete, and the flags-off single-batcher serve HLO +
    program-cache keys are byte-identical with the router module
    imported and a whole fleet run behind it."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.inference.router import ServeRouter

    model, cfg, batch, n_params, roofline = _serving_model()
    rngm = np.random.RandomState(4)
    if on_tpu:
        sys_len, n_req = 256, 16
        tail_lens = [16, 48, 32, 64] * 4
        n_new, chunk, max_len, pchunk, ps = 128, 64, 640, 32, 32
        rb = max(1, batch // 2)         # per-replica slots
    else:
        sys_len, n_req = 24, 8
        tail_lens = [4, 8, 6, 5] * 2
        n_new, chunk, max_len, pchunk, ps = 8, 4, 48, 4, 8
        rb = 1
    sys_prompt = rngm.randint(0, cfg.vocab_size, sys_len) \
        .astype(np.int32)
    prompts = [np.concatenate(
        [sys_prompt, rngm.randint(0, cfg.vocab_size, L)
         .astype(np.int32)]) for L in tail_lens[:n_req]]
    geom = dict(max_batch_size=rb, max_len=max_len, chunk=chunk,
                prefill_chunk=pchunk, page_size=ps)
    # stagger rounds before the tail arrives: enough for the shared
    # system prompt to finish prefilling (its pages then sit in the
    # early replicas' prefix tries, so later routes can chase them) —
    # one admit chunk advances admit_steps*prefill_chunk prompt rows
    stagger = max(1, -(-sys_len // max(1, (chunk // 4) * pchunk)) + 1)

    def fingerprint():
        bat = ContinuousBatcher(model, **geom)
        keys = (bat._program_key(1, bat.chunk),
                bat._program_key(bat.prefill_chunk, bat.admit_steps))
        return keys, (bat.lower_step(mixed=False).as_text(),
                      bat.lower_step(mixed=True).as_text())

    keys0, hlo0 = fingerprint()
    last_stats = {}
    hold = []

    def fleet_once():
        bats = [ContinuousBatcher(model, **geom) for _ in range(2)]
        router = ServeRouter(batchers=bats)
        hold[:] = [router]
        n_first = max(2, 2 * rb)
        for p_ in prompts[:n_first]:
            router.submit(p_, n_new)
        t0 = time.perf_counter()
        for _ in range(stagger):
            router.step()
        for p_ in prompts[n_first:]:
            router.submit(p_, n_new)
        outs = router.run()
        dt = time.perf_counter() - t0
        last_stats.clear()
        last_stats.update(router.stats())
        return sum(len(v) for v in outs.values()) / dt

    def single_once():
        bat = ContinuousBatcher(model, **geom)
        hold[:] = [bat]
        n_first = max(2, 2 * rb)
        for p_ in prompts[:n_first]:
            bat.submit(p_, n_new)
        t0 = time.perf_counter()
        for _ in range(stagger):
            bat.step()
        for p_ in prompts[n_first:]:
            bat.submit(p_, n_new)
        outs = bat.run()
        return sum(len(v) for v in outs.values()) \
            / (time.perf_counter() - t0)

    fleet_once()                               # compile (shared progs)
    single_once()
    tok_s, spread, vals = _measure(fleet_once)
    st = dict(last_stats)
    single_tok = _measure(single_once)[0]
    keys1, hlo1 = fingerprint()
    assert keys0 == keys1, \
        "running the serve-fleet router changed single-batcher " \
        "program keys"
    assert hlo0 == hlo1, \
        "running the serve-fleet router changed the flags-off " \
        "single-batcher serve HLO"
    if not on_tpu:
        # CPU smoke: the fleet must be REAL — both replicas routed
        # traffic, nothing requeued/shed, every request completed,
        # and prefix-affinity actually steered at least one route
        routed = st["routed_by_replica"]
        assert all(v > 0 for v in routed.values()), st
        assert st["requests_requeued"] == 0 \
            and st["requests_shed"] == 0, st
        assert st["requests_completed"] == n_req, st
        assert st["prefix_route_hit_rate"] > 0, st
        assert all(r.get("dead") is False
                   for r in st["per_replica"]), st
    vs_single = tok_s / max(single_tok, 1e-9)
    _emit("llama_serve_fleet_tokens_per_sec", tok_s,
          f"aggregate tok/s, {n_req} staggered reqs sharing a "
          f"{sys_len}-token system prompt across 2 replicas x {rb} "
          f"slots; prefix_route_hit_rate="
          f"{st['prefix_route_hit_rate']:.2f}, routed="
          f"{st['routed_by_replica']}, decide p50="
          f"{st['decision_ms']['p50']}ms, "
          f"vs_single_replica={vs_single:.2f}x",
          tok_s / max(roofline, 1e-9), spread, vals,
          extra={"replicas": 2,
                 "slots_per_replica": rb,
                 "prefix_route_hit_rate": st["prefix_route_hit_rate"],
                 "routed_by_replica": {str(k): v for k, v in
                                       st["routed_by_replica"].items()},
                 "requeued": st["requests_requeued"],
                 "decision_ms": st["decision_ms"],
                 "vs_single_replica": round(vs_single, 3),
                 "single_replica_tokens_per_sec": round(single_tok, 1),
                 **_peak_hbm_fields()})


def bench_llama_serve_autoscale():
    """SLO-driven elastic autoscaler (ISSUE 19): the deterministic
    diurnal load curve through a ServeRouter fleet with an
    AutoscalerDaemon closing the loop (start at min_replicas, scale
    out into the peak, scale back in at the trough) vs a STATIC
    min-size fleet on the same schedule under the same bounded queue.
    Reports aggregate tok/s plus the action journal summary and the
    interactive attainment of both fleets.  The CPU smoke asserts the
    loop is REAL: >= 1 scale-out and >= 1 scale-in executed, flap
    count 0, zero requests shed by the autoscaled fleet (the static
    fleet DOES shed under the same pressure — that's the capacity the
    autoscaler buys), and interactive attainment >= the static
    baseline."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    import paddle_tpu as paddle
    from paddle_tpu.fleet import (AutoscalePolicy, AutoscalerDaemon,
                                  DiurnalLoadSim)
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.inference.router import ServeRouter
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    from autoscale_report import analyze_journal

    model, cfg, batch, n_params, roofline = _serving_model()
    if on_tpu:
        ticks, period, low, high = 16, 8, 2, 12
        plen, n_new, chunk, max_len, pchunk, ps = 48, 64, 32, 384, 32, 32
        rb, qdepth, steps_per_tick = max(2, batch // 2), 16, 8
    else:
        # per-replica throughput = rb slots * steps_per_tick / (2
        # prefill + 6 decode steps) = 2 req/tick: one replica sits
        # below the 3.5 req/tick diurnal average (static fleet sheds),
        # three cover the peak of 6 (autoscaled fleet sheds nothing)
        ticks, period, low, high = 12, 6, 1, 6
        plen, n_new, chunk, max_len, pchunk, ps = 6, 6, 4, 48, 4, 8
        rb, qdepth, steps_per_tick = 2, 6, 8
    drain_ticks = 4
    geom = dict(max_batch_size=rb, max_len=max_len, chunk=chunk,
                prefill_chunk=pchunk, page_size=ps)
    sim = DiurnalLoadSim(vocab=cfg.vocab_size, seed=3, period=period,
                         low=low, high=high, prompt_len=plen,
                         max_new=n_new)
    policy = AutoscalePolicy(min_replicas=1, max_replicas=3, window=1,
                             cooldown=2, queue_high=0.75,
                             queue_low=0.5, lease_ttl_s=0.0)

    def mk():
        return ContinuousBatcher(model, **geom)

    last = {}

    def run_curve(autoscale):
        router = ServeRouter(batchers=[mk()])
        daemon = AutoscalerDaemon(router, policy=policy, spawn=mk) \
            if autoscale else None
        paddle.set_flags({"FLAGS_autoscale": bool(autoscale),
                          "FLAGS_serve_queue_depth": qdepth})
        gids = []
        t0 = time.perf_counter()
        try:
            # submission ticks, then load-free drain ticks so the
            # trailing trough gives the daemon room to scale back in
            for t in range(ticks + drain_ticks):
                if t < ticks:
                    for r in sim.requests(t):
                        gids.append(router.submit(
                            r["prompt"], r["max_new"], slo=r["slo"]))
                if daemon is not None:
                    daemon.tick()
                for _ in range(steps_per_tick):
                    router.step()
            outs = router.run()
        finally:
            paddle.set_flags({"FLAGS_autoscale": False,
                              "FLAGS_serve_queue_depth": 0})
        dt = time.perf_counter() - t0
        by_cls = {}
        for g in gids:
            rr = router._reqs[g]
            tot, ok = by_cls.get(rr.slo, (0, 0))
            by_cls[rr.slo] = (tot + 1, ok + (0 if rr.shed else 1))
        att = {c: round(ok / tot, 4)
               for c, (tot, ok) in by_cls.items()}
        st = router.stats()
        last.clear()
        last.update({"stats": st, "attainment": att,
                     "journal": daemon.journal() if daemon else [],
                     "tokens": sum(len(v) for v in outs.values())})
        return last["tokens"] / dt

    run_curve(True)                     # compile (programs shared)
    tok_s, spread, vals = _measure(lambda: run_curve(True))
    auto = dict(last)
    static_tok = _measure(lambda: run_curve(False))[0]
    static = dict(last)
    jr = analyze_journal(auto["journal"], cooldown=policy.cooldown)
    auto_att = auto["attainment"].get("interactive", 1.0)
    static_att = static["attainment"].get("interactive", 1.0)
    if not on_tpu:
        # the loop must be REAL: the curve forced >= 1 scale-out into
        # the peak and >= 1 scale-in at the trough, without a single
        # flap; the autoscaled fleet dropped NOTHING while the static
        # min fleet shed under the same bounded queue; and interactive
        # attainment is no worse than the static baseline
        assert jr["executed_by_kind"].get("scale_out", 0) >= 1, jr
        assert jr["executed_by_kind"].get("scale_in", 0) >= 1, jr
        assert jr["flaps"] == 0, jr
        assert not jr["pending"] and jr["epochs_unique"], jr
        assert auto["stats"]["requests_shed"] == 0, auto["stats"]
        assert static["stats"]["requests_shed"] > 0, static["stats"]
        assert auto_att >= static_att, (auto_att, static_att)
    vs_static = tok_s / max(static_tok, 1e-9)
    _emit("llama_serve_autoscale_tokens_per_sec", tok_s,
          f"aggregate tok/s over a {ticks}-tick diurnal curve "
          f"(rate {low}..{high}/tick), autoscaled 1..3 replicas x "
          f"{rb} slots; actions={jr['executed_by_kind']}, flaps="
          f"{jr['flaps']}, shed={auto['stats']['requests_shed']} "
          f"(static min-fleet shed "
          f"{static['stats']['requests_shed']}), attainment(int)="
          f"{auto_att:.2f} vs static {static_att:.2f}, "
          f"vs_static={vs_static:.2f}x",
          tok_s / max(roofline, 1e-9), spread, vals,
          extra={"actions": jr["executed_by_kind"],
                 "rollbacks": len(jr["rollbacks"]),
                 "flaps": jr["flaps"],
                 "shed": auto["stats"]["requests_shed"],
                 "static_shed": static["stats"]["requests_shed"],
                 "attainment_interactive": auto_att,
                 "static_attainment_interactive": static_att,
                 "replicas_final": auto["stats"]["live_replicas"],
                 "vs_static_min_fleet": round(vs_static, 3),
                 "static_tokens_per_sec": round(static_tok, 1),
                 **_peak_hbm_fields()})


def bench_llama_serve_disagg():
    """Disaggregated prefill/decode serving (ISSUE 20): the SAME
    fixed-size fleet (2 replicas) run role-split — prefill workers
    freeze finished prompts and stream their KV pages to decode
    workers, which admit at pos = prompt_len — vs run symmetric, on a
    mixed long-prefill/short-decode workload sharing a system prompt.
    Reports aggregate tok/s plus TTFT/TPOT p50 for both fleets and
    the hand-off counters.  The CPU smoke asserts the topology is
    REAL: hand-offs > 0, cross-replica prefix-import hits > 0, ZERO
    prefill tokens ever computed on the decode side, outputs
    bit-exact vs the symmetric fleet, nothing shed."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.inference.router import ServeRouter

    model, cfg, batch, n_params, roofline = _serving_model()
    rngm = np.random.RandomState(6)
    if on_tpu:
        sys_len, n_req = 256, 16
        tail_lens = [96, 16, 128, 24] * 4
        new_toks = [24, 96, 16, 64] * 4
        chunk, max_len, pchunk, ps = 64, 768, 32, 32
        rb = max(1, batch // 2)
    else:
        sys_len, n_req = 24, 8
        tail_lens = [10, 4, 12, 5] * 2
        new_toks = [4, 10, 4, 8] * 2
        chunk, max_len, pchunk, ps = 4, 64, 4, 8
        rb = 1
    sys_prompt = rngm.randint(0, cfg.vocab_size, sys_len) \
        .astype(np.int32)
    prompts = [np.concatenate(
        [sys_prompt, rngm.randint(0, cfg.vocab_size, L)
         .astype(np.int32)]) for L in tail_lens[:n_req]]
    geom = dict(max_batch_size=rb, max_len=max_len, chunk=chunk,
                prefill_chunk=pchunk, page_size=ps)
    last = {}

    def fleet_once(roles):
        bats = [ContinuousBatcher(model, **geom) for _ in range(2)]
        router = ServeRouter(batchers=bats, roles=roles)
        for p_, n_ in zip(prompts, new_toks):
            router.submit(p_, n_)
        t0 = time.perf_counter()
        outs = router.run()
        dt = time.perf_counter() - t0
        last.clear()
        last.update(stats=router.stats(), outs=outs,
                    decode=[r.bat.stats() for r in router._reps
                            if r.role == "decode"])
        return sum(len(v) for v in outs.values()) / dt

    fleet_once(None)                           # compile (shared progs)
    base_tok, base_spread, _ = _measure(lambda: fleet_once(None))
    base = {k: v for k, v in last.items()}
    fleet_once(["prefill", "decode"])
    tok_s, spread, vals = _measure(
        lambda: fleet_once(["prefill", "decode"]))
    st, outs = last["stats"], last["outs"]

    def _p50(s, k):
        lat = s["stats"]["latency"].get(k) or {}
        return float(lat.get("p50") or 0.0)

    ttft, tpot = _p50(last, "ttft_ms"), _p50(last, "tpot_ms")
    base_ttft, base_tpot = _p50(base, "ttft_ms"), _p50(base, "tpot_ms")
    cross = int(st["cross_prefix_hit_tokens"])
    if not on_tpu:
        # CPU smoke: the disaggregation must be REAL and lossless
        assert st["handoffs"] > 0, st
        assert st["handoff_staged"] == 0, st
        assert cross > 0, st
        assert st["requests_shed"] == 0, st
        assert st["requests_completed"] == n_req, st
        for ds in last["decode"]:
            assert ds["prefill_tokens"] == 0, \
                "decode worker recomputed prefill after hand-off"
        assert set(outs) == set(base["outs"])
        # role-split must not change a single sampled token
        for g in outs:
            assert np.array_equal(outs[g], base["outs"][g]), g
    else:
        # the perf contract is an accelerator property: on CPU the
        # host-plane hand-off (ms-scale page gather/scatter) swamps
        # the scheduling win the split buys on real prefill/decode
        # interference, so tok/s and TTFT gate on TPU only
        assert tok_s >= base_tok, (tok_s, base_tok)
        assert ttft <= base_ttft, (ttft, base_ttft)
    vs_sym = tok_s / max(base_tok, 1e-9)
    _emit("llama_serve_disagg_tokens_per_sec", tok_s,
          f"aggregate tok/s, {n_req} mixed reqs sharing a "
          f"{sys_len}-token system prompt on a FIXED 2x{rb}-slot "
          f"fleet split prefill/decode; handoffs={st['handoffs']} "
          f"({st['handoff_bytes']}B, p50="
          f"{st['handoff_ms']['p50']}ms), cross_prefix_hits={cross} "
          f"tok, ttft p50={ttft:.1f}ms (sym {base_ttft:.1f}ms), "
          f"tpot p50={tpot:.1f}ms (sym {base_tpot:.1f}ms), "
          f"vs_symmetric={vs_sym:.2f}x",
          tok_s / max(roofline, 1e-9), spread, vals,
          extra={"replicas": 2, "slots_per_replica": rb,
                 "handoffs": st["handoffs"],
                 "handoff_bytes": st["handoff_bytes"],
                 "handoff_ms": st["handoff_ms"],
                 "cross_prefix_hit_tokens": cross,
                 "replicated_pages": st["replicated_pages"],
                 "ttft_ms_p50": round(ttft, 3),
                 "tpot_ms_p50": round(tpot, 3),
                 "symmetric_ttft_ms_p50": round(base_ttft, 3),
                 "symmetric_tpot_ms_p50": round(base_tpot, 3),
                 "vs_symmetric_fleet": round(vs_sym, 3),
                 "symmetric_tokens_per_sec": round(base_tok, 1),
                 **_peak_hbm_fields()})


def bench_serve_all():
    """BENCH_CONFIG=serve runs the mixed-length leg, the prefix-shared
    leg, the speculative leg, the serve-fleet router leg AND the
    elastic-autoscaler leg (fresh vs-baseline numbers for all —
    BENCH_r05 predates the r6 batcher, the r12 paged pool, the r15
    draft/verify scan, the r19 router and the ISSUE-19 autoscaler)."""
    bench_llama_serve()
    bench_llama_serve_prefix_shared()
    bench_llama_serve_speculative()
    bench_llama_serve_fleet()
    bench_llama_serve_autoscale()
    bench_llama_serve_disagg()


CONFIGS = {
    "llama": bench_llama,
    "offload": lambda: bench_llama(offload=True),
    "overlap": bench_llama_overlap,
    "bert": bench_bert,
    "resnet": bench_resnet,
    "unet": bench_unet,
    "decode": bench_llama_decode,
    "serve": bench_serve_all,
    "longctx": bench_longctx,
    "hybrid": bench_llama_hybrid,
}

# one table resolves config aliases AND emitted metric names, for both
# BENCH_CONFIG= and `bench.py --only <metric-or-config>` (the
# in-isolation re-measure interface — reps + spread like a full run)
_ALIASES = {
    "resnet50": "resnet", "cifar": "resnet", "sd": "unet",
    "diffusion": "unet", "generate": "decode", "serving": "serve",
    "llama_serve_mixed": "serve",
    "llama_serve_mixed_tokens_per_sec": "serve",
    "serve_prefix": "serve",
    "llama_serve_prefix_shared": "serve",
    "llama_serve_prefix_shared_tokens_per_sec": "serve",
    "serve_spec": "serve",
    "llama_serve_speculative": "serve",
    "llama_serve_speculative_tokens_per_sec": "serve",
    "serve_fleet": "serve",
    "fleet_serve": "serve",
    "llama_serve_fleet": "serve",
    "llama_serve_fleet_tokens_per_sec": "serve",
    "autoscale": "serve",
    "serve_autoscale": "serve",
    "llama_serve_autoscale": "serve",
    "llama_serve_autoscale_tokens_per_sec": "serve",
    "disagg": "serve",
    "serve_disagg": "serve",
    "llama_serve_disagg": "serve",
    "llama_serve_disagg_tokens_per_sec": "serve",
    "llama_decode": "decode",
    "llama_decode_tokens_per_sec_per_chip": "decode",
    "llama_train_tokens_per_sec_per_chip": "llama",
    "llama_offload_train_tokens_per_sec_per_chip": "offload",
    "comm_overlap": "overlap",
    "llama_sharded_overlap": "overlap",
    "llama_sharded_overlap_tokens_per_sec_per_chip": "overlap",
    "bert_base_train_tokens_per_sec_per_chip": "bert",
    "resnet50_cifar_images_per_sec": "resnet",
    "sd_unet_train_samples_per_sec": "unet",
    "llama_longctx_train_tokens_per_sec_per_chip": "longctx",
    "hybrid_parallel": "hybrid",
    "llama_hybrid": "hybrid",
    "llama_hybrid_tokens_per_sec_per_chip": "hybrid",
}


def _assert_analysis_zero_overhead():
    """FLAGS off ⇒ the verifier never touches the replay hot path: the
    Executor replay-cache key set is identical before/after loading the
    analysis subsystem AND across repeat runs, and VERIFY_CALLS does not
    move during flags-off replays (the zero-overhead contract of
    paddle_tpu/analysis — verification must be free when not asked
    for).  Cheap (tiny program), runs before every bench config."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.static as static
    from paddle_tpu.analysis import verifier

    static.enable_static()
    try:
        main_p = static.Program()
        with static.program_guard(main_p, static.Program()):
            x = static.data("x", [2, 4], "float32")
            w = paddle.to_tensor(np.ones((4, 3), np.float32))
            loss = paddle.matmul(x, w).mean()
        exe = static.Executor()
        xv = np.ones((2, 4), np.float32)
        exe.run(main_p, feed={"x": xv}, fetch_list=[loss])
        keys = set(main_p._exec_cache)
        calls = verifier.VERIFY_CALLS
        for _ in range(3):
            exe.run(main_p, feed={"x": xv}, fetch_list=[loss])
        assert verifier.VERIFY_CALLS == calls, \
            "verifier ran on the replay hot path with FLAGS off"
        assert set(main_p._exec_cache) == keys, \
            "flags-off replays changed the replay-cache key set"
    finally:
        static.disable_static()


def _assert_fault_tolerance_zero_overhead():
    """FLAGS off ⇒ the fault-tolerant runtime costs the step path
    nothing: no guard ops compiled into the train step (no is_finite /
    old-vs-new selects), no checkpoint IO, and the fault registry never
    counts a hit (its unset fast path is one cached string compare).
    Cheap (tiny MLP), runs before every bench config."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fault
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.parallel import ShardedTrainStep

    assert not fault.is_active(), \
        "FLAGS_fault_injection armed during a bench run"

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
        m, opt, build_mesh(devices=jax.devices()[:1]),
        loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.ones((4, 8), np.float32))
    hlo = step.compiled_hlo(x, y, optimized=False)
    assert "is_finite" not in hlo and "is-finite" not in hlo, \
        "guard ops compiled into the flags-off train step"
    writes, hits = ckpt.WRITE_CALLS, fault.hit_counts()
    for _ in range(2):
        step(x, y)
    assert ckpt.WRITE_CALLS == writes, \
        "flags-off train steps performed checkpoint IO"
    assert fault.hit_counts() == hits, \
        "flags-off train steps consulted the fault registry"

    # elastic reshard machinery (ISSUE 13) is flags-off free: with
    # FLAGS_ckpt_save_sharded off, (a) the trainer HLO is untouched by
    # toggling the flag (it is pure host-plane — the step never sees
    # it), and (b) checkpoint MANIFEST bytes and shard container bytes
    # are byte-identical across an arm/disarm cycle — the r9 on-disk
    # format survives the elastic merge exactly
    import os
    import shutil
    import tempfile

    def _save_bytes():
        d = tempfile.mkdtemp(prefix="bench_ckpt_")
        try:
            ckpt.save_state_dict(
                {"w": paddle.to_tensor(np.ones((8, 8), np.float32))}, d)
            with open(os.path.join(d, "metadata.json"), "rb") as f:
                manifest = f.read()
            with open(os.path.join(d, "0.distcp"), "rb") as f:
                shard = f.read()
            return manifest, shard
        finally:
            shutil.rmtree(d, ignore_errors=True)

    hlo_before = step.compiled_hlo(x, y, optimized=False)
    man_before, shard_before = _save_bytes()
    paddle.set_flags({"FLAGS_ckpt_save_sharded": True})
    try:
        man_armed, _ = _save_bytes()   # armed save must still work
        assert man_armed
    finally:
        paddle.set_flags({"FLAGS_ckpt_save_sharded": False})
    man_after, shard_after = _save_bytes()
    assert man_after == man_before, \
        "FLAGS_ckpt_save_sharded toggle changed flags-off manifests"
    assert shard_after == shard_before, \
        "FLAGS_ckpt_save_sharded toggle changed flags-off shard bytes"
    assert step.compiled_hlo(x, y, optimized=False) == hlo_before, \
        "FLAGS_ckpt_save_sharded toggle changed the train-step HLO"


def _assert_mfu_fusion_zero_overhead():
    """FLAGS_fused_ce / FLAGS_bf16_adamw_moments are toggle-stable:
    building the same tiny-llama step before, during and after toggling
    the flags must yield (a) identical flags-off StableHLO text both
    times — arming and disarming the flags leaves zero residue in the
    flags-off program — (b) a different program with the flags on (the
    fusions really engage), and (c) no 'ef' key in the flags-off
    optimizer state.  (This checks toggle idempotence, not identity
    with the pre-PR program: the flags-off loss/norm code paths were
    themselves deduplicated in this PR, value-pinned by regression
    tests.)
    Cheap (tiny llama, lowering only — no compile/execute), runs before
    every bench config."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int32))

    def build(fused, bf16m):
        set_flags({"FLAGS_fused_ce": fused,
                   "FLAGS_bf16_adamw_moments": bf16m})
        try:
            paddle.seed(0)
            m = LlamaForCausalLM(llama_tiny_config())
            opt = paddle.optimizer.AdamW(
                1e-3, parameters=m.parameters(), weight_decay=0.1)
            step = ShardedTrainStep(
                m, opt, build_mesh(devices=jax.devices()[:1]),
                sharding_stage=0)
            hlo = step.compiled_hlo(ids, ids, optimized=False)
            state_keys = set(step._opt_states[0])
        finally:
            set_flags({"FLAGS_fused_ce": False,
                       "FLAGS_bf16_adamw_moments": False})
        return hlo, state_keys

    off1, keys_off = build(False, False)
    on, keys_on = build(True, True)
    off2, _ = build(False, False)
    assert off1 == off2, \
        "flags-off train step is not byte-identical across flag toggles"
    assert on != off1, "MFU-fusion flags changed nothing in the program"
    assert "ef" not in keys_off and "ef" in keys_on, \
        f"optimizer state keys wrong: off={keys_off}, on={keys_on}"


def _assert_comm_overlap_zero_overhead():
    """FLAGS_comm_overlap is toggle-stable (ISSUE 16): building the
    same tiny-llama step before, during and after toggling the flag
    must yield identical flags-off StableHLO text both times — arming
    and disarming the overlap engine leaves zero residue in the
    flags-off program.  On a single-device mesh the flag-ON program
    must ALSO be byte-identical (no cross-rank comm exists to overlap
    — the plan correctly declines to build); the multi-device
    "genuinely engages + stays bit-exact" half is tier-1-pinned on the
    8-virtual-device mesh (tests/test_comm_overlap.py), which this
    bench process does not have.  Cheap (tiny llama, lowering only),
    runs before every bench config."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int32))

    def build(overlap):
        set_flags({"FLAGS_comm_overlap": overlap})
        try:
            paddle.seed(0)
            m = LlamaForCausalLM(llama_tiny_config())
            opt = paddle.optimizer.AdamW(
                1e-3, parameters=m.parameters(), weight_decay=0.1)
            step = ShardedTrainStep(
                m, opt, build_mesh(devices=jax.devices()[:1]),
                sharding_stage=0)
            hlo = step.compiled_hlo(ids, ids, optimized=False)
            plan = step._overlap_plan
        finally:
            set_flags({"FLAGS_comm_overlap": False})
        return hlo, plan

    off1, _ = build(False)
    on, plan_on = build(True)
    off2, _ = build(False)
    assert off1 == off2, \
        "flags-off train step is not byte-identical across comm_overlap toggles"
    assert plan_on is None, \
        "comm-overlap plan built on a single-device mesh (no comm to overlap)"
    assert on == off1, \
        "comm_overlap changed the single-device program (must be inert)"


def _assert_hybrid_zero_overhead():
    """The hybrid engine is residue-free on a single axis (ISSUE 17):
    a HybridParallelEngine at the trivial strategy point (all degrees
    1) must compile the SAME program as a directly-built
    ShardedTrainStep — byte-identical flags-off StableHLO — and
    toggling FLAGS_sep_ring_attention with no sep axis in the mesh
    must leave that program byte-identical too (the flag is read at
    trace time and routes through the ring kernel only when the
    activation scope carries a sep axis of size > 1).  The composed
    multi-axis half (parity to fp32 tolerance on the 8-virtual-device
    mesh) is tier-1-pinned in tests/test_hybrid_engine.py, which this
    bench process does not have the devices for.  Cheap (tiny llama,
    lowering only), runs before every bench config."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    from paddle_tpu.parallel import HybridParallelEngine, ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int32))

    def build(engine, ring):
        set_flags({"FLAGS_sep_ring_attention": ring})
        try:
            paddle.seed(0)
            m = LlamaForCausalLM(llama_tiny_config())
            opt = paddle.optimizer.AdamW(
                1e-3, parameters=m.parameters(), weight_decay=0.1)
            if engine:
                eng = HybridParallelEngine(m, opt)
                step = eng.step
            else:
                step = ShardedTrainStep(
                    m, opt, build_mesh(devices=jax.devices()[:1]),
                    sharding_stage=0)
            hlo = step.compiled_hlo(ids, ids, optimized=False)
        finally:
            set_flags({"FLAGS_sep_ring_attention": False})
        return hlo

    direct = build(False, False)
    hybrid = build(True, False)
    hybrid_ring = build(True, True)
    assert hybrid == direct, \
        "trivial-point HybridParallelEngine program differs from the " \
        "directly-built ShardedTrainStep (must be byte-identical)"
    assert hybrid_ring == direct, \
        "FLAGS_sep_ring_attention changed the program with no sep axis " \
        "in the mesh (must be inert)"


def _assert_telemetry_zero_overhead():
    """No sink attached + FLAGS_compile_cache_dir unset ⇒ the telemetry
    plane costs the hot paths nothing: the compiled train-step HLO is
    byte-identical to flags-off (arming and disarming a sink + the
    incident flight recorder + the compile cache leaves zero residue
    in the program — with FLAGS_numerics_stats unset; ON, the flag
    must genuinely change the program, asserted below), and flags-off
    static-executor replays neither grow the replay-cache key set nor
    emit events.  Cheap (tiny MLP + tiny program), runs before every
    bench config."""
    import tempfile
    import numpy as np
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.static as static
    from paddle_tpu import telemetry
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.parallel import ShardedTrainStep

    assert not telemetry.active(), \
        "a telemetry sink is attached during a bench run"
    from paddle_tpu.framework.flags import get_flag
    assert not get_flag("compile_cache_dir"), \
        "FLAGS_compile_cache_dir armed during a bench run"

    def build_hlo():
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
            m, opt, build_mesh(devices=jax.devices()[:1]),
            loss_fn=lambda o, y: paddle.nn.functional.mse_loss(o, y))
        x = paddle.to_tensor(np.ones((4, 8), np.float32))
        return step, x, step.compiled_hlo(x, x, optimized=False)

    _, _, hlo_off = build_hlo()
    with tempfile.TemporaryDirectory() as d:
        import os as _os
        sink = telemetry.attach_jsonl(_os.path.join(d, "s.jsonl"))
        # arm the WHOLE observability surface at once: sink + compile
        # cache + fleet identity + straggler detector flag — the r11
        # byte-identical contract extends to the ISSUE 10 fleet plane
        # (rank tagging, memory-ledger registration, fleet flags are
        # all host-side)
        telemetry.set_rank(0, 2)
        # the incident flight recorder joins the armed surface (ISSUE
        # 14): it is a plain sink (ring append + trigger lookup), so
        # attaching it — with FLAGS_numerics_stats left unset — must
        # leave the compiled step AND its cache keys byte-identical.
        # Scope it: a production recorder armed via FLAGS_flightrec_dir
        # must be back in place when the assert finishes
        _prev_rec = telemetry.flightrec.detach()
        telemetry.flightrec.attach(_os.path.join(d, "incidents"))
        # FLAGS_mfu_floor joins the armed surface (ISSUE 12): the cost
        # ledger's drift floor is host-plane only, so arming it must
        # leave the compiled step byte-identical too
        # (the flag arms the AOT store inside the cache directory in
        # force and moves nothing: there is no real one to restore)
        set_flags({"FLAGS_compile_cache_dir": "1",
                   "FLAGS_straggler_skew_ms": 50.0,
                   "FLAGS_mfu_floor": 0.5})
        try:
            step, x, hlo_armed = build_hlo()
            step(x, x)                      # exercise the armed path
        finally:
            set_flags({"FLAGS_compile_cache_dir": "",
                       "FLAGS_straggler_skew_ms": 0.0,
                       "FLAGS_mfu_floor": 0.0})
            telemetry.flightrec.detach()
            telemetry.flightrec.restore(_prev_rec)
            telemetry.remove_sink(sink)
    _, _, hlo_off2 = build_hlo()
    assert hlo_off == hlo_armed == hlo_off2, \
        "telemetry sink / compile-cache / fleet / cost-ledger / " \
        "flight-recorder arming changed the train-step program"
    # the numerics plane is a PROGRAM switch (ISSUE 14): ON it must
    # actually change the build (per-layer reductions in-graph) — a
    # vacuous flag would make the byte-identical assert above prove
    # nothing about it
    set_flags({"FLAGS_numerics_stats": True})
    try:
        _, _, hlo_num = build_hlo()
    finally:
        set_flags({"FLAGS_numerics_stats": False})
    assert hlo_num != hlo_off, \
        "FLAGS_numerics_stats did not reach the compiled train step"
    # scrub the assert's own footprint (steps/compile records from the
    # tiny MLP) so the telemetry snapshot embedded in this config's
    # metric lines reflects ONLY the config's run — then put the
    # production flight recorder back (reset() detaches every sink,
    # which would otherwise undo the finally-block restore above)
    telemetry.reset()
    telemetry.clear_report()
    telemetry.flightrec.restore(_prev_rec)

    # static-executor replay hot path: flags-off replays must not grow
    # the replay-cache key set or publish events
    static.enable_static()
    try:
        main_p = static.Program()
        with static.program_guard(main_p, static.Program()):
            xs = static.data("x", [2, 4], "float32")
            w = paddle.to_tensor(np.ones((4, 3), np.float32))
            loss = paddle.matmul(xs, w).mean()
        exe = static.Executor()
        xv = np.ones((2, 4), np.float32)
        exe.run(main_p, feed={"x": xv}, fetch_list=[loss])
        keys = set(main_p._exec_cache)
        probe = telemetry.MemorySink()
        telemetry.add_sink(probe)
        try:
            for _ in range(3):
                exe.run(main_p, feed={"x": xv}, fetch_list=[loss])
        finally:
            telemetry.remove_sink(probe)
        assert set(main_p._exec_cache) == keys, \
            "replays with a sink attached changed the replay-cache keys"
        assert not probe.records, \
            "flags-off executor replays published telemetry events"
    finally:
        static.disable_static()


def _assert_serve_robustness_zero_overhead():
    """The serve-plane robustness layer (ISSUE 9: SLO admission,
    deadlines, load shedding, fault recovery) is HOST-plane control
    flow only: with the flags off NOTHING about the compiled serve
    step may change, and with the flags ON the programs must be the
    very same ones — program-cache keys AND lowered step HLO
    byte-identical across the flag toggle, exactly 2 compiled programs
    under a mixed-SLO multi-length workload (prompt length and SLO mix
    never reach a program shape).  Cheap (1-layer tiny llama); runs
    before every bench config."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import telemetry
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)

    paddle.seed(3)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    model = LlamaForCausalLM(cfg)
    geom = dict(max_batch_size=2, max_len=32, chunk=4, prefill_chunk=4)

    def fingerprint():
        bat = ContinuousBatcher(model, **geom)
        keys = (bat._program_key(1, bat.chunk),
                bat._program_key(bat.prefill_chunk, bat.admit_steps))
        hlo = (bat.lower_step(mixed=False).as_text(),
               bat.lower_step(mixed=True).as_text())
        return bat, keys, hlo

    _, keys_off, hlo_off = fingerprint()
    # the flight recorder joins the armed surface here too (ISSUE 14):
    # with it attached (and FLAGS_numerics_stats unset) the serve-step
    # HLO and program-cache keys must stay byte-identical
    import tempfile as _tempfile
    _fr_dir = _tempfile.mkdtemp(prefix="bench-flightrec-")
    _prev_rec = telemetry.flightrec.detach()   # scope: restore below
    telemetry.flightrec.attach(_fr_dir)
    set_flags({"FLAGS_serve_queue_depth": 8,
               "FLAGS_serve_default_deadline_ms": 60000.0})
    try:
        bat_on, keys_on, hlo_on = fingerprint()
        rng = np.random.RandomState(0)
        for L, slo in ((3, "interactive"), (7, "batch"),
                       (5, "best_effort"), (9, "interactive"),
                       (11, "batch")):
            bat_on.submit(rng.randint(1, 64, L).astype(np.int32), 4,
                          slo=slo)
        outs = bat_on.run()
        st = bat_on.stats()
    finally:
        set_flags({"FLAGS_serve_queue_depth": 0,
                   "FLAGS_serve_default_deadline_ms": 0.0})
        telemetry.flightrec.detach()
        telemetry.flightrec.restore(_prev_rec)
        import shutil as _shutil
        _shutil.rmtree(_fr_dir, ignore_errors=True)
    assert keys_off == keys_on, \
        f"robustness flags / flight recorder leaked into serve " \
        f"program keys: {keys_off} vs {keys_on}"
    assert hlo_off == hlo_on, \
        "robustness flags / flight-recorder arming changed the " \
        "lowered serve-step HLO"
    assert st["compiled_programs"] == 2, \
        f"mixed-SLO multi-length workload compiled " \
        f"{st['compiled_programs']} programs (want 2)"
    assert st["requests_shed"] == 0 \
        and st["requests_completed"] == len(outs), st
    _, _, hlo_off2 = fingerprint()
    assert hlo_off == hlo_off2, \
        "serve-step HLO changed after the flag round-trip"


def _assert_autoscale_zero_overhead():
    """ISSUE 19 flags-off contract: the elastic autoscaler is a HOST
    control loop that must cost NOTHING when off.  With FLAGS_autoscale
    unset a constructed AutoscalerDaemon's tick() is one flag read —
    zero KV-plane traffic (no lease, no journal, no recovery scan) —
    and importing the fleet package + building a daemon leaves the
    serve-step program-cache keys and lowered HLO byte-identical across
    the flag round-trip.  Cheap (1-layer tiny llama); runs before
    every bench config."""
    import paddle_tpu as paddle
    from paddle_tpu.fleet import AutoscalerDaemon
    from paddle_tpu.fleet.autoscaler import _LocalKV
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.inference.router import ServeRouter
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)

    paddle.seed(3)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    model = LlamaForCausalLM(cfg)
    geom = dict(max_batch_size=2, max_len=32, chunk=4, prefill_chunk=4)

    def fingerprint():
        bat = ContinuousBatcher(model, **geom)
        keys = (bat._program_key(1, bat.chunk),
                bat._program_key(bat.prefill_chunk, bat.admit_steps))
        hlo = (bat.lower_step(mixed=False).as_text(),
               bat.lower_step(mixed=True).as_text())
        return bat, keys, hlo

    class _CountingKV:
        """Every KV verb the daemon could issue, counted."""

        def __init__(self, inner):
            self._inner = inner
            self.calls = 0

        def __getattr__(self, name):
            attr = getattr(self._inner, name)
            if not callable(attr):
                return attr

            def wrapped(*a, **k):
                self.calls += 1
                return attr(*a, **k)
            return wrapped

    _, keys_off, hlo_off = fingerprint()
    kv = _CountingKV(_LocalKV())
    router = ServeRouter(batchers=[ContinuousBatcher(model, **geom)])
    daemon = AutoscalerDaemon(router, kv=kv)
    for _ in range(4):
        out = daemon.tick()
        assert out.get("status") == "disabled", out
    assert kv.calls == 0, \
        f"FLAGS_autoscale off but the daemon issued {kv.calls} " \
        f"KV-plane calls (the zero-overhead gate is the flag check)"
    set_flags({"FLAGS_autoscale": True})
    try:
        _, keys_on, hlo_on = fingerprint()
    finally:
        set_flags({"FLAGS_autoscale": False})
    assert keys_off == keys_on, \
        f"FLAGS_autoscale leaked into serve program keys: " \
        f"{keys_off} vs {keys_on}"
    assert hlo_off == hlo_on, \
        "FLAGS_autoscale changed the lowered serve-step HLO"
    _, _, hlo_off2 = fingerprint()
    assert hlo_off == hlo_off2, \
        "serve-step HLO changed after the autoscale flag round-trip"


def _assert_disagg_zero_overhead():
    """ISSUE 20 flags-off contract: disaggregation must cost NOTHING
    when unused.  With FLAGS_serve_disagg off a unified serve run —
    hand-off/replication code imported, a whole router fleet behind
    it — leaves the single-batcher serve program-cache keys and
    lowered HLO byte-identical across the flag round-trip, compiles
    ZERO page export/import programs, and the no-op replication sweep
    issues zero KV-plane verbs.  Cheap (1-layer tiny llama); runs
    before every bench config."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.inference.generation import _program_cache_contains
    from paddle_tpu.inference.router import ServeRouter
    from paddle_tpu.inference.serving import (pack_handoff,   # noqa: F401
                                              unpack_handoff)

    paddle.seed(3)
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    model = LlamaForCausalLM(cfg)
    geom = dict(max_batch_size=2, max_len=32, chunk=4, prefill_chunk=4)

    def fingerprint():
        bat = ContinuousBatcher(model, **geom)
        keys = (bat._program_key(1, bat.chunk),
                bat._program_key(bat.prefill_chunk, bat.admit_steps))
        hlo = (bat.lower_step(mixed=False).as_text(),
               bat.lower_step(mixed=True).as_text())
        return bat, keys, hlo

    bat0, keys_off, hlo_off = fingerprint()
    page_keys = [("serve_page_export", bat0.num_pages, bat0.page_size,
                  bat0.pages_per_slot, bat0._kv_dtype),
                 ("serve_page_import", bat0.num_pages, bat0.page_size,
                  bat0.pages_per_slot, bat0._kv_dtype)]
    # a flags-off unified fleet run: no role ever set, so no freeze,
    # no hand-off, no page program may compile
    rng = np.random.RandomState(1)
    router = ServeRouter(batchers=[ContinuousBatcher(model, **geom)
                                   for _ in range(2)])
    for L in (5, 7, 6):
        router.submit(rng.randint(1, 64, L).astype(np.int32), 4)
    outs = router.run()
    assert len(outs) == 3 and router.stats()["handoffs"] == 0
    for k in page_keys:
        assert not _program_cache_contains(model, k), \
            f"flags-off serve compiled a hand-off page program: {k}"
    set_flags({"FLAGS_serve_disagg": True,
               "FLAGS_router_migration_budget": 4})
    try:
        _, keys_on, hlo_on = fingerprint()
    finally:
        set_flags({"FLAGS_serve_disagg": False,
                   "FLAGS_router_migration_budget": 0})
    assert keys_off == keys_on, \
        f"FLAGS_serve_disagg leaked into serve program keys: " \
        f"{keys_off} vs {keys_on}"
    assert hlo_off == hlo_on, \
        "FLAGS_serve_disagg changed the lowered serve-step HLO"
    _, keys_off2, hlo_off2 = fingerprint()
    assert keys_off == keys_off2 and hlo_off == hlo_off2, \
        "serve programs changed after the disagg flag round-trip"


def _assert_decode_roofline_zero_overhead():
    """ISSUE 11 flags-off contract: FLAGS_weight_only_dtype and the
    speculation flags leave the flags-off programs byte-identical.
    (a) the serve-step HLO and program keys of an UNQUANTIZED,
    non-speculative batcher are identical before/during/after a flag
    toggle cycle; (b) the llama TRAIN step never reads the flags at
    all (HLO identical with them armed); (c) the protection is real:
    under the armed flag the program-cache fingerprint changes, so a
    program traced at flags-off can never be replayed (stale-replay
    guard), and speculation swaps the decode program key; (d) restored
    defaults hit the original programs warm.  Cheap (1-layer tiny
    llama, lowering only); runs before every bench config."""
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.inference import ContinuousBatcher
    from paddle_tpu.inference.generation import _program_cache_contains
    from paddle_tpu.models.llama import (LlamaForCausalLM,
                                         llama_tiny_config)
    from paddle_tpu.parallel import ShardedTrainStep
    from paddle_tpu.distributed.topology import build_mesh

    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64,
                            num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=64)
    model = LlamaForCausalLM(cfg)
    geom = dict(max_batch_size=2, max_len=32, chunk=4, prefill_chunk=4)

    def fingerprint(**kw):
        bat = ContinuousBatcher(model, weight_only_dtype="none",
                                **geom, **kw)
        keys = (bat._program_key(1, bat.chunk),
                bat._program_key(bat.prefill_chunk, bat.admit_steps))
        hlo = (bat.lower_step(mixed=False).as_text(),
               bat.lower_step(mixed=True).as_text())
        return bat, keys, hlo

    def train_hlo():
        paddle.seed(8)
        m = LlamaForCausalLM(llama_tiny_config())
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters(),
                                     weight_decay=0.1)
        ids = paddle.to_tensor(np.random.RandomState(0).randint(
            0, 512, (2, 16)).astype(np.int32))
        step = ShardedTrainStep(m, opt,
                                build_mesh(devices=jax.devices()[:1]),
                                sharding_stage=0)
        return step.compiled_hlo(ids, ids, optimized=False)

    bat0, keys_off, hlo_off = fingerprint()
    probe_key = keys_off[0]
    # build the real decode program so the cache-miss guard below has
    # something to protect
    bat0._step_fn(1, bat0.chunk)
    assert _program_cache_contains(model, probe_key)
    t_off = train_hlo()
    set_flags({"FLAGS_weight_only_dtype": "int8"})
    try:
        _, keys_on, hlo_on = fingerprint()
        # the flags-off-traced program is UNREACHABLE under the armed
        # flag (fingerprinted cache key) even though the lowered HLO of
        # an unquantized model is unchanged — that is the stale-replay
        # guard, not a recompile of different code
        assert not _program_cache_contains(model, probe_key), \
            "weight-only flag flip did not invalidate cached programs"
        assert keys_on == keys_off, \
            "weight-only flag leaked into the serve program keys"
        assert hlo_on == hlo_off, \
            "weight-only flag changed an unquantized serve-step HLO"
        assert train_hlo() == t_off, \
            "weight-only flag changed the llama train-step HLO"
    finally:
        set_flags({"FLAGS_weight_only_dtype": "none"})
    assert _program_cache_contains(model, probe_key), \
        "restored flags no longer hit the original serve programs"
    # speculation swaps the decode program (key and HLO both differ) —
    # and restoring the default gives back the original byte-for-byte
    bat_s, keys_spec, hlo_spec = fingerprint(spec_tokens=2,
                                             draft_layers=1)
    assert keys_spec[0] != keys_off[0], \
        "speculation did not change the decode program key"
    assert hlo_spec[0] != hlo_off[0], \
        "speculation did not change the decode program"
    # donation lint over every new program shape: the draft/verify
    # decode scan and the draft-carrying admit scan must alias every
    # carry (a forgotten donate_argnum doubles the KV pool in HBM)
    from paddle_tpu.analysis import lint_serve_programs
    findings = lint_serve_programs(bat_s) + lint_serve_programs(bat0)
    assert not findings, \
        f"serve programs hold undonated carries: {findings}"
    _, keys_off2, hlo_off2 = fingerprint()
    assert keys_off2 == keys_off and hlo_off2 == hlo_off, \
        "serve programs changed after the speculation round-trip"


def _run_matrix():
    """The default run: the full matrix, llama first.  Each config runs
    in its OWN subprocess — the chip belongs to one process at a time,
    and a config's params/opt-state would otherwise stay resident and
    OOM the 16G chip for every config after it.  So this parent never
    touches jax (or paddle_tpu, which imports it): it only starts the
    children, passes their metric lines on, and reports whether any of
    them failed."""
    import subprocess
    here = os.path.abspath(__file__)
    budget = float(os.environ.get("BENCH_CONFIG_TIMEOUT", "1500"))
    failed = 0
    for name in CONFIGS:
        env = dict(os.environ)
        env["BENCH_CONFIG"] = name
        try:
            proc = subprocess.run(
                [sys.executable, here], env=env, text=True,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                timeout=budget)
        except subprocess.TimeoutExpired:
            err = f"timeout {budget}s"
        else:
            out = proc.stdout.strip()
            if proc.returncode == 0 and out:
                print(out, flush=True)
                continue
            tail = (proc.stderr or proc.stdout or "")[-200:]
            err = f"rc={proc.returncode}: {tail}"
        failed += 1
        print(json.dumps({"metric": f"{name}_bench_error", "value": 0,
                          "unit": err, "vs_baseline": 0.0}), flush=True)
    return 1 if failed else 0


def main():
    which = os.environ.get("BENCH_CONFIG", "all").lower()
    if "--only" in sys.argv:
        i = sys.argv.index("--only")
        if i + 1 >= len(sys.argv):
            print(json.dumps({"metric": "bench_config_error", "value": 0,
                              "unit": "--only requires a metric/config "
                                      "name", "vs_baseline": 0.0}),
                  flush=True)
            return 2
        which = sys.argv[i + 1].lower()
    which = _ALIASES.get(which, which)
    # legacy interface: BENCH_OFFLOAD=1 turns the llama config into the
    # offload config (r4 drivers invoke it this way)
    offload = os.environ.get("BENCH_OFFLOAD", "") not in ("", "0") \
        and which in ("llama", "offload", "all")
    if which == "all" and not offload:
        return _run_matrix()
    if which not in CONFIGS and not offload:
        print(json.dumps({"metric": "bench_config_error", "value": 0,
                          "unit": f"unknown BENCH_CONFIG={which!r}; "
                                  f"choose {sorted(CONFIGS)} or 'all'",
                          "vs_baseline": 0.0}), flush=True)
        return 2
    # one config in this process: the zero-overhead contracts first
    # (they build and compile programs, so they run HERE, in the
    # process that owns the chip — never in the matrix parent)
    _assert_serve_robustness_zero_overhead()
    _assert_autoscale_zero_overhead()
    _assert_disagg_zero_overhead()
    _assert_decode_roofline_zero_overhead()
    _assert_analysis_zero_overhead()
    _assert_fault_tolerance_zero_overhead()
    _assert_mfu_fusion_zero_overhead()
    _assert_comm_overlap_zero_overhead()
    _assert_hybrid_zero_overhead()
    _assert_telemetry_zero_overhead()
    if offload:
        return bench_llama(offload=True)
    return CONFIGS[which]()


if __name__ == "__main__":
    sys.exit(main() or 0)
