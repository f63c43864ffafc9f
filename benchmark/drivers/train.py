"""The training window: `ShardedTrainStep.__call__` on batches drawn from the
seed, built the way `chip_smoke.build_trainer` builds it (a copy, not an
import), on the mesh the cell's file names.

Set-up builds ONE trainer, drives it through its first three steps with the
window's own call and feed (these are the steps the reference follows), and
hands that same object to the window.  The window dispatches one step ahead
and ends in `block_until_ready`; its rate is every token of every step over
the whole window.
"""
import gc
import time

import numpy as np

import harness
import traffic
import weights as weights_mod
from drivers.llama_program import build_model, program_name

CHECK_STEPS = 3           # the steps the reference follows (two in full)
TRACED_STEPS = 4
SETTLE_STEPS = 2          # steady steps between the checks and the trace


def build_trainer(ctx):
    """(model, trainer): as chip_smoke.build_trainer, from the cell's data."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models.llama import shard_llama_tp
    from paddle_tpu.parallel import ShardedTrainStep
    tr, mesh_axes = ctx.workload["trainer"], ctx.workload.get("mesh", {})
    mesh = build_mesh(devices=ctx.devices, **mesh_axes)
    model = build_model(ctx.config, ctx.seed, tr["param_dtype"])
    if mesh_axes.get("mp", 1) > 1:
        shard_llama_tp(model, mesh)
    opt = paddle.optimizer.AdamW(
        tr["learning_rate"], beta1=tr["beta1"], beta2=tr["beta2"],
        epsilon=tr["epsilon"], parameters=model.parameters(),
        weight_decay=tr["weight_decay"], moment_dtype=tr["moment_dtype"])
    step = ShardedTrainStep(model, opt, mesh,
                            sharding_stage=tr["sharding_stage"],
                            rematerialize=tr["rematerialize"])
    return model, step


def run(ctx):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import telemetry
    cfg, mix, tr = ctx.config, ctx.mix, ctx.workload["trainer"]
    vocab, spans = cfg["vocab_size"], ctx.spans
    model, step = build_trainer(ctx)
    names = [n for n, _, _, _ in weights_mod.leaf_specs(cfg)]
    t_built = time.perf_counter()

    def one_step(i):
        with spans.span("bench.data_draw"):
            x = paddle.to_tensor(traffic.train_batch(mix, ctx.seed, i, vocab))
        with spans.span("bench.train_step"):
            return step(x, x).value

    # the first steps, through the window's own call: what `correct` reads
    evidence = {"losses": [], "grad_norms": {}, "grad_samples": {},
                "change_norms": {}}
    for i in range(CHECK_STEPS):
        evidence["losses"].append(float(jax.block_until_ready(one_step(i))))
        state, _ = step.train_state()
        if i == 0:
            # Adam's first moment after one step is (1 - beta1) * gradient
            for n in names:
                m1 = state[f"opt.{program_name(n)}.moment1"]
                evidence["grad_norms"][n] = \
                    weights_mod.norm(m1) / (1.0 - tr["beta1"])
                evidence["grad_samples"][n] = weights_mod.sample(
                    m1, ctx.seed, cfg, n) / (1.0 - tr["beta1"])
        if i == 1:
            for n in names:
                evidence["change_norms"][n] = weights_mod.change_norm(
                    state[f"model.{program_name(n)}"], ctx.seed, cfg, n,
                    tr["param_dtype"])
        del state
    t_checked = time.perf_counter()
    i = CHECK_STEPS
    for _ in range(SETTLE_STEPS):
        jax.block_until_ready(one_step(i))
        i += 1
    if ctx.tracer.enabled:
        ctx.tracer.start()
        pending = None
        for _ in range(TRACED_STEPS):
            loss = one_step(i)
            i += 1
            if pending is not None:
                jax.block_until_ready(pending)
            pending = loss
        jax.block_until_ready(pending)
        ctx.tracer.stop()

    # the measured window
    compiles_before = harness.compile_requests()
    losses, stamps, pending = [], [], None
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    ctx.note(f"set-up {setup_s:.1f} s: {t_built - ctx.t_start:.1f} to the "
             f"built trainer, {t_checked - t_built:.1f} for the first "
             f"{CHECK_STEPS} steps with their readings (the first compiles "
             f"or loads the step program), {t0 - t_checked:.1f} to settle")
    while True:
        loss = one_step(i)
        i += 1
        if pending is not None:
            jax.block_until_ready(pending)
            stamps.append(time.perf_counter())
        pending = loss
        losses.append(loss)
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    jax.block_until_ready(pending)
    t1 = time.perf_counter()
    stamps.append(t1)
    window_s = t1 - t0
    compiles = harness.compile_requests() - compiles_before
    steps = len(losses)
    tokens = steps * int(mix["batch"]) * int(mix["sequence"])
    finite = np.isfinite(np.asarray(jax.device_get(losses), np.float64))
    peak = ctx.memory_peak_bytes()

    del model, step, losses, pending, loss
    telemetry.reset()
    gc.collect()
    return {
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": setup_s},
        "counters": {"steps": steps, "tokens": tokens, "window_s": window_s,
                     "step_intervals_ms": (np.diff([t0] + stamps) * 1e3)
                     .tolist(),
                     "compile_requests_in_window": compiles,
                     "traced_steps": TRACED_STEPS, "window_t0": t0},
        "evidence": evidence,
        "attempted": steps, "failed": int((~finite).sum()),
        "memory_peak_bytes": peak,
    }


def reference_numbers(ctx, precision="float32", rows=None, trainer=None):
    """What the reference (or, at a lower precision, on part of the rows or
    with other hyperparameters, a control or a planted fault) gives for the
    first steps."""
    from reference import decoder_f32
    batches = [traffic.train_batch(ctx.mix, ctx.seed, i,
                                   ctx.config["vocab_size"])
               for i in range(CHECK_STEPS)]
    if rows is not None:
        batches = [b[rows] for b in batches]
    return decoder_f32.train_reference(
        ctx.seed, ctx.config, batches, trainer or ctx.workload["trainer"],
        precision)


def compare(got, ref):
    """The numbers `correct` holds, each got-against-reference:

    loss_gap_<k>     |loss - reference| / reference, step k
    grad_norm_gap    worst leaf: | ||g|| - ||g_ref|| | over the larger of
                     that leaf's ||g_ref|| and the median leaf's
    grad_sample_gap  median leaf: ||g - g_ref|| / ||g_ref|| over 65536
                     elements of the leaf at places drawn from the seed.  The
                     norms above average rounding noise away (it enters them
                     squared); this one reads it, and is what a lower
                     precision fails
    change_norm_gap  the same of the parameters' change after two steps,
                     leaving out leaves whose reference gradient is under a
                     thousandth of the median leaf's (they move by round-off
                     under Adam)
    Returns ({name: value}, {name: worst leaf})."""
    out, worst = {}, {}
    for k, (a, b) in enumerate(zip(got["losses"], ref["losses"]), 1):
        out[f"loss_gap_{k}"] = abs(a - b) / abs(b)
    g_ref = ref["grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    gaps = {n: abs(got["grad_norms"][n] - g_ref[n]) / max(g_ref[n], g_med)
            for n in g_ref}
    worst["grad_norm_gap"] = max(gaps, key=gaps.get)
    out["grad_norm_gap"] = gaps[worst["grad_norm_gap"]]
    rel = {n: float(np.linalg.norm(got["grad_samples"][n]
                                   - ref["grad_samples"][n])
                    / max(np.linalg.norm(ref["grad_samples"][n]), 1e-30))
           for n in g_ref if g_ref[n] >= 1e-3 * g_med}
    out["grad_sample_gap"] = float(np.median(list(rel.values())))
    worst["grad_sample_gap"] = {n: round(v, 5) for n, v in sorted(
        rel.items(), key=lambda kv: -kv[1])[:3]}
    c_ref = ref["change_norms"]
    moving = [n for n in c_ref if g_ref[n] >= 1e-3 * g_med]
    c_med = float(np.median([c_ref[n] for n in moving]))
    gaps = {n: abs(got["change_norms"][n] - c_ref[n]) / max(c_ref[n], c_med)
            for n in moving}
    worst["change_norm_gap"] = max(gaps, key=gaps.get)
    out["change_norm_gap"] = gaps[worst["change_norm_gap"]]
    return out, worst


def check(ctx, evidence):
    """[(name, value, limit)] against the float32 reference.  The loss gaps
    are printed and not held to a limit: no control and no planted fault
    reads three times what sound runs read (PERF.md, section 2)."""
    values, worst = compare(evidence, reference_numbers(ctx))
    ctx.note(f"worst leaves: {worst}")
    ctx.note("not compared: " + ", ".join(
        f"{n} = {v:.3g}" for n, v in values.items() if n.startswith("loss")))
    limits = ctx.workload["correct"]
    return [(name, values[name], limits[name]) for name in limits]
