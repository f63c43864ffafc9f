"""The serving window of `drivers/serve.py` for a configuration whose model
class neither `drivers/llama_program.py` nor the other copied drivers'
builders can build: the window-and-full-attention sparse-expert decoder
(`model_type` `exaone_moe`).

The accepted driver imports its builder and its reference at the top of the
module, so a further model class cannot be handed to it; until a `benchmark`
PR lets it take builder, weights, reference and counts from the
configuration's `model_class` (PERF.md section 7, item 11: this is the
FOURTH copied window), this file holds a COPY of its `run`, made as
`drivers/serve_moe.py` is.  What is a name there is imported (`_Record`,
`TRACED_CHUNKS`, `SAMPLE_REQUESTS`); `run` is lines 50-234 of
`drivers/serve.py`, line for line, but for the two places marked
`(changed)`: the builder, and the model's and the cache's own counters
(`ContinuousBatcher.stats()`'s: the expert layers' `moe_*`, the page walk by
kind of layer) passed into `counters`.  `reference_gaps`, `check` and
`off_share` are `drivers/serve_moe.py`'s with this architecture's reference
in place of `mla_moe_f32`.  The window, the closed loop, TTFT / TPOT /
tokens arithmetic and the sample drawn for `correct` are therefore the
accepted cells'.
"""
import gc
import time

import numpy as np

import harness
import traffic
from drivers.exaone_moe_program import build_model       # (changed)
from drivers.serve import SAMPLE_REQUESTS, TRACED_CHUNKS, _Record


def build_batcher(ctx, **program_options):
    """program_options: only for a CONTROL run."""
    from paddle_tpu.inference import ContinuousBatcher
    model = build_model(ctx.config, ctx.seed, ctx.config["torch_dtype"])
    model.eval()
    cap = ctx.workload["capacity"]
    return ContinuousBatcher(model, max_batch_size=cap["max_batch_size"],
                             max_len=cap["max_len"], **program_options)


# -- drivers/serve.py lines 50-234, line for line but for `(changed)` ---------

def run(ctx, **program_options):
    bat = build_batcher(ctx, **program_options)
    t_built = time.perf_counter()
    mix, spans = ctx.mix, ctx.spans
    arrivals = mix["arrivals"]
    source = traffic.serve_requests(mix, ctx.seed, ctx.config["vocab_size"])
    records, bursts = {}, []      # bursts: (time, tokens) of each delivery

    def on_token(rid, tokens, done):
        now = time.perf_counter()
        with spans.span("bench.on_token"):
            rec = records[rid]
            if len(tokens):
                if rec.first is None:
                    rec.first = now
                rec.last = now
                bursts.append((now, len(tokens)))
                rec.tokens.extend(int(t) for t in tokens)
            if done:
                rec.done_at = now

    def submit(due=None):
        prompt, want = next(source)
        with spans.span("bench.submit"):
            now = time.perf_counter()
            rid = bat.submit(prompt, max_new_tokens=want, on_token=on_token)
        records[rid] = _Record(prompt, want, now if due is None else due)

    chunk_log = []        # (admit chunks, decode chunks, live tokens) a step

    def live_tokens():
        return [len(r.prompt) + len(r.tokens) for r in records.values()
                if r.done_at is None]

    seen = {"admit_chunks": 0, "decode_chunks": 0}

    def step():
        live = live_tokens()
        with spans.span("bench.serve_step"):
            finished = bat.step()
        now = bat.stats()     # one call a chunk: the program's own counters
        chunk_log.append((now["admit_chunks"] - seen["admit_chunks"],
                          now["decode_chunks"] - seen["decode_chunks"], live))
        seen.update(admit_chunks=now["admit_chunks"],
                    decode_chunks=now["decode_chunks"])
        return finished

    # one throwaway request to the end: both step programs compile or load
    # here, before any request that is timed is sent
    bat.submit(np.arange(8, dtype=np.int32) % ctx.config["vocab_size"],
               max_new_tokens=bat.admit_steps + bat.chunk)
    while bat.queued or bat.active:
        bat.step()
    t_warm = time.perf_counter()
    closed = arrivals["mode"] == "closed"
    if closed:
        for _ in range(int(arrivals["clients"])):
            submit()
        while not any(r.done_at for r in records.values()):
            for _ in step():
                submit()
    if ctx.tracer.enabled:
        if not closed:
            # an open loop has no steady state before its window: trace a
            # full batch being served instead
            for _ in range(bat.B):
                submit()
        ctx.tracer.start()
        traced_from = len(chunk_log)
        for _ in range(TRACED_CHUNKS):
            for _ in step():
                if closed:
                    submit()
        ctx.tracer.stop()
        traced_chunks = chunk_log[traced_from:]
        while not closed and any(r.done_at is None for r in records.values()):
            step()
    else:
        traced_chunks = []

    # the measured window
    window_from = len(chunk_log)
    before = bat.stats()
    compiles_before = harness.compile_requests()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    ctx.note(f"set-up {setup_s:.1f} s: {t_built - ctx.t_start:.1f} to the "
             f"built batcher, {t_warm - t_built:.1f} for one request that "
             f"compiles or loads both step programs, {t0 - t_warm:.1f} to "
             "reach the window's state")
    lateness = []
    if closed:
        while time.perf_counter() - t0 < ctx.seconds:
            for _ in step():
                submit()
    else:
        due = [t0 + d for d in traffic.open_schedule(mix, ctx.seed,
                                                     ctx.seconds)]
        k = 0
        while time.perf_counter() - t0 < ctx.seconds:
            now = time.perf_counter()
            while k < len(due) and due[k] <= now:
                submit(due[k])
                lateness.append(time.perf_counter() - due[k])
                k += 1
            if bat.queued or bat.active:
                step()
            elif k < len(due):
                with spans.span("bench.wait_for_arrival"):
                    time.sleep(max(0.0, min(due[k], t0 + ctx.seconds)
                                   - time.perf_counter()))
            else:
                break
    t1 = time.perf_counter()
    window_s = t1 - t0
    after = bat.stats()
    compiles = harness.compile_requests() - compiles_before
    peak = ctx.memory_peak_bytes()

    sent = [r for r in records.values() if t0 <= r.due < t1]
    finished = [r for r in records.values()
                if r.done_at is not None and t0 <= r.done_at <= t1]
    failed = [r for r in finished if len(r.tokens) != r.want]
    tokens_in_window = sum(n for t, n in bursts if t0 < t <= t1)
    waits = []
    for r in sent:
        if r in failed:
            waits.append(None)
        elif r.first is not None and r.first <= t1:
            waits.append(r.first - r.due)
        else:
            waits.append(t1 - r.due)
    worst = max([w for w in waits if w is not None] or [window_s])
    ttft_ms = [1e3 * (worst if w is None else w) for w in waits]
    tpot_ms = [1e3 * (r.last - r.first) / (len(r.tokens) - 1)
               for r in finished if r not in failed and len(r.tokens) > 1]
    ctx.note(f"samples: ttft {len(ttft_ms)} requests sent in the window, "
             f"tpot {len(tpot_ms)} completed in it, "
             f"{tokens_in_window} tokens delivered in {window_s:.3f} s"
             + (f"; the generator sent at most {1e3 * max(lateness):.1f} ms "
                "late" if lateness else ""))

    rng = np.random.default_rng([int(ctx.seed), 4])
    good = [r for r in finished if r not in failed]
    sample = []
    if good:
        longest = max(good, key=lambda r: len(r.prompt) + len(r.tokens))
        rest = [r for r in good if r is not longest]
        picks = rng.permutation(len(rest))[:SAMPLE_REQUESTS]
        sample = [longest] + [rest[j] for j in picks]
    evidence = {"sample": [(r.prompt, np.asarray(r.tokens, np.int32))
                           for r in sample],
                "pad_to": int(ctx.workload["capacity"]["max_len"])}
    counters = {
        # (changed) the model's own counts over the window, from stats():
        # sums, but a `_max` is the program's whole life's; and the page
        # walk by kind of layer, with the cache's geometry by kind
        **{name: after[name] if name.endswith("_max")
           else after[name] - before[name]
           for name in bat.model.step_counter_names()},
        **{name: after[name] - before[name] for name in after
           if name.startswith(("kv_pages_walked_", "kv_pages_window_"))},
        "kv_pool_bytes": after.get("kv_pool_bytes"),
        "kv_ring_pages": after.get("kv_ring_pages"),
        "window_s": window_s, "window_t0": t0,
        "tokens_delivered": tokens_in_window,
        "admit_chunks": after["admit_chunks"] - before["admit_chunks"],
        "decode_chunks": after["decode_chunks"] - before["decode_chunks"],
        "prefill_tokens": after["prefill_tokens"] - before["prefill_tokens"],
        "decode_tokens": after["decode_tokens"] - before["decode_tokens"],
        "compiled_programs": after["compiled_programs"],
        "requests_shed": after["requests_shed"] - before["requests_shed"],
        "compile_requests_in_window": compiles,
        "generator_lateness_ms_max": 1e3 * max(lateness) if lateness else 0.0,
        "chunk": bat.chunk, "admit_steps": bat.admit_steps,
        "prefill_chunk": bat.prefill_chunk, "page_size": bat.page_size,
        "pages_per_slot": bat.pages_per_slot, "slots": bat.B,
        "kv_dtype": after["kv_dtype"],
        "window_chunks": chunk_log[window_from:],
        "traced_chunks": traced_chunks,
    }
    end_to_end = {"serve_tokens_per_s": tokens_in_window / window_s,
                  "setup_s": setup_s}
    if ttft_ms:
        end_to_end["ttft_p95_ms"] = harness.percentile(ttft_ms, 95)
    if tpot_ms:
        end_to_end["tpot_p95_ms"] = harness.percentile(tpot_ms, 95)
    attempted, n_failed = len(sent), len(failed)
    del bat, records, sent, finished, good, sample, source
    from paddle_tpu import telemetry
    telemetry.reset()
    gc.collect()
    return {"end_to_end": end_to_end, "counters": counters,
            "evidence": evidence, "attempted": attempted,
            "failed": n_failed, "memory_peak_bytes": peak}


# -- drivers/serve_moe.py's reference_gaps, check and off_share, this reference

def reference_logits(ctx, evidence):
    from reference import window_moe_f32
    return window_moe_f32.teacher_forced_logits(
        ctx.seed, ctx.config, evidence["sample"], ctx.config["torch_dtype"],
        evidence["pad_to"])


def reference_gaps(ctx, evidence, precision="float32", fault=None, ref=None):
    """For each sampled request's served tokens: how far the token's
    reference logit lies below the reference's best at that position.  With
    a lower `precision` (a control) or a planted `fault` the token judged is
    the one THAT forward puts first, at the same positions of the same
    prompts and tokens.  `ref`: reference_logits() of the same evidence,
    where a caller judges several.  Returns one flat array."""
    import jax.numpy as jnp
    from reference import window_moe_f32
    cfg, dtype = ctx.config, ctx.config["torch_dtype"]
    if ref is None:
        ref = reference_logits(ctx, evidence)
    judged = [jnp.asarray(tokens) for _, tokens in evidence["sample"]]
    if precision != "float32" or fault is not None:
        low = window_moe_f32.teacher_forced_logits(
            ctx.seed, cfg, evidence["sample"], dtype, evidence["pad_to"],
            precision, fault)
        judged = [jnp.argmax(rows, -1) for rows in low]
    gaps = [jnp.max(rows, -1)
            - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]
            for rows, tok in zip(ref, judged)]
    return np.concatenate([np.asarray(g) for g in gaps])


def check(ctx, evidence):
    """[(name, value, limit)]: `served_token_gap`, the widest gap by which a
    served token's logit lies below the reference's best (the accepted
    cell's number), and `served_token_off_share`, the percent of the judged
    tokens that lie more than the workload's `off_gap` below.  Two numbers
    because this model's gaps are spread otherwise than a dense one's: a
    near-tie in the router's top k flips on bfloat16 rounding and moves ONE
    token by one expert's worth, so the widest gap of a sound run is a
    single token's and reads high, while a wrong forward moves MANY tokens
    (PERF.md section 2 has the readings both limits are set from)."""
    limits = ctx.workload["correct"]
    if not evidence["sample"]:
        return [(name, float("inf"), limit) for name, limit in limits.items()]
    gaps = reference_gaps(ctx, evidence)
    ctx.note(f"compared {gaps.size} served tokens of "
             f"{len(evidence['sample'])} requests; median gap "
             f"{float(np.median(gaps)):.5f}")
    return [("served_token_gap", float(gaps.max()),
             limits["served_token_gap"]),
            ("served_token_off_share", off_share(ctx, gaps),
             limits["served_token_off_share"])]


def off_share(ctx, gaps):
    """Percent of the judged tokens more than `off_gap` below the best."""
    return 100.0 * float(np.mean(gaps > ctx.workload["off_gap"]))
