"""The serving window: `ContinuousBatcher.submit` / `.step`, tokens through
`on_token`, under the arrivals the cell's traffic file names.

closed  N clients; each sends its next request when its last one completes.
        Set-up submits one request per client and steps until the first has
        completed, so the window opens with every slot occupied and the
        slots out of step with each other.
open    a schedule of due times drawn from the seed at a fixed rate; each
        request is timed from when it was DUE, and how late the generator
        sent it is reported.  The window opens on an empty batcher.
Both: set-up first serves one throwaway request to its end, so that both
step programs are compiled or loaded before any timed request is sent.

The cell sets capacity (`max_batch_size`, `max_len`); every other knob of
the batcher stays at the program's default, so a better default shows.
"""
import gc
import time

import numpy as np

import harness
import traffic
from drivers.llama_program import build_model

TRACED_CHUNKS = 3
SAMPLE_REQUESTS = 6       # besides the longest: some hundreds of tokens


class _Record:
    __slots__ = ("prompt", "want", "due", "first", "last", "done_at",
                 "tokens")

    def __init__(self, prompt, want, due):
        self.prompt, self.want, self.due = prompt, want, due
        self.first = self.last = self.done_at = None
        self.tokens = []


def build_batcher(ctx, **program_options):
    """program_options: only for a CONTROL run (e.g. kv_dtype="int8")."""
    from paddle_tpu.inference import ContinuousBatcher
    model = build_model(ctx.config, ctx.seed, ctx.config["torch_dtype"])
    model.eval()
    cap = ctx.workload["capacity"]
    return ContinuousBatcher(model, max_batch_size=cap["max_batch_size"],
                             max_len=cap["max_len"], **program_options)


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


def reference_gaps(ctx, evidence, precision="float32"):
    """For each sampled request's served tokens: how far the token's
    reference logit lies below the reference's best at that position.  With
    a lower `precision` (a control) the token judged is the one that
    precision puts first, at the same positions of the same prompts and
    tokens.  Returns one flat array."""
    import jax.numpy as jnp
    from reference import decoder_f32
    cfg, dtype = ctx.config, ctx.config["torch_dtype"]
    ref = decoder_f32.teacher_forced_logits(
        ctx.seed, cfg, evidence["sample"], dtype, evidence["pad_to"])
    judged = [jnp.asarray(tokens) for _, tokens in evidence["sample"]]
    if precision != "float32":
        low = decoder_f32.teacher_forced_logits(
            ctx.seed, cfg, evidence["sample"], dtype, evidence["pad_to"],
            precision)
        judged = [jnp.argmax(rows, -1) for rows in low]
    gaps = [jnp.max(rows, -1)
            - jnp.take_along_axis(rows, tok[:, None], -1)[:, 0]
            for rows, tok in zip(ref, judged)]
    return np.concatenate([np.asarray(g) for g in gaps])


def check(ctx, evidence):
    """[(name, value, limit)]: the widest gap by which a served token's
    logit lies below the reference's best."""
    limits = ctx.workload["correct"]
    if not evidence["sample"]:
        return [("served_token_gap", float("inf"),
                 limits["served_token_gap"])]
    gaps = reference_gaps(ctx, evidence)
    ctx.note(f"compared {gaps.size} served tokens of "
             f"{len(evidence['sample'])} requests; median gap "
             f"{float(np.median(gaps)):.5f}")
    return [("served_token_gap", float(gaps.max()),
             limits["served_token_gap"])]
