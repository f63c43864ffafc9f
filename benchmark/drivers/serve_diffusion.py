"""The serving window of `drivers/serve.py` for a configuration that
generates by diffusion over blocks (`model_type` `sdar_moe`): a THIRD copy of
its `run`, beside `drivers/serve_moe.py`'s.

The accepted drivers import their builders and references at the top of the
module and may not be edited, so a third model class costs a third copied
window (PERF.md section 7, Open question 11: one serve driver that takes
builder, weights, reference and counts from the configuration's
`model_class` / `model_type` is a `benchmark` issue's).  What is a name there
is imported (`_Record`, `TRACED_CHUNKS`, `SAMPLE_REQUESTS`); `run` is lines
50-234 of `drivers/serve.py`, line for line, but for the places marked
`(changed)`: the builder; the pass at which each delivered token was fixed,
kept from the finished `Request` (the evidence `check` needs beside prompt
and tokens); and the program's own counters (`ContinuousBatcher.stats()`'s:
the model's `moe_*` and the block schedule's) passed into `counters`.  The
window, the closed loop, TTFT / TPOT / tokens arithmetic and the sample drawn
for `correct` are therefore the accepted cells'.

`check` judges what the TIMED path delivered against
`reference/block_diffusion_moe_f32.replayed_logits`: every denoise pass of
every whole block of the sampled requests, under the state the served run
had at that pass.
"""
import gc
import time

import numpy as np

import harness
import traffic
from drivers.sdar_moe_program import build_model          # (changed)
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
    passes = {}           # (changed) request id -> its tokens' passes

    def live_tokens():
        return [len(r.prompt) + len(r.tokens) for r in records.values()
                if r.done_at is None]

    seen = {"admit_chunks": 0, "decode_chunks": 0}

    def step():
        live = live_tokens()
        with spans.span("bench.serve_step"):
            finished = bat.step()
        for req in finished:  # (changed) the evidence beside the tokens
            passes[req.req_id] = req.output_passes()
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
    rid_of = {id(r): rid for rid, r in records.items()}        # (changed)
    evidence = {"sample": [(r.prompt, np.asarray(r.tokens, np.int32),
                            passes[rid_of[id(r)]])             # (changed)
                           for r in sample],
                "pad_to": int(ctx.workload["capacity"]["max_len"])}
    from paddle_tpu.inference.serving import DIFFUSION_COUNTERS  # (changed)
    counters = {
        # (changed) the program's own counts over the window, from stats():
        # the model's and the block schedule's; sums, but a `_max` is the
        # program's whole life's
        **{name: after[name] if name.endswith("_max")
           else after[name] - before[name]
           for name in tuple(bat.model.step_counter_names())
           + DIFFUSION_COUNTERS},
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


# -- what decides `correct` ---------------------------------------------------

def _replayed(ctx, evidence, precision="float32", fault=None):
    from reference import block_diffusion_moe_f32 as reference
    return reference.replayed_logits(
        ctx.seed, ctx.config, evidence["sample"], ctx.config["torch_dtype"],
        evidence["pad_to"], precision, fault)


def judgement(ctx, evidence, precision="float32", fault=None):
    """{"token": gaps, "lane": gaps, "count_off": flags, "block_passes":
    counts} of the sampled requests' whole blocks.  `token` and `lane` have
    one entry a token the served run fixed, each judged AT THE PASS it was
    fixed, under the state the run had there.

    token  how far the token's reference logit lies below the reference's
           best in that lane.
    lane   the run fixed k lanes in that pass; how far the lane's reference
           log-confidence (log softmax of the lane's best token) lies below
           the reference's k-th best among the lanes then masked (0 where
           the lane is among the reference's k best).

    The SCHEDULE, which the two above take as given (they ask which lanes,
    not how many):

    count_off     one entry a denoise pass: whether the run fixed another
                  NUMBER of lanes there than the rule fixes on the
                  reference's own confidences in that state (the pass's
                  quota, or every masked lane above the threshold if those
                  number at least the quota; a pass past the step budget
                  has no quota and is off).
    block_passes  one entry a block: the denoise passes the run gave it,
                  and the commit.

    With a lower `precision` (a control) or a planted `fault` of the FORWARD
    the token and the lanes judged are those THAT forward puts first, in the
    same passes of the same served states; the schedule is the served run's
    either way."""
    import jax
    import jax.numpy as jnp
    from reference import block_diffusion_moe_f32 as reference
    changed = precision != "float32" or fault is not None
    low = _replayed(ctx, evidence, precision, fault) if changed else None
    quotas = reference.step_quotas(ctx.config)
    threshold = ctx.config["confidence_threshold"]
    tokens, lanes, count_off, block_passes = [], [], [], []
    for plan, ref in _replayed(ctx, evidence):
        masked, fixed = plan["masked"], plan["fixed"]
        best = jnp.max(ref, -1)
        conf = np.asarray(best - jax.nn.logsumexp(ref, -1))      # [n, L]
        token, choice_conf = jnp.asarray(plan["token"]), conf
        if changed:
            _, mine = next(low)
            token = jnp.argmax(mine, -1)
            choice_conf = np.asarray(jnp.max(mine, -1)
                                     - jax.nn.logsumexp(mine, -1))
        at = jnp.take_along_axis(ref, token[..., None], -1)[..., 0]
        token_gap = np.asarray(best - at)
        k = fixed.sum(1)
        # the reference's k-th best masked lane of every pass
        ranked = -np.sort(-np.where(masked, conf, -np.inf), axis=1)
        kth = ranked[np.arange(len(k)), np.maximum(k, 1) - 1]
        chosen = fixed
        if changed:
            order = np.argsort(-np.where(masked, choice_conf, -np.inf),
                               axis=1, kind="stable")
            chosen = np.zeros_like(fixed)
            for s, n in enumerate(k):
                chosen[s, order[s, :n]] = True
        tokens.append(token_gap[fixed])
        lanes.append(np.maximum(kth[:, None] - conf, 0.0)[chosen])
        last = {}
        for s, (b, p) in enumerate(plan["states"]):
            due = reference.unmask_choice(
                np.exp(conf[s]), masked[s], quotas[p], threshold).sum() \
                if p < len(quotas) else -1
            count_off.append(due != k[s])
            last[b] = p
        block_passes.extend(p + 2 for p in last.values())
    return {"token": np.concatenate(tokens), "lane": np.concatenate(lanes),
            "count_off": np.asarray(count_off, bool),
            "block_passes": np.asarray(block_passes, np.int64)}


def numbers(ctx, gaps):
    """The numbers of a judgement: those the workload's `correct` holds to
    a limit, and `served_lane_gap`, which the control prints (the widest
    lane gap separates a sound run from lanes taken in position order by
    1.5 times only; its share separates them by 7, PERF.md section 2)."""
    off = ctx.workload["off_gap"]
    return {"served_token_gap": float(gaps["token"].max()),
            "served_token_off_share":
                100.0 * float(np.mean(gaps["token"] > off["token"])),
            "served_lane_gap": float(gaps["lane"].max()),
            "served_lane_off_share":
                100.0 * float(np.mean(gaps["lane"] > off["lane"])),
            "served_pass_count_off_share":
                100.0 * float(np.mean(gaps["count_off"])),
            "served_block_passes_max": float(gaps["block_passes"].max())}


def check(ctx, evidence):
    """[(name, value, limit)]: the widest gap of the served tokens, the share
    of gaps over the workload's `off_gap` of the served tokens and of the
    lanes the run chose to fix; and the schedule: the share of denoise
    passes that fixed another number of lanes than the rule gives, and the
    most passes a block had.  A widest gap and a share, as in `serve_moe`: a
    near-tie in the router's top k (or between two lanes' confidences) that
    flips on bfloat16 rounding moves ONE token by one expert's worth, while
    a wrong forward moves many (PERF.md section 2 has the readings the
    limits are set from)."""
    limits = ctx.workload["correct"]
    if not evidence["sample"]:
        return [(name, float("inf"), limit) for name, limit in limits.items()]
    t0 = time.perf_counter()
    gaps = judgement(ctx, evidence)
    ctx.note(f"compared {gaps['token'].size} served tokens of "
             f"{len(evidence['sample'])} requests at the pass each was "
             f"fixed ({gaps['count_off'].size} passes of "
             f"{gaps['block_passes'].size} blocks), in "
             f"{time.perf_counter() - t0:.1f} s; median gap "
             f"{float(np.median(gaps['token'])):.5f}")
    got = numbers(ctx, gaps)
    return [(name, got[name], limit) for name, limit in limits.items()]
