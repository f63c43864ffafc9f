"""Continuous batching (inference/serving.py — round-5 verdict item 8).

Reference analog: block_multihead_attention.py paged-KV scheduling.
The contract under test: staggered requests flowing through ONE
batcher produce EXACTLY the tokens each request gets from an isolated
greedy generate() run — admission, eviction, and slot reuse must never
leak state across sequences.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            intermediate_size=128,
                            num_attention_heads=4,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _isolated(model, ids, n):
    out = model.generate(paddle.to_tensor(np.asarray([ids], np.int32)),
                         max_new_tokens=n)
    return np.asarray(out.value)[0]


def test_staggered_requests_match_isolated(model):
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (4, 7, 4, 11, 7)]
    new = [6, 9, 12, 5, 8]

    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                            chunk=4)
    # stagger: two submitted up-front, rest arrive while running
    ids = [bat.submit(prompts[0], new[0]), bat.submit(prompts[1], new[1])]
    bat.step()
    ids.append(bat.submit(prompts[2], new[2]))
    bat.step()
    ids.append(bat.submit(prompts[3], new[3]))
    ids.append(bat.submit(prompts[4], new[4]))
    outs = bat.run()

    assert sorted(outs) == sorted(ids)
    for rid, prompt, n in zip(ids, prompts, new):
        want = _isolated(model, prompt, n)
        got = outs[rid]
        np.testing.assert_array_equal(got, want[: len(got)])
        assert len(got) == n


def test_slot_reuse_no_state_leak(model):
    """A slot that served a LONG sequence must serve a later SHORT one
    identically to isolation (stale cache rows beyond the new prompt
    must stay invisible)."""
    rng = np.random.RandomState(9)
    long_p = rng.randint(1, 128, 20).astype(np.int32)
    short_p = rng.randint(1, 128, 5).astype(np.int32)

    bat = ContinuousBatcher(model, max_batch_size=1, max_len=64,
                            chunk=8)
    r1 = bat.submit(long_p, 16)
    r2 = bat.submit(short_p, 10)      # queued until slot 0 frees
    outs = bat.run()
    np.testing.assert_array_equal(outs[r1],
                                  _isolated(model, long_p, 16))
    np.testing.assert_array_equal(outs[r2],
                                  _isolated(model, short_p, 10))


def test_eos_eviction(model):
    """eos finishes a sequence early; its slot frees for the queue."""
    rng = np.random.RandomState(1)
    p = rng.randint(1, 128, 6).astype(np.int32)
    ref = _isolated(model, p, 24)
    eos = int(ref[2])                  # force an early-ish stop token
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=64,
                            chunk=4, eos_token_id=eos)
    rid = bat.submit(p, 24)
    outs = bat.run()
    got = outs[rid]
    assert got[-1] == eos and len(got) <= 24
    np.testing.assert_array_equal(got, ref[: len(got)])


def test_mixed_lengths_aggregate(model):
    """Mixed prompt lengths in flight simultaneously (one shared
    admission program, one shared decode program)."""
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (3, 9, 15, 6)]
    bat = ContinuousBatcher(model, max_batch_size=4, max_len=64,
                            chunk=8)
    rids = [bat.submit(p, 8) for p in prompts]
    outs = bat.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid], _isolated(model, p, 8))


def test_chunked_admission_overlaps_decode(model):
    """Chunked-prefill parity: prompts LONGER than prefill_chunk are
    consumed across several admission-mode chunks while the resident
    slot keeps decoding (staggered arrival mid-decode); every request
    must still match its isolated greedy run bit-for-bit."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (5, 13, 11, 9)]
    new = [10, 7, 9, 6]
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                            chunk=4, prefill_chunk=4)
    ids = [bat.submit(prompts[0], new[0])]
    bat.step()                      # slot 0 decoding alone
    # 13-token prompt = 4 admission chunks, admitted while decoding
    ids.append(bat.submit(prompts[1], new[1]))
    bat.step()
    ids.append(bat.submit(prompts[2], new[2]))
    ids.append(bat.submit(prompts[3], new[3]))
    outs = bat.run()
    for rid, p, n in zip(ids, prompts, new):
        np.testing.assert_array_equal(outs[rid], _isolated(model, p, n))
    st = bat.stats()
    # every prompt token consumed exactly once, through the scan
    assert st["prefill_tokens"] == sum(len(p) for p in prompts)
    assert st["admit_chunks"] > 0 and st["decode_chunks"] > 0
    assert 0.0 < st["avg_occupancy"] <= 1.0
    assert st["tokens_produced"] >= sum(new)


def test_admission_no_recompile_per_prompt_length(model):
    """Prompt length never reaches a program shape: a workload of many
    DISTINCT lengths runs through exactly two compiled scans (the C=1
    decode program + the C=prefill_chunk admission program).  The
    budget is enforced by analysis.recompile_guard — on violation it
    raises with the offending avals instead of a bare count — which
    also records the model-level program-cache misses."""
    from paddle_tpu.analysis import recompile_guard
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                            chunk=4, prefill_chunk=4)
    rng = np.random.RandomState(13)
    ids = []
    for L in (3, 5, 7, 9, 11, 14, 17, 21):   # 8 distinct lengths
        ids.append(bat.submit(rng.randint(1, 128, L).astype(np.int32),
                              4))
    with recompile_guard(max_programs=2, match="serve_step") as g:
        outs = bat.run()
    assert sorted(outs) == sorted(ids)
    assert bat.compiled_programs == 2
    assert len([k for k in g.cache_builds
                if isinstance(k, tuple) and k
                and k[0] == "serve_step"]) <= 2
    # and the programs live on the MODEL: a second batcher of the same
    # shape reuses them — ZERO compiles and ZERO cache misses allowed
    bat2 = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                             chunk=4, prefill_chunk=4)
    bat2.submit(rng.randint(1, 128, 6).astype(np.int32), 4)
    with recompile_guard(max_programs=0, match="serve_step") as g2:
        bat2.run()
    assert g2.count == 0
    assert [k for k in g2.cache_builds
            if isinstance(k, tuple) and k
            and k[0] == "serve_step"] == []


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_a_boundary_s_slot_writes_are_one_program(model, layout, monkeypatch):
    """What eviction and admission write of the slots' device state is
    staged on the host and applied by ONE program of fixed shapes a chunk
    boundary, whatever the number of requests that ended and were admitted
    there (requests of one length end together: a dispatch a field a slot
    was the host's largest cost in such a burst), and that program compiles
    once."""
    from paddle_tpu.inference import serving
    calls = []
    sound = serving._slot_writes
    monkeypatch.setattr(
        serving, "_slot_writes",
        lambda state, masks, values: calls.append(
            {f: int(m.sum()) for f, m in masks.items()})
        or sound(state, masks, values))
    before = sound._cache_size()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (5, 9, 6, 12, 7, 10, 4, 8)]
    bat = ContinuousBatcher(model, max_batch_size=4, max_len=64, chunk=4,
                            kv_layout=layout)
    ids = [bat.submit(p, 6) for p in prompts[:4]]
    bat.step()
    assert len(calls) == 1 and calls[0]["prompt"] == 4 \
        and not bat._staged
    # equal lengths: the four end in one chunk and four replace them
    steps = 1
    while bat.active == 4 and not bat._finished:
        bat.step()
        steps += 1
    ids += [bat.submit(p, 6) for p in prompts[4:]]
    n = len(calls)
    bat.step()
    assert len(calls) == n + 1 and calls[-1]["prompt"] == 4 \
        and calls[-1]["done"] == 4
    outs = bat.run()
    assert len(calls) <= steps + 1 + bat.stats()["admit_chunks"] \
        + bat.stats()["decode_chunks"]
    assert sound._cache_size() - before <= 2     # admission; eviction alone
    for rid, prompt in zip(ids, prompts):
        np.testing.assert_array_equal(outs[rid], _isolated(model, prompt, 6))


# ---------------------------------------------------------------------------
# streaming token callbacks (ISSUE 11 satellite: the r13 leftover)


def test_streaming_callbacks_match_outputs(model):
    """Every request's streamed bursts concatenate to EXACTLY its
    final output (EOS-trimmed, max_new-capped), done fires exactly
    once per request, and the first burst lands BEFORE run() returns
    everything (TTFT is a chunk boundary, not batch completion)."""
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (5, 9, 4)]
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                            chunk=4, prefill_chunk=4)
    events = {}

    def cb(rid, toks, done):
        events.setdefault(rid, []).append((list(toks), done))

    rids = [bat.submit(p, 6, on_token=cb) for p in prompts]
    outs = bat.run()
    for rid in rids:
        bursts = events[rid]
        streamed = [t for ts, _ in bursts for t in ts]
        assert streamed == [int(t) for t in outs[rid]]
        assert [d for _, d in bursts].count(True) == 1
        assert bursts[-1][1] is True
        # chunked decode of 6 tokens through chunk=4 must take >1 burst
        assert len([b for b, _ in bursts if b]) >= 2


def test_streaming_never_delivers_past_eos(model):
    """A chunk can harvest tokens past EOS before the boundary evicts
    the slot — the stream must stop at EOS exactly like output()."""
    rng = np.random.RandomState(22)
    prompt = rng.randint(1, 128, 5).astype(np.int32)
    # find the greedy first token and use it as eos so the request
    # terminates mid-chunk
    first = int(_isolated(model, prompt, 1)[0])
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=64,
                            chunk=4, prefill_chunk=4,
                            eos_token_id=first)
    got = []
    rid = bat.submit(prompt, 8,
                     on_token=lambda r, t, d: got.extend(t))
    outs = bat.run()
    assert got == [int(t) for t in outs[rid]]
    assert got[-1] == first and len(got) == list(outs[rid]).index(
        first) + 1


def test_streaming_callback_errors_counted_not_fatal(model):
    rng = np.random.RandomState(23)
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=64,
                            chunk=4, prefill_chunk=4)

    def bad(rid, toks, done):
        raise RuntimeError("consumer went away")

    rid = bat.submit(rng.randint(1, 128, 5).astype(np.int32), 5,
                     on_token=bad)
    outs = bat.run()
    assert len(outs[rid]) == 5                 # batch unharmed
    assert bat.stats()["callback_errors"] >= 1


def test_streaming_requeue_no_duplicate_delivery(model):
    """A faulted-slot requeue discards the request's tokens for a
    bit-exact re-decode — the stream must NOT re-send the prefix the
    caller already has (delivered survives the requeue)."""
    from paddle_tpu.distributed import fault
    rng = np.random.RandomState(24)
    prompts = [rng.randint(1, 128, L).astype(np.int32) for L in (5, 7)]
    paddle.set_flags({"FLAGS_fault_injection":
                      "serve.decode:step=3:mode=error"})
    fault.reset()
    try:
        bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                                chunk=4, prefill_chunk=4)
        events = {}

        def cb(rid, toks, done):
            events.setdefault(rid, []).append((list(toks), done))

        rids = [bat.submit(p, 6, on_token=cb) for p in prompts]
        outs = bat.run()
        fired = fault.fired_counts().get("serve.decode", 0)
    finally:
        paddle.set_flags({"FLAGS_fault_injection": ""})
        fault.reset()
    assert fired >= 1 and bat.stats()["requests_requeued"] >= 1
    for rid in rids:
        if bat._finished[rid].shed:
            continue
        streamed = [t for ts, _ in events[rid] for t in ts]
        # no duplicates, full coverage: the stream is exactly the
        # final output once, even though the slot re-decoded
        assert streamed == [int(t) for t in outs[rid]]


def test_streaming_shed_after_fault_keeps_delivered_prefix(model):
    """A streaming request shed after repeated decode faults must not
    DISOWN tokens the consumer already holds: the delivered prefix
    survives as a partial result, so streamed == output even on the
    shed path (review fix: the no-retraction contract)."""
    from paddle_tpu.distributed import fault
    rng = np.random.RandomState(25)
    prompt = rng.randint(1, 128, 5).astype(np.int32)
    paddle.set_flags({"FLAGS_fault_injection":
                      "serve.decode:step=3:mode=error:times=*"})
    fault.reset()
    try:
        bat = ContinuousBatcher(model, max_batch_size=1, max_len=64,
                                chunk=4, prefill_chunk=4)
        events = []
        rid = bat.submit(prompt, 8,
                         on_token=lambda r, t, d: events.append(
                             (list(t), d)))
        outs = bat.run()
    finally:
        paddle.set_flags({"FLAGS_fault_injection": ""})
        fault.reset()
    req = bat._finished[rid]
    assert req.shed and req.partial
    streamed = [t for ts, _ in events for t in ts]
    assert streamed, "fault fired before any delivery — workload bug"
    assert streamed == [int(t) for t in outs[rid]]
    assert [d for _, d in events].count(True) == 1


def test_speculation_defaults_prefix_sharing_off(model):
    """Prefix sharing starves the DRAFT cache (skipped prefill chunks
    never reach it), so speculation defaults it off; explicit True
    warns but keeps both (review fix: silent accept-rate collapse)."""
    import warnings
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                            chunk=4, prefill_chunk=4,
                            kv_layout="paged", spec_tokens=2,
                            draft_model=model)
    assert bat.prefix_sharing is False
    plain = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                              chunk=4, prefill_chunk=4,
                              kv_layout="paged")
    assert plain.prefix_sharing is True
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        both = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                                 chunk=4, prefill_chunk=4,
                                 kv_layout="paged", spec_tokens=2,
                                 draft_model=model,
                                 prefix_sharing=True)
    assert both.prefix_sharing is True
    assert any("accept_rate" in str(x.message) for x in w)


def test_identity_draft_ships_no_second_param_list(model):
    """Self-speculation (draft IS the target) must not re-ship the
    whole state_dict per chunk — the target's swap covers the draft
    (review fix)."""
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                            chunk=4, prefill_chunk=4, spec_tokens=2,
                            draft_model=model)
    assert bat._draft_names == []
    assert bat._draft_param_vals() == []
    rng = np.random.RandomState(26)
    rid = bat.submit(rng.randint(1, 128, 5).astype(np.int32), 4)
    outs = bat.run()
    assert len(outs[rid]) == 4
