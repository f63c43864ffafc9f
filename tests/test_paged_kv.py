"""Paged KV cache with prefix sharing and int8 KV (ISSUE 7).

The contracts under test:

  * PARITY — the paged path (pool + page table + gather twin) is
    BIT-exact against the dense per-slot ring buffers at full-precision
    KV, and within tolerance at int8 KV; sharing a prefix changes no
    request's tokens (copy-on-write divergence included).
  * SHARING — an admission whose prompt prefix matches resident pages
    skips those prefill chunks entirely (prefill_tokens +
    prefix_hit_tokens == total prompt tokens, and the prefill work
    measurably drops vs the unshared run).
  * PRESSURE — a pool smaller than total demand evicts cached prefix
    pages LRU-first and defers admissions; every request still
    completes, still bit-exact.
  * r6 CONTRACTS stay pinned on the paged path: exactly 2 compiled
    step programs per batcher shape with and without prefix hits,
    every carry (pool, scales, page tables included) donated AND
    aliased, and a forced program-cache clear mid-life re-traces
    without disturbing counters (the r11 serve pattern).
  * KV-LAYOUT program-cache guard: toggling FLAGS_kv_cache_dtype or
    pool geometry mid-process can never replay a stale compiled
    program.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.paged_kv import PageAllocator
from paddle_tpu.models.llama import (LlamaForCausalLM,
                                     llama_tiny_config)


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


# ---------------------------------------------------------------------------
# parity: paged vs dense


def test_paged_matches_dense_bitexact(model):
    """Same staggered workload through a paged and a dense batcher:
    identical tokens, request for request (and both match isolation —
    the gather twin's masked rows exp to exactly 0)."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (4, 11, 7)]
    outs = {}
    for layout in ("paged", "dense"):
        bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                                chunk=4, prefill_chunk=4,
                                kv_layout=layout, page_size=8)
        rids = [bat.submit(p, 7) for p in prompts]
        got = bat.run()
        outs[layout] = [got[r] for r in rids]
        assert bat.stats()["kv_layout"] == layout
    for pg, dn, p in zip(outs["paged"], outs["dense"], prompts):
        np.testing.assert_array_equal(pg, dn)
        np.testing.assert_array_equal(pg, _isolated(model, p, 7))


def test_paged_bf16_kv_deterministic(model):
    """Explicit kv_dtype plumbing: a bf16 pool reports its dtype and
    two identical runs produce identical tokens.  (The bit-exactness
    contract binds at EQUAL KV dtypes — covered above, where the
    module model's bf16 compute dtype is also the KV dtype on both
    paths; an explicitly down-cast pool is a precision choice, not a
    parity bug.)"""
    rng = np.random.RandomState(5)
    p = rng.randint(1, 128, 9).astype(np.int32)
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                            chunk=4, prefill_chunk=4, page_size=8,
                            kv_dtype="bfloat16")
    rid = bat.submit(p, 6)
    out1 = bat.run()[rid]
    assert bat.stats()["kv_dtype"] == "bfloat16"
    bat2 = ContinuousBatcher(model, max_batch_size=1, max_len=32,
                             chunk=4, prefill_chunk=4, page_size=8,
                             kv_dtype="bfloat16")
    rid2 = bat2.submit(p, 6)
    np.testing.assert_array_equal(out1, bat2.run()[rid2])
    assert len(out1) == 6


def test_int8_kv_logit_parity(model):
    """int8 KV quantization: per-page per-head scales keep the decode
    logits within a few percent of the fp32 dense path (unit-level —
    token-level greedy flips are legal under quantization)."""
    import jax.numpy as jnp
    B, ps, P_slot = 2, 8, 6
    pt = jnp.asarray(
        np.arange(1, 1 + B * P_slot).reshape(B, P_slot), jnp.int32)
    dense = model.init_cache(B, P_slot * ps)
    paddle.set_flags({"FLAGS_kv_cache_dtype": "int8"})
    try:
        paged = model.init_paged_cache(1 + B * P_slot, ps)
        assert paged["k"].dtype == jnp.int8
        assert "k_scale" in paged and "v_scale" in paged
    finally:
        paddle.set_flags({"FLAGS_kv_cache_dtype": "auto"})
    rng = np.random.RandomState(0)
    pos = jnp.zeros((B,), jnp.int32)
    for C in (5, 3, 1, 1):
        ids = jnp.asarray(rng.randint(1, 128, (B, C)), jnp.int32)
        lg_d, dense = model.forward_cached(ids, dense, pos)
        lg_p, paged = model.forward_cached_paged(ids, paged, pt, pos)
        ref = np.asarray(lg_d, np.float32)
        got = np.asarray(lg_p, np.float32)
        rel = np.max(np.abs(ref - got)) / (np.max(np.abs(ref)) + 1e-9)
        assert rel < 0.1, f"int8 KV drifted {rel:.3f} at C={C}"
        pos = pos + C


def test_int8_kv_halves_pool_bytes(model):
    """The int8 pool reports (just over) half the KV HBM of the
    full-precision pool of identical geometry — scales are the only
    overhead."""
    kw = dict(max_batch_size=2, max_len=32, chunk=4, prefill_chunk=4,
              page_size=8)
    full = ContinuousBatcher(model, kv_dtype="float32", **kw)
    quant = ContinuousBatcher(model, kv_dtype="int8", **kw)
    rng = np.random.RandomState(1)
    p = rng.randint(1, 128, 6).astype(np.int32)
    for bat in (full, quant):
        rid = bat.submit(p, 5)
        out = bat.run()[rid]
        assert len(out) == 5
    b_full = full.stats()["kv_bytes"]
    b_q = quant.stats()["kv_bytes"]
    assert b_q < 0.3 * b_full, (b_q, b_full)  # int8 vs fp32: ~4x
    # the allocation-free estimator (bench's sizing probe) matches the
    # real instance byte for byte
    for bat, dt in ((full, "float32"), (quant, "int8")):
        est = ContinuousBatcher.paged_kv_bytes(
            model, max_batch_size=2, max_len=32, prefill_chunk=4,
            page_size=8, kv_dtype=dt)
        assert est == bat.kv_cache_bytes(), (dt, est,
                                             bat.kv_cache_bytes())


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write


def test_prefix_sharing_skips_prefill(model):
    """Staggered requests sharing a long system prompt: every output
    still bit-matches isolation, the shared pages are prefilled ONCE
    (prefill_tokens + prefix_hit_tokens == total prompt tokens), and
    the prefill work drops vs the sharing-disabled run."""
    rng = np.random.RandomState(3)
    sys_p = rng.randint(1, 128, 24).astype(np.int32)  # 3 pages at ps=8
    tails = [rng.randint(1, 128, L).astype(np.int32)
             for L in (5, 9, 3, 7)]
    prompts = [np.concatenate([sys_p, t]) for t in tails]
    total = sum(len(p) for p in prompts)

    stats = {}
    for sharing in (True, False):
        bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                                chunk=4, prefill_chunk=4, page_size=8,
                                prefix_sharing=sharing)
        rids = [bat.submit(prompts[0], 6)]
        bat.step()
        rids += [bat.submit(p, 6) for p in prompts[1:]]
        outs = bat.run()
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(outs[rid],
                                          _isolated(model, p, 6))
        stats[sharing] = bat.stats()
    shared, unshared = stats[True], stats[False]
    assert shared["prefix_hit_tokens"] > 0
    assert shared["prefix_hit_tokens"] + shared["prefill_tokens"] \
        == total
    assert unshared["prefix_hit_tokens"] == 0
    assert shared["prefill_tokens"] < unshared["prefill_tokens"]
    # fewer admission-mode chunks: skipped prefill is skipped WORK
    assert shared["admit_chunks"] <= unshared["admit_chunks"]


def test_cow_divergence_matches_unshared(model):
    """Two requests sharing a prefix that diverges MID-page: the
    second maps the full pages, copy-on-writes the divergence page,
    and must produce exactly the tokens of an unshared run."""
    rng = np.random.RandomState(9)
    base = rng.randint(1, 128, 20).astype(np.int32)   # 2.5 pages (ps=8)
    a = np.concatenate([base, rng.randint(1, 128, 4).astype(np.int32)])
    b = np.concatenate([base, rng.randint(1, 128, 6).astype(np.int32)])
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=64,
                            chunk=4, prefill_chunk=4, page_size=8)
    r1, r2 = bat.submit(a, 5), bat.submit(b, 5)
    outs = bat.run()
    np.testing.assert_array_equal(outs[r1], _isolated(model, a, 5))
    np.testing.assert_array_equal(outs[r2], _isolated(model, b, 5))
    st = bat.stats()
    # b matched 2 full pages (16 tokens) + 4 rows of page 2 via CoW
    assert st["prefix_hit_tokens"] == 20, st["prefix_hit_tokens"]


def test_whole_prompt_resident_still_emits(model):
    """A prompt IDENTICAL to a resident one shares everything except
    the final token (the match is capped at plen-1): the last token
    must prefill so its logit seeds the first sampled token."""
    rng = np.random.RandomState(2)
    p = rng.randint(1, 128, 17).astype(np.int32)   # 2 pages + 1 row
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=48,
                            chunk=4, prefill_chunk=4, page_size=8)
    r1, r2 = bat.submit(p, 6), bat.submit(p, 6)
    outs = bat.run()
    want = _isolated(model, p, 6)
    np.testing.assert_array_equal(outs[r1], want)
    np.testing.assert_array_equal(outs[r2], want)
    assert bat.stats()["prefix_hit_tokens"] == 16


# ---------------------------------------------------------------------------
# pool pressure


def test_eviction_under_pressure_completes_all(model):
    """Pool smaller than total demand: cached prefix pages are evicted
    LRU-first to serve new admissions, further admissions defer to
    later boundaries, and every request still completes bit-exact."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 128, L).astype(np.int32)
               for L in (17, 19, 18, 21)]
    # each request needs ~5-6 pages (ps=8); 11 usable pages force both
    # cached-page eviction and deferred admission across the workload
    bat = ContinuousBatcher(model, max_batch_size=4, max_len=48,
                            chunk=4, prefill_chunk=4, page_size=8,
                            num_pages=12)
    rids = [bat.submit(p, 5) for p in prompts]
    outs = bat.run()
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid],
                                      _isolated(model, p, 5))
    st = bat.stats()
    assert st["evictions"] > 0, st
    # at drain nothing is MAPPED — whatever stays resident is cached
    # prefix pages (refcount 0, reclaimable)
    assert st["kv_pages_used"] == st["kv_pages_cached"], st


def test_pool_too_small_raises(model):
    rng = np.random.RandomState(1)
    bat = ContinuousBatcher(model, max_batch_size=1, max_len=48,
                            chunk=4, prefill_chunk=4, page_size=8,
                            num_pages=3)
    bat.submit(rng.randint(1, 128, 20).astype(np.int32), 8)
    with pytest.raises(RuntimeError, match="cannot ever hold"):
        bat.run()


# ---------------------------------------------------------------------------
# r6 contracts on the paged path


def test_paged_two_programs_with_prefix_hits(model):
    """recompile_guard pins the 2-programs-per-shape contract across
    admissions WITH and WITHOUT prefix hits, and across a forced
    program-cache clear mid-run (the r11 serve pattern): counters
    survive, the re-trace is bounded, prompt length never recompiles."""
    from paddle_tpu.analysis import recompile_guard
    rng = np.random.RandomState(13)
    sys_p = rng.randint(1, 128, 16).astype(np.int32)
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64,
                            chunk=4, prefill_chunk=4, page_size=8)
    rids = []
    for L in (3, 7, 11, 6):                    # no-hit admissions
        rids.append(bat.submit(
            rng.randint(1, 128, L).astype(np.int32), 4))
    for L in (5, 9):                           # prefix-hit admissions
        rids.append(bat.submit(np.concatenate(
            [sys_p, rng.randint(1, 128, L).astype(np.int32)]), 4))
    with recompile_guard(max_programs=2, match="serve_step") as g:
        outs = bat.run()
    assert sorted(outs) == sorted(rids)
    assert bat.compiled_programs == 2
    assert len([k for k in g.cache_builds
                if isinstance(k, tuple) and k
                and k[0] == "serve_step"]) <= 2

    # forced program-cache clear mid-life: the next chunk re-traces
    # (bounded at the same 2 programs) and stats survive
    before = bat.stats()
    model.__dict__.get("_gen_compiled", {}).clear()
    r_more = bat.submit(np.concatenate(
        [sys_p, rng.randint(1, 128, 4).astype(np.int32)]), 4)
    with recompile_guard(max_programs=2, match="serve_step"):
        outs2 = bat.run()
    after = bat.stats()
    assert len(outs2[r_more]) == 4
    assert bat.compiled_programs == 2
    assert after["chunks"] > before["chunks"]
    assert after["prefix_hit_tokens"] >= before["prefix_hit_tokens"]


def test_paged_carries_all_donated(model):
    """lint_donation over the lowered step programs: the page pool,
    the scales, the page table and every other carry must be aliased
    to an output — a silently-undonated pool would double serving's
    dominant HBM buffer every chunk."""
    from paddle_tpu.analysis import lint_donation
    for kv_dtype in (None, "int8"):
        bat = ContinuousBatcher(model, max_batch_size=2, max_len=32,
                                chunk=4, prefill_chunk=4, page_size=8,
                                kv_dtype=kv_dtype)
        for mixed in (False, True):
            findings = lint_donation(bat.lower_step(mixed=mixed))
            assert not findings, [f.message for f in findings]


# ---------------------------------------------------------------------------
# KV-layout program-cache guard (ISSUE 7 small fix)


def test_program_cache_keys_guard_kv_layout(model):
    """Toggling FLAGS_kv_cache_dtype (or pool geometry) mid-process
    must re-build cached programs, never replay stale ones: the
    program cache key carries the KV-layout fingerprint."""
    from paddle_tpu.inference.generation import (
        _model_program_cache, _kv_layout_fingerprint)
    builds = []

    def build():
        builds.append(1)
        return lambda: None

    key = ("kvguard_probe", 1, 2)
    _model_program_cache(model, key, build)
    _model_program_cache(model, key, build)
    assert len(builds) == 1                    # warm hit
    fp0 = _kv_layout_fingerprint()
    paddle.set_flags({"FLAGS_kv_cache_dtype": "int8"})
    try:
        assert _kv_layout_fingerprint() != fp0
        _model_program_cache(model, key, build)
        assert len(builds) == 2                # layout flip rebuilds
        paddle.set_flags({"FLAGS_kv_page_size": 32})
        _model_program_cache(model, key, build)
        assert len(builds) == 3                # geometry flip rebuilds
    finally:
        paddle.set_flags({"FLAGS_kv_cache_dtype": "auto",
                          "FLAGS_kv_page_size": 16})
    _model_program_cache(model, key, build)
    assert len(builds) == 3                    # restored layout: warm hit


# ---------------------------------------------------------------------------
# host-side allocator / trie units


def test_allocator_refcounts_and_lru_eviction():
    al = PageAllocator(num_pages=6, page_size=4)
    assert al.pages_free == 5
    a = al.alloc(2)
    b = al.alloc(2)
    assert al.pages_used == 4 and al.pages_free == 1
    # register a's pages as prompt chunks and cache them
    n1 = al.register_chunk(None, [1, 2, 3, 4], a[0])
    n2 = al.register_chunk(n1, [5, 6, 7, 8], a[1])
    al.complete_node(n1), al.complete_node(n2)
    for p in a:
        al.release_page(p)
    assert al.pages_cached == 2 and al.pages_free == 1
    # pressure: allocating 3 must evict BOTH cached pages (leaf first)
    c = al.alloc(3)
    assert c is not None and al.evictions == 2
    assert al.pages_cached == 0
    # beyond capacity: fails cleanly
    assert al.alloc(2) is None
    for p in b + c:
        al.release_page(p)
    assert al.pages_free == 5


def test_admit_never_evicts_its_own_matched_pages():
    """Regression: under pressure, admit() must pin its matched prefix
    pages BEFORE allocating privates — otherwise the eviction loop can
    reclaim those very pages and recycle them as this plan's privates
    (a silent shared/private alias corrupting the shared K/V)."""
    al = PageAllocator(num_pages=6, page_size=4)     # 5 usable
    sys_p = list(range(10, 18))                      # exactly 2 pages
    plan_a = al.admit(sys_p + [1, 2], covered_pages=3)
    for n in plan_a.nodes:
        al.complete_node(n)
    al.release_plan(plan_a)
    assert al.pages_cached == 2 and al.pages_free == 3
    held = al.alloc(2)                               # free -> 1
    # B matches both cached pages and needs 2 privates with only 1
    # free: the ONLY reclaimable pages are B's own match — admission
    # must defer, not cannibalize itself
    plan_b = al.admit(sys_p + [9, 9, 9, 9], covered_pages=4)
    assert plan_b is None
    # and the pins rolled back: the match is still cached, nothing
    # leaked a refcount
    assert al.pages_cached == 2 and al.pages_free == 1
    for p in held:
        al.release_page(p)
    # with pressure relieved the same admission succeeds, alias-free
    plan_b = al.admit(sys_p + [9, 9, 9, 9], covered_pages=4)
    assert plan_b is not None and plan_b.n_shared_pages == 2
    assert len(set(plan_b.pages)) == len(plan_b.pages)


def test_cow_source_pinned_until_copy():
    """The CoW source page arrives pinned from admit() (pressure must
    not reclaim it before the device copy); releasing it afterwards
    returns it to the cached state."""
    al = PageAllocator(num_pages=8, page_size=4)
    prompt = list(range(20, 30))                     # 2 full pages + 2
    plan_a = al.admit(prompt, covered_pages=3)
    for n in plan_a.nodes:
        al.complete_node(n)
    al.release_plan(plan_a)
    # diverge mid-page-2: full match page 0, CoW from page 1's node
    plan_b = al.admit(prompt[:6] + [99, 98, 97, 96], covered_pages=3)
    assert plan_b is not None and plan_b.cow is not None
    src, dst = plan_b.cow
    assert src not in plan_b.pages and dst == plan_b.pages[1]
    assert al._ref.get(src, 0) == 1                  # pinned for copy
    al.release_page(src)                             # batcher, post-copy
    assert al._ref.get(src, 0) == 0
    al.release_plan(plan_b)


def test_allocator_match_and_partial():
    al = PageAllocator(num_pages=8, page_size=4)
    prompt = list(range(10, 22))              # 3 pages
    plan = al.admit(prompt, covered_pages=4)
    assert plan is not None and plan.shared_tokens == 0
    assert len(plan.nodes) == 3
    for n in plan.nodes:
        al.complete_node(n)
    # full + partial match: same 8 tokens, then diverge mid-page
    probe = prompt[:9] + [99, 98, 97]
    full, partial = al.match_prefix(probe, max_share=len(probe) - 1)
    assert len(full) == 2
    assert partial is not None and partial[1] == 1
    # incomplete nodes never match
    al2 = PageAllocator(num_pages=8, page_size=4)
    plan2 = al2.admit(prompt, covered_pages=4)
    full2, partial2 = al2.match_prefix(prompt, max_share=8)
    assert not full2 and partial2 is None
    al2.release_plan(plan2)
    assert al2.pages_free == 7                # pending nodes dropped


# ---------------------------------------------------------------------------
# eviction-under-pressure interleaved with copy-on-write (ISSUE 9
# satellite): the CoW source sits between trie match and device copy
# while the SAME admission's private allocation is evicting under
# pressure — the pinned source must survive and never alias a private


def test_cow_admission_evicts_others_never_its_source():
    """An admission that full-matches one chain, CoW-matches its next
    page, and needs more privates than the free list holds: the
    eviction loop must reclaim OTHER cached chains and must never
    touch the (pinned) CoW source or the matched page — the window
    between match_prefix and the device copy is exactly where a
    reclaimed source would silently alias a private page."""
    al = PageAllocator(num_pages=7, page_size=4)          # 6 usable
    # chain A: two complete cached pages (the future match + source)
    plan_a = al.admit(list(range(10, 18)) + [1, 2], covered_pages=3)
    for n in plan_a.nodes:
        al.complete_node(n)
    al.release_plan(plan_a)
    # chain C: two more complete cached pages (the eviction victims)
    plan_c = al.admit(list(range(50, 58)) + [3, 4], covered_pages=3)
    for n in plan_c.nodes:
        al.complete_node(n)
    al.release_plan(plan_c)
    assert al.pages_cached == 4 and al.pages_free == 2
    # D: full-match A page 1, diverge mid A page 2 (m=2), 3 privates
    # needed with only 2 free -> pressure evicts from chain C
    evicted_before = al.evictions
    plan_d = al.admit([10, 11, 12, 13, 14, 15, 99, 98, 97, 96],
                      covered_pages=4)
    assert plan_d is not None and plan_d.cow is not None
    src, dst = plan_d.cow
    assert al.evictions > evicted_before
    assert al.cow_copies == 1
    # the pinned source survived the eviction sweep and is not among
    # the plan's pages (it will be copied into dst, a fresh private)
    assert src not in plan_d.pages and dst == plan_d.pages[1]
    assert al._node_of.get(src) is not None
    assert al._ref.get(src, 0) == 1                       # copy pin
    assert len(set(plan_d.pages)) == len(plan_d.pages)
    # matched tokens: one full page + the 2-token partial
    assert plan_d.shared_tokens == 4 + 2
    al.release_page(src)                                  # post-copy
    al.release_plan(plan_d)


def test_cow_admissions_interleave_pressure_bitexact(model):
    """Batcher-level: staggered admissions where a CoW divergence and
    pool-pressure evictions interleave — every request still completes
    bit-exact (the copied page's content equals what an unshared
    prefill would have written, even though its source was under
    eviction pressure while mapped)."""
    rng = np.random.RandomState(21)
    sys_p = rng.randint(1, 128, 12).astype(np.int32)   # 1.5 pages @8
    tails = [rng.randint(1, 128, 4).astype(np.int32) for _ in range(2)]
    fresh = rng.randint(1, 128, 16).astype(np.int32)
    prompts = [np.concatenate([sys_p, tails[0]]),      # seeds the trie
               np.concatenate([sys_p, tails[1]]),      # CoW at page 2
               fresh]                                  # needs evictions
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=48,
                            chunk=4, prefill_chunk=8, page_size=8,
                            num_pages=8)
    rids = [bat.submit(prompts[0], 6)]
    bat.step()
    rids += [bat.submit(prompts[1], 6), bat.submit(prompts[2], 6)]
    outs = bat.run()
    st = bat.stats()
    assert st["cow_copies"] >= 1, st
    assert st["evictions"] >= 1, st
    assert st["prefix_hit_tokens"] > 0, st
    for rid, p in zip(rids, prompts):
        np.testing.assert_array_equal(outs[rid],
                                      _isolated(model, p, 6))
    assert st["requests_submitted"] == st["requests_completed"] == 3


# ---------------------------------------------------------------------------
# the page walk's counters (ISSUE 26)


def _walk_oracle(slots, width, steps, ps, per_slot, max_len):
    """One chunk's pages, slot by slot and step by step, in plain python:
    `slots` is [(pos, prompt_len, prefilling)] — what step_core does to a
    live slot's depth, and ceil((pos + width) / ps) pages a step."""
    total = 0
    for pos, plen, filling in slots:
        for _ in range(steps):
            total += min(-(-(pos + width) // ps), per_slot)
            if pos >= max_len - 1:
                continue
            if filling:
                pos += min(width, plen - pos)
                filling = pos < plen
            else:
                pos += 1
    return total


@pytest.mark.parametrize("spans", [False, True], ids=["stats", "span_ids"])
def test_kv_page_counters_follow_the_walk(model, spans):
    """stats()["kv_pages_walked"] is the kernel's own bound
    (pages_walked) summed over every scan step of every chunk, replayed
    on the host: equal to a slot-by-slot oracle for an admission chunk
    and for a decode chunk, equal to kv_pages_live while every slot is
    occupied, and a free slot at depth 0 costs one page a step.  The
    `serve.dispatch` span carries each chunk's pair as ids."""
    from paddle_tpu import telemetry
    rng = np.random.RandomState(5)
    ps, C, A, K, max_len = 8, 4, 2, 4, 64
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=max_len,
                            chunk=K, prefill_chunk=C, admit_steps=A,
                            kv_layout="paged", page_size=ps)
    lens = (11, 6)
    for n, new in zip(lens, (5, 16)):   # slot 0 frees long before slot 1
        bat.submit(rng.randint(1, 128, n).astype(np.int32), new)
    sink = telemetry.add_sink(telemetry.MemorySink()) if spans else None
    try:
        want = 0
        while True:
            before = bat.stats()
            pos = [int(p) for p in bat._pos_host]
            filling = [bool(m) for m in bat._mode_host]
            free = [r is None for r in bat._slots]
            bat.step()
            after = bat.stats()
            if after["chunks"] == before["chunks"]:
                break
            if before["chunks"] == 0:
                # the first chunk admits both requests at depth 0
                pos, filling, free = [0, 0], [True, True], [False, False]
            elif any(free):
                break       # a request finished: the walk below is over
            mixed = after["admit_chunks"] > before["admit_chunks"]
            got = after["kv_pages_walked"] - before["kv_pages_walked"]
            assert got == _walk_oracle(
                list(zip(pos, lens, filling)), C if mixed else 1,
                A if mixed else K, ps, bat.pages_per_slot, max_len)
            want += got
            assert after["kv_pages_live"] == after["kv_pages_walked"] \
                == want
        assert want > 0 and before["decode_chunks"] > 0
        # one slot free at depth 0: it walks one page a decode step,
        # and holds nothing live
        bat.run()
        st = bat.stats()
        assert st["kv_pages_walked"] > st["kv_pages_live"] > want
        if spans:
            ids = [r for r in sink.records
                   if r.get("event") == "serve.dispatch"]
            assert len(ids) == st["chunks"]
            assert sum(r["kv_pages_walked"] for r in ids) \
                == st["kv_pages_walked"]
            assert sum(r["kv_pages_live"] for r in ids) \
                == st["kv_pages_live"]
    finally:
        if sink is not None:
            telemetry.remove_sink(sink)
