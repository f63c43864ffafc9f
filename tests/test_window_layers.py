"""Sliding-window layers beside full ones (a pool a KIND of layer), against a
naive mask and against the benchmark's plain float32 reference
(benchmark/reference/window_moe_f32.py) at a tiny size on the CPU: each
attention op with a `window`, the Pallas kernel in interpret mode against its
twin over a slot's ring, the decoder `paddle_tpu.models.llama` builds from
such a configuration, its paged path, `ContinuousBatcher` over it, the
planted faults of `benchmark/control_window.py`, the counts and the
refusals."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops as tpu_ops
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.ops.pallas import paged_attention as paged_kernel

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import weights_exaone_moe                            # noqa: E402
from drivers import exaone_moe_program               # noqa: E402
from reference import window_moe_f32 as ref          # noqa: E402

SEED = 11
W = 8       # the tiny model's window


def tiny_cfg(**over):
    """The published configuration's keys at a tiny size: layers L L L G L
    (window 8), layer 0 dense and 4 expert layers, 16 routed experts of
    which [4, 8) are held, top 3."""
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    cfg = {"model_class": "paddle_tpu.models.llama",
           "model_type": "exaone_moe", "torch_dtype": "float32",
           "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
           "num_hidden_layers": 5, "first_k_dense_replace": 1,
           "layer_types": kinds * 2, "sliding_window": W,
           "sliding_windows": [W, W, W, 0] * 2,
           "mlp_layer_types": ["dense"] + ["sparse"] * 7,
           "num_attention_heads": 8, "num_key_value_heads": 2,
           "head_dim": 16, "rms_norm_eps": 1e-5,
           "rope_parameters": {"rope_theta": 1000000,
                               "rope_type": "default"},
           "max_position_embeddings": 512,
           "moe_intermediate_size": 32, "num_experts": 4,
           "router_width": 16, "experts_held": [4, 8],
           "num_experts_per_tok": 3, "num_shared_experts": 1,
           "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
           "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
           "num_nextn_predict_layers": 0, "tie_word_embeddings": False}
    cfg.update(over)
    return cfg


def ref_params(cfg, seed=SEED):
    return {n: v.astype(jnp.float32)
            for n, v in weights_exaone_moe.leaves(seed, cfg, "float32")}


@pytest.fixture(scope="module")
def model():
    m = exaone_moe_program.build_model(tiny_cfg(), SEED, "float32")
    m.eval()
    return m


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(0, 256, (2, 48)).astype(np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    cfg = tiny_cfg()
    return np.asarray(ref.forward_logits(ref_params(cfg), ids, cfg))


# -- (1) each attention op with a window, against a naive mask ---------------

def naive_attention(q, k_rows, v_rows, pos, window):
    """q [B, C, h, d]; k_rows / v_rows [B, S, n_kv, d] by ABSOLUTE position;
    lane c of slot b at position pos[b] + c sees rows j <= it with
    it - j < window (window 0: all of them)."""
    B, C, h, d = q.shape
    g = h // k_rows.shape[2]
    out = np.zeros(q.shape, np.float32)
    for b in range(B):
        for c in range(C):
            i = int(pos[b]) + c
            lo = max(0, i - window + 1) if window else 0
            for n in range(h):
                kk, vv = k_rows[b, lo:i + 1, n // g], v_rows[b, lo:i + 1, n // g]
                s = kk @ q[b, c, n] / np.sqrt(d)
                p = np.exp(s - s.max())
                out[b, c, n] = (p / p.sum()) @ vv
    return out


def _rows(rng, B, S, n_kv=2, d=16):
    return (rng.randn(B, S, n_kv, d).astype(np.float32),
            rng.randn(B, S, n_kv, d).astype(np.float32))


def _ring_pool(k_rows, v_rows, upto, C, ps, window, layers=2, layer=1):
    """The window pool after every slot b streamed rows 0 .. upto[b] + C - 1
    through ops.paged_kv_update in steps of C lanes (the last step at
    upto[b]): (k_pool, v_pool, table, ring pages)."""
    B, _, n_kv, d = k_rows.shape
    ring = tpu_ops.ring_pages(window, C, ps)
    table = jnp.asarray(np.arange(B * ring, dtype=np.int32).reshape(B, ring))
    kp = jnp.zeros((B * ring, layers, n_kv, ps, d), jnp.float32)
    vp = jnp.zeros_like(kp)
    pos = np.zeros(B, np.int64)
    while True:
        at = np.minimum(pos, upto)
        rows = [np.stack([a[b, at[b]:at[b] + C] for b in range(B)])
                for a in (k_rows, v_rows)]
        kp, vp, _, _ = tpu_ops.paged_kv_update(
            kp, vp, None, None, table, jnp.asarray(at, jnp.int32),
            jnp.asarray(rows[0]), jnp.asarray(rows[1]), layer, ring=True)
        if (pos >= upto).all():
            return kp, vp, table, ring
        pos = pos + C


@pytest.mark.parametrize("op", ["attention", "xla_attention"])
def test_uncached_attention_with_a_window(op):
    rng = np.random.RandomState(1)
    k, v = _rows(rng, 2, 29)
    q = rng.randn(2, 29, 8, 16).astype(np.float32)
    got = getattr(tpu_ops, op)(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), causal=True, window=W)
    want = naive_attention(q, k, v, np.zeros(2, int), W)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("vector_pos", [False, True])
def test_cached_attention_with_a_window(vector_pos):
    """The dense buffer holds the whole depth: the ring that never wraps."""
    rng = np.random.RandomState(2)
    k, v = _rows(rng, 2, 40)
    q = rng.randn(2, 5, 8, 16).astype(np.float32)
    pos = np.array([3, 31]) if vector_pos else np.array([20, 20])
    got = tpu_ops.cached_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(pos if vector_pos else 20, jnp.int32), window=W)
    np.testing.assert_allclose(np.asarray(got),
                               naive_attention(q, k, v, pos, W),
                               rtol=1e-5, atol=1e-5)


# depths below, at and far above the window; C 5 and 16 cross a page of 4 or
# 8 rows, and at the far depths the step's write crosses the ring's wrap
DEPTHS = [np.array([0, 3, W - 1]), np.array([W, W + 1, 2 * W + 3]),
          np.array([5, 61, 150])]


@pytest.mark.parametrize("C", [1, 5, 16])
@pytest.mark.parametrize("depth", range(3))
@pytest.mark.parametrize("op", ["xla_paged_attention", "paged_attention",
                                "kernel"])
def test_paged_attention_over_a_ring(op, depth, C):
    """The twin and the Pallas kernel (interpret mode) against the naive
    mask, the rows written through ops.paged_kv_update's ring form."""
    rng = np.random.RandomState(3 + C)
    pos, ps = DEPTHS[depth], 4
    k, v = _rows(rng, 3, 200)
    kp, vp, table, ring = _ring_pool(k, v, pos, C, ps, W)
    assert ring == -(-(W + C - 1) // ps) + 1
    q = jnp.asarray(rng.randn(3, C, 8, 16).astype(np.float32))
    args = (q, kp, vp, table, jnp.asarray(pos, jnp.int32), 1)
    if op == "kernel":
        got = paged_kernel.paged_attention(*args, window=W, interpret=True)
    else:
        got = getattr(tpu_ops, op)(*args, window=W)
    np.testing.assert_allclose(
        np.asarray(got), naive_attention(np.asarray(q), k, v, pos, W),
        rtol=1e-5, atol=1e-5)


def test_a_whole_depth_table_is_the_ring_that_never_wraps():
    """`window` over a table of the slot's whole depth: the kernel's walk
    starts at the window's first page, the rows are the same."""
    rng = np.random.RandomState(5)
    k, v = _rows(rng, 2, 64)
    ps, C, pos = 8, 3, np.array([50, 9])
    table = jnp.asarray(1 + np.arange(16, dtype=np.int32).reshape(2, 8))
    paged = [jnp.asarray(a.reshape(2, 8, ps, 2, 16).transpose(0, 1, 3, 2, 4)
                         .reshape(16, 1, 2, ps, 16)) for a in (k, v)]
    pools = [jnp.concatenate([jnp.zeros_like(p[:1]), p]) for p in paged]
    q = jnp.asarray(rng.randn(2, C, 8, 16).astype(np.float32))
    want = naive_attention(np.asarray(q), k, v, pos, W)
    for got in (tpu_ops.xla_paged_attention(q, *pools, table,
                                            jnp.asarray(pos), 0, window=W),
                paged_kernel.paged_attention(q, *pools, table,
                                             jnp.asarray(pos), 0, window=W,
                                             interpret=True)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)


def test_pages_walked_with_a_window_counts_the_pages_that_hold_its_rows():
    ps, ring = 4, 7
    for pos in (0, 3, 7, 8, 21, 150):
        for C in (1, 5, 16):
            pages = {r // ps for r in range(max(pos - W + 1, 0), pos + C)}
            assert paged_kernel.pages_walked(
                np.array([pos]), C, ps, ring, W)[0] == len(pages)
            assert paged_kernel.first_page(np.array([pos]), ps, W)[0] \
                == min(pages)
    # no window: the bound as it always was
    assert paged_kernel.pages_walked(np.array([21]), 5, 4, 99)[0] == 7


def test_a_window_refuses_the_block_mask_and_a_short_ring():
    q = jnp.zeros((1, 4, 8, 16))
    with pytest.raises(ValueError, match="block"):
        tpu_ops.xla_attention(q, q[:, :, :2], q[:, :, :2], causal=True,
                              block_length=4, window=W)
    pool = jnp.zeros((2, 1, 2, 4, 16))
    with pytest.raises(ValueError, match="straddles"):
        paged_kernel.paged_attention(
            q, pool, pool, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,)), 0,
            window=W, interpret=True)


# -- (2) the model against the reference ------------------------------------

def test_full_forward_logits_match_reference(model, ids, ref_logits):
    """The uncached forward takes the window through the XLA attention.
    Tolerance: float32 on both sides, sums in another order."""
    got = np.asarray(model(paddle.to_tensor(ids)).value)
    np.testing.assert_allclose(got, ref_logits, rtol=2e-4, atol=2e-4)


def _kind_cache(model, slots, C, ps, pages=32):
    ring = tpu_ops.ring_pages(W, C, ps)
    return model.init_paged_cache(pages, ps, window_pages=slots * ring), ring


@pytest.mark.parametrize("split", [(16, 16, 8, 1, 1), (8, 5, 16, 11, 1, 1)])
def test_paged_prefill_then_decode_match_reference(model, ids, ref_logits,
                                                   split):
    """Chunks of a prompt and then single tokens through
    forward_cached_paged, contexts over 3 windows deep: every lane's logits
    are the full causal forward's; the window layers' rows lie in a ring of
    7 pages of 4 rows (28 rows for a depth of 42)."""
    ps = 4
    cache, ring = _kind_cache(model, 2, 16, ps)
    assert ring == 7
    assert cache["k"].shape == (32, 1, 2, ps, 16)          # 1 full layer
    assert cache["k_window"].shape == (2 * ring, 4, 2, ps, 16)
    table = jnp.asarray(np.arange(1, 25, dtype=np.int32).reshape(2, 12))
    got, at = [], 0
    for n in split:
        lg, cache = model.forward_cached_paged(
            jnp.asarray(ids[:, at:at + n]), cache, table,
            jnp.full((2,), at, jnp.int32))
        got.append(np.asarray(lg))
        at += n
    assert at > 3 * W
    np.testing.assert_allclose(np.concatenate(got, 1), ref_logits[:, :at],
                               rtol=2e-4, atol=2e-4)


def test_two_slots_at_different_depths_share_a_step(model, ids, ref_logits):
    """Slot 0 is 30 rows deep and decodes, slot 1 starts its prompt: one
    step serves both, each against its own ring."""
    ps = 4
    cache, _ = _kind_cache(model, 2, 8, ps)
    table = jnp.asarray(np.arange(1, 25, dtype=np.int32).reshape(2, 12))
    for at in (0, 8, 16):       # slot 0 alone; slot 1's lanes are junk
        _, cache = model.forward_cached_paged(
            jnp.asarray(ids[:, at:at + 8]), cache, table,
            jnp.asarray([at, 0], jnp.int32))
    x = np.concatenate([ids[:1, 24:32], ids[1:, :8]])
    lg, cache = model.forward_cached_paged(
        jnp.asarray(x), cache, table, jnp.asarray([24, 0], jnp.int32))
    np.testing.assert_allclose(np.asarray(lg[0]), ref_logits[0, 24:32],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lg[1]), ref_logits[1, :8],
                               rtol=2e-4, atol=2e-4)


def _served(model, prompts, want, slots=2, **knobs):
    knobs = dict(dict(max_len=96, chunk=4, prefill_chunk=8, page_size=4),
                 **knobs)
    bat = ContinuousBatcher(model, max_batch_size=slots, **knobs)
    rids = [bat.submit(p, max_new_tokens=want) for p in prompts]
    out = bat.run()
    return bat, [np.asarray(out[r]) for r in rids]


def test_batcher_serves_reference_tokens(model, ids):
    """Chunked prefill, then decode, through ContinuousBatcher's two step
    programs: each served token's REFERENCE logit lies at most rounding
    below the reference's best (logits compared, not tokens; float32 both
    sides, so 1e-3 is sums in another order).  Prompts of 5 to 40 tokens and
    12 more each, more requests than slots, so that slots at different
    depths stand side by side and a ring is reused by the next request."""
    cfg = tiny_cfg()
    prompts = [ids[i % 2, :n] for i, n in enumerate((40, 13, 29, 5, 33))]
    bat, served = _served(model, prompts, 12)
    assert set(bat._cache) == {"k", "v", "k_window", "v_window"}
    assert bat.ring_pages == 5 and not bat.prefix_sharing
    params = ref_params(cfg)
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 12
        seq = np.concatenate([prompt, tokens])[None]
        rows = np.asarray(ref.forward_logits(params, seq, cfg))[0]
        rows = rows[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        gap = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
        assert gap.max() < 1e-3, gap


def test_batcher_with_the_kernel_serves_the_twins_tokens(model, ids,
                                                         monkeypatch):
    """The same requests with the Pallas kernel (interpret mode) in both
    step programs: the tokens the twin served."""
    prompts = [ids[0, :21], ids[1, :9]]
    _, twin = _served(model, prompts, 6)
    monkeypatch.setattr(tpu_ops, "_on_tpu", lambda: True)
    m = exaone_moe_program.build_model(tiny_cfg(), SEED, "float32")
    m.eval()      # a model of its own: the step programs are cached on it
    _, kernel = _served(m, prompts, 6)
    for a, b in zip(twin, kernel):
        np.testing.assert_array_equal(a, b)


# -- (3) the planted faults of benchmark/control_window.py ------------------

@pytest.mark.parametrize("fault", ref.FAULTS)
def test_every_planted_fault_moves_the_logits(fault, ids, ref_logits):
    """Each departure from the equations moves the reference's own logits
    by far more than the program may differ from the sound reference (2e-4):
    in float32 on the CPU no fault hides, the window one row short
    included."""
    cfg = tiny_cfg()
    off = np.asarray(ref.forward_logits(ref_params(cfg), ids, cfg,
                                        fault=fault))
    assert np.abs(off - ref_logits).max() > 5e-3, fault
    # before any lane is W deep the window faults are no faults
    if fault.startswith("window"):
        np.testing.assert_allclose(off[:, :W - 1], ref_logits[:, :W - 1],
                                   rtol=1e-5, atol=1e-5)


def test_control_window_names_the_references_faults():
    import control_window
    assert control_window.fault_names() == ref.FAULTS and len(ref.FAULTS) == 9


# -- (4) the cache's two kinds: bytes, counts, spans, refusals --------------

def test_kv_row_spec_names_the_kinds(model):
    spec = model.kv_row_spec()
    kinds = spec["kinds"]
    assert kinds["window"]["layers"] == (0, 1, 2, 4)
    assert kinds["full"]["layers"] == (3,)
    assert kinds["window"]["window"] == W and kinds["full"]["window"] == 0
    assert kinds["window"]["rows"](8) == W + 7
    assert set(spec["pools"]) == {"k", "v", "k_window", "v_window"}
    dense = paddle.models.llama.LlamaForCausalLM(
        paddle.models.llama.llama_tiny_config())
    assert "kinds" not in dense.kv_row_spec()


def test_paged_kv_bytes_equals_a_real_instance_with_two_kinds(model):
    knobs = dict(max_batch_size=3, max_len=96, prefill_chunk=8, page_size=4)
    bat = ContinuousBatcher(model, chunk=4, **knobs)
    want = ContinuousBatcher.paged_kv_bytes(model, **knobs)
    assert want == bat.kv_cache_bytes()
    st = bat.stats()
    # a row is 2 (k, v) x 2 heads x 16 x 4 bytes = 256 B a layer
    assert st["kv_pool_bytes"] == {
        "full": bat.num_pages * 4 * 1 * 256,
        "window": 3 * bat.ring_pages * 4 * 4 * 256}
    assert sum(st["kv_pool_bytes"].values()) + bat._page_table.nbytes == want
    # one pool over all five layers would hold five times the full pool
    assert want < 0.45 * (5 * st["kv_pool_bytes"]["full"])


def test_page_counts_by_kind(model, ids):
    """walked_full: the full layer's call to each slot's frontier;
    walked_window: the four window layers' walk from the window's first
    page; needed: the pages that hold the rows the VALID lanes may attend."""
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=96, chunk=4,
                            prefill_chunk=8, page_size=4)
    bat.submit(ids[0, :40], max_new_tokens=2)
    bat.submit(ids[1, :3], max_new_tokens=2)
    assert bat.admit_steps == 1
    bat.step()                  # both at depth 0, 8 lanes: 2 pages each
    st = bat.stats()
    assert st["kv_pages_live"] == st["kv_pages_walked"] == 4
    assert st["kv_pages_walked_full"] == 1 * 4
    assert st["kv_pages_walked_window"] == 4 * 4
    # slot 0 needs rows 0..7 (2 pages), slot 1 rows 0..2 (1 page)
    assert st["kv_pages_window_needed"] == 4 * 3
    for _ in range(4):
        bat.step()              # slot 0 reaches depth 40
    before = bat.stats()
    bat.step()
    st = bat.stats()
    # a DECODE chunk of 4 steps (the short request is done): slot 0 decodes
    # at depth 40..43, walks frontier pages 11 each step in the full layer,
    # rows pos - 7 .. pos in the window layers (3 pages: 33..40 straddles)
    assert bat._slots[1] is None
    full = st["kv_pages_walked_full"] - before["kv_pages_walked_full"]
    win = st["kv_pages_walked_window"] - before["kv_pages_walked_window"]
    need = st["kv_pages_window_needed"] - before["kv_pages_window_needed"]
    assert full >= 4 * 11 and win <= 4 * 4 * (3 + 1) and need <= win
    assert win < 0.5 * 4 * full


def test_dispatch_span_carries_the_counts_by_kind(model, ids, monkeypatch):
    from paddle_tpu.inference import serving
    seen = []
    real = serving.ContinuousBatcher._phase

    def spy(self, name, **ids_):
        if name == "dispatch":
            seen.append(ids_)
        return real(self, name, **ids_)
    monkeypatch.setattr(serving.ContinuousBatcher, "_phase", spy)
    bat, _ = _served(model, [ids[0, :20]], 3)
    assert seen and all(set(serving.KIND_PAGE_COUNTS) <= set(s) for s in seen)
    st = bat.stats()
    for name in serving.KIND_PAGE_COUNTS:
        assert sum(s[name] for s in seen) == st[name]


def test_scopes_name_the_kind_of_each_layer(model, ids):
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=96, chunk=4,
                            prefill_chunk=8, page_size=4)
    text = bat.lower_step(mixed=True).as_text(debug_info=True)
    for i, kind in enumerate("wwwfw"):
        scope = "attn.window" if kind == "w" else "attn.full"
        assert f"llama.layer{i}/{scope}" in text
        other = "attn.full" if kind == "w" else "attn.window"
        assert f"llama.layer{i}/{other}" not in text


@pytest.mark.parametrize("knobs, error, says", [
    (dict(prefix_sharing=True), ValueError, "prefix_sharing"),
    (dict(role="prefill"), ValueError, "hand-off"),
    (dict(spec_tokens=2, draft_layers=1), ValueError, "spec_tokens"),
    (dict(kv_dtype="int8"), ValueError, "int8"),
    (dict(kv_layout="dense"), TypeError, "paged"),
])
def test_what_a_model_with_rings_is_refused(model, knobs, error, says):
    with pytest.raises(error, match=says):
        ContinuousBatcher(model, max_batch_size=2, max_len=64, **knobs)


def test_hand_off_is_refused_after_construction_too(model):
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64)
    assert bat.prefix_sharing is False
    with pytest.raises(ValueError, match="hand-off"):
        bat.set_role("decode")
    with pytest.raises(ValueError, match="rings"):
        bat.import_handoff({}, {})


def test_both_pools_are_donated_through_both_step_programs(model):
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64, chunk=4,
                            prefill_chunk=8, page_size=4)
    report = bat.preflight()
    assert not report.errors and not report.warnings, report
