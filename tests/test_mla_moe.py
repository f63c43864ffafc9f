"""Latent attention (MLA), YaRN and the share-aware dropless expert layer,
against the benchmark's plain float32 reference
(benchmark/reference/mla_moe_f32.py) at a tiny size on the CPU: the decoder
`paddle_tpu.models.llama` builds from such a configuration, its paged
(latent-pool) path, `ContinuousBatcher` over it, and the pieces alone."""
import math
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops as tpu_ops
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.incubate.distributed.models import moe as moe_module
from paddle_tpu.incubate.distributed.models.moe import (
    MoELayer, SigmoidGate, StepCounters, dropless_experts, sorted_lengths)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import weights_mla_moe                               # noqa: E402
from drivers import mla_moe_program                  # noqa: E402
from reference import mla_moe_f32 as ref             # noqa: E402

SEED = 7


def tiny_cfg(**over):
    """The published configuration's keys at a tiny size: 1 dense layer and
    2 expert layers, 16 routed experts of which [4, 8) are held, top 3."""
    cfg = {"model_class": "paddle_tpu.models.llama",
           "model_type": "sarvam_mla", "torch_dtype": "float32",
           "hidden_size": 64, "intermediate_size": 128, "vocab_size": 256,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "num_attention_heads": 4, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "use_qk_norm": True, "rms_norm_eps": 1e-6, "rope_theta": 10000,
           "max_position_embeddings": 512,
           "rope_scaling": {"type": "deepseek_yarn", "factor": 40,
                            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                            "mscale_all_dim": 1,
                            "original_max_position_embeddings": 64},
           "moe_intermediate_size": 32, "num_experts": 4,
           "router_width": 16, "experts_held": [4, 8],
           "num_experts_per_tok": 3, "num_shared_experts": 1,
           "routed_scaling_factor": 2.5,
           "moe_router_enable_expert_bias": True,
           "tie_word_embeddings": False}
    cfg.update(over)
    return cfg


def ref_params(cfg, seed=SEED):
    return {n: v.astype(jnp.float32)
            for n, v in weights_mla_moe.leaves(seed, cfg, "float32")}


@pytest.fixture(scope="module")
def model():
    m = mla_moe_program.build_model(tiny_cfg(), SEED, "float32")
    m.eval()
    return m


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(0, 256, (2, 40)).astype(np.int32)


@pytest.fixture(scope="module")
def ref_logits(ids):
    cfg = tiny_cfg()
    return np.asarray(ref.forward_logits(ref_params(cfg), ids, cfg))


# -- (1) the full forward ---------------------------------------------------

def test_full_forward_logits_match_reference(model, ids, ref_logits):
    got = np.asarray(model(paddle.to_tensor(ids)).value)
    np.testing.assert_allclose(got, ref_logits, rtol=2e-4, atol=2e-4)


# -- (2) prefill then decode through the latent pool ------------------------

@pytest.mark.parametrize("split", [(16, 16, 8), (37, 1, 1, 1)])
def test_paged_prefill_then_decode_match_reference(model, ids, ref_logits,
                                                   split):
    """Chunks of a prompt and then single tokens through
    forward_cached_paged: every lane's logits are the full causal
    forward's (absorbed attention against the latent pool)."""
    cache = model.init_paged_cache(16, 8)
    assert set(cache) == {"kv"} and cache["kv"].shape == (16, 3, 8, 40)
    table = jnp.asarray(np.arange(1, 13, dtype=np.int32).reshape(2, 6))
    got, at = [], 0
    for n in split:
        lg, cache = model.forward_cached_paged(
            jnp.asarray(ids[:, at:at + n]), cache, table,
            jnp.full((2,), at, jnp.int32))
        got.append(np.asarray(lg))
        at += n
    np.testing.assert_allclose(np.concatenate(got, 1), ref_logits[:, :at],
                               rtol=2e-4, atol=2e-4)


def _served(model, prompts, want, slots=3, **knobs):
    bat = ContinuousBatcher(model, max_batch_size=slots, max_len=64, chunk=4,
                            prefill_chunk=8, page_size=8, **knobs)
    rids = [bat.submit(p, max_new_tokens=want) for p in prompts]
    out = bat.run()
    return bat, [np.asarray(out[r]) for r in rids]


def test_batcher_serves_reference_tokens(model, ids):
    """served_token_gap at tiny size: each served token's reference logit
    lies at most rounding below the reference's best (logits compared, not
    tokens), more requests than slots so that slots are reused."""
    cfg = tiny_cfg()
    prompts = [ids[i % 2, :9 + 3 * i] for i in range(5)]
    bat, served = _served(model, prompts, 7)
    assert bat.kv_layout == "paged" and set(bat._cache) == {"kv"}
    params = ref_params(cfg)
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 7
        seq = np.concatenate([prompt, tokens])[None]
        rows = np.asarray(ref.forward_logits(params, seq, cfg))[0]
        rows = rows[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
        gap = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
        assert gap.max() < 1e-3, gap


def test_prefix_sharing_and_page_copy_serve_the_latent_pool(model, ids):
    """The page programs index pages whatever a page holds: a shared prefix
    maps resident latent pages and the outputs stay those of isolation."""
    sys_p = ids[0, :24]
    prompts = [np.concatenate([sys_p, ids[1, :n]]) for n in (5, 9, 3)]
    alone = [_served(model, [p], 5, prefix_sharing=False)[1][0]
             for p in prompts]
    # one slot: each request finds its predecessor's full pages resident
    bat, shared = _served(model, prompts, 5, slots=1, prefix_sharing=True)
    assert bat.stats()["prefix_hit_tokens"] >= 2 * 24
    for a, b in zip(alone, shared):
        np.testing.assert_array_equal(a, b)


# -- (3) absorbed and expanded attention ------------------------------------

def test_absorbed_attention_equals_expanded(model, ids):
    """One layer's attention alone: the whole-sequence expanded form
    (k and v of every head built from the latent) against the paged
    absorbed form (queries carried into latent space), same numbers."""
    attn = model.llama.layers[1].self_attn
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, 64), jnp.float32)
    cos, sin = model.llama._rope_tables(24)
    expanded = np.asarray(attn._expanded(x, cos, sin))
    cache = {"kv": jnp.zeros((8, 3, 8, 40), jnp.float32)}
    table = jnp.asarray(np.arange(1, 7, dtype=np.int32).reshape(2, 3))
    pos = jnp.zeros((2,), jnp.int32)
    cos_b, sin_b = model.llama._rope_tables(
        24, pos[:, None] + jnp.arange(24)[None])
    absorbed, _ = attn.forward_cached_paged(x, cos_b, sin_b, cache, table,
                                            pos, 1)
    np.testing.assert_allclose(np.asarray(absorbed), expanded, rtol=1e-4,
                               atol=1e-5)


def test_latent_attention_walks_blocks(monkeypatch):
    """More than one block of the walk, ragged depths: the running softmax
    over blocks equals one softmax over each slot's rows."""
    monkeypatch.setattr(tpu_ops, "LATENT_BLOCK_ROWS", 16)
    rng = np.random.RandomState(5)
    B, C, h, R, r, ps = 3, 2, 4, 16, 8, 8
    pool = jnp.asarray(rng.randn(20, 2, ps, R + r), jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, 19)).reshape(3, 6)
                        .astype(np.int32))
    pos = jnp.asarray([37, 0, 20], jnp.int32)
    q_lat = jnp.asarray(rng.randn(B, C, h, R), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, C, h, r), jnp.float32)
    got = np.asarray(tpu_ops.latent_paged_attention(
        q_lat, q_rope, pool, table, pos, 1, 0.3))
    rows = np.asarray(pool)[np.asarray(table), 1].reshape(B, 6 * ps, R + r)
    q = np.concatenate([np.asarray(q_lat), np.asarray(q_rope)], -1)
    for b in range(B):
        for c in range(C):
            n = int(pos[b]) + c + 1
            s = np.einsum("hw,kw->hk", q[b, c], rows[b, :n]) * 0.3
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            np.testing.assert_allclose(got[b, c], p @ rows[b, :n, :R],
                                       rtol=1e-4, atol=1e-5)
    walked = tpu_ops.latent_pages_walked(np.asarray(pos), C, ps, 6)
    assert walked.tolist() == [6, 6, 6]         # 3 blocks of 2 pages, all
    assert tpu_ops.latent_pages_walked(np.array([3, 0]), 1, ps, 6) \
        .tolist() == [2, 2]


def _latent_case(dtype, C, seed=9):
    """One pool written by ops.latent_kv_update (pages of 8 rows, scattered
    over the pool), 8 slots of 6 pages: depths on both sides of a page's
    end (7, 8) and of a block's (15, 16, 17: blocks of 2 pages), a walk of
    three blocks (37), a slot at depth 0 and a FREE slot, depth 0 and a
    table of null pages only, which reads whatever the null page holds."""
    rng = np.random.RandomState(seed)
    B, h, R, r, ps, P_slot = 8, 4, 16, 8, 8, 6
    table = 1 + rng.permutation(B * P_slot).reshape(B, P_slot)
    table[1] = 0
    table = jnp.asarray(table, jnp.int32)
    pool = jnp.zeros((1 + B * P_slot, 2, ps, R + r), dtype)
    for r0 in range(0, P_slot * ps, 12):
        pool = tpu_ops.latent_kv_update(
            pool, table, jnp.full((B,), r0, jnp.int32),
            jnp.asarray(rng.randn(B, 12, R + r), dtype), 1)
    pos = jnp.asarray([0, 0, 7, 8, 15, 16, 17, 37], jnp.int32)
    return (jnp.asarray(rng.randn(B, C, h, R), dtype),
            jnp.asarray(rng.randn(B, C, h, r), dtype), pool, table, pos)


@pytest.mark.parametrize("C", [1, 3], ids=["decode", "lanes3"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_equals_the_xla_walk(dtype, C):
    """ops/pallas/latent_attention.py (interpret mode) against its twin on
    one pool: blocks of 2 pages, and at 3 lanes (12 query rows) two blocks
    of 8 query rows, the second half padding."""
    from paddle_tpu.ops.pallas import latent_attention as kernel
    args = _latent_case(jnp.dtype(dtype), C)
    want = np.asarray(tpu_ops.xla_latent_paged_attention(*args, 1, 0.3),
                      np.float32)
    for query_rows, key_rows in ((8, 16), (2048, 256)):
        got = kernel.latent_attention(*args, 1, 0.3, interpret=True,
                                      query_rows=query_rows,
                                      key_rows=key_rows)
        assert got.dtype == args[0].dtype and got.shape == args[0].shape
        # bf16: probabilities and outputs round at other partial sums
        tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
            else dict(rtol=2 ** -6, atol=2 ** -6)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, **tol)


def test_latent_kernel_work_list_names_the_live_blocks():
    """The grid's items: every (slot, query block, LIVE block) once, slot
    by slot, and as many as the walk's bound gives."""
    from paddle_tpu.ops.pallas import latent_attention as kernel
    from paddle_tpu.ops.pallas.paged_attention import pages_walked
    pos = np.array([0, 37, 16, 100], np.int32)       # 100: past the table
    n_blocks = -(-pages_walked(pos, 3, 8, 6) // 2)
    assert n_blocks.tolist() == [1, 3, 2, 3]
    items, slot, qb, block, last = (np.asarray(a) for a in kernel._work_list(
        jnp.asarray(pos), 3, 8, 6, 2, 2))
    assert items == 2 * n_blocks.sum() and len(slot) == 4 * 2 * 3
    want = [(b, q, i) for b in range(4) for q in range(2)
            for i in range(n_blocks[b])]
    assert list(zip(slot[:items], qb[:items], block[:items])) == want
    assert last[:items].tolist() == [
        pages_walked(pos, 3, 8, 6)[b] - 1 for b, _, _ in want]


def test_latent_kernel_refuses_what_mosaic_would():
    """supports: from the pool's shape, the rank and the dtype; on the chip
    a page must lie on whole sublane tiles and the latent on whole lanes."""
    from paddle_tpu.ops.pallas import latent_attention as kernel
    bf, f32 = jnp.bfloat16, jnp.float32
    assert kernel.supports((16385, 5, 16, 576), 512, bf, interpret=False)
    assert kernel.supports((9, 2, 8, 576), 512, f32, interpret=False)
    assert not kernel.supports((9, 2, 8, 576), 512, bf, interpret=False)
    assert not kernel.supports((9, 2, 16, 564), 500, bf, interpret=False)
    assert kernel.supports((9, 2, 8, 24), 16, f32, interpret=True)
    assert not kernel.supports((9, 2, 4, 8, 128), 64, bf, interpret=True)
    assert not kernel.supports((9, 2, 8, 24), 24, f32, interpret=True)
    args = _latent_case(jnp.float32, 1)
    with pytest.raises(ValueError, match="row width"):
        kernel.latent_attention(args[0], args[1][..., :4], *args[2:], 1, 0.3)
    with pytest.raises(ValueError, match="tiling needs"):
        kernel.latent_attention(*args, 1, 0.3, interpret=False)


def test_latent_walk_bound_follows_the_program(model, ids, monkeypatch):
    """kv_row_spec's bound is the K/V kernel's frontier where
    ops.latent_paged_attention takes the kernel and latent_pages_walked
    where it takes the XLA walk; the batcher's host replay counts with it,
    and the kernel serves the walk's tokens."""
    from paddle_tpu.ops.pallas import latent_attention as kernel
    from paddle_tpu.ops.pallas.paged_attention import pages_walked
    pos = np.array([37, 0, 20, 5])

    def bound():
        return model.kv_row_spec()["pages_walked"](pos, 2, 8, 6)
    np.testing.assert_array_equal(
        bound(), tpu_ops.latent_pages_walked(pos, 2, 8, 6))

    def serve():
        bat = ContinuousBatcher(model, max_batch_size=3, max_len=64, chunk=4,
                                prefill_chunk=8, page_size=8)
        rids = [bat.submit(ids[0, :30], max_new_tokens=3),
                bat.submit(ids[1, :5], max_new_tokens=3)]
        bat.step()
        first = bat.stats()
        out = bat.run()
        return first, [out[r] for r in rids], bat.pages_per_slot
    walked, tokens, per_slot = serve()
    # one block of 512 rows covers the table: all 3 slots walk all of it
    assert walked["kv_pages_walked"] == 3 * per_slot
    # the choice itself is the backend's and the shapes'; here the CPU is
    # told it has the kernel (interpret mode: supports takes any tiling)
    monkeypatch.setattr(tpu_ops, "_latent_kernel",
                        lambda shape, rank, dtype: kernel.supports(
                            shape, rank, dtype) and kernel)
    np.testing.assert_array_equal(bound(), pages_walked(pos, 2, 8, 6))
    live, kernel_tokens, _ = serve()
    # both occupied slots at depth 0 and the free slot: a page each
    assert live["kv_pages_live"] == 2 and live["kv_pages_walked"] == 3
    for a, b in zip(tokens, kernel_tokens):
        np.testing.assert_array_equal(a, b)


def test_walked_over_live_reads_the_dispatch_spans(monkeypatch):
    """benchmark/metrics/kv_pages_walked_over_live.py: the sums of the two
    ids over the traced chunks' serve.dispatch spans; nothing (and no
    error) without a trace, without such spans or without the ids."""
    import program_spans
    from metrics import kv_pages_walked_over_live as reader

    def span(name, **ids):
        return program_spans.Span(name, 0.0, 1.0, ids, None)
    programs = {
        "ids": [span("serve.step", chunk=1),
                span("serve.dispatch", kind="admit", chunk=1,
                     kv_pages_live=40, kv_pages_walked=130),
                span("serve.dispatch", kind="admit", chunk=2,
                     kv_pages_live=60, kv_pages_walked=170)],
        "no ids": [span("serve.dispatch", kind="admit", chunk=1)],
        "no spans": []}
    monkeypatch.setattr(
        program_spans, "for_cell", lambda trace, cell: trace and
        program_spans.Program(programs[trace], []))
    assert reader.read("ids", {}, {}) == 3.0
    for trace in (None, "no ids", "no spans"):
        assert reader.read(trace, {}, {}) is None


def test_rows_sorted_over_held_reads_the_counters():
    """benchmark/metrics/moe_rows_sorted_over_held.py: the window's rows of
    the sorted buffers over the assignments held; nothing (and no error)
    from a program without the counter, or with nothing held."""
    from metrics import moe_rows_sorted_over_held as reader
    assert reader.read(None, {"moe_rows_sorted": 4096,
                              "moe_assignments_held": 1280}, {}) == 3.2
    for counters in ({}, {"moe_assignments_held": 1280},
                     {"moe_rows_sorted": 4096, "moe_assignments_held": 0}):
        assert reader.read(None, counters, {}) is None


# -- (4) the shares add up --------------------------------------------------

@pytest.mark.parametrize("E, held, k", [(8, 2, 3), (128, 16, 8)],
                         ids=["four_of_2", "eight_of_16"])
def test_four_shares_add_up_to_the_uncut_layer(E, held, k):
    """E experts over E / held chips (8 over 4, and a 128-wide router over
    eight chips of 16, top 8: k-exaone-236b-a23b's layer): each share routes
    over all E, normalises over all the chosen and computes its own
    experts' part; the parts summed, the shared expert counted once, are
    the uncut reference layer."""
    d, f = 32, 16
    base = tiny_cfg(hidden_size=d, moe_intermediate_size=f, router_width=E,
                    num_experts_per_tok=k)
    rng = np.random.RandomState(11)
    whole = {"router": rng.randn(d, E) * d ** -0.5,
             "router_bias": rng.randn(E) * 0.05,
             "experts_w1": rng.randn(E, d, 2 * f) * d ** -0.5,
             "experts_w2": rng.randn(E, f, d) * f ** -0.5,
             "shared_w1": rng.randn(d, 2 * f) * d ** -0.5,
             "shared_w2": rng.randn(f, d) * f ** -0.5}
    whole = {n: jnp.asarray(v, jnp.float32) for n, v in whole.items()}
    x = jnp.asarray(rng.randn(2, 9, d), jnp.float32)
    mm = ref.weight_matmul("float32")
    uncut = ref.expert_layer(
        whole, x, dict(base, num_experts=E, experts_held=[0, E]), mm)
    shared = ref.swiglu(x, whole["shared_w1"], whole["shared_w2"], mm)
    total = jnp.zeros_like(x)
    for first in range(0, E, held):
        layer = MoELayer(d_model=d, d_hidden=f, num_experts=held,
                         gate="sigmoid", top_k=k, activation="swiglu",
                         experts_held=(first, held), router_width=E,
                         routed_scaling=2.5, router_bias=True,
                         shared_hidden=f)
        vals = {"gate": whole["router"], "bias": whole["router_bias"],
                "w1": whole["experts_w1"][first:first + held],
                "w2": whole["experts_w2"][first:first + held],
                "shared_w1": whole["shared_w1"],
                "shared_w2": whole["shared_w2"]}
        part = layer._dropless(x, vals)
        np.testing.assert_allclose(            # the share against ITS reference
            np.asarray(part), np.asarray(ref.expert_layer(
                dict(whole, experts_w1=vals["w1"], experts_w2=vals["w2"]), x,
                dict(base, num_experts=held,
                     experts_held=[first, first + held]),
                mm)), rtol=1e-4, atol=1e-5)
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)


# -- (5) the router ---------------------------------------------------------

def test_router_bias_moves_the_choice_not_the_weight():
    gate = SigmoidGate(8, 6, top_k=2, scaling=2.5, bias=True)
    w = jnp.asarray(np.random.RandomState(2).randn(8, 6), jnp.float32)
    x = jnp.asarray(np.random.RandomState(3).randn(5, 8), jnp.bfloat16)
    plain_i, plain_w, s = gate.route(x, w, jnp.zeros((6,)))
    assert plain_w.dtype == s.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(plain_w.sum(-1)), 2.5, rtol=1e-6)
    bias = jnp.zeros((6,)).at[4].set(10.0)       # expert 4 wins every choice
    top_i, top_w, s2 = gate.route(x, w, bias)
    assert (np.asarray(top_i)[:, 0] == 4).all()
    assert not np.array_equal(np.asarray(top_i), np.asarray(plain_i))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s2))
    chosen = np.take_along_axis(np.asarray(s), np.asarray(top_i), -1)
    np.testing.assert_allclose(                   # weights are of s, not s + b
        np.asarray(top_w), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(top_w.sum(-1)), 2.5, rtol=1e-6)


# -- (6) dropless under skew ------------------------------------------------

def _full_length_experts(tokens, topi, topw, w1, w2, act, first=0, valid=None,
                         b1=None, b2=None, counters=None):
    """The oracle: `dropless_experts` as it was before the sorted buffer
    followed the assignments held (PR 34's text, to the letter but for the
    counter's fourth sum): all S*k sorted rows gathered, multiplied,
    gathered back, selected and summed."""
    S, k = topi.shape
    count = w1.shape[0]
    local = topi.astype(jnp.int32) - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    with jax.named_scope("moe.dispatch"):
        key = jnp.where(held, local, count).reshape(-1)
        order = jnp.argsort(key, stable=True)
        # int32 whatever jax_enable_x64 says: the grouped product's
        # TPU lowering takes no 64-bit group sizes
        sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0,
                        promote_integers=False)
        rows = jnp.take(tokens, order // k, axis=0)          # [S*k, d]
    if counters is not None:
        n_valid = S if valid is None else jnp.sum(valid.astype(jnp.int32))
        counters.add(n_valid, k, sizes, S * k)
    with jax.named_scope("moe.experts"):
        expert = jnp.take(key, order)     # of each sorted row
        h = jax.lax.ragged_dot(rows, w1, sizes)
        if b1 is not None:
            h = h + jnp.take(b1[:, 0], expert, axis=0, mode="clip")
        out = jax.lax.ragged_dot(
            moe_module._expert_act(h, act).astype(rows.dtype), w2, sizes)
        if b2 is not None:
            out = out + jnp.take(b2[:, 0], expert, axis=0, mode="clip")
    with jax.named_scope("moe.combine"):
        back = jnp.zeros((S * k,), jnp.int32).at[order].set(
            jnp.arange(S * k, dtype=jnp.int32))
        mine = jnp.take(out, back, axis=0).reshape(S, k, -1)
        # rows past the last group are whatever the kernel left there
        mine = jnp.where(held[..., None], mine.astype(jnp.float32), 0.0)
        return jnp.sum(mine * topw[..., None].astype(jnp.float32), axis=1)


@pytest.fixture()
def short_rungs(monkeypatch):
    """The ladder at a test's sizes: a rung may be as short as one row."""
    monkeypatch.setattr(moe_module, "MIN_RUNG_ROWS", 1)


def _rung_of(lengths, n_held):
    return next(r for r in lengths if n_held <= r)


@pytest.mark.parametrize("chosen, held_rows", [
    ((5, 12), 16), ((1, 0), 0), ((5, 6), 32)],
    ids=["one held", "none held", "every one held"])
def test_dropless_under_skew(short_rungs, chosen, held_rows):
    """Every token to ONE held expert (no capacity drops a row), every token
    to absent experts (the share adds nothing), and every token to held
    experts ONLY: all S*k assignments are held, the longest rung serves
    them, nothing is dropped."""
    rng = np.random.RandomState(4)
    S, d, f, first = 16, 16, 8, 4
    assert sorted_lengths(2 * S) == (2, 8, 32)
    w1 = jnp.asarray(rng.randn(4, d, 2 * f), jnp.float32)
    w2 = jnp.asarray(rng.randn(4, f, d), jnp.float32)
    x = jnp.asarray(rng.randn(S, d), jnp.float32)
    topi = jnp.stack([jnp.full((S,), e) for e in chosen], 1)
    topw = jnp.asarray(rng.rand(S, 2), jnp.float32)
    counters = StepCounters()
    y = np.asarray(dropless_experts(x, topi, topw, w1, w2, "swiglu", first,
                                    counters=counters))
    want = np.zeros((S, d), np.float32)
    for j, e in enumerate(chosen):
        if first <= e < first + 4:
            gu = np.asarray(x) @ np.asarray(w1[e - first])
            act = gu[:, :f] / (1 + np.exp(-gu[:, :f])) * gu[:, f:]
            want += np.asarray(topw)[:, j:j + 1] * (
                act @ np.asarray(w2[e - first]))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    hit = held_rows // S
    assert counters.vector().tolist() == [
        2 * S, held_rows, hit, _rung_of((2, 8, 32), held_rows),
        S if hit else 0]


# -- (6b) the sorted buffer follows the assignments held ----------------------

def _routing(rng, S, k, first, count, width, n_held, with_valid):
    """topi [S, k] (distinct ids a token), valid [S] or None, and how many
    assignments are held by a valid token: `n_held` of them where a share
    is held (any number can be), the nearest multiple of k not below it
    where every expert is (a valid token's k are all held)."""
    every = count == width
    want = np.zeros((S, k), bool)
    if every:
        want[:-(-n_held // k)] = True
    else:
        want.reshape(-1)[rng.permutation(S * k)[:n_held]] = True
    valid = np.ones((S,), bool)
    if with_valid:
        # a share: up to a quarter of the tokens invalid, as many as leave
        # room for n_held assignments among the others
        valid = want.any(1) if every else np.arange(S) >= min(
            S // 4, (S * k - n_held) // k)
        if not every:
            # what an invalid token chose is held by nobody
            want[~valid] = False
            spare = np.flatnonzero(valid.repeat(k) & ~want.reshape(-1))
            short = n_held - int(want.sum())
            want.reshape(-1)[rng.permutation(spare)[:short]] = True
    absent = np.setdiff1d(np.arange(width), np.arange(first, first + count))
    topi = np.zeros((S, k), np.int64)
    for s in range(S):
        inside = first + rng.permutation(count)
        outside = rng.permutation(absent) if len(absent) else inside[::-1]
        picks = np.where(want[s], inside[:k], outside[:k])
        if not valid[s] and not every:
            picks = (first + rng.permutation(width - first))[:k]
        topi[s] = picks
    ids = topi - first
    held = int((((ids >= 0) & (ids < count)) & valid[:, None]).sum())
    return (jnp.asarray(topi, jnp.int32),
            jnp.asarray(valid) if with_valid else None, held)


S_LADDER, K_LADDER = 32, 8                 # 256 assignments: rungs 16, 64, 256


# every expert held and every lane valid, all S*k are held: one case
LADDER_CASES = [(n, share, with_valid)
                for n in (0, 1, 16, 17, 64, 65, 256)
                for share, with_valid in ((True, False), (True, True),
                                          (False, True))] + [(256, False,
                                                              False)]


@pytest.mark.parametrize("leaves", ["float32 biased", "bfloat16 biased",
                                    "bfloat16 plain"])
@pytest.mark.parametrize(
    "n_held, share, with_valid", LADDER_CASES,
    ids=[f"{n} held, {'a share' if share else 'every expert'}, "
         f"{'valid' if with_valid else 'all'} lanes"
         for n, share, with_valid in LADDER_CASES])
def test_laddered_experts_equal_the_full_length_program(
        short_rungs, n_held, share, with_valid, leaves):
    """`dropless_experts` over the rung it takes against the full-length
    program, at 0, 1, R and R + 1 assignments held for every rung R and at
    all S*k.  The longest rung is the oracle's own program: equal to the
    last bit.  The shortest sums a token's rows in another order (a
    scatter-add, not a sum over k; the middle one keeps the gather):
    within fp32 rounding of the sum.  With BIASES in bfloat16 the compiler
    also rounds `out + b2` where it fuses it into another pass: within ONE
    bfloat16 step of the largest output (0.39 of a step read here)."""
    S, k, d, f = S_LADDER, K_LADDER, 16, 8
    lengths = sorted_lengths(S * k)
    assert lengths == (16, 64, 256)
    first, count, width = (8, 8, 32) if share else (0, 8, 8)
    dtype, biased = leaves.split()
    rng = np.random.RandomState(n_held + 7 * share + 3 * with_valid)
    topi, valid, held = _routing(rng, S, k, first, count, width, n_held,
                                 with_valid)
    assert held == n_held or (not share and 0 <= held - n_held < k)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.randn(S, d), dt)
    w1 = jnp.asarray(rng.randn(count, d, 2 * f) * 0.3, dt)
    w2 = jnp.asarray(rng.randn(count, f, d) * 0.3, dt)
    b1 = b2 = None
    if biased == "biased":
        b1 = jnp.asarray(rng.randn(count, 1, 2 * f) * 0.1, dt)
        b2 = jnp.asarray(rng.randn(count, 1, d) * 0.1, dt)
    topw = jnp.asarray(rng.rand(S, k), jnp.float32)

    def run(fn):
        # one compiled program a side (eager, the oracle would run op by
        # op and the ladder's branches as programs: rounded differently)
        def program(*leaves):
            counters = StepCounters(valid)
            y = fn(*leaves[:5], "swiglu", first, valid, *leaves[5:],
                   counters)
            return y, counters.vector()
        y, counts = jax.jit(program)(x, topi, topw, w1, w2, b1, b2)
        return np.asarray(y), counts.tolist()
    got, got_c = run(dropless_experts)
    want, want_c = run(_full_length_experts)
    assert got.dtype == np.float32 and got.shape == (S, d)
    rung = _rung_of(lengths, held)
    if rung == S * k:
        np.testing.assert_array_equal(got, want)
    else:
        largest = max(np.abs(want).max(), 2.0 ** -100)
        step = 2.0 ** (np.floor(np.log2(largest)) - 7) \
            if leaves == "bfloat16 biased" else k * 2.0 ** -23 * largest
        np.testing.assert_allclose(got, want, rtol=0, atol=step)
    if held == 0:
        assert not got.any()
    assert got_c[1] == held and got_c[3] == rung and want_c[3] == S * k
    assert got_c[:3] + got_c[4:] == want_c[:3] + want_c[4:]


def test_grad_through_a_rung_equals_the_full_length_programs(short_rungs):
    """The conditional and the scatter-add differentiate: the gradient of a
    laddered call (a share, some lanes invalid, the middle rung) is the
    full-length program's."""
    S, k, d, f, first, count = S_LADDER, K_LADDER, 16, 8, 8, 8
    rng = np.random.RandomState(11)
    topi, valid, held = _routing(rng, S, k, first, count, 32, 40, True)
    assert _rung_of(sorted_lengths(S * k), held) == 64
    args = (jnp.asarray(rng.randn(S, d), jnp.float32),
            jnp.asarray(rng.rand(S, k), jnp.float32),
            jnp.asarray(rng.randn(count, d, 2 * f) * 0.3, jnp.float32),
            jnp.asarray(rng.randn(count, f, d) * 0.3, jnp.float32))
    mix = jnp.asarray(rng.randn(S, d), jnp.float32)

    def loss(fn):
        return lambda x, topw, w1, w2: jnp.sum(mix * fn(
            x, topi, topw, w1, w2, "swiglu", first, valid))
    got = jax.grad(loss(dropless_experts), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss(_full_length_experts), argnums=(0, 1, 2, 3))(*args)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(w).max()))


def test_every_expert_held_and_every_lane_valid_is_the_full_length_program(
        short_rungs, monkeypatch):
    """What the layer knows while tracing it decides while tracing: every
    expert of the router held and no lane invalid (training through the
    `naive` gate), every assignment is held: no conditional, and the jaxpr
    is the full-length program's to the letter.  A share, or a step with
    invalid lanes, takes the ladder."""
    paddle.seed(1)
    layer = MoELayer(d_model=8, d_hidden=16, num_experts=4, gate="naive",
                     top_k=2)
    vals = {k: t.value for k, t in layer._dropless_leaves().items()}
    x = jnp.asarray(np.random.RandomState(0).randn(2, 16, 8), jnp.float32)

    def text(**kw):
        return str(jax.make_jaxpr(lambda v: layer._dropless(v, vals, **kw))(x))
    plain = text()
    assert "cond" not in plain and "ragged_dot" in plain
    assert "cond[" in text(valid=jnp.ones((32,), bool))
    monkeypatch.setattr(
        moe_module, "_sorted_experts",
        lambda *args: _full_length_experts(*args[:-1]))
    assert text() == plain
    paddle.seed(1)
    share = MoELayer(d_model=8, d_hidden=16, num_experts=4, gate="naive",
                     top_k=2, experts_held=(0, 4), router_width=8)
    monkeypatch.undo()
    monkeypatch.setattr(moe_module, "MIN_RUNG_ROWS", 1)
    assert "cond[" in str(jax.make_jaxpr(lambda v: share._dropless(
        v, {k: t.value for k, t in share._dropless_leaves().items()}))(x))


def test_naive_gate_takes_the_sorted_dispatch():
    """The gate without a capacity no longer runs every expert on every
    token: its program is the grouped product over sorted rows, and it
    gives what a loop over the chosen experts gives."""
    paddle.seed(1)
    moe = MoELayer(d_model=8, d_hidden=16, num_experts=4, gate="naive",
                   top_k=2)
    x = np.random.RandomState(0).randn(2, 5, 8).astype(np.float32)
    out = np.asarray(moe(paddle.to_tensor(x)).value).reshape(10, 8)
    vals = {k: np.asarray(t.value) for k, t in moe._dropless_leaves().items()}
    tokens = x.reshape(10, 8)
    logits = tokens @ vals["gate"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(tokens)
    for s_, row in enumerate(tokens):
        top = np.argsort(-probs[s_])[:2]
        for e in top:
            h = np.asarray(jax.nn.gelu(row @ vals["w1"][e] + vals["b1"][e, 0]))
            want[s_] += probs[s_, e] / probs[s_, top].sum() \
                * (h @ vals["w2"][e] + vals["b2"][e, 0])
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    text = str(jax.make_jaxpr(lambda v: moe._dropless(
        v, {k: t.value for k, t in moe._dropless_leaves().items()}))(
            jnp.asarray(x)))
    assert "ragged_dot" in text


# -- (7) YaRN ---------------------------------------------------------------

def test_yarn_frequencies_and_mscale_closed_form():
    dim, base, factor, span = 64, 10000.0, 40.0, 4096
    got = np.asarray(tpu_ops.yarn_inv_freq(dim, base, factor, span, 32, 1))

    def corr(rot):
        return dim * math.log(span / (rot * 2 * math.pi)) / (2 * math.log(base))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    for i in range(dim // 2):
        plain = base ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        np.testing.assert_allclose(got[i], plain * (1 - ramp)
                                   + plain / factor * ramp, rtol=1e-6)
    np.testing.assert_allclose(got[:11], [base ** (-2 * i / dim)
                                          for i in range(11)], rtol=1e-6)
    np.testing.assert_allclose(got[23:] * factor,
                               [base ** (-2 * i / dim)
                                for i in range(23, 32)], rtol=1e-6)
    m = tpu_ops.yarn_mscale(factor, 1.0)
    assert abs(m - (0.1 * math.log(40.0) + 1)) < 1e-12 and round(m, 4) == 1.3689
    assert tpu_ops.yarn_mscale(1.0) == 1.0
    # the reference's own (numpy, float64) and the attention's scale
    np.testing.assert_allclose(got, np.asarray(ref.yarn_inv_freq(
        dim, base, {"factor": factor, "beta_fast": 32, "beta_slow": 1,
                    "original_max_position_embeddings": span})), rtol=1e-6)
    scaling = {"type": "deepseek_yarn", "factor": factor, "beta_fast": 32,
               "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
               "original_max_position_embeddings": span}
    cos, sin = tpu_ops.rope_cos_sin(4, dim, base, scaling=scaling)
    np.testing.assert_allclose(np.asarray(cos)[3, :32], np.cos(3 * got),
                               rtol=1e-5, atol=1e-6)    # unscaled: ratio 1
    with pytest.raises(ValueError, match="rope_scaling"):
        tpu_ops.rope_cos_sin(4, dim, base, scaling={"type": "linear"})


def test_attention_scale_carries_mscale_squared(model):
    m = 0.1 * math.log(40.0) + 1
    assert abs(model.llama.layers[0].self_attn.scale
               - 24 ** -0.5 * m * m) < 1e-9


# -- (8) the counters -------------------------------------------------------

def test_step_counters_equal_a_host_recount(short_rungs, model, ids):
    """moe_assignments_held / moe_expert_steps_hit / moe_rows_sorted / the
    largest load of one paged step with junk lanes, against numpy over the
    reference's routing of the same hidden states (the program's own, layer
    by layer); the rows sorted are the rung each layer took of the ladder
    over its 16 x 3 assignments."""
    cfg = tiny_cfg()
    x = jnp.asarray(ids[:, :8])
    n_valid = jnp.asarray([8, 3])
    valid = jnp.arange(8)[None] < n_valid[:, None]
    cache = model.init_paged_cache(8, 8)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    counters = StepCounters(valid)
    with paddle.no_grad():
        model.forward_cached_paged(x, cache, table,
                                   jnp.zeros((2,), jnp.int32), counters)
    # the layers' inputs, from the same model without counting
    seen = []
    for li in (1, 2):
        mlp = model.llama.layers[li].mlp
        orig = mlp._dropless

        def spy(xv, vals, valid=None, counters=None, orig=orig):
            seen.append((np.asarray(xv), {k: np.asarray(v)
                                          for k, v in vals.items()}))
            return orig(xv, vals, valid, counters)
        mlp._dropless = spy
    try:
        with paddle.no_grad():
            model.forward_cached_paged(x, cache, table,
                                       jnp.zeros((2,), jnp.int32))
    finally:
        for li in (1, 2):
            del model.llama.layers[li].mlp._dropless
    keep = np.asarray(valid).reshape(-1)
    lengths = sorted_lengths(16 * 3)
    assert lengths == (3, 12, 48)
    held = hit = biggest = rows = 0
    for xv, vals in seen:
        chosen, _ = ref.routing(jnp.asarray(xv.reshape(-1, 64)),
                                jnp.asarray(vals["gate"]),
                                jnp.asarray(vals["bias"]), cfg)
        chosen = np.asarray(chosen)[keep]
        loads = np.bincount(chosen.reshape(-1), minlength=16)[4:8]
        held, hit = held + loads.sum(), hit + (loads > 0).sum()
        biggest = max(biggest, loads.max())
        rows += _rung_of(lengths, loads.sum())
    assert counters.vector().tolist() == [11 * 3 * 2, held, hit, rows,
                                          biggest]
    assert 0 < held < 11 * 3 * 2 and held <= rows < 2 * 48


def test_batcher_counts_on_the_device_and_reports_in_stats(model, ids):
    from paddle_tpu import telemetry
    sink = telemetry.MemorySink()
    telemetry.add_sink(sink)
    try:
        bat, served = _served(model, [ids[0, :11], ids[1, :20]], 6)
    finally:
        telemetry.remove_sink(sink)
    st = bat.stats()
    # every valid token of every expert layer chose 3: prompt tokens once,
    # and each decode step's one token a slot
    work = st["prefill_tokens"] + st["decode_tokens"]
    assert st["moe_assignments"] == work * 3 * 2
    assert 0 < st["moe_assignments_held"] < st["moe_assignments"]
    assert 0 < st["moe_expert_steps_hit"] <= st["chunks"] * 4 * 2 * 4
    # the rungs taken, recounted: at these sizes (3 slots x 8 lanes x 3 an
    # admission step, 3 x 1 x 3 a decode step) the ladder has one rung, all
    # S*k, for each of the 2 expert layers of every step of every chunk
    assert sorted_lengths(3 * 8 * 3) == (72,)
    assert st["moe_rows_sorted"] == 2 * 3 * 3 * (
        st["admit_chunks"] * bat.admit_steps * bat.prefill_chunk
        + st["decode_chunks"] * bat.chunk)
    assert 0 < st["moe_tokens_per_expert_max"] <= 2 * 8
    assert model.step_counter_names() == tuple(
        k for k in st if k.startswith("moe_"))
    # a dense llama counts nothing and its program has no such output
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    dense = LlamaForCausalLM(llama_tiny_config())
    assert dense.step_counter_names() == ()
    assert not any(k.startswith("moe_") for k in ContinuousBatcher(
        dense, max_batch_size=1, max_len=16).stats())


# -- (9) what latent rows refuse, and what the batcher asks the model --------

def test_int8_kv_on_latent_rows_refuses(model):
    with pytest.raises(ValueError, match="int8 KV is not implemented for "
                                         "latent"):
        ContinuousBatcher(model, max_batch_size=1, max_len=16,
                          kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="paged pool only"):
        model.init_cache(1, 16)
    from paddle_tpu.ops.pallas import paged_attention as kernel
    assert not kernel.supports((16, 3, 8, 40), interpret=True)
    assert kernel.supports((16, 3, 4, 8, 128), interpret=False)


@pytest.mark.parametrize("latent", [True, False])
def test_paged_kv_bytes_takes_the_row_from_the_model(model, latent):
    """The allocation-free estimate equals a real instance for both row
    layouts, and the spec says what a row is."""
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
    m = model if latent else LlamaForCausalLM(llama_tiny_config())
    spec = m.kv_row_spec()
    assert spec["pools"] == ({"kv": (40,)} if latent
                             else {"k": (4, 32), "v": (4, 32)})
    for dt in ("float32",) if latent else ("float32", "int8"):
        bat = ContinuousBatcher(m, max_batch_size=2, max_len=32,
                                prefill_chunk=4, page_size=8, kv_dtype=dt)
        assert ContinuousBatcher.paged_kv_bytes(
            m, max_batch_size=2, max_len=32, prefill_chunk=4, page_size=8,
            kv_dtype=dt) == bat.kv_cache_bytes() == bat.stats()["kv_bytes"]
        assert bat.stats()["kv_dtype"] == dt


def test_kv_pages_live_and_walked_keep_their_meaning(model, ids):
    """live: pages up to each occupied slot's frontier; walked: what the
    latent walk reads — every slot to the deepest one's block."""
    bat = ContinuousBatcher(model, max_batch_size=3, max_len=64, chunk=4,
                            prefill_chunk=8, page_size=8)
    bat.submit(ids[0, :30], max_new_tokens=2)
    bat.submit(ids[1, :5], max_new_tokens=2)
    bat.step()                    # one admission chunk: 1 step of 8 lanes
    assert bat.admit_steps == 1
    st = bat.stats()
    # frontiers (pos + 7) // 8 + 1 with both slots at depth 0
    assert st["kv_pages_live"] == 1 + 1
    # one block of 512 rows covers the whole table: all 3 slots walk it
    assert st["kv_pages_walked"] == 3 * bat.pages_per_slot
    bat.step()       # slot 0 at depth 8; slot 1 at 5 rides the 8 lanes too
    st = bat.stats()
    assert st["kv_pages_live"] == 2 + (2 + 2)


def test_hand_off_moves_latent_pages(model, ids):
    """A prefill replica's finished prompt leaves as latent pages and a
    decode replica resumes it without recomputing prefill: the tokens are
    those of one unified batcher."""
    from paddle_tpu.inference import pack_handoff, unpack_handoff
    prompt = ids[0, :21]
    _, (alone,) = _served(model, [prompt], 6)
    knobs = dict(max_batch_size=2, max_len=64, chunk=4, prefill_chunk=8,
                 page_size=8)
    pre = ContinuousBatcher(model, role="prefill", **knobs)
    dec = ContinuousBatcher(model, role="decode", **knobs)
    rid = pre.submit(prompt, max_new_tokens=6)
    for _ in range(32):
        pre.step()
        if rid in pre._handoff_ready:
            break
    meta, data = unpack_handoff(pack_handoff(*pre.export_handoff(rid)))
    assert set(data) == {"kv"}
    lid = dec.import_handoff(meta, data)
    out = dec.run()
    np.testing.assert_array_equal(np.asarray(out[lid]), alone)
    assert dec.stats()["prefill_tokens"] == 0


def test_latent_pool_is_donated_through_both_step_programs(model):
    """The static sentinel's donation lint over the serve programs: the
    latent pool and every other carry alias their outputs (an unaliased
    pool would double its HBM), with the counters as one more output."""
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64, chunk=4,
                            prefill_chunk=8, page_size=8)
    report = bat.preflight()
    assert not report.errors and not report.warnings, report
