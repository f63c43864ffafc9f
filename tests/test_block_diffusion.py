"""Generation by diffusion over blocks (SDAR's `sdar_moe`), against the
benchmark's plain float32 reference
(benchmark/reference/block_diffusion_moe_f32.py) at a tiny size on the CPU:
the decoder `paddle_tpu.models.llama` builds from such a configuration
(explicit head size, per-head q/k norms, a softmax top-k router over all the
experts, the block-causal mask), its paged path, the block schedule
`ContinuousBatcher` runs for it, and the pieces alone."""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops as tpu_ops
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.inference.serving import DIFFUSION_COUNTERS
from paddle_tpu.incubate.distributed.models.moe import MoELayer
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     llama_tiny_config)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import weights_sdar_moe                                   # noqa: E402
from drivers import sdar_moe_program                      # noqa: E402
from reference import block_diffusion_moe_f32 as ref      # noqa: E402
from reference.decoder_f32 import weight_matmul           # noqa: E402

SEED, L, S, MASK = 7, 4, 4, 255


def tiny_cfg(**over):
    """The published configuration's keys at a tiny size: 2 layers, 8 query
    heads of 16 on 2 kv heads (hidden / heads would be 8), 8 experts, top
    3, blocks of 4 in 4 denoising steps."""
    cfg = {"model_class": "paddle_tpu.models.llama", "model_type": "sdar_moe",
           "torch_dtype": "float32", "hidden_size": 64,
           "intermediate_size": 128, "vocab_size": 256,
           "num_hidden_layers": 2, "num_attention_heads": 8,
           "num_key_value_heads": 2, "head_dim": 16, "attention_bias": False,
           "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
           "max_position_embeddings": 512, "moe_intermediate_size": 32,
           "num_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": True,
           "decoder_sparse_step": 1, "mlp_only_layers": [],
           "use_sliding_window": False, "tie_word_embeddings": False,
           "block_length": L, "denoising_steps": S, "mask_token_id": MASK,
           "confidence_threshold": 0.9,
           "remasking_strategy": "low_confidence_dynamic",
           "sampling": "greedy"}
    cfg.update(over)
    return cfg


def ref_params(cfg, seed=SEED):
    return {n: v.astype(jnp.float32)
            for n, v in weights_sdar_moe.leaves(seed, cfg, "float32")}


@pytest.fixture(scope="module")
def model():
    m = sdar_moe_program.build_model(tiny_cfg(), SEED, "float32")
    m.eval()
    return m


@pytest.fixture(scope="module")
def params():
    return ref_params(tiny_cfg())


@pytest.fixture(scope="module")
def ids():
    return np.random.RandomState(0).randint(0, 250, (2, 40)).astype(np.int32)


def _batcher(model, slots=3, **knobs):
    knobs = dict(dict(max_len=64, chunk=4, prefill_chunk=8, page_size=8),
                 **knobs)
    return ContinuousBatcher(model, max_batch_size=slots, **knobs)


def _served(model, prompts, want, **knobs):
    """(batcher, [(tokens, passes)]) of `prompts` through one batcher."""
    bat = _batcher(model, **knobs)
    rids = [bat.submit(p, max_new_tokens=want) for p in prompts]
    out = bat.run()
    return bat, [(np.asarray(out[r]), bat._finished[r].output_passes())
                 for r in rids]


def _assert_serves_generate(model, params, prompts, want, cfg=None, **knobs):
    bat, served = _served(model, prompts, want, **knobs)
    for prompt, (tokens, passes) in zip(prompts, served):
        g = ref.generate(params, prompt, want, cfg or tiny_cfg())
        np.testing.assert_array_equal(tokens, g["tokens"])
        np.testing.assert_array_equal(passes, g["passes"])
    return bat


# -- (1) the model -----------------------------------------------------------

def test_full_forward_under_the_block_mask_matches_reference(model, params,
                                                             ids):
    got = np.asarray(model(paddle.to_tensor(ids[:, :22])).value)
    for r in range(2):
        want = np.asarray(ref.forward_logits(params, ids[r, :22], tiny_cfg()))
        np.testing.assert_allclose(got[r], want, rtol=2e-4, atol=2e-4)
    # and the mask is the block's, not the causal one: position 0 reads
    # position 3
    moved = ids[:1, :22].copy()
    moved[0, 3] += 1
    again = np.asarray(model(paddle.to_tensor(moved)).value)
    assert np.abs(again[0, 0] - got[0, 0]).max() > 1e-3
    assert np.abs(again[0, 4:] - got[0, 4:]).max() > 1e-3


def test_paged_prefill_then_block_passes_match_reference(model, params, ids):
    """Whole prompt blocks through forward_cached_paged, then every denoise
    pass of every block and its commit pass: each pass's logits are the
    reference's full forward's at that pass, so a denoise pass's rows are
    overwritten and only the commit pass's stay."""
    cfg, prompt = tiny_cfg(), ids[0, :10]
    g = ref.generate(params, prompt, 10, cfg)
    plan = ref.replay_plan(prompt, g["tokens"], g["passes"], cfg, pad_to=32)
    cache = model.init_paged_cache(8, 8)
    table = jnp.asarray(np.arange(1, 7, dtype=np.int32)[None])
    _, cache = model.forward_cached_paged(
        jnp.asarray(prompt[None, :8]), cache, table,
        jnp.zeros((1,), jnp.int32))
    seq = np.concatenate([prompt, g["tokens"]])
    assert [(b, p) for b, p, _ in g["pass_logits"][:len(plan["states"])]] \
        == plan["states"]
    for s, (b, p) in enumerate(plan["states"]):
        at = jnp.full((1,), b * L, jnp.int32)
        block = plan["ids"][32 + s * L:32 + (s + 1) * L]
        lg, cache = model.forward_cached_paged(
            jnp.asarray(block[None]), cache, table, at, head_lanes=L)
        np.testing.assert_allclose(np.asarray(lg[0]), g["pass_logits"][s][2],
                                   rtol=2e-4, atol=2e-4)
        if s + 1 == len(plan["states"]) or plan["states"][s + 1][0] != b:
            _, cache = model.forward_cached_paged(
                jnp.asarray(seq[None, b * L:(b + 1) * L]), cache, table, at)


def test_explicit_head_dim_and_qk_norm_build_and_match():
    """A causal model with head_dim != hidden / heads and per-head q/k
    norms: the leaves have the stated shapes, the norms take part, and
    the full forward, the paged path and the dense path agree."""
    paddle.seed(3)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64, num_attention_heads=4,
                            num_key_value_heads=2, vocab_size=64,
                            head_dim=16, use_qk_norm=True, dtype="float32")
    assert cfg.attn_head_dim == 16 \
        and llama_tiny_config().attn_head_dim == 32
    # a copy of a config that states no head size still derives it
    import dataclasses
    wide = dataclasses.replace(llama_tiny_config(), hidden_size=256)
    assert wide.head_dim is None and wide.attn_head_dim == 64
    assert dataclasses.replace(cfg, hidden_size=128).attn_head_dim == 16
    m = LlamaForCausalLM(cfg)
    m.eval()
    attn = m.llama.layers[0].self_attn
    assert attn.q_proj.shape == [32, 64] and attn.k_proj.shape == [32, 32]
    assert attn.o_proj.shape == [64, 32] and attn.q_norm.shape == [16]
    rs = np.random.RandomState(1)
    attn.q_norm._value = jnp.asarray(1 + 0.3 * rs.randn(16), jnp.float32)
    attn.k_norm._value = jnp.asarray(1 + 0.3 * rs.randn(16), jnp.float32)
    x = rs.randint(0, 64, (2, 12)).astype(np.int32)
    full = np.asarray(m(paddle.to_tensor(x)).value)
    cache = m.init_paged_cache(8, 8)
    assert cache["k"].shape == (8, 1, 2, 8, 16)
    table = jnp.asarray(np.arange(1, 7, dtype=np.int32).reshape(2, 3))
    paged, _ = m.forward_cached_paged(jnp.asarray(x), cache, table,
                                      jnp.zeros((2,), jnp.int32))
    dense, _ = m.forward_cached(jnp.asarray(x), m.init_cache(2, 16),
                                jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(np.asarray(paged), full, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dense), full, rtol=2e-4, atol=2e-4)
    attn.q_norm._value = jnp.ones((16,), jnp.float32)
    assert np.abs(np.asarray(m(paddle.to_tensor(x)).value) - full).max() > 1e-3


def test_softmax_top8_of_128_router_matches_reference():
    """The dropless softmax gate at the published router's width: float32
    softmax over all 128, top 8, renormalised; no expert bias."""
    d, f, e, k = 32, 16, 128, 8
    paddle.seed(5)
    moe = MoELayer(d_model=d, d_hidden=f, num_experts=e, gate="naive",
                   top_k=k, activation="swiglu", expert_bias=False)
    assert moe.b1 is None and moe.b2 is None
    x = np.random.RandomState(2).randn(24, d).astype(np.float32)
    cfg = {"num_experts": e, "num_experts_per_tok": k, "norm_topk_prob": True}
    p = {"router": moe.gate.weight.value, "experts_w1": moe.w1.value,
         "experts_w2": moe.w2.value}
    want = ref.expert_layer(p, jnp.asarray(x), cfg, weight_matmul("float32"))
    got = moe(paddle.to_tensor(x)).value
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    chosen, w = ref.routing(jnp.asarray(x), p["router"], cfg)
    assert chosen.shape == (24, k)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    with pytest.raises(ValueError, match="dropless"):
        MoELayer(d_model=d, d_hidden=f, num_experts=4, gate="gshard",
                 expert_bias=False)


# -- (2) the attention's mask ------------------------------------------------

@pytest.mark.parametrize("width", [4, 8])
@pytest.mark.parametrize("block", [1, 4])
def test_paged_kernel_and_twins_agree_under_the_block_mask(block, width):
    """The Pallas kernel (interpret mode), its gather twin and the dense
    cached attention at GQA group 8, slots at different depths: block
    length 4 sees to the end of each lane's block, block length 1 is the
    causal result bit for bit."""
    from paddle_tpu.ops.pallas.paged_attention import paged_attention
    rs = np.random.RandomState(block * 10 + width)
    B, h, n_kv, d, ps, P_slot, layers = 3, 16, 2, 16, 8, 5, 2
    pages = 1 + B * P_slot
    kp = jnp.asarray(rs.randn(pages, layers, n_kv, ps, d), jnp.float32)
    vp = jnp.asarray(rs.randn(pages, layers, n_kv, ps, d), jnp.float32)
    q = jnp.asarray(rs.randn(B, width, h, d), jnp.float32)
    table = jnp.asarray(1 + np.arange(B * P_slot).reshape(B, P_slot),
                        jnp.int32)
    pos = jnp.asarray([0, 12, 28], jnp.int32)
    kern = paged_attention(q, kp, vp, table, pos, 1, block_length=block,
                           interpret=True)
    twin = tpu_ops.xla_paged_attention(q, kp, vp, table, pos, 1,
                                       block_length=block)
    # the dense view, row by row
    def rows(pool):
        return jnp.take(pool[:, 1], table, axis=0).transpose(0, 1, 3, 2, 4) \
            .reshape(B, P_slot * ps, n_kv, d)
    dense = tpu_ops.cached_attention(q, rows(kp), rows(vp), pos,
                                     block_length=block)
    np.testing.assert_array_equal(np.asarray(twin), np.asarray(dense))
    np.testing.assert_allclose(np.asarray(kern), np.asarray(twin),
                               rtol=2e-5, atol=2e-5)
    causal = paged_attention(q, kp, vp, table, pos, 1, interpret=True)
    causal_twin = tpu_ops.xla_paged_attention(q, kp, vp, table, pos, 1)
    if block == 1:
        np.testing.assert_array_equal(np.asarray(kern), np.asarray(causal))
        np.testing.assert_array_equal(np.asarray(twin),
                                      np.asarray(causal_twin))
    else:
        # lane 0 sees lanes 1..3 of its block: not the causal result
        assert np.abs(np.asarray(kern - causal))[:, 0].max() > 1e-3
        # and the last lane of a block sees what the causal one does
        np.testing.assert_allclose(np.asarray(kern)[:, 3::4],
                                   np.asarray(causal)[:, 3::4],
                                   rtol=2e-5, atol=2e-5)


def test_uncached_attention_takes_the_block_length():
    rs = np.random.RandomState(4)
    q = jnp.asarray(rs.randn(1, 8, 4, 8), jnp.float32)
    k = jnp.asarray(rs.randn(1, 8, 2, 8), jnp.float32)
    v = jnp.asarray(rs.randn(1, 8, 2, 8), jnp.float32)
    mask = jnp.asarray(ref.block_visible(8, 4))[None, None]
    np.testing.assert_allclose(
        np.asarray(tpu_ops.attention(q, k, v, causal=True, block_length=4)),
        np.asarray(tpu_ops.xla_attention(q, k, v, mask=mask)),
        rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(tpu_ops.xla_attention(q, k, v, causal=True,
                                         block_length=1)),
        np.asarray(tpu_ops.xla_attention(q, k, v, causal=True)))


# -- (3) the block schedule in ContinuousBatcher -----------------------------

@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_batcher_serves_generate_for_every_prompt_length_mod_block(
        model, params, ids, tail):
    """Tokens AND the pass at which each was fixed, for prompts of 8 + tail
    and 16 + tail tokens (whole blocks prefilled, the tail handed to the
    first block), 13 new tokens each: across pages of 8 rows, more
    requests than slots."""
    prompts = [ids[i % 2, :n + tail] for i, n in enumerate((8, 16, 8, 12))]
    bat = _assert_serves_generate(model, params, prompts, 13, slots=2)
    assert bat.stats()["diffusion_unmasked_by_threshold"] == 0


def test_prompts_shorter_than_a_block_never_prefill(model, params, ids):
    prompts = [ids[0, :1], ids[1, :2], ids[0, 5:8]]
    bat = _assert_serves_generate(model, params, prompts, 9,
                                  prefix_sharing=False)
    assert bat.stats()["prefill_tokens"] == 0


def test_a_slot_decodes_inside_admission_chunks(model, params, ids):
    """A decodes alone, then B's long prompt arrives: A's blocks now pass
    through the admission program ([B, prefill_chunk] lanes, 28 of them
    junk for A) and still are generate's."""
    cfg = tiny_cfg()
    bat = _batcher(model, slots=2, max_len=96)
    a = bat.submit(ids[0, :6], max_new_tokens=30)
    bat.step()
    bat.step()
    assert bat.stats()["decode_chunks"] >= 1
    b = bat.submit(np.concatenate([ids[1], ids[0, :37]]), max_new_tokens=8)
    out = bat.run()
    assert bat.stats()["admit_chunks"] >= 4
    for rid, prompt, want in ((a, ids[0, :6], 30),
                              (b, np.concatenate([ids[1], ids[0, :37]]), 8)):
        g = ref.generate(params, prompt, want, cfg)
        np.testing.assert_array_equal(out[rid], g["tokens"])
        np.testing.assert_array_equal(bat._finished[rid].output_passes(),
                                      g["passes"])


@pytest.fixture(scope="module")
def peaked():
    """A model (and the reference's parameters) whose head is scaled up so
    that many confidences pass the threshold."""
    cfg = tiny_cfg()
    m = sdar_moe_program.build_model(cfg, SEED + 1, "float32")
    m.eval()
    p = ref_params(cfg, SEED + 1)
    m.lm_head._value = m.lm_head.value * 14.0
    p["lm_head"] = p["lm_head"] * 14.0
    return m, p


def test_confidence_threshold_unmasks_several_lanes_in_one_pass(peaked, ids):
    """With planted (peaked) logits the threshold fires: several lanes are
    fixed in one pass, a block takes fewer than S + 1 passes, and where too
    few lanes pass it the quota's most confident are taken: generate's
    tokens and passes either way."""
    m, p = peaked
    prompts = [ids[0, :9], ids[1, :16], ids[0, 3:14]]
    bat = _assert_serves_generate(m, p, prompts, 16)
    st = bat.stats()
    by_threshold = st["diffusion_unmasked_by_threshold"]
    assert 0 < by_threshold < st["diffusion_tokens_unmasked"]
    assert st["diffusion_tokens_unmasked"] > st["diffusion_denoise_passes"]
    assert (st["diffusion_denoise_passes"]
            + st["diffusion_blocks_committed"]) \
        < (S + 1) * st["diffusion_blocks_committed"]
    passes = np.concatenate([ref.generate(p, q, 16, tiny_cfg())["passes"]
                             for q in prompts])
    assert len(set(passes.tolist())) > 1 and passes.max() < S


def test_a_prompt_holding_the_mask_id_is_not_generated_again(model, params,
                                                             ids):
    """Masked is a flag, not `id == mask`: mask ids in the prompt's whole
    blocks and in its tail stay the prompt's."""
    prompt = ids[0, :11].copy()
    prompt[[2, 8, 10]] = MASK
    _assert_serves_generate(model, params, [prompt], 9)
    g = ref.generate(params, prompt, 9, tiny_cfg())
    assert len(g["tokens"]) == 9 and g["passes"][0] in (0,) \
        and g["pass_logits"][0][0] == 2 and len(
            [1 for b, _, _ in g["pass_logits"] if b == 2]) == 1


def test_max_new_tokens_inside_a_block_is_cut_at_delivery(model, params, ids):
    got = []
    bat = _batcher(model, slots=1)
    rid = bat.submit(ids[0, :8], max_new_tokens=10,
                     on_token=lambda r, toks, done: got.extend(toks))
    out = bat.run()
    g = ref.generate(params, ids[0, :8], 10, tiny_cfg())
    assert len(got) == 10 and got == g["tokens"].tolist()
    np.testing.assert_array_equal(out[rid], g["tokens"])
    req = bat._finished[rid]
    assert len(req.tokens) >= 12 and len(req.token_passes) == len(req.tokens)
    assert len(req.output_passes()) == 10


def test_prefix_sharing_serves_the_unshared_tokens(model, params, ids):
    """Shared rows are rounded down to whole blocks: a divergence inside a
    block (row 13) and inside the prompt's tail resumes at row 12, and the
    run serves what a run without sharing serves."""
    base = ids[0, :24]
    forks = [base, np.concatenate([base[:13], ids[1, :9]]),
             np.concatenate([base[:22], ids[1, :1]]), base[:19]]
    shared, with_sharing = _served(model, forks, 9, slots=1,
                                   prefix_sharing=True)
    _, without = _served(model, forks, 9, slots=1, prefix_sharing=False)
    assert shared.stats()["prefix_hit_tokens"] >= 8 + 16 + 16
    for (a, pa), (b, pb) in zip(with_sharing, without):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(pa, pb)


def test_device_counters_equal_a_host_recount(model, ids):
    """One request alone in one slot: every scan step it is in is a prefill
    step or one of its passes, so the schedule's counts follow from the
    chunk counts and its tokens."""
    bat = _batcher(model, slots=1, prefix_sharing=False)
    rid = bat.submit(ids[0, :18], max_new_tokens=15)
    bat.run()
    st, req = bat.stats(), bat._finished[rid]
    steps = st["admit_chunks"] * bat.admit_steps \
        + st["decode_chunks"] * bat.chunk
    decode_steps = steps - 16 // bat.prefill_chunk
    blocks = (len(req.tokens) + 18 % L) // L
    assert set(DIFFUSION_COUNTERS) <= set(st)
    assert st["prefill_tokens"] == 16
    assert st["decode_tokens"] == len(req.tokens) == blocks * L - 2
    assert st["diffusion_blocks_committed"] == blocks
    assert st["diffusion_denoise_passes"] == decode_steps - blocks
    # the first block had 3 passes, every other 5; the block the
    # eviction cut is in neither count
    assert st["diffusion_committed_block_passes"] == 3 + 5 * (blocks - 1)
    assert st["diffusion_tokens_unmasked"] == st["diffusion_denoise_passes"]
    assert st["decode_lanes"] == decode_steps * L
    # the expert layers route the valid lanes and no other
    assert st["moe_assignments"] == 3 * 2 * (16 + st["decode_lanes"])
    # 5 passes a whole block, 3 for the first (two lanes were the prompt's)
    fixed = np.asarray(req.token_passes)
    assert fixed[:2].tolist() in ([0, 1], [1, 0])
    assert sorted(fixed[2:6].tolist()) == [0, 1, 2, 3]
    assert st["kv_pages_walked"] >= st["kv_pages_live"] > 0


@pytest.mark.parametrize("knobs, error", [
    (dict(spec_tokens=2, draft_layers=1), ValueError),
    (dict(kv_layout="dense"), TypeError),
    (dict(role="prefill"), ValueError),
    (dict(role="decode"), ValueError),
    (dict(prefill_chunk=6), ValueError),
    (dict(page_size=6), ValueError),
    (dict(kv_dtype="int8"), ValueError),
], ids=["speculation", "dense-layout", "prefill-role", "decode-role",
        "prefill-chunk", "page-size", "int8-kv"])
def test_what_does_not_compose_is_refused(model, knobs, error):
    with pytest.raises(error):
        _batcher(model, **knobs)


def test_role_flip_handoff_and_the_token_loop_are_refused(model, ids):
    bat = _batcher(model)
    with pytest.raises(ValueError, match="unified"):
        bat.set_role("prefill")
    with pytest.raises(ValueError, match="unified"):
        bat.import_handoff({"page_size": 8, "kv_dtype": "float32"}, {})
    with pytest.raises(NotImplementedError, match="ContinuousBatcher"):
        model.generate(paddle.to_tensor(ids[:1, :8]), max_new_tokens=4)
    with pytest.raises(ValueError, match="mask_token_id"):
        LlamaConfig(block_length=4, denoising_steps=4).block_diffusion()
    with pytest.raises(ValueError, match="denoising_steps"):
        LlamaConfig(block_length=4, denoising_steps=5,
                    mask_token_id=3).block_diffusion()
    assert LlamaConfig().block_diffusion() is None


# -- (4) the reference's replay and its planted faults -----------------------

@pytest.fixture(scope="module")
def sample(params, ids):
    out = []
    for prompt, want in ((ids[0, :10], 14), (ids[1, :7], 12)):
        g = ref.generate(params, prompt, want, tiny_cfg())
        out.append((prompt, g["tokens"], g["passes"], g["pass_logits"]))
    return out


def _replayed(sample, **kw):
    return list(ref.replayed_logits(
        SEED, tiny_cfg(), [s[:3] for s in sample], "float32", 32, **kw))


def test_replayed_logits_are_generates_own(sample):
    """Layer by layer over committed rows and (block, pass) states: the
    logits generate() saw at every denoise pass of every whole block."""
    for (plan, logits), (_, _, _, own) in zip(_replayed(sample), sample):
        assert len(plan["states"]) == logits.shape[0] > 8
        for s, state in enumerate(plan["states"]):
            b, p, want = own[s]
            assert (b, p) == state
            np.testing.assert_allclose(np.asarray(logits[s]), want,
                                       rtol=2e-4, atol=2e-4)
        assert plan["fixed"].sum(1).min() >= 1
        assert (plan["fixed"] <= plan["masked"]).all()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference(sample, fault):
    """The holes' safety net: whatever the chip's limits catch, at tiny
    size every planted departure moves the replayed logits."""
    sound = _replayed(sample)
    planted = _replayed(sample, fault=fault)
    moved = max(float(jnp.abs(a[1] - b[1]).max())
                for a, b in zip(sound, planted))
    assert moved > 1e-2, moved
