"""State-space (Mamba-2) layers beside attention layers, a state a slot next
to the paged pool, against a naive recurrence and against the benchmark's
plain float32 reference (benchmark/reference/granite_hybrid_f32.py, the
recurrence token after token) at a tiny size on the CPU: the `ssm_scan`
kernel in interpret mode and its XLA twin at each width and valid count,
the decoder `paddle_tpu.models.llama` builds from such a configuration, its
paged path through chunks whose pad lanes must leave the state alone,
`ContinuousBatcher` over it (slots re-occupied), the refusals and the
bytes."""
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops as tpu_ops
from paddle_tpu.inference import ContinuousBatcher
from paddle_tpu.ops.pallas import ssm_scan as scan_kernel

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import weights_granite_hybrid                        # noqa: E402
from drivers import granite_hybrid_program           # noqa: E402
from reference import granite_hybrid_f32 as ref      # noqa: E402

SEED = 2 ** 31 + 40


def tiny_cfg(**over):
    """The published configuration's keys at a tiny size: layers mamba,
    attention, mamba; 8 mamba heads of 64 with a state of 128 (the kernel's
    tiles), 4 query heads of 64 on 2 KV heads (packed two to a row)."""
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=256, intermediate_size=256,
               shared_intermediate_size=256, num_hidden_layers=3,
               layer_types=["mamba", "attention", "mamba"],
               num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=64, mamba_expand=2,
               mamba_d_state=128, vocab_size=256, torch_dtype="float32",
               mamba_state_dtype="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return {n: np.asarray(v, np.float32)
            for n, v in weights_granite_hybrid.leaves(SEED, cfg, "float32")}


def _model(cfg):
    model = granite_hybrid_program.build_model(cfg, SEED, "float32")
    model.eval()
    return model


@pytest.fixture(scope="module")
def model(cfg):
    return _model(cfg)


# -- the scan ---------------------------------------------------------------

def _naive(state, x, b, c, dt, a, d, n, reset, layer, stored=np.float64):
    """The recurrence in float64, lane after lane, slot after slot; the
    state rounded to `stored` where a step leaves it."""
    state = np.array(state, np.float64)
    y = np.zeros(x.shape, np.float64)
    for s in range(x.shape[0]):
        h = np.zeros_like(state[layer, s]) if reset[s] else state[layer, s]
        for t in range(n[s]):
            h = np.exp(dt[s, t] * a)[:, None, None] * h \
                + (dt[s, t][:, None] * x[s, t])[:, :, None] * b[s, t]
            y[s, t] = h @ c[s, t] + d[:, None] * x[s, t]
        state[layer, s] = np.asarray(jnp.asarray(h, stored), np.float64)
    return y, state


def _bf16(a):
    """Values exact in bfloat16, as the program's x, B and C are."""
    return np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)


def _operands(width, seed=0):
    rng = np.random.default_rng(seed)
    L, B, H, P, N = 2, 3, 4, 16, 128
    return dict(
        state=rng.normal(size=(L, B, H, P, N)).astype(np.float32),
        x=_bf16(rng.normal(size=(B, width, H, P))),
        b=_bf16(rng.normal(size=(B, width, N)) * 0.3),
        c=_bf16(rng.normal(size=(B, width, N)) * 0.3),
        dt=rng.uniform(0.001, 0.2, size=(B, width, H)).astype(np.float32),
        a=-rng.uniform(1.0, 16.0, size=(H,)).astype(np.float32),
        d=rng.normal(size=(H,)).astype(np.float32))


@pytest.mark.parametrize("stored", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", ["kernel", "twin"])
@pytest.mark.parametrize("width,count", [(1, 0), (1, 1), (32, 0), (32, 1),
                                         (32, 31), (32, 32)])
def test_scan_against_the_recurrence(path, width, count, stored):
    """Slot 0 advances `count` lanes, slot 1 starts fresh with the step's
    last valid lane, slot 2 every lane; the lanes past a slot's count leave
    its state EXACTLY as it was (and the other layer is not touched).  A
    state stored in bf16 is advanced in fp32 and rounded once, where the
    step leaves it."""
    o = _operands(width)
    o["state"] = np.asarray(jnp.asarray(o["state"], stored), np.float32)
    tol = 2e-5 if stored == "float32" else 1e-2
    n = np.array([count, max(count, 1) if width > 1 else 1, width],
                 np.int32)
    reset = np.array([0, 1, 0], np.int32)
    lanes = np.arange(width)
    dt = np.where(lanes[None, :, None] < n[:, None, None], o["dt"], 0.0)
    args = [jnp.asarray(o["state"], stored)] \
        + [jnp.asarray(o[k]) for k in ("x", "b", "c")] \
        + [jnp.asarray(dt, jnp.float32), jnp.asarray(o["a"]),
           jnp.asarray(o["d"]), jnp.asarray(reset)]
    if path == "kernel":
        y, state = scan_kernel.ssm_scan(*args, 1, interpret=True)
    else:
        y, state = tpu_ops.xla_ssm_scan(*args, jnp.int32(1))
    want_y, want_state = _naive(o["state"], o["x"], o["b"], o["c"],
                                o["dt"], o["a"], o["d"], n, reset, 1,
                                getattr(jnp, stored))
    assert state.dtype == jnp.dtype(stored)
    state, y = np.asarray(state, np.float32), np.asarray(y)
    np.testing.assert_array_equal(state[0], o["state"][0])
    if count == 0:
        np.testing.assert_array_equal(state[1, 0], o["state"][1, 0])
    np.testing.assert_allclose(state, want_state, rtol=tol, atol=tol)
    for s in range(3):
        np.testing.assert_allclose(y[s, :n[s]], want_y[s, :n[s]],
                                   rtol=tol, atol=tol)


def test_ops_scan_masks_the_pad_lanes():
    """ops.ssm_scan is told the valid counts and masks dt itself: a slot
    with 0 valid lanes keeps its state bit for bit, whatever its lanes
    hold."""
    o = _operands(4, seed=1)
    args = [jnp.asarray(o[k]) for k in ("state", "x", "b", "c", "dt", "a",
                                        "d")]
    _, state = tpu_ops.ssm_scan(*args, jnp.asarray([0, 2, 4], jnp.int32),
                                jnp.zeros((3,), jnp.int32), 0)
    np.testing.assert_array_equal(np.asarray(state)[0, 0], o["state"][0, 0])
    assert not np.array_equal(np.asarray(state)[0, 1], o["state"][0, 1])


def test_kernel_supports_the_cells_tiles_only():
    assert scan_kernel.supports((36, 64, 64, 64, 128), 1)
    assert scan_kernel.supports((36, 64, 64, 64, 128), 32)
    assert not scan_kernel.supports((36, 64, 64, 64, 128), 512)
    assert not scan_kernel.supports((36, 64, 64, 64, 96), 1)
    assert scan_kernel.head_block(64, 64, 128, 32) in (16, 32, 64)


# -- the model --------------------------------------------------------------

def test_uncached_forward_against_the_reference(cfg, params, model):
    ids = np.random.default_rng(0).integers(0, 256, size=37).astype(np.int32)
    got = np.asarray(model(paddle.to_tensor(ids[None])).value[0])
    want = np.asarray(ref.forward_logits(params, ids, cfg))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_state_is_stored_in_the_configured_dtype(cfg):
    """The configuration's `mamba_state_dtype` is the pool's: bf16 halves
    the state's bytes and the served tokens stay the reference's at this
    size (the recurrence runs in fp32 and rounds the state once a step)."""
    model = _model(dict(cfg, mamba_state_dtype="bfloat16"))
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64, chunk=4,
                            prefill_chunk=8)
    assert bat._cache["ssm"].dtype == jnp.bfloat16
    assert bat._state_bytes["ssm"] == 2 * 2 * 8 * 64 * 128 * 2
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, size=n).astype(np.int32)
               for n in (13, 5, 21)]
    rids = [bat.submit(p, max_new_tokens=6) for p in prompts]
    out = bat.run()
    assert _gaps(cfg, list(zip(rids, prompts)), out).max() <= 5e-3


def test_config_kinds_and_packed_rows(model):
    cfg = model.config
    assert cfg.state_layers() == (0, 2)
    spec = model.kv_row_spec()
    assert spec["pools"]["k"] == (1, 128)      # two 64-wide heads a row
    assert spec["kinds"]["full"]["layers"] == (1,)
    assert spec["kinds"]["state"]["arrays"]["ssm"][0] == (8, 64, 128)
    assert spec["kinds"]["state"]["arrays"]["conv"][0] == (3, 512 + 256)
    with pytest.raises(NotImplementedError, match="paged cache only"):
        model.init_cache(2, 16)


def _paged(model, slots=2, pages=32, page_size=4):
    cache = model.init_paged_cache(pages, page_size, state_slots=slots)
    per = (pages - 1) // slots
    table = jnp.asarray(1 + np.arange(slots * per).reshape(slots, per),
                        jnp.int32)
    return cache, table


def test_paged_chunks_against_the_reference(cfg, params, model):
    """Chunked prefill of prompts whose length the chunk does not divide,
    then decode through the state, with steps where one slot decodes in
    lane 0 and rides pad lanes beside another slot's prefill, and steps
    where a slot has nothing valid: every valid lane's logits are the
    reference's full forward's."""
    rng = np.random.default_rng(1)
    seqs = [rng.integers(0, 256, size=n).astype(np.int32) for n in (23, 14)]
    want = [np.asarray(ref.forward_logits(params, s, cfg)) for s in seqs]
    cache, table = _paged(model)
    done = np.zeros(2, np.int32)
    # (width, slot 0's valid lanes, slot 1's)
    plan = [(8, 8, 8), (8, 8, 3), (8, 7, 1), (8, 0, 1), (1, 0, 0), (1, 0, 1)]
    for width, *counts in plan:
        ids = np.zeros((2, width), np.int32)
        for s, n in enumerate(counts):
            ids[s, :n] = seqs[s][done[s]:done[s] + n]
        lg, cache = model.forward_cached_paged(
            jnp.asarray(ids), cache, table, jnp.asarray(done),
            lanes_valid=jnp.asarray(counts, jnp.int32))
        for s, n in enumerate(counts):
            np.testing.assert_allclose(
                np.asarray(lg)[s, :n], want[s][done[s]:done[s] + n],
                rtol=1e-4, atol=1e-5)
            done[s] += n
    assert list(done) == [23, 14]


def test_a_reoccupied_slot_starts_from_zero_state(model):
    """A request in a slot that another one held gives the logits a fresh
    cache gives it: its depth 0 zeroes the slot's state and conv window."""
    rng = np.random.default_rng(2)
    first, second = (rng.integers(0, 256, size=(2, 8)).astype(np.int32)
                     for _ in range(2))
    cache, table = _paged(model)
    _, cache = model.forward_cached_paged(jnp.asarray(first), cache, table,
                                          jnp.zeros(2, jnp.int32))
    _, cache = model.forward_cached_paged(jnp.asarray(first), cache, table,
                                          jnp.full(2, 8, jnp.int32))
    reused, _ = model.forward_cached_paged(jnp.asarray(second), cache,
                                           table, jnp.zeros(2, jnp.int32))
    fresh_cache, _ = _paged(model)
    fresh, _ = model.forward_cached_paged(jnp.asarray(second), fresh_cache,
                                          table, jnp.zeros(2, jnp.int32))
    np.testing.assert_array_equal(np.asarray(reused), np.asarray(fresh))


def _gaps(cfg, prompts, out):
    seqs = [(p, np.asarray(out[r], np.int32)) for r, p in prompts]
    got = ref.teacher_forced_logits(SEED, cfg, seqs, "float32", 128)
    return np.concatenate([
        np.asarray(jnp.max(lg, -1) - jnp.take_along_axis(
            lg, jnp.asarray(t)[:, None], -1)[:, 0])
        for (_, t), lg in zip(seqs, got)])


@pytest.fixture(params=["twin", "kernel"])
def scan_path(request, monkeypatch):
    """Serve through the XLA twin (the CPU's path) or through the Pallas
    kernel in interpret mode (the chip's)."""
    if request.param == "kernel":
        monkeypatch.setattr(tpu_ops, "_ssm_kernel",
                            lambda shape, width, itemsize=4: scan_kernel)
    return request.param


def test_batcher_serves_the_references_tokens(cfg, scan_path):
    """ContinuousBatcher over the hybrid model: 5 requests on 2 slots
    (slots finish and are re-occupied), prompts the prefill chunk does not
    divide: every served token is the reference's best at its position."""
    model = _model(cfg)
    bat = ContinuousBatcher(model, max_batch_size=2, max_len=64, chunk=4,
                            prefill_chunk=8)
    assert bat._state and bat.ring_pages == 0 and not bat.prefix_sharing
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, size=n).astype(np.int32)
               for n in (13, 5, 21, 9, 3)]
    rids = [bat.submit(p, max_new_tokens=6) for p in prompts]
    out = bat.run()
    assert all(len(out[r]) == 6 for r in rids)
    assert _gaps(cfg, list(zip(rids, prompts)), out).max() <= 1e-5


@pytest.mark.parametrize("knobs,error,match", [
    (dict(prefix_sharing=True), ValueError, "prefix_sharing"),
    (dict(role="prefill"), ValueError, "role"),
    (dict(spec_tokens=2, draft_layers=1), ValueError, "spec_tokens"),
    (dict(kv_dtype="int8"), ValueError, "int8"),
    (dict(kv_layout="dense"), TypeError, "kv_layout")],
    ids=["prefix", "role", "speculation", "int8", "dense"])
def test_refusals(model, knobs, error, match):
    with pytest.raises(error, match=match):
        ContinuousBatcher(model, max_batch_size=2, max_len=32, **knobs)


def test_paged_kv_bytes_counts_the_state(model):
    cfg = model.config
    bat = ContinuousBatcher(model, max_batch_size=3, max_len=32,
                            prefill_chunk=8)
    est = ContinuousBatcher.paged_kv_bytes(model, 3, 32, 8)
    assert est == bat.kv_cache_bytes()
    state = 3 * 2 * (8 * 64 * 128 * 4 + 3 * cfg.mamba_conv_dim * 4)
    assert bat.stats()["kv_pool_bytes"]["state"] == state
    assert bat._state_bytes == {"ssm": 3 * 2 * 8 * 64 * 128 * 4,
                                "conv": 3 * 2 * 3 * cfg.mamba_conv_dim * 4}
    assert est > state
