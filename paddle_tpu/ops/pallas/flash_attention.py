"""Flash attention — Pallas TPU kernel, forward + backward.

Reference: the reference wraps the external flash-attention CUDA library
(`cmake/external/flashattn.cmake`, `phi/kernels/gpu/flash_attn_kernel.cu`);
this is the TPU-native equivalent, written directly against the MXU:

  - online-softmax forward over a 3-D grid (batch*head, q-block, k-block)
    with fp32 running max/denominator in VMEM scratch — only ONE K/V tile
    is resident per step, so VMEM use is O(block) and 32k+ contexts fit
  - GQA without materialising repeated KV: the K/V BlockSpec index maps
    fold the q-head → kv-head mapping, so HBM traffic is ∝ num_kv_heads
  - causal masking CLAMPS the K-block index map past the diagonal —
    Mosaic elides the DMA when the block index repeats, so masked blocks
    cost neither bandwidth nor (via pl.when) compute
  - recompute backward: dq kernel (grid over q blocks × k blocks) and
    dkv kernel (grid over kv blocks × (group × q blocks)) — the s×s
    matrix never hits HBM, and dk/dv accumulate over the query-head
    group in-kernel

Layout contract: [b, s, h, d] at the API (paddle flash-attn layout),
transposed to [b*h, s, d] (queries) / [b*h_kv, s, d] (keys, values).
Requires a block size dividing each sequence length (picked from
{512..8} automatically) and d a multiple of 64: `supports` is that
predicate, and paddle_tpu.ops.attention asks it to choose between this
kernel and the XLA path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _interpret():
    # interpret mode is the CPU backend's (the tests'); never a chip's
    return jax.default_backend() != "tpu"


DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _pick_block(seq, preferred):
    """Largest power-of-two divisor of seq, capped at preferred (min 8)."""
    b = 8
    while b * 2 <= min(preferred, seq):
        b *= 2
    while b >= 8:
        if seq % b == 0:
            return b
        b //= 2
    return None


def _kv_head_map(h, hk):
    """bh-grid-index (over b*h) → kv row (over b*hk)."""
    group = h // hk

    def m(bh):
        return (bh // h) * hk + (bh % h) // group
    return m


# ---------------------------------------------------------------------------
# resident-KV fast path (moderate context): the whole K/V for one kv head
# lives in VMEM and a fori_loop walks its blocks — causal skips trailing
# blocks entirely (dynamic loop bound) and there is no per-KV-block grid
# overhead.  ~2× faster than the blocked path at 2-8k context; selected
# by flash_attention() when the VMEM working set fits.
# ---------------------------------------------------------------------------
def _fwd_small_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_q, block_k, seq_k):
    # dots keep the INPUT dtype (bf16 on the MXU — fp32 operands run at
    # ~1/8 the matmul rate); accumulation is fp32 via
    # preferred_element_type, softmax math is fp32, and the scale is
    # applied to the fp32 logits after the dot
    qi = pl.program_id(1)
    q = q_ref[0]                                                   # [BQ, D]

    # all index arithmetic in int32: mosaic rejects mixed i32/i64 (python
    # ints are weak int64 under jax_enable_x64)
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    num_kb = i32(seq_k // block_k)
    if causal:
        # K blocks through the diagonal of the block's LAST query row
        num_kb = jnp.minimum(
            num_kb,
            ((qi + i32(1)) * i32(block_q) - i32(1)) // i32(block_k) + i32(1))

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * i32(block_k), block_k), :]
        v = v_ref[0, pl.ds(j * i32(block_k), block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = qi * i32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * i32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    d = q_ref.shape[-1]
    init = (jnp.zeros((block_q, d), jnp.float32),
            jnp.full((block_q,), NEG_INF, jnp.float32),
            jnp.zeros((block_q,), jnp.float32))
    acc, m, l = jax.lax.fori_loop(i32(0), num_kb, body, init)
    l = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l))[:, None]


def _fwd_small(q3, k2, v2, scale, causal, block_q, block_k, h, hk):
    bh, sq, d = q3.shape
    sk = k2.shape[1]
    kvm = _kv_head_map(h, hk)
    kv_spec = lambda b, i: (kvm(b), 0, 0)
    grid = (bh, sq // block_q)
    with x64_off():
        out, lse = pl.pallas_call(
            functools.partial(_fwd_small_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k, seq_k=sk),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, sk, d), kv_spec),
                pl.BlockSpec((1, sk, d), kv_spec),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
            name="flash_attention_fwd",
            interpret=_interpret(),
        )(q3, k2, v2)
    return out, lse


def _bwd_dq_small_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, scale, causal, block_q, block_k, seq_k):
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, 0]
    delta = delta_ref[0][:, 0]

    i32 = lambda v: jnp.asarray(v, jnp.int32)
    num_kb = i32(seq_k // block_k)
    if causal:
        num_kb = jnp.minimum(
            num_kb,
            ((qi + i32(1)) * i32(block_q) - i32(1)) // i32(block_k) + i32(1))

    def body(j, dq):
        k = k_ref[0, pl.ds(j * i32(block_k), block_k), :]
        v = v_ref[0, pl.ds(j * i32(block_k), block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = qi * i32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * i32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        return dq + jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    d = q_ref.shape[-1]
    dq = jax.lax.fori_loop(i32(0), num_kb, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_small_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                          block_q, block_k, seq_q, group):
    """Grid (b*h_kv, group, kv blocks); each step holds ONE query head's
    q/do row resident (constant over the inner kv-block sweep) and
    accumulates that head's contribution to kv-block kj into full-row
    fp32 VMEM scratch; the last group head flushes scratch to the
    (1, sk, d) output rows."""
    g = pl.program_id(1)
    kj = pl.program_id(2)
    k = k_ref[0]
    v = v_ref[0]

    i32 = lambda v: jnp.asarray(v, jnp.int32)
    num_qb = i32(seq_q // block_q)
    if causal:
        start_qb = kj * i32(block_k) // i32(block_q)
    else:
        start_qb = i32(0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * i32(block_q), block_q), :]
        do = do_ref[0, pl.ds(i * i32(block_q), block_q), :]
        lse = lse_ref[0, pl.ds(i * i32(block_q), block_q), 0]
        delta = delta_ref[0, pl.ds(i * i32(block_q), block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = i * i32(block_q) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * i32(block_k) + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])                   # [BQ, BK]
        dv_new = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # [BK, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)  # [BQ, BK]
        dk_new = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    d = k_ref.shape[-1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = jax.lax.fori_loop(start_qb, num_qb, body, init)

    sl = pl.ds(kj * i32(block_k), block_k)

    @pl.when(g == 0)
    def _set():
        dk_acc[sl, :] = dk
        dv_acc[sl, :] = dv

    @pl.when(g != 0)
    def _add():
        dk_acc[sl, :] = dk_acc[sl, :] + dk
        dv_acc[sl, :] = dv_acc[sl, :] + dv

    @pl.when(g == group - 1)
    def _flush():
        dk_ref[0, sl, :] = dk_acc[sl, :].astype(dk_ref.dtype)
        dv_ref[0, sl, :] = dv_acc[sl, :].astype(dv_ref.dtype)


def _bwd_small(scale, causal, block_q, block_k, h, hk, res, do3):
    q3, k2, v2, out, lse = res
    bh, sq, d = q3.shape
    bkv, sk, _ = k2.shape
    group = h // hk
    delta = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [bh, sq, 1]
    kvm = _kv_head_map(h, hk)
    kv_spec = lambda b, i: (kvm(b), 0, 0)

    with x64_off():
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_small_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, seq_k=sk),
            grid=(bh, sq // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, sk, d), kv_spec),
                pl.BlockSpec((1, sk, d), kv_spec),
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            name="flash_attention_bwd_dq",
            interpret=_interpret(),
        )(q3, k2, v2, do3, lse, delta)

        # row b*group + g of the [b*h, sq, ·] arrays is query head g of the
        # group sharing kv row b; full-row outputs + fp32 scratch let the
        # group accumulate across grid steps
        qg_spec = lambda b, g, j: (b * group + g, 0, 0)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_small_kernel, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, seq_q=sq, group=group),
            grid=(bkv, group, sk // block_k),
            in_specs=[
                pl.BlockSpec((1, sq, d), qg_spec),
                pl.BlockSpec((1, block_k, d), lambda b, g, j: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, g, j: (b, j, 0)),
                pl.BlockSpec((1, sq, d), qg_spec),
                pl.BlockSpec((1, sq, 1), qg_spec),
                pl.BlockSpec((1, sq, 1), qg_spec),
            ],
            out_specs=[
                pl.BlockSpec((1, sk, d), lambda b, g, j: (b, 0, 0)),
                pl.BlockSpec((1, sk, d), lambda b, g, j: (b, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bkv, sk, d), k2.dtype),
                jax.ShapeDtypeStruct((bkv, sk, d), v2.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((sk, d), jnp.float32),
                pltpu.VMEM((sk, d), jnp.float32),
            ],
            name="flash_attention_bwd_dkv",
            interpret=_interpret(),
        )(q3, k2, v2, do3, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# packed single-block path (short context, MHA): when the whole sequence
# fits in ONE block (sq == block_q, sk == block_k) and h == h_kv, the
# per-head work is tiny (s=512, d=64 → 67 MFLOP) and a (b*h,)-sized grid
# is dominated by per-instance overhead — BERT-base at s=512 ran its 12
# attention layers at ~4% MFU.  This path packs `gh` heads per grid
# instance (python-unrolled; refs are [gh, s, d]) and fuses the ENTIRE
# backward — dq, dk, dv — into one kernel so the s×s score matrix is
# recomputed once, not twice.
# ---------------------------------------------------------------------------
def _fwd_1b_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                   gh):
    for g in range(gh):
        q = q_ref[g]                                            # [SQ, D]
        k = k_ref[g]
        v = v_ref[g]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        m = jnp.max(s, axis=1)
        p = jnp.exp(s - m[:, None])
        l = jnp.maximum(jnp.sum(p, axis=1), jnp.float32(1e-30))
        o = jax.lax.dot_general(p.astype(v.dtype), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[g] = (o / l[:, None]).astype(o_ref.dtype)
        lse_ref[g] = (m + jnp.log(l))[:, None]


def _fwd_1b(q3, k2, v2, scale, causal, gh):
    bh, sq, d = q3.shape
    sk = k2.shape[1]
    spec = lambda b: (b, 0, 0)
    with x64_off():
        out, lse = pl.pallas_call(
            functools.partial(_fwd_1b_kernel, scale=scale, causal=causal,
                              gh=gh),
            grid=(bh // gh,),
            in_specs=[
                pl.BlockSpec((gh, sq, d), spec),
                pl.BlockSpec((gh, sk, d), spec),
                pl.BlockSpec((gh, sk, d), spec),
            ],
            out_specs=[
                pl.BlockSpec((gh, sq, d), spec),
                pl.BlockSpec((gh, sq, 1), spec),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
            name="flash_attention_fwd",
            interpret=_interpret(),
        )(q3, k2, v2)
    return out, lse


def _bwd_1b_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dk_ref, dv_ref, *, scale, causal, gh):
    for g in range(gh):
        q = q_ref[g]
        k = k_ref[g]
        v = v_ref[g]
        do = do_ref[g]
        lse = lse_ref[g][:, 0]
        delta = delta_ref[g][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])                           # [SQ, SK]
        dv_ref[g] = jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        dq_ref[g] = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)
        dk_ref[g] = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _bwd_1b(scale, causal, gh, res, do3):
    q3, k2, v2, out, lse = res
    bh, sq, d = q3.shape
    sk = k2.shape[1]
    delta = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    spec = lambda b: (b, 0, 0)
    with x64_off():
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_1b_kernel, scale=scale, causal=causal,
                              gh=gh),
            grid=(bh // gh,),
            in_specs=[
                pl.BlockSpec((gh, sq, d), spec),
                pl.BlockSpec((gh, sk, d), spec),
                pl.BlockSpec((gh, sk, d), spec),
                pl.BlockSpec((gh, sq, d), spec),
                pl.BlockSpec((gh, sq, 1), spec),
                pl.BlockSpec((gh, sq, 1), spec),
            ],
            out_specs=[
                pl.BlockSpec((gh, sq, d), spec),
                pl.BlockSpec((gh, sk, d), spec),
                pl.BlockSpec((gh, sk, d), spec),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), k2.dtype),
                jax.ShapeDtypeStruct((bh, sk, d), v2.dtype),
            ],
            name="flash_attention_bwd",
            interpret=_interpret(),
        )(q3, k2, v2, do3, lse, delta)
    return dq, dk, dv


# per-instance VMEM budget for the packed path: 7 [s,d] operand/result
# rows per head, DOUBLE-buffered by Mosaic, + ~4 concurrent fp32 s×s
# intermediates (scores, p, dp + spill); the scoped limit is 16M so
# leave real headroom
ONE_BLOCK_BUDGET = int(__import__('os').environ.get('PD_FLASH_1B_BUDGET', 9 * 1024 * 1024))


def _pick_gh(bh, sq, sk, d, esize):
    fixed = 4 * sq * sk * 4
    per_head = 2 * 7 * max(sq, sk) * d * esize
    if fixed + per_head > ONE_BLOCK_BUDGET:
        return 0
    cap = min(16, (ONE_BLOCK_BUDGET - fixed) // per_head)
    for g in range(int(cap), 0, -1):
        if bh % g == 0:
            return g
    return 0


# ---------------------------------------------------------------------------
# blocked path (long context): one K/V tile resident per grid step
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, num_kb):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # past-diagonal K blocks are fully masked: skip compute (their DMA is
    # already elided by the clamped index map)
    live = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]                                            # [BQ, D]
        k = k_ref[0]                                            # [BK, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        m_prev = m_ref[...][:, 0]
        l_prev = l_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]
        l_ref[...] = l_new[:, None]

    @pl.when(kj == num_kb - 1)
    def _finalize():
        m = m_ref[...][:, 0]
        l = jnp.maximum(l_ref[...][:, 0], jnp.float32(1e-30))
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m + jnp.log(l))[:, None]


def _causal_clamp(block_q, block_k, num_kb):
    """K-block index for grid step (qi, kj): clamp past the diagonal so the
    repeated index elides the DMA."""
    def idx(qi, kj):
        last = ((qi + 1) * block_q - 1) // block_k  # last live K block
        return jnp.minimum(kj, jnp.minimum(last, num_kb - 1))
    return idx


def _fwd(q3, k2, v2, scale, causal, block_q, block_k, h, hk):
    bh, sq, d = q3.shape
    sk = k2.shape[1]
    num_kb = sk // block_k
    kvm = _kv_head_map(h, hk)
    if causal:
        kidx = _causal_clamp(block_q, block_k, num_kb)
        kv_spec = lambda b, i, j: (kvm(b), kidx(i, j), 0)
    else:
        kv_spec = lambda b, i, j: (kvm(b), j, 0)
    grid = (bh, sq // block_q, num_kb)
    # mosaic rejects the i64/f64 weak constants x64 mode produces; trace the
    # kernel with x64 off (all operands are explicitly typed anyway)
    with x64_off():
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              num_kb=num_kb),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), kv_spec),
                pl.BlockSpec((1, block_k, d), kv_spec),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
                jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
            name="flash_attention_fwd",
            interpret=_interpret(),
        )(q3, k2, v2)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq  (grid over q blocks × k blocks, accumulate dq in scratch)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, num_kb):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (kj * block_k <= (qi + 1) * block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)
        acc_ref[...] = acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_kb - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk/dv  (grid over kv blocks × (group × q blocks); dk/dv
# accumulate over the whole query-head group in VMEM scratch)
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, num_qb, num_t):
    kj = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % num_qb

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # q blocks strictly above the diagonal contribute nothing
    live = ((qi + 1) * block_q - 1 >= kj * block_k) if causal else True

    @pl.when(live)
    def _compute():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0]
        delta = delta_ref[0][:, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * jnp.float32(scale)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse[:, None])                       # [BQ, BK]
        dv_acc[...] = dv_acc[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [BK, D]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * jnp.float32(scale)  # [BQ, BK]
        dk_acc[...] = dk_acc[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == num_t - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, h, hk, res, do3):
    q3, k2, v2, out, lse = res
    bh, sq, d = q3.shape
    bkv, sk, _ = k2.shape
    group = h // hk
    num_qb = sq // block_q
    num_kb = sk // block_k
    delta = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [bh, sq, 1]

    kvm = _kv_head_map(h, hk)
    if causal:
        kidx = _causal_clamp(block_q, block_k, num_kb)
        kv_spec = lambda b, i, j: (kvm(b), kidx(i, j), 0)
    else:
        kv_spec = lambda b, i, j: (kvm(b), j, 0)

    with x64_off():
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              num_kb=num_kb),
            grid=(bh, num_qb, num_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_k, d), kv_spec),
                pl.BlockSpec((1, block_k, d), kv_spec),
                pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            name="flash_attention_bwd_dq",
            interpret=_interpret(),
        )(q3, k2, v2, do3, lse, delta)

        # dkv grid: minor axis t enumerates (g, qi) pairs — for each query
        # head g in the group, all q blocks.  Index maps fold the group
        # head offset into the q-row lookup.
        num_t = group * num_qb

        def q_row(b, j, t):
            g = t // num_qb
            return (b // hk) * h + (b % hk) * group + g

        if causal:
            def q_blk(b, j, t):
                qi = t % num_qb
                first = (j * block_k) // block_q   # first live q block
                return jnp.maximum(qi, first)
        else:
            def q_blk(b, j, t):
                return t % num_qb

        q_spec = lambda b, j, t: (q_row(b, j, t), q_blk(b, j, t), 0)

        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                              block_q=block_q, block_k=block_k,
                              num_qb=num_qb, num_t=num_t),
            grid=(bkv, num_kb, num_t),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_spec),
                pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
                pl.BlockSpec((1, block_q, d), q_spec),
                pl.BlockSpec((1, block_q, 1), q_spec),
                pl.BlockSpec((1, block_q, 1), q_spec),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bkv, sk, d), k2.dtype),
                jax.ShapeDtypeStruct((bkv, sk, d), v2.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            name="flash_attention_bwd_dkv",
            interpret=_interpret(),
        )(q3, k2, v2, do3, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry (custom_vjp over [b*h, s, d] / [b*h_kv, s, d] tensors)
# ---------------------------------------------------------------------------
def _run_fwd(q3, k2, v2, scale, causal, block_q, block_k, h, hk,
             small_fwd, gh1b):
    if gh1b:
        return _fwd_1b(q3, k2, v2, scale, causal, gh1b)
    fwd = _fwd_small if small_fwd else _fwd
    return fwd(q3, k2, v2, scale, causal, block_q, block_k, h, hk)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash3(q3, k2, v2, scale, causal, block_q, block_k, h, hk,
            small_fwd, small_bwd, gh1b):
    out, _ = _run_fwd(q3, k2, v2, scale, causal, block_q, block_k, h, hk,
                      small_fwd, gh1b)
    return out


def _flash3_fwd(q3, k2, v2, scale, causal, block_q, block_k, h, hk,
                small_fwd, small_bwd, gh1b):
    out, lse = _run_fwd(q3, k2, v2, scale, causal, block_q, block_k, h,
                        hk, small_fwd, gh1b)
    # the kernels use a trailing size-1 dim for lse (Mosaic-friendly
    # blocks), but a (bh, sq, 1) RESIDUAL would be stored 128-lane padded
    # (128x memory) between forward and backward — keep it dense 2D and
    # re-expand at the kernel boundary
    return out, (q3, k2, v2, out, lse.reshape(lse.shape[:2]))


def _flash3_bwd(scale, causal, block_q, block_k, h, hk, small_fwd,
                small_bwd, gh1b, res, do3):
    q3, k2, v2, out, lse2 = res
    res3 = (q3, k2, v2, out, lse2[..., None])
    if gh1b:
        return _bwd_1b(scale, causal, gh1b, res3, do3)
    bwd = _bwd_small if small_bwd else _bwd
    return bwd(scale, causal, block_q, block_k, h, hk, res3, do3)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)

# resident-KV path budgets: the scoped VMEM limit is ~16 MiB and blocks
# are double-buffered, so the resident operands must stay well under half
SMALL_KV_BYTES = 4 * 1024 * 1024       # K+V for one kv head (fwd, dq)
SMALL_DKV_SCRATCH_BYTES = 4 * 1024 * 1024  # fp32 dk+dv row scratch (dkv)


def _blocks(sq, sk, d, block_q=None, block_k=None):
    # keep the working set (q, k, v tiles + fp32 acc) well under VMEM:
    # shrink blocks as head_dim grows
    pref = DEFAULT_BLOCK_Q if d <= 128 else max(128, 32768 // d)
    return (_pick_block(sq, block_q or pref),
            _pick_block(sk, block_k or pref))


def supports(q_shape, k_shape, causal=False) -> bool:
    """Shape predicate for ops.attention's kernel-or-XLA choice: a
    block size must divide each sequence length, head_dim must be a
    multiple of 64, and causal masking needs sq == sk (the kernel masks
    top-left aligned; the framework convention, ops.xla_attention, is
    bottom-right for cross lengths)."""
    sq, d = q_shape[1], q_shape[3]
    sk = k_shape[1]
    return (None not in _blocks(sq, sk, d) and d % 64 == 0
            and not (causal and sq != sk))


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None):
    """q/k/v: [b, s, h, d] (paddle layout; k/v may have fewer heads for
    GQA/MQA — h % h_kv == 0).  Returns [b, s, h, d].  Raises ValueError
    for shapes `supports` refuses."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    hk = k.shape[2]
    if h % hk:
        raise ValueError("num q heads must be a multiple of num kv heads")
    if not supports(q.shape, k.shape, causal):
        raise ValueError(
            f"unsupported shape for pallas flash attention (q {q.shape}, "
            f"k {k.shape}, causal={causal})")
    bq, bk = _blocks(sq, sk, d, block_q, block_k)
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    esize = jnp.dtype(q.dtype).itemsize
    group = h // hk
    small_fwd = 2 * sk * d * esize <= SMALL_KV_BYTES
    small_bwd = (small_fwd
                 and 8 * sk * d <= SMALL_DKV_SCRATCH_BYTES
                 and 2 * sq * d * esize <= SMALL_KV_BYTES)
    # packed whole-sequence path: MHA with the full sequence in one block
    gh1b = _pick_gh(b * h, sq, sk, d, esize) \
        if (group == 1 and bq == sq and bk == sk) else 0

    q3 = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    k2 = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    v2 = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    out = _flash3(q3, k2, v2, float(s), bool(causal), bq, bk, h, hk,
                  small_fwd, small_bwd, gh1b)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
