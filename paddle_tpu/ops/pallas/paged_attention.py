"""Paged-attention decode kernel — gather-by-page-table inside the
kernel (ISSUE 7 tentpole).

Reference design point: vLLM's PagedAttention, adapted to a statically
shaped XLA program the way TPU serving stacks do it: the page table is
a SCALAR-PREFETCH operand (pltpu.PrefetchScalarGridSpec), so the K/V
BlockSpec index maps read `page_table[b, j]` to pick WHICH physical
page the next grid step DMAs — the gather happens in the DMA engine,
and the [B, S_max] logical KV view is never materialized in HBM
(the jnp twin in paddle_tpu.ops does exactly that materializing
`take`-based gather, bit-matching this kernel's math off-TPU).

Layout contract (paddle_tpu.models.llama.init_paged_cache):

  k_pool/v_pool  [num_pages, layers, n_kv, page_size, head_dim] —
                 (page_size, head_dim) minor, so one page of one kv
                 head is a whole-tile block the TPU lowering accepts
  k/v scales     [num_pages, layers, n_kv] fp32  (int8 pools only)
  page_table     [B, pages_per_slot] int32; entry 0 is the reserved
                 null page (reads masked by position)
  pos            [B] int32 — per-slot write depth; query lane c of
                 slot b attends rows <= pos[b] + c

Grid: (B, n_kv, pages_per_slot) — the page walk is the innermost
(sequential) dimension, accumulating an online softmax per (slot,
kv head) in VMEM scratch, flash-attention style.  Pages past a slot's
frontier clamp their index map to the last useful page — Mosaic elides
the repeated-block DMA, so dead pages cost neither bandwidth nor
(via pl.when) compute.  int8 dequant is fused: the wrapper gathers the
slot's per-page scales of this layer into a [P_slot, 1] column per
(slot, kv head) — a block of whole minor dims — and the kernel reduces
row j of it to a scalar that multiplies the tile right after the DMA,
so the HBM read stays 1 byte/element.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret():
    # interpret mode is the CPU backend's (the tests'); never a chip's
    return jax.default_backend() != "tpu"


def _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
            o_ref, acc_ref, m_ref, l_ref, *, scale, page_size, group,
            q_len, quant):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    ps = page_size
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages whose first row is past the slot's last query frontier
    # contribute nothing — their DMA was already elided by the clamped
    # index map; skip the compute too
    @pl.when(j * ps <= pos + (q_len - 1))
    def _page():
        # q rows are pre-arranged [C*group, d] by the wrapper (row =
        # c*group + g) — no in-kernel reshape across sublanes
        q = q_ref[0, 0]
        k = k_ref[0, 0, 0]                                # [ps, d]
        v = v_ref[0, 0, 0]
        if quant:
            # this page's scale: row j of the slot's [P_slot, 1] column
            # (cast back to the compute dtype, as the twin does)
            row = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[2:], 0)

            def page_scale(ref):
                return jnp.sum(jnp.where(row == j, ref[0, 0], 0.0))
            k = (k.astype(jnp.float32) * page_scale(ks_ref)).astype(q.dtype)
            v = (v.astype(jnp.float32) * page_scale(vs_ref)).astype(q.dtype)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        # query row r = c*group + g sits at global position pos + c;
        # key column r' sits at global position j*ps + r'
        qpos = pos + jax.lax.broadcasted_iota(
            jnp.int32, (q_len * group, ps), 0) // jnp.int32(group)
        kpos = j * jnp.int32(ps) + jax.lax.broadcasted_iota(
            jnp.int32, (q_len * group, ps), 1)
        s = jnp.where(kpos <= qpos, s, jnp.float32(NEG_INF))
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + \
            jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new

    @pl.when(j == nj - 1)
    def _done():
        l = jnp.maximum(l_ref[:, 0], jnp.float32(1e-30))
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def supports(pool_shape, interpret=None) -> bool:
    """Shape predicate for ops.paged_attention's kernel-or-twin choice:
    the K/V block is one page of one kv head, (page_size, head_dim) —
    the pool's two minor dims — so Mosaic needs head_dim on whole
    128-lane tiles and page_size on whole 8-row sublane tiles.
    Interpret mode (CPU tests) has no tiling."""
    interp = _interpret() if interpret is None else interpret
    ps, d = pool_shape[3], pool_shape[4]
    return interp or (d % 128 == 0 and ps % 8 == 0)


def paged_attention(q, k_pool, v_pool, page_table, pos, layer,
                    k_scale=None, v_scale=None, scale=None,
                    interpret=None):
    """q: [B, C, h, d]; pools [P, L, n_kv, ps, d]; page_table
    [B, P_slot] int32; pos [B] int32.  Returns [B, C, h, d] in
    q.dtype.  Raises ValueError for shapes `supports` refuses —
    ops.paged_attention asks the predicate first and takes the jnp
    twin for those."""
    interp = _interpret() if interpret is None else interpret
    B, C, h, d = q.shape
    P, L, n_kv, ps, _ = k_pool.shape
    P_slot = page_table.shape[1]
    group = h // n_kv
    if h % n_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads "
                         f"{n_kv}")
    if not supports(k_pool.shape, interp):
        raise ValueError(
            f"paged_attention tiling needs head_dim % 128 == 0 and "
            f"page_size % 8 == 0 (got d={d}, page_size={ps})")
    quant = k_pool.dtype == jnp.int8
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV pool needs k_scale/v_scale")
    s = scale if scale is not None else 1.0 / (d ** 0.5)

    pt = jnp.asarray(page_table, jnp.int32)
    posv = jnp.asarray(pos, jnp.int32)
    if posv.ndim == 0:
        posv = jnp.broadcast_to(posv, (B,))

    def slot_scales(scales):
        # [P, L, n_kv] -> this layer's scale of every page the slot
        # maps, as a [P_slot, 1] column per (slot, kv head): a block of
        # whole minor dims, so the kernel picks row j with a dynamic
        # sublane slice.  B*n_kv*P_slot floats — noise beside the pool.
        if not quant:
            # dummy operand keeps ONE kernel signature; never read
            return jnp.ones((B, n_kv, P_slot, 1), jnp.float32)
        sc = jnp.take(scales[:, layer], pt, axis=0)    # [B, P_slot, n_kv]
        return sc.transpose(0, 2, 1)[..., None].astype(jnp.float32)

    def page_ix(b, kvh, j, pt_ref, pos_ref):
        # clamp the walk to the slot's frontier page: repeated block
        # index => Mosaic elides the DMA for dead pages
        last = jnp.maximum(pos_ref[b] + (C - 1), 0) // ps
        return (pt_ref[b, jnp.minimum(j, last)], layer, kvh, 0, 0)

    def slot_ix(b, kvh, j, pt_ref, pos_ref):
        return (b, kvh, 0, 0)

    # pre-arrange q per kv head with rows row = c*group + g — the
    # kernel then reads a ready [C*group, d] tile (an in-kernel
    # sublane reshape would be a Mosaic relayout)
    qr = q.reshape(B, C, n_kv, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, n_kv, C * group, d)
    grid = (B, n_kv, P_slot)
    kern = functools.partial(_kernel, scale=s, page_size=ps,
                             group=group, q_len=C, quant=quant)
    with x64_off():
        out = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((1, 1, C * group, d), slot_ix),
                    pl.BlockSpec((1, 1, 1, ps, d), page_ix),
                    pl.BlockSpec((1, 1, 1, ps, d), page_ix),
                    pl.BlockSpec((1, 1, P_slot, 1), slot_ix),
                    pl.BlockSpec((1, 1, P_slot, 1), slot_ix),
                ],
                out_specs=pl.BlockSpec((1, 1, C * group, d), slot_ix),
                scratch_shapes=[
                    pltpu.VMEM((C * group, d), jnp.float32),
                    pltpu.VMEM((C * group, 1), jnp.float32),
                    pltpu.VMEM((C * group, 1), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, n_kv, C * group, d),
                                           q.dtype),
            name="paged_attention",
            interpret=interp,
        )(pt, posv, qr, k_pool, v_pool, slot_scales(k_scale),
          slot_scales(v_scale))
    return out.reshape(B, n_kv, C, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, h, d)
