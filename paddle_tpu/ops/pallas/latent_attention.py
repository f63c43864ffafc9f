"""Latent (MLA) paged-attention kernel — absorbed attention of one slot a
grid step, over THAT slot's live pages only (ISSUE 33).

The latent twin of paged_attention.py: the page table, the slots' depths
and the layer are SCALAR-PREFETCH operands, the pool is taken whole, in its
default dimension order, and sliced by nobody in front of the kernel.  The
XLA walk in paddle_tpu.ops (`xla_latent_paged_attention`) takes every slot
to the DEEPEST slot's block; it stays as the CPU's path and as what the
tests hold this kernel to.

Layout contract (paddle_tpu.models.llama.init_paged_cache, latent rows):

  pool        [num_pages, layers, page_size, R + r] — a token's row in a
              layer is [c | k_r]: the normed kv latent (R = kv_lora_rank
              columns) and the rotated shared key (r columns).  A row is
              key (all of it) and value (its first R columns) at once, for
              EVERY head: the MQA case with a group of all the heads
  page_table  [B, pages_per_slot] int32; entry 0 the reserved null page
  pos         [B] int32 >= 0; query lane c of slot b sees rows
              <= pos[b] + c

Grid: one step per LIVE (slot, block of `query_rows` query rows, block of
`T` pages), slot by slot: a work list made from the depths in front of the
call (`_work_list`), its length the grid's size, so a block past a slot's
frontier costs no step, no transfer and no compute.  The walk's bound is
paged_attention.pages_walked(pos, C, ...), that slot's frontier, not the
deepest one's.  A slot's C*h queries (row = c*h + head) are one tile
against its rows, latent and rotary parts two operands whose score
products the kernel adds; an admission step's 2048 rows are one block.

The pages of a block reach the kernel through `T` BlockSpecs over the SAME
pool, each a whole page [page_size, R + r] of this layer picked by the
page table in its index map — not through the kernel's own copies, as
paged_attention.py's do: at R + r = 576 the pool lies 640 lanes wide in
HBM and Mosaic refuses a hand-made transfer whose source is 576 of them
("Slice shape along dimension 3 must be aligned to tiling (128)"), while
the pipeline's own transfers take whole minor dimensions of any width.
The pipeline double-buffers the pages and fetches a step's while its
predecessor computes, across slots too.  In a slot's last block a place
past the frontier names the frontier's page again (fetched once): it
holds real rows, finite and masked by position, so nothing is blanked.

A block is the score products [rows, R] x [T*page_size, R] and [rows, r] x
[T*page_size, r], an fp32 running softmax per row in VMEM scratch, and one
[rows, T*page_size] x [T*page_size, R] product against the SAME rows' first
R columns (a lane-aligned slice: no second transfer).  Numerics are the
twin's: products in the query dtype accumulated in fp32, probabilities cast
to the query dtype before PV, `acc / l` at the end.  On the chip, at the
latent serve cell's geometry (64 slots, 64 heads, 512 + 64, depths as its
traffic leaves them): 2.8 ms an admission call, 55 % of the MXU's peak,
against the walk's 20.5; 0.5 ms a decode call against 1.2 (PR 33).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from .paged_attention import NEG_INF, _interpret, pages_walked
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# key rows of one block of the walk: enough to amortise the rescale of the
# [rows, R] fp32 accumulator and a grid step's fixed cost, few enough that
# a shallow slot's last block is not mostly dead rows
_KEY_ROWS = 256
# query rows of one grid step
_QUERY_ROWS = 2048
# at 2048 query rows: the query and output tiles (double-buffered by the
# pipeline) 9.4 MB, the fp32 accumulator and softmax state 6.3 MB, a
# block's score and probability tiles 5 MB
_VMEM_LIMIT = 64 * 2 ** 20


def _kernel(slot_of, qb_of, block_of, last_of, pt_ref, pos_ref, layer_ref,
            ql_ref, qr_ref, *rest, scale, page_size, heads, T):
    *page_refs, o_ref, acc_ref, m_ref, l_ref = rest
    ps = page_size
    rows, rank = ql_ref.shape[1:]
    w = pl.program_id(0)
    b, qb, i = slot_of[w], qb_of[w], block_of[w]

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    dtype = ql_ref.dtype
    kv = jnp.concatenate([ref[...] for ref in page_refs],
                         axis=0).astype(dtype)            # [T*ps, R + r]
    c = kv[:, :rank]

    def scores(q, k):
        return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
    s = (scores(ql_ref[0], c) + scores(qr_ref[0], kv[:, rank:])) * scale
    # query row r of block qb is head (qb*rows + r) % heads of lane
    # (qb*rows + r) // heads, at global position pos + lane; key column c'
    # of block i sits at global position i*T*ps + c'
    qpos = pos_ref[b] + (qb * rows + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)) // heads
    kpos = i * (T * ps) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos <= qpos, s, NEG_INF)
    m_prev = m_ref[...]                                   # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(dtype), c, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when((i + 1) * T > last_of[w])
    def _():
        # block 0 holds row 0, which every query sees: l > 0
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def supports(pool_shape, rank, dtype, interpret=None) -> bool:
    """Shape predicate for ops.latent_paged_attention's kernel-or-twin
    choice: a pool [P, L, ps, R + r] of latent rows.  A transfer is one
    page of one layer, [ps, R + r], whole in its two minor dims; T of them
    are laid one under the other and the value is the first R columns of
    that: Mosaic needs page_size on whole sublane tiles of the pool's
    dtype (16 rows bf16, 8 fp32) and R on whole 128-lane tiles.  Interpret
    mode (CPU tests) has no tiling."""
    if len(pool_shape) != 4 or not 0 < rank < pool_shape[3]:
        return False
    interp = _interpret() if interpret is None else interpret
    itemsize = jnp.dtype(dtype).itemsize
    return interp or (itemsize in (2, 4) and rank % 128 == 0
                      and pool_shape[2] % (32 // itemsize) == 0)


def latent_attention(q_lat, q_rope, pool, page_table, pos, layer, scale,
                     interpret=None, query_rows=_QUERY_ROWS,
                     key_rows=_KEY_ROWS):
    """q_lat [B, C, h, R]; q_rope [B, C, h, r]; pool [P, L, ps, R + r];
    page_table [B, P_slot] int32; pos [B] int32.  Returns u [B, C, h, R]
    in q_lat.dtype: each query's probability-weighted sum of the latents it
    sees.  Raises ValueError for shapes `supports` refuses —
    ops.latent_paged_attention asks the predicate first and takes the XLA
    walk for those.  `query_rows` and `key_rows` are the tests' handle on
    the blocking; callers leave them alone."""
    interp = _interpret() if interpret is None else interpret
    R, W = q_lat.shape[3], pool.shape[3]
    if R + q_rope.shape[3] != W:
        raise ValueError(f"latent rank {R} + rope {q_rope.shape[3]} is not "
                         f"the pool's row width {W}")
    if not supports(pool.shape, R, pool.dtype, interp):
        raise ValueError(
            f"latent_attention tiling needs kv_lora_rank % 128 == 0 and "
            f"page_size on whole sublane tiles (got rank={R}, pool "
            f"{pool.shape} {pool.dtype})")
    posv = jnp.asarray(pos, jnp.int32)
    if posv.ndim == 0:
        posv = jnp.broadcast_to(posv, q_lat.shape[:1])
    # the layer is an operand, not a constant of the kernel: a model's
    # layers share ONE trace and ONE lowering of it in a step program
    return _call(q_lat, q_rope, pool, jnp.asarray(page_table, jnp.int32),
                 posv, jnp.asarray(layer, jnp.int32).reshape(1),
                 scale=float(scale), interpret=bool(interp),
                 query_rows=int(query_rows), key_rows=int(key_rows))


def _work_list(pos, q_len, page_size, pages_per_slot, T, nq):
    """The grid's items, one a (slot, block of query rows, block of T LIVE
    pages), slot-major: (items, slot_of [N], qb_of [N], block_of [N],
    last_of [N]: the slot's frontier page, as an entry of its table) with N
    the static bound B * nq * ceil(P_slot / T) and `items` of them real.
    Compare-and-sum over [N, B]: a few small fusions (a gather or a
    searching loop costs the TPU 0.2 ms each), the same for every layer of
    a step program."""
    B = pos.shape[0]
    n_pages = pages_walked(pos, q_len, page_size, pages_per_slot)    # [B]
    n_blocks = (n_pages + (T - 1)) // T
    ends = jnp.cumsum(n_blocks * nq)
    w = jnp.arange(B * nq * -(-pages_per_slot // T), dtype=jnp.int32)
    slot = jnp.sum(w[:, None] >= ends[None], axis=1, dtype=jnp.int32)
    mine = slot[:, None] == jnp.arange(B, dtype=jnp.int32)[None]

    def of_slot(per_slot):
        return jnp.sum(jnp.where(mine, per_slot[None], 0), axis=1,
                       dtype=jnp.int32)
    blocks = jnp.maximum(of_slot(n_blocks), 1)       # 0 past the last item
    local = w - of_slot(ends - n_blocks * nq)
    return ends[-1].astype(jnp.int32), jnp.minimum(slot, B - 1), \
        local // blocks, local % blocks, of_slot(n_pages - 1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "query_rows", "key_rows"))
def _call(q_lat, q_rope, pool, pt, pos, layer, *, scale, interpret,
          query_rows, key_rows):
    B, C, h, R = q_lat.shape
    P, L, ps, W = pool.shape
    P_slot = pt.shape[1]
    # one query tile a slot, row = c*h + head, padded to whole blocks of
    # whole sublane tiles; latent and rotary parts stay two operands (the
    # kernel adds their score products: joining them costs an HBM pass)
    n = C * h
    rb = min(-(-n // 8) * 8, max(8, query_rows // 8 * 8))
    rows = -(-n // rb) * rb
    ql = q_lat.reshape(B, n, R)
    qr = q_rope.astype(q_lat.dtype).reshape(B, n, W - R)
    if rows != n:
        ql, qr = (jnp.pad(q, ((0, 0), (0, rows - n), (0, 0)))
                  for q in (ql, qr))
    T = max(1, min(P_slot, key_rows // ps))
    items, *work = _work_list(pos, C, ps, P_slot, T, rows // rb)

    def tile_ix(w, slot_of, qb_of, *prefetched):
        return (slot_of[w], qb_of[w], 0)

    def page_ix(t):
        def ix(w, slot_of, qb_of, block_of, last_of, pt_ref, pos_ref,
               layer_ref):
            return (pt_ref[slot_of[w], jnp.minimum(block_of[w] * T + t,
                                                   last_of[w])],
                    layer_ref[0], 0, 0)
        return ix

    kern = functools.partial(_kernel, scale=scale, page_size=ps, heads=h,
                             T=T)
    with x64_off():
        out = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=7,
                # as many steps as there are LIVE blocks
                grid=(items,),
                in_specs=[pl.BlockSpec((1, rb, R), tile_ix),
                          pl.BlockSpec((1, rb, W - R), tile_ix)] + [
                    pl.BlockSpec((None, None, ps, W), page_ix(t))
                    for t in range(T)],
                out_specs=pl.BlockSpec((1, rb, R), tile_ix),
                scratch_shapes=[
                    pltpu.VMEM((rb, R), jnp.float32),
                    pltpu.VMEM((rb, 1), jnp.float32),
                    pltpu.VMEM((rb, 1), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, rows, R), q_lat.dtype),
            # a slot's blocks follow one another: the steps run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="latent_attention",
            interpret=interpret,
        )(*work, pt, pos, layer, ql, qr, *[pool] * T)
    return out[:, :n].reshape(B, C, h, R)
