"""Paged-attention kernel — a walk over each slot's LIVE pages, all KV
heads of a page in one transfer (ISSUE 7 tentpole, rewritten in ISSUE 26).

Reference design point: vLLM's PagedAttention, adapted to a statically
shaped XLA program the way TPU serving stacks do it: the page table and
the slots' depths are SCALAR-PREFETCH operands, the pools stay in HBM
(`pl.ANY`) and the kernel itself copies `page_table[b, j]` of this layer
into VMEM, so the [B, S_max] logical KV view is never materialized in
HBM (the jnp twin in paddle_tpu.ops does exactly that materializing
`take`-based gather, and is what the tests hold this kernel to).

Layout contract (paddle_tpu.models.llama.init_paged_cache):

  k_pool/v_pool  [num_pages, layers, n_kv, page_size, head_dim] — all
                 kv heads of one page in one layer are ONE contiguous
                 block, and that block is what a transfer moves
  k/v scales     [num_pages, layers, n_kv] fp32  (int8 pools only)
  page_table     [B, pages_per_slot] int32; entry 0 is the reserved
                 null page (reads masked by position)
  pos            [B] int32 >= 0 — per-slot write depth; query lane c of
                 slot b attends rows <= pos[b] + c, or with a block
                 length L > 1 (generation by diffusion over blocks)
                 rows <= the end of the block of L that holds pos[b] + c

Grid: one step per (slot, block of `hb` kv heads) — `hb` is all of
n_kv wherever that fits VMEM (`_blocking`).  Inside a step a loop runs
over the slot's live pages only, `pages_walked(pos, C, ...)` of them,
`T` pages (128 key rows) at a time: pages past the frontier cost no
step, no transfer and no compute.  The copies are double-buffered, and
the last block of one grid step already starts the first block of the
next, so a slot's first pages arrive under its predecessor's products.
Each block is one [C*group, d] x [T*page_size, d] product per kv head
with an online softmax per (head, row) in VMEM scratch, flash-attention
style.  The layer is picked in the copy's source slice: nothing slices
the pool in front of the kernel.

int8 pools: the page's per-head scale multiplies the page's COLUMNS of
the score tile (K) and of the probabilities (V) in fp32 — the same
dequantisation, distributed over the product, so the int8 rows go to
the MXU exactly.  The slot's scales of this layer reach the kernel as
an SMEM block [pages_per_slot, n_kv].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# key rows of one product: the MXU's tile on every TPU generation
_BLOCK_ROWS = 128
# VMEM the kernel's own buffers may take (double-buffered K and V
# blocks, query and output tiles, fp32 accumulators); the compiler is
# given twice that for the score tiles it keeps beside them
_VMEM_BUDGET = 12 * 2 ** 20
# heads whose products are laid out in one basic block, for the scheduler
# to overlap one head's softmax with the next one's products (on the chip
# 1 -> 8 took a call from 0.66 to 0.28 ms at C = 1 and 32 kv heads; 32
# gave no more)
_HEAD_UNROLL = 8


def _interpret():
    # interpret mode is the CPU backend's (the tests'); never a chip's
    return jax.default_backend() != "tpu"


def first_page(pos, page_size, window):
    """The logical page that holds the oldest row a sliding-window lane
    at depth `pos` may attend, row max(pos - window + 1, 0): where a
    window layer's walk STARTS.  Arithmetic for numpy vectors and int32
    scalars alike, as pages_walked."""
    low = pos - (window - 1)
    return (low - low * (low < 0)) // page_size


def pages_walked(pos, q_len, page_size, pages_per_slot, window=0):
    """Pages of its table a slot at depth `pos` walks for `q_len` query
    lanes: up to the frontier page (pos + q_len - 1) // page_size, at
    least one and never past the table; with a `window` from the
    window's first page (first_page) and not from page 0.  Plain
    arithmetic, so it serves a numpy vector on the host (the batcher's
    `kv_pages_walked`) and an int32 scalar in the kernel (the walk's
    bound) alike."""
    n = (pos + (q_len - 1)) // page_size + 1
    if window:
        n = n - first_page(pos, page_size, window)
    n = n + (1 - n) * (n < 1)
    return n - (n - pages_per_slot) * (n > pages_per_slot)


def _blocking(n_kv, rows, page_size, head_dim, kv_itemsize, q_itemsize,
              pages_per_slot, vmem_budget=_VMEM_BUDGET):
    """(hb, T): kv heads a grid step takes and pages a block holds.
    T pages make one 128-row key tile; hb is the largest divisor of
    n_kv whose double-buffered K and V blocks, query and output tiles
    and fp32 softmax state fit the budget — all of n_kv at serving
    shapes.  From the shapes and the itemsizes alone."""
    T = max(1, min(pages_per_slot, _BLOCK_ROWS // page_size))

    def need(hb):
        kv = 2 * 2 * T * hb * page_size * head_dim * kv_itemsize
        q_and_out = 2 * 2 * hb * rows * head_dim * q_itemsize
        state = hb * rows * (head_dim + 2 * 128) * 4
        return kv + q_and_out + state
    for hb in range(n_kv, 0, -1):
        if n_kv % hb == 0 and need(hb) <= vmem_budget:
            return hb, T
    return 1, T


def _kernel(pt_ref, pos_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest, scale,
            page_size, group, q_len, hb, T, quant, block_length, window=0):
    if quant:
        ks_ref, vs_ref, *rest = rest
    o_ref, kbuf, vbuf, sem, acc_ref, m_ref, l_ref, buf_ref = rest
    ps = page_size
    P_slot = pt_ref.shape[1]
    rows, d = q_ref.shape[2], q_ref.shape[3]
    nh = k_hbm.shape[2] // hb
    n_items = pl.num_programs(0)
    w = pl.program_id(0)
    b, hblk = w // nh, w % nh
    pos = pos_ref[b]
    layer = layer_ref[0]
    loop = jax.lax.fori_loop

    if window:
        # a sliding-window layer: the walk covers the pages that hold
        # rows pos - window + 1 .. pos + q_len - 1 and no others, and the
        # table is the slot's ring (logical page p in entry p mod P_slot;
        # a table of the whole depth never wraps)
        def walk(slot):
            return pages_walked(pos_ref[slot], q_len, ps, P_slot, window)

        def entry(slot, j):
            return (first_page(pos_ref[slot], ps, window) + j) % P_slot
    else:
        def walk(slot):
            return pages_walked(pos_ref[slot], q_len, ps, P_slot)

        def entry(slot, j):
            return j

    def copies(slot, heads, j, buf, t):
        """The K and V transfers of page j of `slot`'s walk into place t
        of buffer `buf` (a wait needs only the destination's size, so
        it may name any page)."""
        src = (pt_ref[slot, entry(slot, j)], layer, pl.ds(heads * hb, hb))
        return (pltpu.make_async_copy(k_hbm.at[src], kbuf.at[buf, t],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[src], vbuf.at[buf, t],
                                      sem.at[1, buf]))

    def start_block(slot, heads, i, buf, valid):
        """Start block i of `slot`: its live pages' transfers, and zeros
        in the places no page lands in — their scores are masked, but
        the probabilities (0.0) still multiply what V holds there."""
        live = jnp.clip(walk(slot) - i * T, 0, T) * valid

        def start(t, c):
            for copy in copies(slot, heads, i * T + t, buf, t):
                copy.start()
            return c

        def blank(t, c):
            vbuf[buf, t] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)
            return c
        loop(0, live, start, 0)
        loop(live, T * valid, blank, 0)

    n_pages = walk(b)
    n_blocks = (n_pages + (T - 1)) // T

    @pl.when(w == 0)
    def _():
        buf_ref[0] = 0
        start_block(b, hblk, 0, 0, 1)

    buf0 = buf_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    # query row r = c*group + g sits at global position pos + c; key
    # column c' of block i sits at global position (i*T)*ps + c'
    qpos = pos + jax.lax.broadcasted_iota(
        jnp.int32, (rows, T * ps), 0) // group
    if block_length > 1:
        # block-causal: a row sees to the end of its own block
        qpos = qpos // block_length * block_length + (block_length - 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, T * ps), 1)
    if window:
        # column c' of block i sits at position (first + i*T)*ps + c'
        col = col + first_page(pos, ps, window) * ps
    col_page = jax.lax.broadcasted_iota(jnp.int32, (1, T * ps), 1) // ps

    def page_scales(ref, i, hh):
        """[1, T*ps]: each column's page scale for head hh of block i."""
        def place(t, row):
            j = entry(b, jnp.minimum(i * T + t, P_slot - 1))
            return jnp.where(col_page == t, ref[0, j, hblk * hb + hh], row)
        return loop(0, T, place, jnp.zeros((1, T * ps), jnp.float32),
                    unroll=True)

    def block(i, carry):
        buf = (buf0 + i) % 2
        # the next block of the flattened walk: this step's, or the
        # first of the next grid step
        more = i + 1 < n_blocks
        nxt = jnp.minimum(w + 1, n_items - 1)
        start_block(jnp.where(more, b, nxt // nh),
                    jnp.where(more, hblk, nxt % nh),
                    jnp.where(more, i + 1, 0), 1 - buf,
                    (more | (w + 1 < n_items)).astype(jnp.int32))

        def wait(t, c):
            for copy in copies(b, hblk, 0, buf, t):
                copy.wait()
            return c
        loop(0, jnp.minimum(n_pages - i * T, T), wait, 0)
        visible = (i * (T * ps) + col) <= qpos
        if window:
            visible &= qpos - (i * (T * ps) + col) < window

        def head(hh, carry):
            q = q_ref[0, hh]                              # [rows, d]
            k = kbuf[buf, :, hh]                          # [T, ps, d]
            v = vbuf[buf, :, hh]
            if quant:
                k = k.astype(jnp.float32).astype(q.dtype)
                v = v.astype(jnp.float32).astype(q.dtype)
            k = k.reshape(T * ps, d)
            v = v.reshape(T * ps, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quant:
                s = s * page_scales(ks_ref, i, hh)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[hh]                            # [rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[hh] = l_ref[hh] * alpha + jnp.sum(p, axis=1,
                                                    keepdims=True)
            if quant:
                p = p * page_scales(vs_ref, i, hh)
            acc_ref[hh] = acc_ref[hh] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[hh] = m_new
            return carry

        # _HEAD_UNROLL heads to a basic block: traced once, unrolled by
        # the lowering
        u = max(n for n in range(1, _HEAD_UNROLL + 1) if hb % n == 0)
        return loop(0, hb // u, lambda g, c: loop(
            0, u, lambda k, c: head(g * u + k, c), c, unroll=True), carry)

    loop(0, n_blocks, block, 0)
    buf_ref[0] = (buf0 + n_blocks) % 2
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def supports(pool_shape, interpret=None) -> bool:
    """Shape predicate for ops.paged_attention's kernel-or-twin choice:
    a transfer is [hb, page_size, head_dim] of the pool, whole in its
    two minor dims, so Mosaic needs head_dim on whole 128-lane tiles and
    page_size on whole 8-row sublane tiles.  Interpret mode (CPU tests)
    has no tiling.  No for latent (MLA) rows, a pool [P, L, ps, width]
    without a head axis and without a V pool: this kernel reads K and V
    of equal head size, a latent row is key (all of it) and value (its
    first kv_lora_rank columns) at once — latent_attention.py is theirs."""
    if len(pool_shape) != 5:
        return False
    interp = _interpret() if interpret is None else interpret
    ps, d = pool_shape[3], pool_shape[4]
    return interp or (d % 128 == 0 and ps % 8 == 0)


def paged_attention(q, k_pool, v_pool, page_table, pos, layer,
                    k_scale=None, v_scale=None, scale=None,
                    interpret=None, vmem_budget=_VMEM_BUDGET,
                    block_length=1, window=0):
    """q: [B, C, h, d]; pools [P, L, n_kv, ps, d]; page_table
    [B, P_slot] int32; pos [B] int32.  Returns [B, C, h, d] in
    q.dtype.  `block_length` L > 1: the block-causal mask (the walk's
    bound, pages_walked, already reaches pos + C - 1: the caller keeps
    pos and C multiples of L).  `window` W > 0: a sliding-window layer:
    lane c sees rows pos + c - W + 1 .. pos + c, the walk covers the
    pages that hold such rows and no others, and the table is the
    slot's ring of at least ops.ring_pages(W, C, page_size) entries.
    Raises ValueError for shapes `supports`
    refuses —
    ops.paged_attention asks the predicate first and takes the jnp
    twin for those.  `vmem_budget` is the tests' handle on the head
    blocking; callers leave it alone."""
    interp = _interpret() if interpret is None else interpret
    h, d = q.shape[2:]
    n_kv, ps = k_pool.shape[2:4]
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
    if window and block_length > 1:
        raise ValueError("no sliding window under the block-causal mask")
    if window:
        from .. import ring_pages
        if ring_pages(window, q.shape[1], ps) > page_table.shape[1]:
            raise ValueError(
                f"a window of {window} rows under {q.shape[1]} lanes "
                f"straddles more pages than the table's "
                f"{page_table.shape[1]}")
    posv = jnp.asarray(pos, jnp.int32)
    if posv.ndim == 0:
        posv = jnp.broadcast_to(posv, q.shape[:1])
    # the layer is an operand, not a constant of the kernel: a model's
    # layers share ONE trace and ONE lowering of it in a step program
    return _call(q, k_pool, v_pool, jnp.asarray(page_table, jnp.int32),
                 posv, jnp.asarray(layer, jnp.int32).reshape(1),
                 k_scale if quant else None, v_scale if quant else None,
                 scale=float(scale if scale is not None else d ** -0.5),
                 interpret=bool(interp), vmem_budget=int(vmem_budget),
                 block_length=int(block_length), window=int(window))


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "vmem_budget",
                                    "block_length", "window"))
def _call(q, k_pool, v_pool, pt, pos, layer, k_scale, v_scale, *, scale,
          interpret, vmem_budget, block_length=1, window=0):
    B, C, h, d = q.shape
    P, L, n_kv, ps, _ = k_pool.shape
    P_slot = pt.shape[1]
    group = h // n_kv
    quant = k_scale is not None

    # pre-arrange q per kv head with rows row = c*group + g, padded to
    # whole sublane tiles — the kernel then reads a ready [rows, d]
    # tile per head (an in-kernel sublane reshape would be a relayout)
    R = C * group
    rows = -(-R // 8) * 8
    qr = q.reshape(B, C, n_kv, group, d).transpose(0, 2, 1, 3, 4) \
        .reshape(B, n_kv, R, d)
    if rows != R:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - R), (0, 0)))
    hb, T = _blocking(n_kv, rows, ps, d, k_pool.dtype.itemsize,
                      q.dtype.itemsize, P_slot, vmem_budget)
    nh = n_kv // hb

    def tile_ix(w, *prefetched):
        return (w // nh, w % nh, 0, 0)

    in_specs = [pl.BlockSpec((1, hb, rows, d), tile_ix),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [qr, k_pool, v_pool]
    if quant:
        # this layer's scale of every page the slot maps: B*P_slot*n_kv
        # floats, noise beside the pool; one slot's block sits in SMEM
        for scales in (k_scale, v_scale):
            operands.append(jnp.take(scales[:, layer[0]], pt, axis=0)
                            .astype(jnp.float32))
            in_specs.append(pl.BlockSpec(
                (1, P_slot, n_kv), lambda w, *prefetched: (w // nh, 0, 0),
                memory_space=pltpu.SMEM))
    kern = functools.partial(_kernel, scale=scale, page_size=ps,
                             group=group, q_len=C, hb=hb, T=T, quant=quant,
                             block_length=block_length, window=window)
    with x64_off():
        out = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B * nh,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, hb, rows, d), tile_ix),
                scratch_shapes=[
                    pltpu.VMEM((2, T, hb, ps, d), k_pool.dtype),
                    pltpu.VMEM((2, T, hb, ps, d), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((hb, rows, d), jnp.float32),
                    pltpu.VMEM((hb, rows, 1), jnp.float32),
                    pltpu.VMEM((hb, rows, 1), jnp.float32),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, n_kv, rows, d), q.dtype),
            # the first block of a grid step is started by the step
            # before it: the steps run in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=2 * _VMEM_BUDGET),
            name="paged_attention",
            interpret=interpret,
        )(pt, pos, layer, *operands)
    return out[:, :, :R].reshape(B, n_kv, C, group, d) \
        .transpose(0, 2, 1, 3, 4).reshape(B, C, h, d)
