"""paddle_tpu.ops — the hot-kernel layer.

Reference: `paddle/phi/kernels/fusion/gpu/` (fused_attention, fused_rms_norm,
fused_rope, flash_attn via external lib) — hand-written CUDA.

TPU-native: each op has an XLA reference implementation (jnp) and, where it
pays, a Pallas TPU kernel (paddle_tpu/ops/pallas/).  Dispatch picks Pallas on
TPU backends and XLA elsewhere; `set_attention_backend` forces a choice
(used by nn.functional.sdp_kernel).  All functions here take/return raw
jax.Arrays — the Tensor wrapper layer calls them through dispatch.run so
eager autograd and jit tracing both work.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

__all__ = ["attention", "cached_attention", "rms_norm", "layer_norm",
           "fused_add_rms_norm", "xla_fused_add_rms_norm",
           "rope", "apply_rope",
           "paged_attention", "xla_paged_attention", "paged_kv_update",
           "latent_kv_update", "latent_paged_attention",
           "xla_latent_paged_attention", "latent_pages_walked",
           "latent_walk_bound", "yarn_inv_freq", "yarn_mscale",
           "xla_apply_rope",
           "swiglu", "get_attention_backend", "set_attention_backend",
           "kernel_mesh_scope",
           "gqa_scores", "gqa_weighted_v",
           "quant_matmul", "xla_quant_matmul",
           "pack_int4", "unpack_int4", "dequant_weight"]

_attention_backend = "auto"  # auto | pallas | xla


def get_attention_backend():
    return _attention_backend


def set_attention_backend(b):
    global _attention_backend
    _attention_backend = b


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_KERNEL_MESH: list = []     # (mesh, batch axes, head axes), innermost last


class kernel_mesh_scope:
    """Entered by a trainer while it traces a program for a mesh of more
    than one device.  GSPMD cannot partition a Mosaic kernel (the
    lowering raises NotImplementedError), so inside the scope dispatch
    runs flash attention, rms norm and rope under `jax.shard_map` over
    `mesh`, as optimizer/jit_update.py does for the fused AdamW update:
    dim 0 over `batch_axes`, the heads dim over the tensor-parallel
    axis "mp", every other dim whole.  Axes that are absent, of size 1, or do not divide
    their dim stay out of the spec (the kernel then runs replicated
    over them), and each kernel's `supports` is asked about the shapes
    ONE device sees.  A kernel dispatched here without a layout (paged
    attention, quant matmul) is called as it is and the lowering's
    refusal reaches the caller."""

    def __init__(self, mesh, batch_axes=("dp", "sharding")):
        live = [a for a in mesh.axis_names if mesh.shape[a] > 1]
        self._entry = (mesh, tuple(a for a in live if a in batch_axes),
                       ("mp",) if "mp" in live else ())

    def __enter__(self):
        _KERNEL_MESH.append(self._entry)
        return self

    def __exit__(self, *exc):
        _KERNEL_MESH.pop()
        return False


def _run_kernel(kernel, supports, args, layouts, out_layouts):
    """`kernel(*args)` if `supports(*shapes)` holds for the shapes one
    device sees, else None (the caller takes the XLA twin).  A layout
    names each dim of an argument or result: "b" batch, "h" heads, "."
    whole.  Outside a `kernel_mesh_scope` the kernel is called as it
    is; inside, per device under shard_map."""
    if not _KERNEL_MESH:
        return kernel(*args) if supports(*(a.shape for a in args)) else None
    mesh, batch_axes, head_axes = _KERNEL_MESH[-1]
    if any(a.ndim != len(lay) for a, lay in zip(args, layouts)):
        return None

    def fits(axes, letter):
        n = math.prod(mesh.shape[a] for a in axes)
        return all(a.shape[i] % n == 0 for a, lay in zip(args, layouts)
                   for i, c in enumerate(lay) if c == letter)

    axes_of = {"b": batch_axes if fits(batch_axes, "b") else (),
               "h": head_axes if fits(head_axes, "h") else (),
               ".": ()}

    def spec(lay):
        return PartitionSpec(*(axes_of[c] or None for c in lay))

    def local(a, lay):
        return tuple(d // math.prod(mesh.shape[x] for x in axes_of[c])
                     for d, c in zip(a.shape, lay))

    if not supports(*(local(a, lay) for a, lay in zip(args, layouts))):
        return None
    outs = tuple(spec(lay) for lay in out_layouts)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=tuple(spec(lay) for lay in layouts),
        out_specs=outs if len(outs) > 1 else outs[0],
        check_vma=False)(*args)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def gqa_scores(q, k):
    """q·kᵀ logits [b, h, sq, sk] (fp32) for q [b, sq, h, d] against
    k [b, sk, hk, d] where hk may divide h (GQA/MQA) — WITHOUT
    materialising repeated KV: the group is folded into an extra q dim and
    the contraction batches over the kv head, so KV HBM traffic stays
    ∝ num_kv_heads."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hk == h:
        return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32)
    qg = q.reshape(b, sq, hk, h // hk, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    return logits.reshape(b, h, sq, sk)


def gqa_weighted_v(w, v):
    """Σₖ w·v → [b, h, sq, d] for weights w [b, h, sq, sk] against
    v [b, sk, hk, d] with hk dividing h; GQA handled as in gqa_scores."""
    b, h, sq, sk = w.shape
    hk, d = v.shape[2], v.shape[3]
    if hk == h:
        return jnp.einsum("bhqk,bkhd->bhqd", w, v)
    wg = w.reshape(b, hk, h // hk, sq, sk)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", wg, v)
    return out.reshape(b, h, sq, d)


def block_end(pos, block_length):
    """Last position of the block of `block_length` that holds position
    `pos` (blocks aligned at 0): what a BLOCK-causal query at `pos` sees
    up to — every token of its own block, both ways, and all of every
    earlier block (generation by diffusion over blocks).  Callers ask it
    only for block_length > 1: block length 1 is the causal mask, and
    each attention traces for it the expression it always traced."""
    return pos // block_length * block_length + (block_length - 1)


def window_visible(k_idx, q_pos, window, ring_rows):
    """Which rows of a buffer of `ring_rows` rows a SLIDING-WINDOW query
    at position `q_pos` sees: buffer row r holds position
    k = q_pos - (q_pos - r) mod ring_rows, the newest position <= q_pos
    that lands there (a ring: position p lies in row p mod ring_rows; a
    buffer that holds the whole depth is the ring that never wraps), and
    the query sees it iff k >= 0 and q_pos - k < window: the token itself
    and the window - 1 before it.  The caller keeps window + lanes - 1
    rows a slot, so no row a lane may see has been overwritten."""
    k_pos = q_pos - (q_pos - k_idx) % ring_rows
    return (k_pos >= 0) & (q_pos - k_pos < window)


def _check_window(window, block_length):
    if window and block_length > 1:
        raise ValueError("a sliding window under the block-causal mask "
                         "(block_length > 1) is not defined here")


def xla_attention(q, k, v, mask=None, causal=False, scale=None,
                  dropout_p=0.0, block_length=1, window=0):
    """Reference math of phi flash_attn kernel, XLA-fused.
    q/k/v: [b, s, h, d] (paddle flash-attn layout).  fp32 softmax.
    `block_length` > 1 (with `causal`): the block-causal mask, key j
    visible to query i iff j // L <= i // L.  `window` W > 0 (with
    `causal`): key j visible to query i iff j <= i and i - j < W."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = gqa_scores(q, k) * s
    _check_window(window, block_length)
    if causal and window:
        row = jnp.arange(sq, dtype=jnp.int32)[:, None] + (sk - sq)
        col = jnp.arange(sk, dtype=jnp.int32)[None]
        cm = (col <= row) & (row - col < window)
        logits = jnp.where(cm[None, None], logits, -1e30)
    elif causal and block_length > 1:
        row = jnp.arange(sq, dtype=jnp.int32)[:, None] + (sk - sq)
        cm = jnp.arange(sk, dtype=jnp.int32)[None] \
            <= block_end(row, block_length)
        logits = jnp.where(cm[None, None], logits, -1e30)
    elif causal:
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm[None, None], logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(logits.dtype)
    w = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0:
        from ..framework.random import next_key
        keep = jax.random.bernoulli(next_key(), 1.0 - dropout_p, w.shape)
        w = w * keep / (1.0 - dropout_p)
    out = gqa_weighted_v(w.astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def cached_attention(q, k_cache, v_cache, q_pos0, scale=None,
                     block_length=1, window=0):
    """Incremental-decode attention against a fixed-size KV ring buffer.

    q: [b, s_new, h, d] (queries for the tokens being appended);
    k_cache/v_cache: [b, S_max, h_kv, d] with positions < q_pos0 + s_new
    valid; q_pos0: int32 scalar — global position of q's first token —
    or a PER-SLOT [b] vector (continuous batching: each sequence sits
    at its own depth).  Query i of slot b attends cache slots
    j <= q_pos0[b] + i; with `block_length` L > 1 (a model that
    generates by diffusion over blocks) slots j <= block_end(q_pos0[b] +
    i, L): its whole block and every earlier one; with `window` W > 0
    (a sliding-window layer) the rows window_visible() names: the
    buffer's S_max rows are then read as a RING (row r holds the newest
    position congruent to r), which a buffer of the whole depth is too.

    The vector form with s_new > 1 is the CHUNKED-PREFILL contract
    (inference/serving.py): a mixed batch where some slots decode one
    token while others consume a multi-token prompt chunk shares this
    one call — each slot's causal frontier is its own pos[b]+lane.
    Lanes past a slot's valid count rely on the caller masking/
    overwriting their KV before any later query can attend them (the
    serving scan's pad-lane discipline).

    Reference: `python/paddle/incubate/nn/functional/
    block_multihead_attention.py` (paged-KV decode).  TPU-native
    design: a ring buffer with STATIC S_max (XLA needs static shapes)
    and one batched masked matmul over every row the buffer could
    hold.  That is the right trade for the DENSE cache only: a kernel
    with one grid step per (slot, head, block) is bound by its step
    count at q_len==1 (measured on v5e: the first paged kernel, 50,688
    steps a call, took 11.9 ms where XLA's gather twin took 5-6), but
    one that walks a slot's live pages inside a grid step, all heads a
    transfer, is not — ops.paged_attention at the same shapes takes
    0.3 ms a call (ISSUE 26, PERF.md section 6)."""
    b, sq, h, d = q.shape
    sk = k_cache.shape[1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = gqa_scores(q, k_cache) * s
    pos0 = jnp.asarray(q_pos0, jnp.int32)
    _check_window(window, block_length)
    if pos0.ndim == 0:
        pos_q = pos0 + jnp.arange(sq, dtype=jnp.int32)[:, None]
        if block_length > 1:
            pos_q = block_end(pos_q, block_length)
        if window:
            valid = window_visible(jnp.arange(sk, dtype=jnp.int32)[None, :],
                                   pos_q, window, sk)
        else:
            valid = jnp.arange(sk, dtype=jnp.int32)[None, :] <= pos_q
        logits = jnp.where(valid[None, None], logits, -1e30)
    else:
        # PER-SLOT positions ([b] vector): each sequence in the batch
        # sits at its own depth — the continuous-batching decode form
        pos_q = pos0[:, None] + jnp.arange(sq, dtype=jnp.int32)[None]
        if block_length > 1:
            pos_q = block_end(pos_q, block_length)
        if window:
            valid = window_visible(
                jnp.arange(sk, dtype=jnp.int32)[None, None, :],
                pos_q[:, :, None], window, sk)
        else:
            valid = jnp.arange(sk, dtype=jnp.int32)[None, None, :] \
                <= pos_q[:, :, None]
        logits = jnp.where(valid[:, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = gqa_weighted_v(w.astype(v_cache.dtype), v_cache)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged KV (ISSUE 7): fixed-size page pool + per-slot page table
# ---------------------------------------------------------------------------
def _dequant_pages(pages, scales):
    """pages [..., n_kv, ps, hd] int8 × per-page per-head scales
    [..., n_kv] → fp32."""
    return pages.astype(jnp.float32) * scales[..., None, None]


def ring_pages(window, q_len, page_size):
    """Pages of the RING a slot keeps in a sliding-window layer's pool:
    the window + q_len - 1 rows that the lanes of one step may attend
    (lane c at position pos + c sees rows pos + c - window + 1 ..
    pos + c) can straddle one page more than they fill.  Row r of a slot
    lies in ring page (r // page_size) mod ring_pages; a step's write
    then overwrites exactly rows that have left every later lane's
    window, and the walk of one step meets no ring page twice."""
    return -(-(window + q_len - 1) // page_size) + 1


def paged_kv_update(k_pool, v_pool, k_scale, v_scale, page_table, pos,
                    k_new, v_new, layer, ring=False):
    """Write one step's K/V rows into the paged pool (the paged twin of
    the dense path's per-slot dynamic_update_slice).

    k_pool/v_pool: [P, L, n_kv, ps, hd] (int8 pools carry per-page
    per-head scales [P, L, n_kv] fp32; None otherwise); page_table
    [B, P_slot] int32 (entry 0 = reserved null page); pos [B] int32;
    k_new/v_new [B, C, n_kv, hd] in the compute dtype; layer: python
    int.  Returns (k_pool, v_pool, k_scale, v_scale).

    Only the WINDOW of pages overlapping rows [pos, pos+C) is gathered,
    row-updated (contiguous DUS — bit-identical rows to the dense
    cache write) and scattered back; untouched window pages scatter
    their ORIGINAL bytes, so shared/read-only pages are never
    re-encoded (int8 requant drift stays confined to pages actually
    being written).  int8 pages requantize against the page's new
    running amax, so a page's scale is always consistent with every
    row it holds.

    The pools (and scales) are indexed WHOLE, by (page, layer): gather
    `pool[ids, layer]`, scatter `pool.at[ids, layer].set`.  Nothing
    slices or writes back along the layer axis, because the layout XLA
    gives the carried pools follows from how they are indexed, and the
    Pallas kernel (ops/pallas/paged_attention.py) takes its `pl.ANY`
    pools in the default dimension order only.  With `pool[:, layer]`
    and `.at[:, layer].set` XLA carries the pools LAYER-major and
    re-lays both out in front of every layer's kernel call (a 1.65 GB
    copy a pool a layer in the 7B serve cell, 65 % of its busy time);
    with the rows scattered one by one (`pool.at[page, layer, :, row]`,
    as ops.latent_kv_update writes its kernel-less pool) it prefers
    page rows above kv heads, and copies as much (ISSUE 28;
    tests/test_chip_compile.py holds the compiled program to this).

    `ring`: the table is a slot's RING (ring_pages() entries of a
    sliding-window layer's pool): logical page p lies in entry
    p mod P_slot, so the window of pages wraps instead of clamping."""
    P, L, n_kv, ps, hd = k_pool.shape
    B, C = k_new.shape[0], k_new.shape[1]
    P_slot = page_table.shape[1]
    n_t = -(-C // ps) + 1          # pages a C-row write can straddle
    quant = k_pool.dtype == jnp.int8
    pos = jnp.asarray(pos, jnp.int32)
    if ring:
        if n_t > P_slot:
            raise ValueError(f"a write of {C} rows straddles {n_t} pages, "
                             f"the ring has {P_slot}")
        p0 = pos // ps
        win = p0[:, None] + jnp.arange(n_t, dtype=jnp.int32)[None]
        ids = jnp.take_along_axis(page_table, win % P_slot, axis=1)
    else:
        p0 = jnp.clip(pos // ps, 0, max(P_slot - n_t, 0))
        win = jnp.clip(p0[:, None]
                       + jnp.arange(n_t, dtype=jnp.int32)[None],
                       0, P_slot - 1)                        # [B, n_t]
        ids = jnp.take_along_axis(page_table, win, axis=1)   # [B, n_t]
    rel0 = pos - p0 * ps
    start = win * ps                # window pages' first logical row
    touched = (start < (pos + C)[:, None]) \
        & ((start + ps) > pos[:, None])                      # [B, n_t]

    def upd(pool, scales, rows):
        raw = pool[ids, layer]                    # [B, n_t, n_kv, ps, hd]
        if quant:
            sc = scales[ids, layer]                       # [B, n_t, n_kv]
            w = _dequant_pages(raw, sc).astype(rows.dtype)
        else:
            w = raw
        # the window as per-head logical rows [B, n_kv, n_t*ps, hd]
        w = w.transpose(0, 2, 1, 3, 4).reshape(B, n_kv, n_t * ps, hd)

        def dus(buf, r, r0):
            z = jnp.zeros((), jnp.int32)
            return jax.lax.dynamic_update_slice(
                buf, r.astype(buf.dtype), (z, r0, z))
        w = jax.vmap(dus)(w, rows.transpose(0, 2, 1, 3), rel0)
        w = w.reshape(B, n_kv, n_t, ps, hd).transpose(0, 2, 1, 3, 4)
        m = touched[:, :, None, None, None]
        if quant:
            amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=(3, 4))
            sc_new = jnp.maximum(amax, 1e-8) / 127.0      # [B, n_t, n_kv]
            q8 = jnp.clip(jnp.round(
                w.astype(jnp.float32) / sc_new[..., None, None]),
                -127, 127).astype(jnp.int8)
            pages_out = jnp.where(m, q8, raw)
            sc_out = jnp.where(touched[..., None], sc_new, sc)
            scales = scales.at[ids, layer].set(sc_out)
        else:
            pages_out = jnp.where(m, w.astype(pool.dtype), raw)
        return pool.at[ids, layer].set(pages_out), scales

    k_pool, k_scale = upd(k_pool, k_scale, k_new)
    v_pool, v_scale = upd(v_pool, v_scale, v_new)
    return k_pool, v_pool, k_scale, v_scale


def _check_paged_args(q, k_pool, k_scale, v_scale):
    """Shared argument validation for both paged-attention paths —
    raised HERE so a bad call fails identically on and off TPU."""
    n_kv = k_pool.shape[2]
    if q.shape[2] % n_kv:
        raise ValueError(f"q heads {q.shape[2]} not a multiple of kv "
                         f"heads {n_kv}")
    if k_pool.dtype == jnp.int8 and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV pool needs k_scale/v_scale")


def xla_paged_attention(q, k_pool, v_pool, page_table, pos, layer,
                        k_scale=None, v_scale=None, scale=None,
                        block_length=1, window=0):
    """jnp twin of pallas.paged_attention: materialize each slot's
    logical KV view with a `take`-based gather over the page table,
    dequant (int8 pools), then EXACTLY the dense cached_attention math
    — masked rows exp to 0.0 exactly, so the padded logical depth
    (P_slot*ps vs the dense cache_len) cannot perturb the softmax and
    the paged path stays bit-identical to the dense one off-TPU.
    `window` W > 0: a sliding-window layer; the table's P_slot pages are
    the slot's ring (logical page p in entry p mod P_slot: a table of
    the whole depth never wraps) and cached_attention reads the gathered
    rows as one."""
    _check_paged_args(q, k_pool, k_scale, v_scale)
    B = q.shape[0]
    P, L, n_kv, ps, hd = k_pool.shape
    P_slot = page_table.shape[1]
    quant = k_pool.dtype == jnp.int8

    def gather(pool, scales):
        lg = jnp.take(pool[:, layer], page_table, axis=0)
        if quant:
            sc = jnp.take(scales[:, layer], page_table, axis=0)
            lg = _dequant_pages(lg, sc).astype(q.dtype)
        # [B, P_slot, n_kv, ps, hd] -> logical rows [B, P_slot*ps, ...]
        return lg.transpose(0, 1, 3, 2, 4).reshape(
            B, P_slot * ps, n_kv, hd)

    return cached_attention(q, gather(k_pool, k_scale),
                            gather(v_pool, v_scale), pos, scale,
                            block_length, window)


def paged_attention(q, k_pool, v_pool, page_table, pos, layer,
                    k_scale=None, v_scale=None, scale=None,
                    block_length=1, window=0):
    """Decode attention against the paged KV pool: Pallas kernel on TPU
    (it copies each slot's LIVE pages of this layer itself, all kv
    heads of a page a transfer, and applies an int8 pool's scales
    inside — see ops/pallas/paged_attention.py), `take`-gather twin
    elsewhere and for shapes the kernel's `supports` predicate refuses.
    The choice is made from the shapes alone: whatever the kernel
    raises — a lowering or compiler refusal included — reaches the
    caller.  `block_length`: as ops.cached_attention's.  `window` W > 0:
    a sliding-window layer against its ring (xla_paged_attention); the
    kernel's walk then starts at the window's first page."""
    _check_paged_args(q, k_pool, k_scale, v_scale)
    _check_window(window, block_length)
    if _on_tpu():
        from .pallas import paged_attention as _k
        if _k.supports(k_pool.shape):
            return _k.paged_attention(q, k_pool, v_pool, page_table, pos,
                                      layer, k_scale, v_scale, scale,
                                      block_length=block_length,
                                      window=window)
    return xla_paged_attention(q, k_pool, v_pool, page_table, pos,
                               layer, k_scale, v_scale, scale,
                               block_length, window)


# ---------------------------------------------------------------------------
# latent rows in the paged pool (MLA): ONE pool, no V pool
# ---------------------------------------------------------------------------
# A token's row in a layer is [c | k_r]: the normed kv latent and the
# rotated shared key, `rank + rope` wide.  The pool is page-major like
# the K/V pools ([P, L, ps, W]; page 0 the null page), so the batcher's
# page copy / export / import programs serve it unchanged.
LATENT_BLOCK_ROWS = 512     # key rows one step of the attention walk takes


def latent_kv_update(pool, page_table, pos, rows, layer):
    """Write one step's latent rows: pool [P, L, ps, W]; page_table
    [B, P_slot]; pos [B]; rows [B, C, W]; layer a python int.  Lane c of
    slot b lands at logical row pos[b] + c of its table — ONE scatter of
    B*C rows straight into the carried pool (no window of pages is read
    back, nothing pool-sized is copied).  Lanes of free slots meet in
    the null page, whose content is junk by contract."""
    ps = pool.shape[2]
    B, C = rows.shape[:2]
    at = jnp.asarray(pos, jnp.int32)[:, None] \
        + jnp.arange(C, dtype=jnp.int32)[None]                   # [B, C]
    slot_page = jnp.clip(at // ps, 0, page_table.shape[1] - 1)
    page = jnp.take_along_axis(page_table, slot_page, axis=1)    # [B, C]
    return pool.at[page, layer, at % ps].set(rows.astype(pool.dtype))


def latent_pages_walked(pos, q_len, page_size, pages_per_slot):
    """Pages of ITS table each slot's attention reads in one
    xla_latent_paged_attention call (numpy, on the host: the batcher's
    `kv_pages_walked` where the XLA walk runs): every slot walks whole
    blocks of LATENT_BLOCK_ROWS key rows up to the DEEPEST slot's frontier
    — the XLA walk cannot stop early for a shallow slot, which is what the
    kernel (ops/pallas/latent_attention.py: one slot a grid step) adds."""
    import numpy as np
    pb = max(1, LATENT_BLOCK_ROWS // page_size)
    frontier = int(np.max(pos)) + q_len - 1
    blocks = min(frontier // (pb * page_size) + 1, -(-pages_per_slot // pb))
    return np.full(np.shape(pos), min(blocks * pb, pages_per_slot), np.int64)


def _latent_kernel(pool_shape, rank, dtype):
    """The Pallas module where latent_paged_attention takes the kernel for
    such a pool, None where it takes the XLA walk: from the backend and
    the shapes alone."""
    if _on_tpu():
        from .pallas import latent_attention as _k
        if _k.supports(pool_shape, rank, dtype):
            return _k
    return None


def latent_walk_bound(width, rank, dtype):
    """f(pos, q_len, page_size, pages_per_slot): the pages of its table
    each slot's attention walks in the program latent_paged_attention
    runs for a pool of rows `width` wide (the first `rank` the latent)
    — the slot's own frontier where the kernel runs, latent_pages_walked
    where the XLA walk does (Llama.kv_row_spec hands it to the batcher)."""
    from .pallas.paged_attention import pages_walked

    def bound(pos, q_len, page_size, pages_per_slot):
        walk = pages_walked if _latent_kernel(
            (1, 1, page_size, width), rank, dtype) else latent_pages_walked
        return walk(pos, q_len, page_size, pages_per_slot)
    return bound


def xla_latent_paged_attention(q_lat, q_rope, pool, page_table, pos, layer,
                               scale):
    """jnp twin of pallas.latent_attention: a walk over blocks of
    LATENT_BLOCK_ROWS rows (gathered by page table for ALL slots) with an
    fp32 running softmax, as many blocks as the DEEPEST slot needs.
    Scores of a whole 4 k-row table at once would be [B, C*h, rows] fp32:
    2 GB at 64 slots x 32 lanes."""
    B, C, h, R = q_lat.shape
    P, L, ps, W = pool.shape
    P_slot = page_table.shape[1]
    pb = max(1, min(P_slot, LATENT_BLOCK_ROWS // ps))
    n_blocks = -(-P_slot // pb)
    rows_blk = pb * ps
    pos = jnp.asarray(pos, jnp.int32)
    q = jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)],
                        axis=-1).reshape(B, C * h, W)
    # the query lane of each of the C*h rows of the tile
    q_pos = pos[:, None] + jnp.repeat(jnp.arange(C, dtype=jnp.int32), h)[None]
    # table padded to whole blocks with the null page
    table = jnp.pad(page_table, ((0, 0), (0, n_blocks * pb - P_slot)))
    need = jnp.minimum((jnp.max(pos) + C - 1) // rows_blk + 1, n_blocks)

    def block(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(table, i * pb, pb, axis=1)
        rows = pool[ids, layer].reshape(B, rows_blk, W)
        s = jnp.einsum("bqw,bkw->bqk", q, rows.astype(q.dtype),
                       preferred_element_type=jnp.float32) * scale
        k_pos = i * rows_blk + jnp.arange(rows_blk, dtype=jnp.int32)
        s = jnp.where(k_pos[None, None, :] <= q_pos[:, :, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        fix = jnp.exp(m - m_new)
        l = l * fix + jnp.sum(p, axis=-1)
        acc = acc * fix[..., None] + jnp.einsum(
            "bqk,bkr->bqr", p.astype(q.dtype),
            rows[..., :R].astype(q.dtype),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((B, C * h), -1e30, jnp.float32)
    l0 = jnp.zeros((B, C * h), jnp.float32)
    acc0 = jnp.zeros((B, C * h, R), jnp.float32)
    # block 0 always holds row 0 <= every query's position, so l > 0
    _, l, acc = jax.lax.fori_loop(0, need, block, (m0, l0, acc0))
    return (acc / l[..., None]).astype(q_lat.dtype).reshape(B, C, h, R)


def latent_paged_attention(q_lat, q_rope, pool, page_table, pos, layer,
                           scale):
    """Absorbed MLA attention against the latent pool.

    q_lat [B, C, h, R]: each head's no-rope query carried into latent
    space (q_nope Wuk^T); q_rope [B, C, h, r] rotated; pool
    [P, L, ps, R + r]; query lane c of slot b sees rows j <= pos[b] + c.
    Returns u [B, C, h, R] fp32-accumulated in q_lat.dtype: the
    probability-weighted sum of the latents, which the caller carries
    back out through Wuv.  All heads share a row, so a slot's C*h
    queries are one [C*h, R + r] tile against its rows.

    Pallas kernel on TPU (one slot a grid step over THAT slot's live
    pages — see ops/pallas/latent_attention.py), the XLA walk elsewhere
    and for shapes the kernel's `supports` predicate refuses.  The choice
    is made from the backend and the shapes alone: whatever the kernel
    raises — a lowering or compiler refusal included — reaches the
    caller."""
    kernel = _latent_kernel(pool.shape, q_lat.shape[3], pool.dtype)
    if kernel is not None:
        return kernel.latent_attention(q_lat, q_rope, pool, page_table, pos,
                                       layer, scale)
    return xla_latent_paged_attention(q_lat, q_rope, pool, page_table, pos,
                                      layer, scale)


def attention(q, k, v, mask=None, causal=False, scale=None, dropout_p=0.0,
              block_length=1, window=0):
    """Flash kernel or XLA, chosen from the backend setting and the
    shapes (flash_attention.supports) — never from an exception: what
    the kernel raises reaches the caller.  A block-causal mask
    (`block_length` > 1) and a sliding window (`window` > 0) are XLA's:
    the flash kernel has the causal mask alone."""
    backend = _attention_backend
    if backend == "auto":
        backend = "pallas" if _on_tpu() else "xla"
    if window:
        return xla_attention(q, k, v, mask, causal, scale, dropout_p,
                             block_length, window)
    if block_length > 1:
        return xla_attention(q, k, v, mask, causal, scale, dropout_p,
                             block_length)
    if backend == "pallas" and mask is None and dropout_p == 0.0:
        from .pallas import flash_attention as _k
        out = _run_kernel(
            lambda q_, k_, v_: _k.flash_attention(q_, k_, v_, causal=causal,
                                                  scale=scale),
            lambda qs, ks, vs: _k.supports(qs, ks, causal),
            (q, k, v), ("b.h.",) * 3, ("b.h.",))
        if out is not None:
            return out
    return xla_attention(q, k, v, mask, causal, scale, dropout_p)


# ---------------------------------------------------------------------------
# rms_norm / layer_norm
# ---------------------------------------------------------------------------
def xla_rms_norm(x, weight=None, epsilon=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + epsilon)
    out = out.astype(x.dtype)
    if weight is not None:
        out = out * weight
    return out


def rms_norm(x, weight=None, epsilon=1e-6):
    """Reference: incubate fused_rms_norm (phi fused kernel).  Pallas kernel
    on TPU for the [*, hidden] LLM case."""
    if _on_tpu() and weight is not None and x.ndim >= 2:
        from .pallas import rms_norm as _k
        rows = "b" + "." * (x.ndim - 1)
        out = _run_kernel(
            lambda x_, w_: _k.rms_norm(x_, w_, epsilon),
            lambda xs, ws: _k.supports(xs, x.dtype),
            (x, weight), (rows, "."), (rows,))
        if out is not None:
            return out
    return xla_rms_norm(x, weight, epsilon)


def xla_fused_add_rms_norm(x, y, weight, epsilon=1e-6):
    """jnp twin of pallas.rms_norm.fused_add_rms_norm — the EXACT ops
    of the unfused path (add in the compute dtype, then xla_rms_norm),
    so threading the fused entry into a model changes nothing
    numerically off-TPU."""
    resid = x + y
    return resid, xla_rms_norm(resid, weight, epsilon)


def fused_add_rms_norm(x, y, weight, epsilon=1e-6):
    """Fused residual-add + RMSNorm: (x + y, rms_norm(x + y) * weight)
    in one Pallas VMEM pass on TPU (the residual sum is written once and
    never re-read — one fewer [tokens, H] HBM round-trip per transformer
    block, a PROFILE_r05 non-matmul gap item).  XLA twin elsewhere."""
    if _on_tpu() and weight is not None and x.ndim >= 2:
        from .pallas import rms_norm as _k
        rows = "b" + "." * (x.ndim - 1)
        out = _run_kernel(
            lambda x_, y_, w_: _k.fused_add_rms_norm(x_, y_, w_, epsilon),
            lambda xs, ys, ws: _k.supports(
                xs, jnp.promote_types(x.dtype, y.dtype)),
            (x, y, weight), (rows, rows, "."), (rows, rows))
        if out is not None:
            return out
    return xla_fused_add_rms_norm(x, y, weight, epsilon)


def layer_norm(x, weight=None, bias=None, epsilon=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = ((xf - mean) * jax.lax.rsqrt(var + epsilon)).astype(x.dtype)
    if weight is not None:
        out = out * weight
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def yarn_inv_freq(dim, base, factor, original_max_position,
                  beta_fast=32, beta_slow=1):
    """`deepseek_yarn` rotary frequencies (DeepSeek-V2's YaRN): each of
    the dim/2 frequencies 1/base^(2i/dim) is blended with itself over
    `factor` by a linear ramp between the correction dims of `beta_fast`
    and `beta_slow` rotations over `original_max_position` positions —
    fast dims keep their frequency, slow ones are interpolated."""
    def correction_dim(rotations):
        return dim * math.log(original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001       # the reference's guard against a 0 divide
    plain = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature 0.1 * mscale * ln(factor) + 1 (1 for
    factor <= 1).  With `mscale_all_dim` it enters the softmax scale
    SQUARED; cos and sin carry mscale / mscale_all_dim."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_cos_sin(seq_len, head_dim, base=10000.0, dtype=jnp.float32,
                 position_ids=None, scaling=None):
    """cos/sin tables [.., s, head_dim] (rotate-half layout).  `scaling`:
    a config's `rope_scaling` group; only `deepseek_yarn` is known."""
    amp = 1.0
    if scaling is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                              dtype=jnp.float32) / head_dim))
    elif scaling.get("type") == "deepseek_yarn":
        inv_freq = yarn_inv_freq(
            head_dim, base, scaling["factor"],
            scaling["original_max_position_embeddings"],
            scaling.get("beta_fast", 32), scaling.get("beta_slow", 1))
        amp = yarn_mscale(scaling["factor"], scaling.get("mscale", 1.0)) \
            / yarn_mscale(scaling["factor"],
                          scaling.get("mscale_all_dim", 0.0))
    else:
        raise ValueError(f"unknown rope_scaling {scaling!r}")
    pos = (jnp.arange(seq_len, dtype=jnp.float32) if position_ids is None
           else position_ids.astype(jnp.float32))
    freqs = jnp.einsum("...s,d->...sd", pos, inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    if amp != 1.0:
        return (jnp.cos(emb) * amp).astype(dtype), \
            (jnp.sin(emb) * amp).astype(dtype)
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(q, k, cos, sin):
    """Reference: incubate fused_rotary_position_embedding (NeoX-style
    rotate-half, matching paddle's use_neox_rotary_style=True).
    q/k: [b, s, h, d]; cos/sin: [s, d] or [b, s, d].

    On TPU the q/k rotation runs as ONE Pallas pass per row block
    (pallas/rope.py — each operand read once, written once; the XLA
    path's concat/slice rotate-half shuffles are a PROFILE_r05
    non-matmul gap item); shapes rope.supports refuses (e.g. the
    batch·seq < 8 decode case) take the XLA path here."""
    if _on_tpu():
        from .pallas import rope as _k
        table = "." * cos.ndim if cos.ndim == 2 else "b.."
        out = _run_kernel(
            _k.rope_apply, lambda qs, ks, cs, ss: _k.supports(qs, ks, cs),
            (q, k, cos, sin), ("b.h.", "b.h.", table, table),
            ("b.h.", "b.h."))
        if out is not None:
            return out
    return xla_apply_rope(q, k, cos, sin)


def xla_apply_rope(q, k, cos, sin):
    """apply_rope's jnp math (fp32 rotate-half), whatever the backend."""
    if cos.ndim == 2:      # [s, d] → [1, s, 1, d]
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    elif cos.ndim == 3:    # [b, s, d] → [b, s, 1, d]
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    cosf = cos.astype(jnp.float32)
    sinf = sin.astype(jnp.float32)
    q_out = (qf * cosf + _rotate_half(qf) * sinf).astype(q.dtype)
    k_out = (kf * cosf + _rotate_half(kf) * sinf).astype(k.dtype)
    return q_out, k_out


def rope(q, k, seq_len=None, base=10000.0, position_ids=None):
    sl = seq_len if seq_len is not None else q.shape[1]
    cos, sin = rope_cos_sin(sl, q.shape[-1], base,
                            position_ids=position_ids)
    return apply_rope(q, k, cos, sin)


# ---------------------------------------------------------------------------
# weight-only quantized matmul (ISSUE 11): int8 per-channel / packed int4
# ---------------------------------------------------------------------------
# Packed-int4 layout contract (the ONE place it is defined; the Pallas
# kernel, the jnp twin and quantization.weight_only all follow it):
# a [K, N] weight is split into HALVES along K — rows 0..K/2-1 live in
# the LOW nibble of packed[K//2, N] int8, rows K/2..K-1 in the HIGH
# nibble.  Unpacking is therefore two nibble extractions and a
# concatenate (no sublane interleave — the kernel's halves feed two
# clean [K/2, N] tiles), and scale groups along K never straddle the
# half boundary (group_size must divide K/2).

def pack_int4(q):
    """Pack an int [K, N] array of int4 values (range [-8, 7]) into
    [K//2, N] int8 bytes: low nibble = row k, high nibble = row
    k + K//2 (the half-split layout above).  K must be even."""
    K = q.shape[0]
    if K % 2:
        raise ValueError(f"pack_int4 needs an even K (got {K})")
    qi = jnp.asarray(q, jnp.int32)
    lo = qi[: K // 2] & 15
    hi = qi[K // 2:] & 15
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4(packed):
    """Inverse of pack_int4: [K//2, N] int8 → [K, N] int32 in [-8, 7].
    Nibbles are two's-complement 4-bit values; sign-extension is the
    branch-free (x ^ 8) - 8 for the low nibble and an arithmetic shift
    for the high one — identical math in the Pallas kernel."""
    p = jnp.asarray(packed, jnp.int32)        # sign-extends the byte
    lo = ((p & 15) ^ 8) - 8
    hi = p >> 4                               # arithmetic: high nibble
    return jnp.concatenate([lo, hi], axis=0)


def dequant_weight(qw, scales, fmt, group_size=None):
    """fp32 [K, N] weight from its weight-only packed form.
    fmt='int8': qw [K, N] int8, scales [N] — per-output-channel.
    fmt='int4': qw [K//2, N] packed int8, scales [K//group, N] —
    group-wise along K (groups never straddle the pack halves).
    THE canonical dequant math — the twin and the kernel both compute
    q_f32 * scale_f32, so the two paths are bit-identical."""
    if fmt == "int8":
        return qw.astype(jnp.float32) * scales.astype(jnp.float32)[None]
    if fmt != "int4":
        raise ValueError(f"unknown weight-only format {fmt!r}")
    if group_size is None:
        raise ValueError("int4 dequant needs group_size")
    q = unpack_int4(qw).astype(jnp.float32)            # [K, N]
    s = jnp.repeat(scales.astype(jnp.float32), int(group_size), axis=0)
    return q * s


def xla_quant_matmul(x, qw, scales, fmt, group_size=None):
    """jnp twin of pallas.quant_matmul: dequantize to fp32, cast to the
    activation dtype (the decode matmuls run in the compute dtype, like
    the unquantized `x @ w.astype(x.dtype)` they replace), contract in
    fp32 accumulation.  Bit-identical to the kernel off-TPU."""
    w = dequant_weight(qw, scales, fmt, group_size).astype(x.dtype)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = jax.lax.dot_general(
        x2, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    return out.reshape(*lead, w.shape[1])


def quant_matmul(x, qw, scales, fmt, group_size=None):
    """x [..., K] @ weight-only packed qw → [..., N] in x.dtype, the
    dequant fused into the matmul (the weight is read from HBM at 1
    byte (int8) or half a byte (int4) per element — the decode-path
    bandwidth multiplier).  Pallas kernel on TPU (dequant in VMEM right
    after the DMA), jnp twin elsewhere and for shapes
    quant_matmul.supports refuses."""
    if fmt not in ("int8", "int4"):
        raise ValueError(f"unknown weight-only format {fmt!r}")
    if fmt == "int4" and group_size is None:
        raise ValueError("int4 quant_matmul needs group_size")
    if _on_tpu():
        from .pallas import quant_matmul as _k
        if _k.supports(x.shape, qw.shape[1]):
            return _k.quant_matmul(x, qw, scales, fmt, group_size)
    return xla_quant_matmul(x, qw, scales, fmt, group_size)


# ---------------------------------------------------------------------------
# swiglu
# ---------------------------------------------------------------------------
def swiglu(x, gate=None):
    if gate is None:
        half = x.shape[-1] // 2
        x, gate = x[..., :half], x[..., half:]
    return jax.nn.silu(x) * gate
