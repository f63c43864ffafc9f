"""Fused chunked linear + cross-entropy — the logits never land in HBM.

Reference problem (PROFILE_r05): the causal-LM loss upcasts the full
[B, S, V] logits to fp32 — at the llama bench shape that single buffer
(256 MB) is the largest live allocation in the step, and the
log_softmax + gather over it is pure memory traffic on the non-matmul
side of the MFU gap.  The memory-efficient fusion popularized by
Liger-Kernel-style chunked losses computes the loss FROM THE HIDDEN
STATES, chunking over rows (tokens), so only a [chunk, V] slice of
logits ever exists:

  per row chunk c:
    logits_c = h_c @ W (+ b)            fp32 accumulation
    lse_c    = logsumexp(logits_c)      one VMEM pass (Pallas on TPU)
    dlog_c   = (softmax - onehot)/n     computed IN THE SAME PASS
    dh_c     = dlog_c @ W.T             written directly
    dW      += h_c.T @ dlog_c

The custom VJP therefore does all gradient work in the forward sweep
(the standard trick: d logits is known up to the scalar upstream
cotangent) and the backward is three scalar multiplies.  For vocabs too
large for a [chunk, V] fp32 tile, `vocab_chunk` switches the statistics
to an ONLINE log-softmax denominator (flash-attention-style running
max/sum folded over vocab chunks) with a second vocab sweep for the
gradients — no [chunk, V] buffer at all.

Vocab-sharded (reference ParallelCrossEntropy / mp_layers.py
c_softmax_with_cross_entropy): under shard_map with `axis_name`, each
shard computes its local max / denominator / picked logit and combines
them with one pmax + psum — the per-shard online-softmax merge — and
psums the hidden gradient (each shard's dlog_c @ W_local.T is a partial
sum over its vocab slice).

All paths share the same fp32 math; the Pallas kernel is used on TPU
(interpret mode in tests) and the jnp twin everywhere else.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_linear_cross_entropy"]

# rows per scan chunk: bounds the transient fp32 logits slice to
# [_DEFAULT_CHUNK, V] (32 MB at V=8192) regardless of batch*seq
_DEFAULT_CHUNK = 1024

# the Pallas kernel walks the [rows, V] logits in row blocks of <=8, so
# its VMEM working set is ~4 fp32 [8, V] buffers, double-buffered across
# grid steps: at V=2^15 that is ~8 MB against ~16 MB of scoped VMEM —
# the safe ceiling.  Vocabs past it dispatch the jnp twin (XLA tiles the
# same math) instead of dying in a Mosaic VMEM error at compile time.
_KERNEL_MAX_VOCAB = 1 << 15


def _interpret():
    return jax.default_backend() != "tpu"


class _CEConfig(NamedTuple):
    ignore_index: Optional[int]
    chunk_rows: int
    vocab_chunk: Optional[int]
    axis_name: Optional[str]
    use_pallas: bool


# ---------------------------------------------------------------------------
# Pallas kernel: one VMEM pass over a [rows, V] logits chunk produces the
# per-row loss AND the (softmax - onehot) gradient — logits are read once.

def _ce_kernel(scale_ref, lg_ref, lbl_ref, loss_ref, dlg_ref):
    x = lg_ref[...].astype(jnp.float32)                 # [br, V]
    lbl = lbl_ref[...]                                  # [br] int32
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    lse = (m + jnp.log(s))[:, 0]
    onehot = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) \
        == lbl[:, None]
    valid = lbl >= 0
    picked = jnp.sum(jnp.where(onehot, x, 0.0), axis=-1)
    scale = scale_ref[0]
    loss_ref[...] = jnp.where(valid, lse - picked, 0.0) * scale
    d = (e / s - onehot.astype(jnp.float32)) * scale
    dlg_ref[...] = jnp.where(valid[:, None], d, 0.0).astype(dlg_ref.dtype)


def _ce_rows_pallas(logits, labels, scale, out_dtype):
    """(loss_rows [C] f32, dlogits [C, V] out_dtype) for one chunk."""
    rows, v = logits.shape
    br = next((d for d in (8, 4, 2, 1) if rows % d == 0), 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    with x64_off():
        loss_rows, dlog = pl.pallas_call(
            _ce_kernel,
            grid=(rows // br,),
            in_specs=[smem,
                      pl.BlockSpec((br, v), lambda i: (i, 0)),
                      pl.BlockSpec((br,), lambda i: (i,))],
            out_specs=[pl.BlockSpec((br,), lambda i: (i,)),
                       pl.BlockSpec((br, v), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows,), jnp.float32),
                       jax.ShapeDtypeStruct((rows, v), out_dtype)],
            name="fused_ce_rows",
            interpret=_interpret(),
        )(scale.reshape(1), logits, labels)
    return loss_rows, dlog


def _ce_rows_jnp(logits, labels, scale, out_dtype):
    """jnp twin of `_ce_kernel` — identical math, XLA-fused."""
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    lse = (m + jnp.log(s))[:, 0]
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    picked = jnp.take_along_axis(x, safe[:, None], axis=-1)[:, 0]
    loss_rows = jnp.where(valid, lse - picked, 0.0) * scale
    onehot = jax.nn.one_hot(safe, x.shape[-1], dtype=jnp.float32)
    d = (e / s - onehot) * scale
    dlog = jnp.where(valid[:, None], d, 0.0).astype(out_dtype)
    return loss_rows, dlog


# ---------------------------------------------------------------------------
# chunk-level fused forward+grad (all sharding/vocab-chunk variants)

def _shard_offset(v_local, axis_name):
    return jax.lax.axis_index(axis_name) * v_local if axis_name else 0


def _vocab_chunked(cfg, v_local):
    # divisibility and the axis_name exclusion are validated at the
    # entry point; a chunk >= the vocab simply means "one chunk" — the
    # direct path already is that
    return bool(cfg.vocab_chunk) and v_local > cfg.vocab_chunk


def _chunk_fwdgrad(h_c, w, b, lbl_c, scale, cfg):
    """One row chunk: (loss_sum, dh_c, dW_partial, db_partial).

    dh/dW carry the 1/n_valid scale (upstream cotangent applied in the
    VJP's backward).  Under `axis_name` the stats are combined across
    vocab shards (pmax on the max, psum on denominator/picked) and dh is
    a psum of the per-shard partial products.
    """
    cd = w.dtype
    v_local = w.shape[1]
    off = _shard_offset(v_local, cfg.axis_name)

    if _vocab_chunked(cfg, v_local):
        return _chunk_fwdgrad_online(h_c, w, b, lbl_c, scale, cfg)

    logits = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    if cfg.axis_name:
        # per-shard online-softmax merge: local max → pmax, local
        # denominator/picked → psum.  The local gather hits only labels
        # that fall inside this shard's [off, off+v_local) slice.
        lbl_loc = lbl_c - off
        in_shard = (lbl_loc >= 0) & (lbl_loc < v_local)
        valid = lbl_c >= 0
        safe = jnp.clip(lbl_loc, 0, v_local - 1)
        m = jax.lax.pmax(jnp.max(logits, axis=-1), cfg.axis_name)
        e = jnp.exp(logits - m[:, None])
        s = jax.lax.psum(jnp.sum(e, axis=-1), cfg.axis_name)
        picked_loc = jnp.take_along_axis(logits, safe[:, None],
                                         axis=-1)[:, 0]
        picked = jax.lax.psum(
            jnp.where(in_shard, picked_loc, 0.0), cfg.axis_name)
        lse = m + jnp.log(s)
        loss_sum = jnp.sum(jnp.where(valid, lse - picked, 0.0)) * scale
        onehot = jax.nn.one_hot(safe, v_local, dtype=jnp.float32) \
            * in_shard[:, None].astype(jnp.float32)
        d = (e / s[:, None] - onehot) * scale
        dlog = jnp.where(valid[:, None], d, 0.0).astype(cd)
        dh = jax.lax.psum(
            jnp.dot(dlog, w.T, preferred_element_type=jnp.float32),
            cfg.axis_name)
    else:
        if cfg.use_pallas and v_local <= _KERNEL_MAX_VOCAB:
            loss_rows, dlog = _ce_rows_pallas(logits, lbl_c, scale, cd)
        else:
            loss_rows, dlog = _ce_rows_jnp(logits, lbl_c, scale, cd)
        loss_sum = jnp.sum(loss_rows)
        dh = jnp.dot(dlog, w.T, preferred_element_type=jnp.float32)
    dw = jnp.dot(h_c.T.astype(cd), dlog,
                 preferred_element_type=jnp.float32)
    db = jnp.sum(dlog.astype(jnp.float32), axis=0) if b is not None \
        else None
    return loss_sum, dh.astype(h_c.dtype), dw, db


def _online_logits_at(h_c, w, b, vc, j):
    wj = jax.lax.dynamic_slice_in_dim(w, j * vc, vc, axis=1)
    lg = jnp.dot(h_c, wj, preferred_element_type=jnp.float32)
    if b is not None:
        lg = lg + jax.lax.dynamic_slice_in_dim(
            b, j * vc, vc).astype(jnp.float32)
    return lg, wj


def _online_stats(h_c, w, b, lbl_c, vc):
    """Flash-attention-style running (max, denom, picked) folded over
    vocab chunks of size vc — never a [rows, V] buffer."""
    rows = h_c.shape[0]
    nvc = w.shape[1] // vc

    def pass1(carry, j):
        m, s, picked = carry
        lg, _ = _online_logits_at(h_c, w, b, vc, j)
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        s = s * jnp.exp(m - m_new) \
            + jnp.sum(jnp.exp(lg - m_new[:, None]), axis=-1)
        loc = lbl_c - j * vc
        hit = (loc >= 0) & (loc < vc)
        safe = jnp.clip(loc, 0, vc - 1)
        picked = picked + jnp.where(
            hit, jnp.take_along_axis(lg, safe[:, None], axis=-1)[:, 0],
            0.0)
        return (m_new, s, picked), None

    (m, s, picked), _ = jax.lax.scan(
        pass1, (jnp.full((rows,), -jnp.inf, jnp.float32),
                jnp.zeros((rows,), jnp.float32),
                jnp.zeros((rows,), jnp.float32)),
        jnp.arange(nvc))
    return m, s, picked


def _chunk_fwdgrad_online(h_c, w, b, lbl_c, scale, cfg):
    """Online-denominator variant: two folds over vocab chunks, never a
    [rows, V] buffer.  Pass 1 carries the running (max, denom, picked);
    pass 2 recomputes each logits slice to emit dh/dW per vocab chunk.
    """
    vc = cfg.vocab_chunk
    v = w.shape[1]
    nvc = v // vc
    rows = h_c.shape[0]
    cd = w.dtype
    valid = lbl_c >= 0

    m, s, picked = _online_stats(h_c, w, b, lbl_c, vc)
    lse = m + jnp.log(s)
    loss_sum = jnp.sum(jnp.where(valid, lse - picked, 0.0)) * scale

    def pass2(carry, j):
        dh, dw, db = carry
        lg, wj = _online_logits_at(h_c, w, b, vc, j)
        loc = lbl_c - j * vc
        hit = (loc >= 0) & (loc < vc)
        safe = jnp.clip(loc, 0, vc - 1)
        onehot = jax.nn.one_hot(safe, vc, dtype=jnp.float32) \
            * hit[:, None].astype(jnp.float32)
        d = (jnp.exp(lg - m[:, None]) / s[:, None] - onehot) * scale
        dlog = jnp.where(valid[:, None], d, 0.0).astype(cd)
        dh = dh + jnp.dot(dlog, wj.T,
                          preferred_element_type=jnp.float32)
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, jnp.dot(h_c.T.astype(cd), dlog,
                        preferred_element_type=jnp.float32),
            j * vc, axis=1)
        if b is not None:
            db = jax.lax.dynamic_update_slice_in_dim(
                db, jnp.sum(dlog.astype(jnp.float32), axis=0), j * vc,
                axis=0)
        return (dh, dw, db), None

    dh0 = jnp.zeros((rows, h_c.shape[1]), jnp.float32)
    dw0 = jnp.zeros((h_c.shape[1], v), jnp.float32)
    db0 = jnp.zeros((v,), jnp.float32) if b is not None else jnp.zeros(())
    (dh, dw, db), _ = jax.lax.scan(pass2, (dh0, dw0, db0),
                                   jnp.arange(nvc))
    return (loss_sum, dh.astype(h_c.dtype), dw,
            db if b is not None else None)


def _chunk_loss_only(h_c, w, b, lbl_c, scale, cfg):
    """Loss without gradient work (the primal when not differentiated).
    Honors vocab_chunk like the fwdgrad path: the online pass-1 stats
    alone give the loss with no [rows, V] buffer."""
    v_local = w.shape[1]
    off = _shard_offset(v_local, cfg.axis_name)
    if _vocab_chunked(cfg, v_local):
        m, s, picked = _online_stats(h_c, w, b, lbl_c, cfg.vocab_chunk)
        valid = lbl_c >= 0
        lse = m + jnp.log(s)
        return jnp.sum(jnp.where(valid, lse - picked, 0.0)) * scale
    logits = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
    if b is not None:
        logits = logits + b.astype(jnp.float32)
    valid = lbl_c >= 0
    if cfg.axis_name:
        lbl_loc = lbl_c - off
        in_shard = (lbl_loc >= 0) & (lbl_loc < v_local)
        safe = jnp.clip(lbl_loc, 0, v_local - 1)
        m = jax.lax.pmax(jnp.max(logits, axis=-1), cfg.axis_name)
        s = jax.lax.psum(
            jnp.sum(jnp.exp(logits - m[:, None]), axis=-1),
            cfg.axis_name)
        picked = jax.lax.psum(jnp.where(
            in_shard,
            jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0],
            0.0), cfg.axis_name)
        lse = m + jnp.log(s)
    else:
        lse = jax.nn.logsumexp(logits, axis=-1)
        safe = jnp.maximum(lbl_c, 0)
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0)) * scale


# ---------------------------------------------------------------------------
# row-chunked scan + custom VJP

def _pad_rows(hidden, labels, chunk):
    n = hidden.shape[0]
    pad = -n % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=-1)
    return hidden, labels, n, pad


def _scan_chunks(fn, hidden, labels, chunk, init):
    h3 = hidden.reshape(-1, chunk, hidden.shape[1])
    l2 = labels.reshape(-1, chunk)
    return jax.lax.scan(fn, init, (h3, l2))


def _scale_of(labels, cfg):
    # NO psum under axis_name: vocab sharding replicates the rows (and
    # their labels) across shards — every shard sees the same count
    valid = (labels >= 0).astype(jnp.float32)
    return 1.0 / jnp.maximum(jnp.sum(valid), 1.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flce(hidden, weight, bias, labels, cfg):
    hidden, labels, _, _ = _pad_rows(hidden, labels, cfg.chunk_rows)
    scale = _scale_of(labels, cfg)

    def body(acc, xs):
        h_c, l_c = xs
        return acc + _chunk_loss_only(h_c, weight, bias, l_c, scale,
                                      cfg), None

    loss, _ = _scan_chunks(body, hidden, labels, cfg.chunk_rows,
                           jnp.zeros((), jnp.float32))
    return loss


def _flce_fwd(hidden, weight, bias, labels, cfg):
    hidden_p, labels_p, n, pad = _pad_rows(hidden, labels,
                                           cfg.chunk_rows)
    scale = _scale_of(labels_p, cfg)
    dw0 = jnp.zeros(weight.shape, jnp.float32)
    db0 = jnp.zeros(bias.shape, jnp.float32) if bias is not None else None

    def body(acc, xs):
        loss, dw, db = acc
        h_c, l_c = xs
        ls, dh_c, dw_c, db_c = _chunk_fwdgrad(h_c, weight, bias, l_c,
                                              scale, cfg)
        if db is not None:
            db = db + db_c
        return (loss + ls, dw + dw_c, db), dh_c

    (loss, dw, db), dh = _scan_chunks(
        body, hidden_p, labels_p, cfg.chunk_rows,
        (jnp.zeros((), jnp.float32), dw0, db0))
    dh = dh.reshape(-1, hidden.shape[1])[:n]
    return loss, (dh, dw.astype(weight.dtype),
                  None if db is None else db.astype(bias.dtype))


def _flce_bwd(cfg, res, g):
    dh, dw, db = res
    g = g.astype(jnp.float32)
    return (dh * g.astype(dh.dtype), dw * g.astype(dw.dtype),
            None if db is None else db * g.astype(db.dtype), None)


_flce.defvjp(_flce_fwd, _flce_bwd)


def fused_linear_cross_entropy(hidden, weight, labels, bias=None, *,
                               transpose_weight=False, ignore_index=None,
                               chunk_rows=None, vocab_chunk=None,
                               axis_name=None, use_pallas=None):
    """Mean cross entropy of `hidden @ weight (+ bias)` against integer
    `labels`, computed in row chunks so the full logits tensor is never
    materialized.  hidden: [N, H] (or [..., H], flattened); weight:
    [H, V] (or [V, H] with transpose_weight — the tied-embedding
    layout); labels: [N] int, rows with `ignore_index` (or any negative
    label) excluded from the masked mean.

    axis_name: vocab-sharded mode for shard_map callers — `weight` is
    this shard's [H, V/n] slice and the softmax statistics are combined
    with one pmax + psum per chunk (the reference ParallelCrossEntropy
    contract).  Gradients flow to hidden, weight and bias via a custom
    VJP whose work happens in the forward sweep.
    """
    h2 = hidden.reshape(-1, hidden.shape[-1])
    lbl = labels.reshape(-1).astype(jnp.int32)
    if ignore_index is not None and ignore_index >= 0:
        lbl = jnp.where(lbl == ignore_index, -1, lbl)
    if transpose_weight:
        weight = weight.T
    n = h2.shape[0]
    chunk = int(chunk_rows) if chunk_rows else min(_DEFAULT_CHUNK, n)
    chunk = max(1, min(chunk, n))
    if vocab_chunk:
        # loud validation beats a silent fall-through to the very
        # [chunk, V] materialization the option exists to avoid
        v = weight.shape[1]
        if axis_name is not None:
            raise ValueError(
                "vocab_chunk is not supported with axis_name: the vocab "
                "is already sharded; size the per-shard slice instead")
        if v % int(vocab_chunk) != 0:
            raise ValueError(
                f"vocab_chunk={vocab_chunk} must divide the vocab "
                f"dimension ({v})")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    cfg = _CEConfig(ignore_index=ignore_index, chunk_rows=chunk,
                    vocab_chunk=vocab_chunk, axis_name=axis_name,
                    use_pallas=bool(use_pallas))
    return _flce(h2, weight, bias, lbl, cfg)
