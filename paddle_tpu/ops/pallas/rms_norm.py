"""Fused RMSNorm — Pallas TPU kernel with custom VJP.

Reference: `python/paddle/incubate/nn/functional/fused_rms_norm.py` → phi
fused CUDA kernel.  TPU-native: one VMEM pass per row block, fp32 stats;
backward recomputes the inverse rms (cheaper than saving it) and reduces
dw across row blocks with a fp32 accumulator output.

  y   = x * rsqrt(mean(x², -1) + eps) * w
  dx  = r*(g*w) - r³/H * x * Σ(g*w*x)      (r = rsqrt(mean x² + eps))
  dw  = Σ_rows g * x * r

`fused_add_rms_norm` extends the same kernel with the residual add that
always precedes the norm in pre-LN transformer blocks (PROFILE_r05: the
add and the norm are separate HBM round-trips at a fusion boundary):
one pass reads (x, y), writes the residual sum AND its norm — the sum
is never re-read.  The residual output is rounded to the storage dtype
BEFORE the statistics, so fused and unfused (`x + y` then `rms_norm`)
are bit-identical; the backward fuses the residual cotangent add into
the norm's dx kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 256


def _interpret():
    # interpret mode is the CPU backend's (the tests'); never a chip's
    return jax.default_backend() != "tpu"


def _fwd_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                      + jnp.float32(eps))
    o_ref[:] = (x * r * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    h = x.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                      + jnp.float32(eps))
    gw = g * w
    dot = jnp.mean(gw * x, axis=-1, keepdims=True)
    dx = r * gw - (r * r * r) * x * dot
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # per-block partial dw, reduced outside (grid dim 0 = row blocks)
    dw_ref[0, 0] = jnp.sum(g * x * r, axis=0)


def _pick_block_rows(rows, h, itemsize):
    """Largest divisor of rows that is sublane-aligned (multiple of 8) and
    keeps the double-buffered [br, h] operand/result blocks (four of them
    in the fused-add kernels) plus the fp32 temporaries inside scoped
    VMEM: 1 MiB per block — 128 rows of bf16 at h=4096, 64 of fp32 (128
    fp32 rows are refused by the compiler there).  None when no such
    block exists."""
    cap = min(BLOCK_ROWS,
              max(8, ((1024 * 1024 // max(h * itemsize, 1)) // 8) * 8))
    for br in range(min(cap, rows), 7, -1):
        if rows % br == 0 and br % 8 == 0:
            return br
    if rows <= cap:
        return rows
    return None


def supports(x_shape, dtype) -> bool:
    """Shape predicate for ops.rms_norm / ops.fused_add_rms_norm's
    kernel-or-XLA choice: the flattened [rows, H] view needs a
    sublane-aligned row block (or few enough rows for one block)."""
    return _pick_block_rows(math.prod(x_shape[:-1]), x_shape[-1],
                            jnp.dtype(dtype).itemsize) is not None


def _check(x_shape, dtype):
    if not supports(x_shape, dtype):
        raise ValueError(
            f"no tiling-compatible row block for {dtype} {x_shape}")


def _rms2(x2, w, eps):
    rows, h = x2.shape
    br = _pick_block_rows(rows, h, x2.dtype.itemsize)
    grid = (rows // br,)
    with x64_off():
        out = pl.pallas_call(
            functools.partial(_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,))],
            out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(
                (rows, h), jnp.promote_types(x2.dtype, w.dtype)),
            name="rms_norm_fwd",
            interpret=_interpret(),
        )(x2, w)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_core(x2, w, eps):
    return _rms2(x2, w, eps)


def _rms_fwd(x2, w, eps):
    return _rms2(x2, w, eps), (x2, w)


def _rms_bwd(eps, res, g2):
    x2, w = res
    rows, h = x2.shape
    br = _pick_block_rows(rows, h, x2.dtype.itemsize)
    nblocks = rows // br
    with x64_off():
        dx, dw_part = pl.pallas_call(
            functools.partial(_bwd_kernel, eps=eps),
            grid=(nblocks,),
            in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,)),
                      pl.BlockSpec((br, h), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                       pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, h), x2.dtype),
                       jax.ShapeDtypeStruct((nblocks, 1, h), jnp.float32)],
            name="rms_norm_bwd",
            interpret=_interpret(),
        )(x2, w, g2)
    dw = jnp.sum(dw_part, axis=(0, 1)).astype(w.dtype)
    return dx, dw


_rms_core.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, weight, epsilon=1e-6):
    """x: [..., H]; weight: [H].  Raises ValueError for shapes
    `supports` refuses."""
    shape = x.shape
    _check(shape, x.dtype)
    h = shape[-1]
    x2 = x.reshape(-1, h)
    out = _rms_core(x2, weight, float(epsilon))
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# fused residual-add + RMSNorm

def _add_fwd_kernel(x_ref, y_ref, w_ref, r_ref, o_ref, *, eps):
    s = x_ref[:].astype(jnp.float32) + y_ref[:].astype(jnp.float32)
    # round to the residual storage dtype FIRST: the statistics then see
    # exactly what the unfused `x + y` produced → bit-identical paths
    s_low = s.astype(r_ref.dtype)
    r_ref[:] = s_low
    sf = s_low.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(sf * sf, axis=-1, keepdims=True)
                      + jnp.float32(eps))
    o_ref[:] = (sf * r * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _add_bwd_kernel(x_ref, w_ref, g_ref, gr_ref, dx_ref, dw_ref, *, eps):
    """Norm backward over the saved residual + fused add of the residual
    cotangent (gr): d(resid) = rms_dx + gr, and dx == dy == d(resid)."""
    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    h = x.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                      + jnp.float32(eps))
    gw = g * w
    dot = jnp.mean(gw * x, axis=-1, keepdims=True)
    dx = r * gw - (r * r * r) * x * dot + gr_ref[:].astype(jnp.float32)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dw_ref[0, 0] = jnp.sum(g * x * r, axis=0)


def _add_rms2(x2, y2, w, eps):
    rows, h = x2.shape
    br = _pick_block_rows(rows, h, x2.dtype.itemsize)
    res_dt = jnp.promote_types(x2.dtype, y2.dtype)
    with x64_off():
        resid, out = pl.pallas_call(
            functools.partial(_add_fwd_kernel, eps=eps),
            grid=(rows // br,),
            in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                      pl.BlockSpec((br, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,))],
            out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                       pl.BlockSpec((br, h), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, h), res_dt),
                       jax.ShapeDtypeStruct(
                           (rows, h), jnp.promote_types(res_dt, w.dtype))],
            name="add_rms_norm_fwd",
            interpret=_interpret(),
        )(x2, y2, w)
    return resid, out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _add_rms_core(x2, y2, w, eps):
    return _add_rms2(x2, y2, w, eps)


def _add_rms_fwd(x2, y2, w, eps):
    resid, out = _add_rms2(x2, y2, w, eps)
    return (resid, out), (resid, w)


def _add_rms_bwd(eps, res, g):
    resid, w = res
    g_resid, g_out = g
    rows, h = resid.shape
    br = _pick_block_rows(rows, h, resid.dtype.itemsize)
    nblocks = rows // br
    with x64_off():
        dresid, dw_part = pl.pallas_call(
            functools.partial(_add_bwd_kernel, eps=eps),
            grid=(nblocks,),
            in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                      pl.BlockSpec((h,), lambda i: (0,)),
                      pl.BlockSpec((br, h), lambda i: (i, 0)),
                      pl.BlockSpec((br, h), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                       pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((rows, h), resid.dtype),
                       jax.ShapeDtypeStruct((nblocks, 1, h), jnp.float32)],
            name="add_rms_norm_bwd",
            interpret=_interpret(),
        )(resid, w, g_out, g_resid)
    dw = jnp.sum(dw_part, axis=(0, 1)).astype(w.dtype)
    return dresid, dresid, dw


_add_rms_core.defvjp(_add_rms_fwd, _add_rms_bwd)


def fused_add_rms_norm(x, y, weight, epsilon=1e-6):
    """(x + y, rms_norm(x + y) * weight) in one VMEM pass.
    x/y: [..., H]; weight: [H].  Returns (residual, normed), both shaped
    like x; the residual is in promote_types(x, y) — identical to the
    unfused `x + y`.  Mixed-dtype operands are cast to the common dtype
    outside the custom VJP (the cast's own autodiff restores each
    operand's gradient dtype)."""
    shape = x.shape
    h = shape[-1]
    if y.shape != shape:
        raise ValueError(f"residual shapes differ: {shape} vs {y.shape}")
    res_dt = jnp.promote_types(x.dtype, y.dtype)
    _check(shape, res_dt)
    resid, out = _add_rms_core(x.reshape(-1, h).astype(res_dt),
                               y.reshape(-1, h).astype(res_dt),
                               weight, float(epsilon))
    return resid.reshape(shape), out.reshape(shape)
