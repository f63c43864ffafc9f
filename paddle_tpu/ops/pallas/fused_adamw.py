"""Fused AdamW — single-pass Pallas TPU kernel over each parameter.

Reference: the reference fuses the AdamW update in CUDA
(`paddle/phi/kernels/gpu/adamw_kernel.cu`, `fused_adam_kernel.cu` multi
tensor) so one kernel reads grad + moments + master once.  TPU-native
equivalent: one Pallas pass that reads (grad, m, v, master) and writes
(param, m, v[, master]) with input/output aliasing, so the state updates
IN PLACE — the optimizer step's HBM traffic is exactly one read + one
write of the state, and XLA never materialises intermediate fp32 copies
of the parameter.

Two storage schemes:
  - half params + fp32 master (reference O2): outputs a fresh half param
    and the aliased fp32 master.
  - fp32 params (flax param_dtype idiom — the param IS the master):
    the param aliases in place; no separate half copy is written.
Moments may be stored in any dtype (bf16 halves state memory); update
math is fp32 regardless.

bf16 moments + error feedback (`ef` operand, FLAGS_bf16_adamw_moments):
plain bf16 storage of the SECOND moment stalls — its per-step increment
(1-β₂)·g² ≈ 1e-3·v sits below bf16's ~4e-3 relative resolution, so
v stops integrating and the effective LR drifts up.  The ef buffer
carries the rounding residual: v is reconstructed as v_bf16 + ef each
step, updated in fp32, and re-split into (bf16 value, bf16 residual).
The FIRST moment needs no residual — its (1-β₁)=0.1 increments are
representable — so the state is m+v+ef = 6 bytes/param vs fp32's 8:
the moments themselves halve (8→4 bytes) and the 2-byte residual rides
along.  The param update consumes the full-precision reconstruction,
keeping N-step trajectories within bf16-rounding distance of fp32
moments (tested).

Bias corrections (1-βᵗ) are computed outside (scalar XLA) and passed in
SMEM; weight decay and betas are compile-time constants.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw", "adamw_hostside"]

# elements per grid step: in+out blocks (up to 4 f32 + 2 bf16 each way)
# double-buffered must fit the ~16 MiB scoped VMEM
_CHUNK = 64 * 1024

# block-size budget: measured NOT to move throughput (178-201 GB/s at
# 8MB and 14MB alike — the kernel is bound elsewhere); 8MB stays safely
# under scoped VMEM for every moment dtype
try:
    _VMEM_BUDGET = int(os.environ.get("PDTPU_ADAMW_VMEM_BUDGET",
                                      8 * 1024 * 1024))
except ValueError:
    _VMEM_BUDGET = 8 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _step_math(g_ref, m_ref, v_ref, mst_ref, lr_ref, c1_ref, c2_ref, *,
               b1, b2, eps, wd, decoupled, ef_ref=None):
    g = g_ref[...].astype(jnp.float32)
    mst = mst_ref[...].astype(jnp.float32)
    if wd and not decoupled:
        g = g + jnp.float32(wd) * mst
    m = jnp.float32(b1) * m_ref[...].astype(jnp.float32) \
        + jnp.float32(1 - b1) * g
    v_prev = v_ref[...].astype(jnp.float32)
    if ef_ref is not None:
        # error feedback: the stored moment plus its rounding residual
        # IS the full-precision second moment
        v_prev = v_prev + ef_ref[...].astype(jnp.float32)
    v = jnp.float32(b2) * v_prev + jnp.float32(1 - b2) * g * g
    mhat = m / c1_ref[0]
    vhat = v / c2_ref[0]
    upd = mhat / (jnp.sqrt(vhat) + jnp.float32(eps))
    if wd and decoupled:
        upd = upd + jnp.float32(wd) * mst
    return mst - lr_ref[0] * upd, m, v


def _kernel_master(lr_ref, c1_ref, c2_ref, g_ref, m_ref, v_ref, mst_ref,
                   p_out, m_out, v_out, mst_out, *, b1, b2, eps, wd,
                   decoupled):
    new_mst, m, v = _step_math(g_ref, m_ref, v_ref, mst_ref, lr_ref,
                               c1_ref, c2_ref, b1=b1, b2=b2, eps=eps,
                               wd=wd, decoupled=decoupled)
    p_out[...] = new_mst.astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)
    mst_out[...] = new_mst


def _kernel_fp32(lr_ref, c1_ref, c2_ref, g_ref, m_ref, v_ref, p_ref,
                 p_out, m_out, v_out, *, b1, b2, eps, wd, decoupled):
    new_p, m, v = _step_math(g_ref, m_ref, v_ref, p_ref, lr_ref,
                             c1_ref, c2_ref, b1=b1, b2=b2, eps=eps,
                             wd=wd, decoupled=decoupled)
    p_out[...] = new_p
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def _split_ef(v, v_out, ef_out):
    v_low = v.astype(v_out.dtype)
    v_out[...] = v_low
    ef_out[...] = (v - v_low.astype(jnp.float32)).astype(ef_out.dtype)


def _kernel_master_ef(lr_ref, c1_ref, c2_ref, g_ref, m_ref, v_ref,
                      mst_ref, ef_ref, p_out, m_out, v_out, mst_out,
                      ef_out, *, b1, b2, eps, wd, decoupled):
    new_mst, m, v = _step_math(g_ref, m_ref, v_ref, mst_ref, lr_ref,
                               c1_ref, c2_ref, b1=b1, b2=b2, eps=eps,
                               wd=wd, decoupled=decoupled, ef_ref=ef_ref)
    p_out[...] = new_mst.astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    mst_out[...] = new_mst
    _split_ef(v, v_out, ef_out)


def _kernel_fp32_ef(lr_ref, c1_ref, c2_ref, g_ref, m_ref, v_ref, p_ref,
                    ef_ref, p_out, m_out, v_out, ef_out, *, b1, b2, eps,
                    wd, decoupled):
    new_p, m, v = _step_math(g_ref, m_ref, v_ref, p_ref, lr_ref,
                             c1_ref, c2_ref, b1=b1, b2=b2, eps=eps,
                             wd=wd, decoupled=decoupled, ef_ref=ef_ref)
    p_out[...] = new_p
    m_out[...] = m.astype(m_out.dtype)
    _split_ef(v, v_out, ef_out)


def fused_adamw(grad, m, v, master, lr, step, *, b1=0.9, b2=0.999,
                eps=1e-8, wd=0.0, decoupled=True, out_dtype=jnp.bfloat16,
                ef=None):
    """One fused AdamW step.  grad: any shape/dtype; m/v: any float dtype
    of the same shape; master: fp32.  Returns (param(out_dtype), m, v,
    master); the state aliases its inputs (updated in place under jit
    donation).  When out_dtype is fp32 the param IS the master (one
    aliased output; the returned master is the new param).

    ef: optional error-feedback residual for low-precision moments (see
    module docstring) — when given, the second moment is reconstructed
    as v + ef, updated in fp32 and re-split; the return gains a fifth
    element (the new residual).

    lr: scalar f32 (traced); step: scalar int (traced, 1-based).
    """
    shape = grad.shape
    n = int(np_prod(shape))
    stepf = jnp.asarray(step, jnp.float32)
    c1 = (1.0 - jnp.float32(b1) ** stepf).reshape(1)
    c2 = (1.0 - jnp.float32(b2) ** stepf).reshape(1)
    lr1 = jnp.asarray(lr, jnp.float32).reshape(1)

    # big tensors: 2-D (rows, 1024) blocks — native (8,128)/(16,128)
    # tiling, large contiguous DMAs per grid step.  Fallback: flat 1-D
    # chunks for shapes that don't divide.
    lanes = 1024
    fp32_params_mode = jnp.dtype(out_dtype) == jnp.float32
    if n % lanes == 0 and (n // lanes) % 8 == 0:
        # Mosaic needs the sublane dim divisible by 8 (or the full
        # array); block rows sized so the double-buffered operand +
        # result set stays well under the ~16 MiB scoped VMEM
        rows = n // lanes
        esz = (jnp.dtype(grad.dtype).itemsize + 4  # g + master
               + 2 * jnp.dtype(m.dtype).itemsize)  # moments in
        esz += esz if fp32_params_mode else esz + 2  # outputs
        if ef is not None:
            esz += 2 * jnp.dtype(ef.dtype).itemsize  # ef in + out
        br = next((d for d in (256, 128, 64, 32, 16, 8)
                   if rows % d == 0
                   and 2 * d * lanes * esz <= _VMEM_BUDGET),
                  None)
        if br is None:
            br = next(d for d in (256, 128, 64, 32, 16, 8)
                      if rows % d == 0)
            br = min(br, 8)
            if rows % br:
                br = None
    else:
        br = None
    pad = 0
    if br is not None:
        work_shape = (rows, lanes)
        grid = (rows // br,)
        blk = pl.BlockSpec((br, lanes), lambda i: (i, 0))
    else:
        # flat path: pad to the packed-tile granule (bf16 packs (16,128)
        # sublane tiles = 2048 elems; also covers fp32 (8,128)=1024) so
        # every block offset AND the final partial block stay
        # sublane-aligned for Mosaic
        align = 2048
        n_pad = -n % align
        pad = n_pad
        work_shape = (n + n_pad,)
        chunk = min(_CHUNK, n + n_pad)
        grid = ((n + n_pad + chunk - 1) // chunk,)
        blk = pl.BlockSpec((chunk,), lambda i: (i,))

    def _flat(a):
        a = a.reshape((n,))
        return jnp.pad(a, (0, pad)) if pad else a

    def _pack(a):
        return _flat(a) if pad else a.reshape(work_shape)

    g1, m1, v1, mst1 = (_pack(grad), _pack(m), _pack(v), _pack(master))
    ef1 = _pack(ef) if ef is not None else None
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    fp32_params = jnp.dtype(out_dtype) == jnp.float32
    kw = dict(b1=b1, b2=b2, eps=eps, wd=wd, decoupled=decoupled)
    with x64_off():
        if fp32_params and ef is None:
            # operand index counts the 3 scalar SMEM refs first
            p1, m1, v1 = pl.pallas_call(
                functools.partial(_kernel_fp32, **kw),
                grid=grid,
                in_specs=[smem, smem, smem, blk, blk, blk, blk],
                out_specs=[blk, blk, blk],
                out_shape=[
                    jax.ShapeDtypeStruct(work_shape, jnp.float32),
                    jax.ShapeDtypeStruct(work_shape, m.dtype),
                    jax.ShapeDtypeStruct(work_shape, v.dtype),
                ],
                input_output_aliases={6: 0, 4: 1, 5: 2},
                name="fused_adamw",
                interpret=_interpret(),
            )(lr1, c1, c2, g1, m1, v1, mst1)
            mst1 = p1
        elif fp32_params:
            p1, m1, v1, ef1 = pl.pallas_call(
                functools.partial(_kernel_fp32_ef, **kw),
                grid=grid,
                in_specs=[smem, smem, smem, blk, blk, blk, blk, blk],
                out_specs=[blk, blk, blk, blk],
                out_shape=[
                    jax.ShapeDtypeStruct(work_shape, jnp.float32),
                    jax.ShapeDtypeStruct(work_shape, m.dtype),
                    jax.ShapeDtypeStruct(work_shape, v.dtype),
                    jax.ShapeDtypeStruct(work_shape, ef.dtype),
                ],
                input_output_aliases={6: 0, 4: 1, 5: 2, 7: 3},
                name="fused_adamw",
                interpret=_interpret(),
            )(lr1, c1, c2, g1, m1, v1, mst1, ef1)
            mst1 = p1
        elif ef is None:
            p1, m1, v1, mst1 = pl.pallas_call(
                functools.partial(_kernel_master, **kw),
                grid=grid,
                in_specs=[smem, smem, smem, blk, blk, blk, blk],
                out_specs=[blk, blk, blk, blk],
                out_shape=[
                    jax.ShapeDtypeStruct(work_shape, out_dtype),
                    jax.ShapeDtypeStruct(work_shape, m.dtype),
                    jax.ShapeDtypeStruct(work_shape, v.dtype),
                    jax.ShapeDtypeStruct(work_shape, jnp.float32),
                ],
                input_output_aliases={4: 1, 5: 2, 6: 3},
                name="fused_adamw",
                interpret=_interpret(),
            )(lr1, c1, c2, g1, m1, v1, mst1)
        else:
            p1, m1, v1, mst1, ef1 = pl.pallas_call(
                functools.partial(_kernel_master_ef, **kw),
                grid=grid,
                in_specs=[smem, smem, smem, blk, blk, blk, blk, blk],
                out_specs=[blk, blk, blk, blk, blk],
                out_shape=[
                    jax.ShapeDtypeStruct(work_shape, out_dtype),
                    jax.ShapeDtypeStruct(work_shape, m.dtype),
                    jax.ShapeDtypeStruct(work_shape, v.dtype),
                    jax.ShapeDtypeStruct(work_shape, jnp.float32),
                    jax.ShapeDtypeStruct(work_shape, ef.dtype),
                ],
                input_output_aliases={4: 1, 5: 2, 6: 3, 7: 4},
                name="fused_adamw",
                interpret=_interpret(),
            )(lr1, c1, c2, g1, m1, v1, mst1, ef1)
    outs = (p1, m1, v1, mst1) + ((ef1,) if ef is not None else ())
    if pad:
        outs = tuple(a[:n] for a in outs)
    return tuple(a.reshape(shape) for a in outs)


def adamw_hostside(grad, m, v, master, lr, step, *, b1=0.9, b2=0.999,
                   eps=1e-8, wd=0.0, decoupled=True,
                   out_dtype=jnp.bfloat16, ef=None):
    """Host-side twin of the fused kernel: the same single-pass AdamW
    math as `_step_math`, expressed in plain jnp so it can run where a
    Pallas launch cannot — off-TPU backends, and inside host-offload
    pipelines that apply each layer's update the moment its gradient
    lands (parallel/offload_pipeline.py backward scan).  Same signature
    and return convention as `fused_adamw` (incl. the optional `ef`
    error-feedback residual); numerics match the kernel (and the
    optimizer's pure `_update` rule) — fp32 update math, any grad/moment
    storage dtype.  When out_dtype is fp32 the param IS the master (the
    returned master is the new param)."""
    lrf = jnp.asarray(lr, jnp.float32)
    g = grad.astype(jnp.float32)
    mst = master.astype(jnp.float32)
    if wd and not decoupled:
        g = g + wd * mst
    mn = b1 * m.astype(jnp.float32) + (1 - b1) * g
    v_prev = v.astype(jnp.float32)
    if ef is not None:
        v_prev = v_prev + ef.astype(jnp.float32)
    vn = b2 * v_prev + (1 - b2) * g * g
    mhat = mn / (1 - b1 ** step)
    vhat = vn / (1 - b2 ** step)
    upd = mhat / (jnp.sqrt(vhat) + eps)
    if wd and decoupled:
        upd = upd + wd * mst
    new_mst = mst - lrf * upd
    out = (new_mst.astype(out_dtype), mn.astype(m.dtype),
           vn.astype(v.dtype), new_mst)
    if ef is not None:
        v_low = vn.astype(v.dtype)
        out += ((vn - v_low.astype(jnp.float32)).astype(ef.dtype),)
    return out


def np_prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out
