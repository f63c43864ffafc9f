"""Fused AdamW — single-pass Pallas TPU kernel over each parameter.

Reference: the reference fuses the AdamW update in CUDA
(`paddle/phi/kernels/gpu/adamw_kernel.cu`, `fused_adam_kernel.cu` multi
tensor) so one kernel reads grad + moments + master once.  TPU-native
equivalent: one Pallas pass that reads (grad, m, v, master) and writes
(param, m, v[, master]) with input/output aliasing, so the state updates
IN PLACE and XLA never materialises intermediate fp32 copies of the
parameter.

The kernel's blocks are cut from the leaf as the leaf lies in memory
(`block_plan`).  On the TPU an array is stored in tiles of its last two
dimensions ((8, 128) fp32, (16, 128) bf16), so a reshape that changes
the last dimension moves every byte: taking every operand as
`[n / 1024, 1024]` puts seven leaf-sized copies around each call (78 ms
of the train cell's 325 ms step when the kernel did; ledger, PR 29).  A
leaf of two or more dimensions whose last two tile is therefore
blocked `(br, bc)` over those two, whole rows where they fit, leading
dimensions as grid axes, and nothing is reshaped: the step's HBM traffic
for it is exactly one read and one write of the state.  Anything else
(norm scales and biases, a shape off the tile grid, the multi-tensor
concatenation) goes raveled and padded through a 1-D grid.

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
import math

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw", "adamw_hostside", "block_plan", "moved_dtypes"]

# flat path, elements per grid step: in+out blocks (up to 4 f32 + 2 bf16
# each way) double-buffered must fit the ~16 MiB scoped VMEM
_FLAT_CHUNK = 64 * 1024

# own-shape path, bytes of one grid step's blocks, operands and results,
# double-buffered: what lets a 14336-wide fp32 row with bf16 moments be
# one block of 16 rows (11 MiB), under the 16 MiB of scoped VMEM
_VMEM_BUDGET = 12 * 1024 * 1024


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


def moved_dtypes(grad_dtype, m_dtype, v_dtype, out_dtype, ef_dtype=None):
    """(operand dtypes, result dtypes) of one leaf's update, in the
    kernels' argument order: g, m, v, master[, ef] in; param, m, v
    [, master][, ef] out (fp32 params: the param IS the master)."""
    ef = () if ef_dtype is None else (jnp.dtype(ef_dtype),)
    ins = (jnp.dtype(grad_dtype), jnp.dtype(m_dtype), jnp.dtype(v_dtype),
           jnp.dtype(jnp.float32)) + ef
    outs = (jnp.dtype(out_dtype), ins[1], ins[2])
    if outs[0] != jnp.float32:
        outs += (jnp.dtype(jnp.float32),)
    return ins, outs + ef


def block_plan(shape, dtypes):
    """(path, grid, block) of one leaf's `pallas_call`: a pure function of
    the leaf's shape and of the dtypes of everything the kernel moves for
    it (operands and results, `moved_dtypes`), so it is decided at trace
    time and the tests can ask it what the kernel will do.

    "own": the last dimension is a multiple of 128 and the second-to-last
    of the narrowest dtype's sublane packing (8 rows of 4-byte elements,
    16 of 2-byte).  The grid runs over the leaf AS IT LIES IN MEMORY:
    block (br, bc) of its last two dimensions, every leading dimension a
    squeezed grid axis; no operand or result is reshaped, so on the chip
    (tiles of the last two dimensions) nothing is relaid.  bc is the
    widest 128-multiple divisor of the row that fits the VMEM budget
    double-buffered at the packing's rows (the whole row is one
    contiguous run of tiles), br then the largest divisor of the rows
    that still fits.

    "flat": everything else (1-D leaves, shapes off the tile grid, a
    multi-tensor group's concatenation): the raveled leaf padded to whole
    packed tiles, `_FLAT_CHUNK` elements a grid step.  For a leaf of two
    or more dimensions that ravel is a relayout; such leaves are the
    small ones.
    """
    shape = tuple(int(d) for d in shape)
    sizes = [jnp.dtype(d).itemsize for d in dtypes]
    sub = 32 // min(sizes)
    if len(shape) >= 2 and shape[-1] % 128 == 0 and shape[-2] % sub == 0:
        rows, cols = shape[-2:]
        # elements of one block: operands and results, double-buffered
        fit = _VMEM_BUDGET // (2 * sum(sizes))
        bc = 128 * max(d for d in range(1, cols // 128 + 1)
                       if (cols // 128) % d == 0 and sub * 128 * d <= fit)
        br = sub * max(r for r in range(1, rows // sub + 1)
                       if (rows // sub) % r == 0 and sub * r * bc <= fit)
        lead = shape[:-2]
        return ("own", lead + (rows // br, cols // bc),
                (None,) * len(lead) + (br, bc))
    padded = _flat_len(shape)
    chunk = min(_FLAT_CHUNK, padded)
    return "flat", (-(-padded // chunk),), (chunk,)


def _flat_len(shape):
    """Elements of the flat path's work array: the leaf padded to the
    packed-tile granule (bf16 packs (16,128) sublane tiles = 2048 elems;
    also covers fp32 (8,128) = 1024) so every block offset AND the final
    partial block stay sublane-aligned for Mosaic."""
    n = math.prod(shape)
    return n + -n % 2048


_KERNELS = {(True, False): _kernel_fp32, (True, True): _kernel_fp32_ef,
            (False, False): _kernel_master, (False, True): _kernel_master_ef}


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

    The grid is `block_plan`'s: a leaf that tiles is blocked in its own
    shape, and no copy surrounds the kernel.
    """
    shape = grad.shape
    stepf = jnp.asarray(step, jnp.float32)
    c1 = (1.0 - jnp.float32(b1) ** stepf).reshape(1)
    c2 = (1.0 - jnp.float32(b2) ** stepf).reshape(1)
    lr1 = jnp.asarray(lr, jnp.float32).reshape(1)

    fp32_params = jnp.dtype(out_dtype) == jnp.float32
    ins = [grad, m, v, master] + ([] if ef is None else [ef])
    in_dtypes, out_dtypes = moved_dtypes(
        grad.dtype, m.dtype, v.dtype, out_dtype,
        None if ef is None else ef.dtype)
    path, grid, block = block_plan(shape, in_dtypes + out_dtypes)
    work_shape = shape
    if path == "flat":
        n = math.prod(shape)
        work_shape = (_flat_len(shape),)
        pad = work_shape[0] - n
        ins = [a.reshape((n,)) for a in ins]
        if pad:
            ins = [jnp.pad(a, (0, pad)) for a in ins]
    blk = pl.BlockSpec(block, lambda *ids: ids)
    # operand index counts the 3 scalar SMEM refs first; each piece of
    # state is aliased to the result that replaces it
    aliases = {6: 0, 4: 1, 5: 2} if fp32_params else {4: 1, 5: 2, 6: 3}
    if ef is not None:
        aliases[7] = len(out_dtypes) - 1
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    with x64_off():
        res = pl.pallas_call(
            functools.partial(_KERNELS[fp32_params, ef is not None],
                              b1=b1, b2=b2, eps=eps, wd=wd,
                              decoupled=decoupled),
            grid=grid,
            in_specs=[smem, smem, smem] + [blk] * len(ins),
            out_specs=[blk] * len(out_dtypes),
            out_shape=[jax.ShapeDtypeStruct(work_shape, d)
                       for d in out_dtypes],
            input_output_aliases=aliases,
            name="fused_adamw",
            interpret=_interpret(),
        )(lr1, c1, c2, *ins)
    res = list(res)
    if path == "flat":
        res = [(a[:n] if pad else a).reshape(shape) for a in res]
    if fp32_params:
        res.insert(3, res[0])     # the new param IS the master
    return tuple(res)


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

