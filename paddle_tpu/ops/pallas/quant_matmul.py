"""Weight-only quantized matmul kernel — dequant-in-VMEM fused into
the decode matmul (ISSUE 11 tentpole).

Decode streams the weights: per generated token every weight byte
crosses HBM once, so tokens/s is bytes/token-bound.  This kernel reads the weight at its PACKED width — 1 byte per
element (int8) or half a byte (int4, two nibbles per byte) — and
dequantizes in VMEM right after the DMA, so the HBM traffic the matmul
pays is the packed traffic.  The activation [M, K] is tiny at decode
(M = slots x verify width) and rides along whole.

Layout contract (paddle_tpu.ops: pack_int4 / dequant_weight):

  int8   qw [K, N] int8, scales [N] fp — per-output-channel
  int4   qw [K//2, N] int8 — row k in the LOW nibble, row k + K//2 in
         the HIGH nibble (half-split: unpack is two nibble extractions
         and a concat, never a sublane interleave); scales
         [K//group, N] fp, groups never straddling the half boundary

Grid: (N // block_n,) — one pass over the output columns; the weight
tile [K(//2), block_n] is the only HBM-heavy operand.  Dequant math is
q_f32 * scale_f32 cast to the activation dtype, IDENTICAL to the jnp
twin (ops.xla_quant_matmul), so the two paths are bit-exact and tier-1
stays CPU-exact.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel_int8(x_ref, w_ref, s_ref, o_ref):
    w = w_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    x = x_ref[...]
    o_ref[...] = jax.lax.dot_general(
        x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _kernel_int4(x_ref, w_ref, s_ref, o_ref, *, group):
    p = w_ref[...].astype(jnp.int32)          # sign-extended bytes
    lo = ((p & 15) ^ 8) - 8                   # low nibble, rows < K/2
    hi = p >> 4                               # high nibble, rows >= K/2
    q = jnp.concatenate([lo, hi], axis=0).astype(jnp.float32)
    s = jnp.repeat(s_ref[...].astype(jnp.float32), group, axis=0)
    x = x_ref[...]
    w = (q * s).astype(x.dtype)
    o_ref[...] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


BLOCK_N = 512


def supports(x_shape, n, block_n=BLOCK_N) -> bool:
    """Shape predicate for ops.quant_matmul's kernel-or-twin choice —
    MXU/VPU tiling: whole [K, block_n] weight tiles (lanes want
    block_n % 128, int8 sublanes K % 256) and M % 8 activation rows.
    N = 11008 (llama-7B ffn) fails it at block_n = 512."""
    K, M = x_shape[-1], math.prod(x_shape[:-1])
    bn = min(int(block_n), n)
    return not (n % bn or bn % 128 or K % 256 or M % 8)


def quant_matmul(x, qw, scales, fmt, group_size=None, block_n=BLOCK_N,
                 interpret=None):
    """x [..., K] @ packed weight → [..., N] in x.dtype.  Raises
    ValueError for shapes `supports` refuses — ops.quant_matmul asks
    the predicate first and takes the jnp twin for those."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = qw.shape[1]
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    if fmt == "int4":
        if group_size is None:
            raise ValueError("int4 quant_matmul needs group_size")
        g = int(group_size)
        if qw.shape[0] * 2 != K:
            raise ValueError(f"packed rows {qw.shape[0]} != K/2 "
                             f"({K}/2)")
        if (K // 2) % g:
            raise ValueError(f"group_size {g} must divide K/2 "
                             f"({K // 2})")
    elif fmt != "int8":
        raise ValueError(f"unknown weight-only format {fmt!r}")
    bn = min(int(block_n), N)
    if not interpret:
        if not supports(x.shape, N, block_n):
            raise ValueError(
                f"quant_matmul tiling needs N % 128 == 0, K % 256 == 0 "
                f"and M % 8 == 0 (got M={M}, K={K}, N={N})")
    elif N % bn:
        bn = N                                 # interpret: one tile
    grid = (N // bn,)
    if fmt == "int8":
        kern = _kernel_int8
        w_spec = pl.BlockSpec((K, bn), lambda j: (0, j))
        s_spec = pl.BlockSpec((1, bn), lambda j: (0, j))
        s_in = scales.reshape(1, N)
    else:
        kern = functools.partial(_kernel_int4, group=int(group_size))
        w_spec = pl.BlockSpec((K // 2, bn), lambda j: (0, j))
        s_spec = pl.BlockSpec((K // int(group_size), bn),
                              lambda j: (0, j))
        s_in = scales
    with x64_off():
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[pl.BlockSpec((M, K), lambda j: (0, 0)),
                      w_spec, s_spec],
            out_specs=pl.BlockSpec((M, bn), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
            name="quant_matmul",
            interpret=interpret,
        )(x2, qw, s_in)
    return out.reshape(*lead, N)
