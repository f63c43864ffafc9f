"""State-space scan kernel — a Mamba-2 mixer's recurrence over one step's
block of lanes, each slot's state read once and written once, in place.

Per slot b, head h and lane t (one group: B and C are shared by the heads):

  h_t = exp(dt_t A_h) h_{t-1} + dt_t x_t (x) B_t      h: [P, N], fp32
  y_t = h_t C_t + D_h x_t

Layout contract (paddle_tpu.models.llama.init_paged_cache, state layers):

  state   [layers, B, H, P, N] — the pool of every state layer's
          per-slot state, stored in fp32 or bf16 (the recurrence runs in
          fp32 either way); `layer` (an operand) picks the layer
  x       [B, W, H, P]   the lanes' inputs
  b, c    [B, W, N]
  dt      [B, W, H] fp32, after its softplus; lanes at or past a slot's
          valid count n carry dt = 0 (ops.ssm_scan masks them), and a lane
          with dt = 0 leaves the state exactly as it was: exp(0) = 1 and
          its input is multiplied by 0
  reset   [B] int32: 1 starts the slot from a zero state (a slot that a
          new request took: its depth is 0)

A grid step is one (slot, block of `hb` heads), all its heads at once.
Within it the lanes are ONE chunk of the state-space dual form, so every
product is a matrix product: with L_t = sum_{s<=t} dt_s A (per head,
non-increasing),

  y^T     = X^T (C B^T o G)^T + exp(L) o (h_0 C^T)        (+ D X, by XLA)
            G[t, s] = exp(L_t - L_s) dt_s for s <= t, else 0
  h_W     = exp(L_last) h_0 + (X^T o w) B,   w_s = exp(L_last - L_s) dt_s

which is the recurrence above summed out (the order of the additions is
the only difference). B, C and X come in bf16-exact (the serving dtype),
so C B^T is exact in one MXU pass; every product with an fp32 side (the
state h_0, the decayed weights) takes two, that side split into bf16
halves (about 16 bits of its mantissa kept, where one pass keeps 8). A
decode step (one lane) is elementwise on the VPU. L and the transposed X
are laid out in front of the call by XLA; they are the size of the lanes,
not of the state. The state block of a grid step is [hb, P, N] of THIS
layer, taken by the index map from the scalar-prefetched layer, and the
pool is aliased to the output: the rest of the pool is neither read nor
written.

A kernel is traced and lowered at every call site: callers reach it through
`paddle_tpu.ops.ssm_scan`, whose jitted entry (`_call`) takes the layer as
an operand, so a step program lowers ONE body per width (decode and
admission) and not one per layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._x64 import x64_off
from .paged_attention import _interpret

# the widest block of lanes one chunk of the dual form takes: its [W, W]
# products stand in VMEM beside the state
MAX_WIDTH = 64
# a grid step's blocks, double-buffered (the state in and out, [hb, P, N]
# fp32 each, and the lanes' operands), and its fp32 temporaries
_VMEM_BUDGET = 24 * 2 ** 20
_VMEM_LIMIT = 64 * 2 ** 20


def supports(state_shape, width, itemsize=4) -> bool:
    """Shape predicate for ops.ssm_scan's kernel-or-twin choice: a state
    block [P, N] on whole sublane and lane tiles of its dtype, and a block
    of lanes one chunk of the dual form holds."""
    if len(state_shape) != 5:
        return False
    P, N = state_shape[3:]
    return 1 <= width <= MAX_WIDTH and P % (32 // itemsize) == 0 \
        and N % 128 == 0


def head_block(H, P, N, W):
    """Heads a grid step takes: the largest divisor of H whose blocks and
    temporaries fit the budget (32 of 64 at the serve cell's widths)."""
    lanes = -(-W // 128) * 128

    def need(hb):
        state = 2 * 2 * hb * P * N * 4 + 3 * hb * P * N * 4
        x = 2 * hb * P * lanes * (2 * 2 + 3 * 4)
        decay = hb * (-(-W // 8) * 8) * lanes * 4 * 4
        return state + x + decay
    for hb in range(H, 0, -1):
        if H % hb == 0 and need(hb) <= _VMEM_BUDGET:
            return hb
    return 1


def _dot(a, b, dims, batch=((), ())):
    """One MXU pass: bf16 operands, fp32 accumulation."""
    return jax.lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (dims, batch),
                               preferred_element_type=jnp.float32)


def _halves(v):
    hi = v.astype(jnp.bfloat16)
    return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot_wide(a, b, dims, batch=((), ()), wide=0):
    """An fp32 operand (`wide`: 0 for a, 1 for b) against one exact in
    bf16: the fp32 side's high and low bf16 halves in two passes, good to
    about 16 bits of its mantissa, where one pass would keep 8."""
    if wide == 0:
        hi, lo = _halves(a)
        return _dot(hi, b, dims, batch) + _dot(lo, b, dims, batch)
    hi, lo = _halves(b)
    return _dot(a, hi, dims, batch) + _dot(a, lo, dims, batch)


def _kernel(reset_ref, layer_ref, xt_ref, b_ref, c_ref, lcol_ref, lrow_ref,
            dt_ref, s_ref, y_ref, out_ref, *, hb, width):
    del layer_ref                       # the index maps' alone
    slot = pl.program_id(0)
    h0 = s_ref[0, 0].astype(jnp.float32)                   # [hb, P, N]
    h0 = jnp.where(reset_ref[slot] > 0, jnp.zeros_like(h0), h0)
    xt = xt_ref[0].astype(jnp.float32)                     # [hb, P, W]
    lr = lrow_ref[0]                                       # [hb, 1, W]
    dr = dt_ref[0]                                         # [hb, 1, W]
    bm = b_ref[0].astype(jnp.float32)                      # [W, N]
    cm = c_ref[0].astype(jnp.float32)
    if width == 1:
        # a decode step: one lane, elementwise on the VPU
        h1 = jnp.exp(lr) * h0 + (xt * dr) * bm[None]
        y_ref[0] = jnp.sum(h1 * cm[None], axis=2, keepdims=True)
        out_ref[0, 0] = h1.astype(out_ref.dtype)
        return
    hb_, P, N = h0.shape
    lc = lcol_ref[0]                                       # [hb, W, 1]
    last = jnp.min(lr, axis=2, keepdims=True)              # [hb, 1, 1]
    t = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    causal = (s <= t)[None]
    cb = _dot(cm, bm, ((1,), (1,)))                        # [W, W] exact
    g = jnp.where(causal, jnp.exp(jnp.where(causal, lc - lr, 0.0)), 0.0)
    m = cb[None] * g * dr                                  # [hb, W, W]
    intra = _dot_wide(xt, m, ((2,), (2,)), ((0,), (0,)), wide=1)
    inter = _dot_wide(h0.reshape(hb_ * P, N), cm, ((1,), (1,)))
    y_ref[0] = intra + jnp.exp(lr) * inter.reshape(hb_, P, width)
    w = jnp.exp(last - lr) * dr                            # [hb, 1, W]
    grow = _dot_wide((xt * w).reshape(hb_ * P, width), bm, ((1,), (0,)))
    out_ref[0, 0] = (jnp.exp(last) * h0
                     + grow.reshape(hb_, P, N)).astype(out_ref.dtype)


def ssm_scan(state, x, b, c, dt, a, d, reset, layer, interpret=None):
    """state [layers, B, H, P, N] fp32; x [B, W, H, P]; b, c [B, W, N];
    dt [B, W, H] fp32 (pad lanes 0: the caller's mask); a (A, negative)
    and d [H] fp32; reset [B] int32; layer an int32 scalar.  Returns
    (y [B, W, H, P] fp32, state) with the layer's state advanced, the pool
    updated in place.  Raises ValueError for shapes `supports` refuses."""
    interp = _interpret() if interpret is None else interpret
    if not supports(state.shape, x.shape[1], state.dtype.itemsize):
        raise ValueError(
            f"ssm_scan needs the state's [P, N] on whole tiles of its dtype "
            f"1..{MAX_WIDTH} lanes (got state {state.shape}, "
            f"{x.shape[1]} lanes)")
    return _call(state, x, b, c, dt, jnp.asarray(a, jnp.float32),
                 jnp.asarray(d, jnp.float32), jnp.asarray(reset, jnp.int32),
                 jnp.asarray(layer, jnp.int32).reshape(1),
                 interpret=bool(interp))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _call(state, x, b, c, dt, a, d, reset, layer, *, interpret):
    _, B, H, P, N = state.shape
    W = x.shape[1]
    hb = head_block(H, P, N, W)
    # the chunk's decay by head, in the two orientations its products take
    dt = dt.astype(jnp.float32).transpose(0, 2, 1)          # [B, H, W]
    L = jnp.cumsum(dt * a[None, :, None], axis=2)
    operands = (x.transpose(0, 2, 3, 1), b, c, L[..., None],
                L[:, :, None, :], dt[:, :, None, :])

    def heads(*shape):
        return pl.BlockSpec((1, hb) + shape,
                            lambda i, j, *_: (i, j) + (0,) * len(shape))

    def lanes(i, j, *_):
        return (i, 0, 0)
    state_spec = pl.BlockSpec((1, 1, hb, P, N),
                              lambda i, j, reset, layer: (layer[0], i, j, 0,
                                                          0))
    kern = functools.partial(_kernel, hb=hb, width=W)
    with x64_off():
        yt, state = pl.pallas_call(
            kern,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, H // hb),
                in_specs=[heads(P, W), pl.BlockSpec((1, W, N), lanes),
                          pl.BlockSpec((1, W, N), lanes),
                          heads(W, 1), heads(1, W), heads(1, W),
                          state_spec],
                out_specs=[heads(P, W), state_spec]),
            out_shape=[jax.ShapeDtypeStruct((B, H, P, W), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operands count the two scalar-prefetch ones: the pool is 8
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            name="ssm_scan",
            interpret=interpret,
        )(reset, layer, *operands, state)
    # the skip, D x, beside the kernel: elementwise on the lanes
    y = yt.transpose(0, 3, 1, 2) \
        + d[None, None, :, None] * x.astype(jnp.float32)
    return y, state
