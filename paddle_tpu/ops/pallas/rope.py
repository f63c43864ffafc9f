"""Fused rotary-position-embedding application — Pallas TPU kernel.

Reference: `python/paddle/incubate/nn/functional/
fused_rotary_position_embedding.py` (NeoX rotate-half).  The XLA path
(ops.apply_rope) builds the rotation from concat/slice ops whose fp32
intermediates and layout shuffles sit on the non-matmul side of the MFU
gap (PROFILE_r05); this kernel applies the rotation to q AND k in one
VMEM pass per row block — each operand is read once, written once.

The q/k backward is the SAME kernel with sin negated — the rotation is
orthogonal (R(θ)ᵀ = R(−θ)): dq = rope(g_q, cos, −sin), dk likewise.
cos/sin cotangents (for learned/scaled caches) are computed in plain
jnp from the saved inputs; when nothing differentiates the cache, XLA
DCE prunes both the computation and the input residuals.  For the
half-split layout the transpose ALSO swaps which sin half multiplies
which gradient half (fwd: o1 = x1·c1 − x2·s1, o2 = x2·c2 + x1·s2 ⇒
adjoint: dx1 = g1·c1 + g2·s2, dx2 = g2·c2 − g1·s1), so the backward
feeds the kernel sin with its halves swapped — a no-op for the
standard NeoX cache (both halves identical) but required for any
user-supplied cache whose halves differ.

Layout: q [b, s, h, d] and k [b, s, hk, d] are viewed as [b·s, h, d]
row-major; cos/sin [s, d] (or [b, s, d]) broadcast to [b·s, d] rows so
one BlockSpec serves every head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from ._x64 import x64_off
from jax.experimental import pallas as pl

__all__ = ["rope_apply", "supports"]

# fp32 working-set budget per grid step (q+k+outs+cos/sin+temps)
_VMEM_BUDGET = 6 * 1024 * 1024


def _interpret():
    return jax.default_backend() != "tpu"


def _rope_kernel(q_ref, k_ref, c_ref, s_ref, oq_ref, ok_ref, *, neg_sin):
    c = c_ref[...].astype(jnp.float32)[:, None, :]    # [br, 1, d]
    s = s_ref[...].astype(jnp.float32)[:, None, :]
    if neg_sin:
        s = -s
    half = c.shape[-1] // 2
    c1, c2 = c[..., :half], c[..., half:]
    s1, s2 = s[..., :half], s[..., half:]

    def rot(x_ref, o_ref):
        x = x_ref[...].astype(jnp.float32)            # [br, h, d]
        x1, x2 = x[..., :half], x[..., half:]
        # out = x*cos + rotate_half(x)*sin, rotate_half = [-x2, x1]
        o_ref[..., :half] = (x1 * c1 - x2 * s1).astype(o_ref.dtype)
        o_ref[..., half:] = (x2 * c2 + x1 * s2).astype(o_ref.dtype)

    rot(q_ref, oq_ref)
    rot(k_ref, ok_ref)


def _pick_rows(rows, h, hk, d):
    per_row = 4 * d * (3 * (h + hk) + 4)   # operands+outputs+temps, f32
    cap = max(8, (_VMEM_BUDGET // max(per_row, 1) // 8) * 8)
    for br in (512, 256, 128, 64, 32, 16, 8):
        if br <= cap and rows % br == 0:
            return br
    return None


def supports(q_shape, k_shape, cos_shape) -> bool:
    """Shape predicate for ops.apply_rope's kernel-or-XLA choice: q/k
    [b, s, h|hk, d] with an even d, cos/sin [s, d] or [b, s, d], and a
    sublane-aligned block of the b·s rows (the batch·seq < 8 decode
    case has none)."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    b, s, h, d = q_shape
    return (d >= 2 and d % 2 == 0
            and tuple(k_shape[:2]) == (b, s) and k_shape[3] == d
            and tuple(cos_shape) in ((s, d), (b, s, d))
            and _pick_rows(b * s, h, k_shape[2], d) is not None)


def _rope3(q3, k3, c2, s2, neg_sin):
    rows, h, d = q3.shape
    hk = k3.shape[1]
    br = _pick_rows(rows, h, hk, d)
    grid = (rows // br,)
    with x64_off():
        oq, ok = pl.pallas_call(
            functools.partial(_rope_kernel, neg_sin=neg_sin),
            grid=grid,
            in_specs=[pl.BlockSpec((br, h, d), lambda i: (i, 0, 0)),
                      pl.BlockSpec((br, hk, d), lambda i: (i, 0, 0)),
                      pl.BlockSpec((br, d), lambda i: (i, 0)),
                      pl.BlockSpec((br, d), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((br, h, d), lambda i: (i, 0, 0)),
                       pl.BlockSpec((br, hk, d), lambda i: (i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                       jax.ShapeDtypeStruct(k3.shape, k3.dtype)],
            name="rope",
            interpret=_interpret(),
        )(q3, k3, c2, s2)
    return oq, ok


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _rope_core(q3, k3, c2, s2):
    return _rope3(q3, k3, c2, s2, neg_sin=False)


def _rope_fwd(q3, k3, c2, s2):
    return _rope3(q3, k3, c2, s2, neg_sin=False), (q3, k3, c2, s2)


def _cos_sin_cotangent(g, x, half):
    """d/dcos, d/dsin of `o1 = x1·c1 − x2·sA, o2 = x2·c2 + x1·sB` for
    one operand, summed over the head axis: dc = [Σ g1⊙x1, Σ g2⊙x2],
    ds = [−Σ g1⊙x2, Σ g2⊙x1]."""
    gf = g.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    g1, g2 = gf[..., :half], gf[..., half:]
    x1, x2 = xf[..., :half], xf[..., half:]
    dc = jnp.concatenate([jnp.sum(g1 * x1, axis=1),
                          jnp.sum(g2 * x2, axis=1)], axis=-1)
    ds = jnp.concatenate([-jnp.sum(g1 * x2, axis=1),
                          jnp.sum(g2 * x1, axis=1)], axis=-1)
    return dc, ds


def _rope_bwd(res, g):
    q3, k3, c2, s2 = res
    gq, gk = g
    half = s2.shape[-1] // 2
    # true adjoint: dx1 needs s2's SECOND half, dx2 its first — swap
    # the halves before the neg_sin kernel (see module docstring)
    s_sw = jnp.concatenate([s2[:, half:], s2[:, :half]], axis=-1)
    dq, dk = _rope3(gq, gk, c2, s_sw, neg_sin=True)
    # cos/sin cotangents in plain jnp (elementwise+reduce — XLA fuses;
    # DCE prunes this AND the q3/k3 residual save when nothing
    # differentiates the cache, restoring the residual-light backward).
    # The XLA fallback path differentiates cos/sin, so the kernel must
    # too — zeros would silently freeze a learned cache on TPU only.
    dcq, dsq = _cos_sin_cotangent(gq, q3, half)
    dck, dsk = _cos_sin_cotangent(gk, k3, half)
    return dq, dk, (dcq + dck).astype(c2.dtype), \
        (dsq + dsk).astype(s2.dtype)


_rope_core.defvjp(_rope_fwd, _rope_bwd)


def rope_apply(q, k, cos, sin):
    """Pallas twin of ops.apply_rope: q [b, s, h, d], k [b, s, hk, d],
    cos/sin [s, d] or [b, s, d].  Raises ValueError for shapes
    `supports` refuses — ops.apply_rope asks the predicate first."""
    if not supports(q.shape, k.shape, cos.shape):
        raise ValueError(
            f"unsupported shapes for the rope kernel (q {q.shape}, "
            f"k {k.shape}, cos {cos.shape})")
    b, s, h, d = q.shape
    hk = k.shape[2]
    if cos.ndim == 2:
        c2 = jnp.broadcast_to(cos[None], (b, s, d)).reshape(b * s, d)
        s2 = jnp.broadcast_to(sin[None], (b, s, d)).reshape(b * s, d)
    else:
        c2 = cos.reshape(b * s, d)
        s2 = sin.reshape(b * s, d)
    oq, ok = _rope_core(q.reshape(b * s, h, d), k.reshape(b * s, hk, d),
                        c2, s2)
    return oq.reshape(q.shape), ok.reshape(k.shape)
