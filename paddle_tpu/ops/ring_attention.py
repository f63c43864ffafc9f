"""Ring attention — context parallelism for long sequences.

The reference has NO ring/context-parallel attention (SURVEY §5.7 verified
absence); this exceeds it, as the build plan requires for the long-context
story.  Design follows the blockwise/ring attention pattern (Liu et al.)
expressed TPU-natively:

* the sequence is sharded over a mesh axis (default 'sep'); each device
  holds a q/k/v block [b, s/n, h, d];
* inside `shard_map`, K/V blocks rotate around the ring via
  `jax.lax.ppermute` (nearest-neighbor ICI hops) while each device
  accumulates its q-block's attention with an online-softmax
  (running max + sum) over the arriving blocks;
* causal masking uses global positions derived from `lax.axis_index`, so
  fully-masked (future) blocks contribute nothing — their compute is
  masked, not skipped (static schedule keeps XLA happy; skipping would be
  the load-imbalanced zigzag variant, a later optimization);
* the ring loop is a `lax.scan` wrapped in `jax.checkpoint`: reverse-mode
  AD replays the rotations instead of saving n KV copies, so activation
  memory stays O(local block).

Gradients come from jax AD through scan+ppermute (the transpose of a
rotation is the reverse rotation), which yields the standard ring-attention
backward comm pattern without a hand-written kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import gqa_scores, gqa_weighted_v

__all__ = ["ring_attention", "ring_attention_local"]


def ring_attention_local(q, k, v, axis_name, causal=False, scale=None):
    """Per-device body; call inside shard_map. q/k/v: [b, s_loc, h, d]
    local blocks of a sequence sharded over `axis_name`."""
    b, s_loc, h, d = q.shape
    # GQA: kv stays at its own head count in the ring carry so each
    # ppermute moves only the original kv bytes; the group fold happens
    # per-step inside gqa_scores/gqa_weighted_v (compute, not comm)
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32) * sc
    # rotate kv blocks "up" the ring: device i hands its block to i+1, so
    # at step t device i holds block (i - t) mod n
    perm = [(i, (i + 1) % n) for i in range(n)]
    q_pos = idx * s_loc + jnp.arange(s_loc)

    def body(carry, t):
        o, m, l, kc, vc = carry
        src = (idx - t) % n
        logits = gqa_scores(qf, kc.astype(jnp.float32))
        if causal:
            k_pos = src * s_loc + jnp.arange(s_loc)
            keep = (q_pos[:, None] >= k_pos[None, :])  # [sq, sk]
            logits = jnp.where(keep[None, None], logits, -jnp.inf)
            keep_f = keep[None, None].astype(jnp.float32)
        else:
            keep_f = jnp.ones((1, 1, s_loc, s_loc), jnp.float32)
        blk_max = jnp.max(logits, axis=-1)                 # [b,h,q]
        m_new = jnp.maximum(m, blk_max)
        # guard fully-masked rows: exp(-inf - -inf) would be nan
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(logits - safe_m[..., None]) * keep_f   # [b,h,q,k]
        corr = jnp.where(jnp.isneginf(m), 0.0,
                         jnp.exp(m - safe_m))
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + gqa_weighted_v(
            p, vc.astype(jnp.float32))
        k_nxt = jax.lax.ppermute(kc, axis_name, perm)
        v_nxt = jax.lax.ppermute(vc, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    (o, m, l, _, _), _ = jax.lax.scan(
        jax.checkpoint(body), (o0, m0, l0, k, v), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (o / l[..., None]).astype(q.dtype)
    return jnp.transpose(out, (0, 2, 1, 3))  # back to [b, s, h, d]


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = "sep",
                   causal: bool = False, scale=None):
    """Global entry: q/k/v [b, s, h, d] (sharded or shardable on
    `seq_axis` along dim 1); returns [b, s, h, d] sharded the same way."""
    spec = P(None, seq_axis, None, None)
    body = functools.partial(ring_attention_local, axis_name=seq_axis,
                             causal=causal, scale=scale)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)
