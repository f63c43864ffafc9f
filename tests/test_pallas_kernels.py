"""Pallas kernel correctness vs XLA reference (interpret mode on CPU).

Reference test pattern: OpTest numeric checks; here compiled-kernel vs
reference-impl equivalence (SURVEY §4: compiled-vs-eager checks).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.rms_norm import (rms_norm as pallas_rms_norm,
                                            fused_add_rms_norm
                                            as pallas_add_rms_norm)
from paddle_tpu.ops.pallas.rope import rope_apply
from paddle_tpu.ops import (xla_attention, xla_rms_norm,
                            xla_fused_add_rms_norm, apply_rope,
                            rope_cos_sin)


_rng = np.random.RandomState(0)


def r(*shape):
    # one stream, drawn sequentially — q/k/v must be DISTINCT arrays so
    # operand swaps / transposition bugs cannot cancel out
    return jnp.asarray(_rng.randn(*shape).astype(np.float32))


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward(self, causal):
        q, k, v = r(2, 256, 2, 128), r(2, 256, 2, 128), r(2, 256, 2, 128)
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128)
        ref = xla_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward(self, causal):
        q, k, v = r(1, 256, 2, 128), r(1, 256, 2, 128), r(1, 256, 2, 128)

        def loss_p(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=128, block_k=128) ** 2)

        def loss_x(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gqa(self, causal):
        q = r(1, 256, 4, 128)
        k = r(1, 256, 2, 128)
        v = r(1, 256, 2, 128)
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128)
        ref = xla_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gqa_backward(self, causal):
        # dk/dv must accumulate over the query-head group in-kernel
        q = r(1, 128, 4, 128)
        k = r(1, 128, 2, 128)
        v = r(1, 128, 2, 128)

        def loss_p(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=64, block_k=64) ** 2)

        def loss_x(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal=causal) ** 2)

        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3)

    def test_mqa_head_dim_64(self):
        # MQA (1 kv head) + head_dim 64 — previously fell back to XLA
        q = r(1, 128, 4, 64)
        k = r(1, 128, 1, 64)
        v = r(1, 128, 1, 64)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_blocked_path_matches_small(self, causal, monkeypatch):
        # force the long-context blocked kernels and check fwd+bwd against
        # the resident-KV path the other tests exercise
        import paddle_tpu.ops.pallas.flash_attention as fa
        q = r(1, 256, 4, 128)
        k = r(1, 256, 2, 128)
        v = r(1, 256, 2, 128)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=64, block_k=64) ** 2)

        o_small = flash_attention(q, k, v, causal=causal, block_q=64,
                                  block_k=64)
        g_small = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setattr(fa, "SMALL_KV_BYTES", 0)
        o_blk = flash_attention(q, k, v, causal=causal, block_q=64,
                                block_k=64)
        g_blk = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(o_small), np.asarray(o_blk),
                                   atol=1e-5, rtol=1e-5)
        for a, b in zip(g_small, g_blk):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_auto_block_pick(self):
        # no explicit blocks: kernel picks pow2 divisors
        q, k, v = r(1, 384, 2, 128), r(1, 384, 2, 128), r(1, 384, 2, 128)
        out = flash_attention(q, k, v, causal=True)
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_cross_attention_lengths(self):
        q = r(1, 128, 2, 128)
        k = r(1, 384, 2, 128)
        v = r(1, 384, 2, 128)
        out = flash_attention(q, k, v, block_q=128, block_k=128)
        ref = xla_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("bq,bk", [(256, 128), (128, 256)])
    def test_causal_mixed_blocks(self, bq, bk):
        # regression: causal K-block bound must cover the block's LAST row
        q, k, v = r(1, 512, 2, 128), r(1, 512, 2, 128), r(1, 512, 2, 128)
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        ref = xla_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_causal_cross_attention_rejected(self):
        # top-left vs bottom-right alignment would silently diverge
        q = r(1, 128, 2, 128)
        k = r(1, 384, 2, 128)
        with pytest.raises(ValueError):
            flash_attention(q, k, k, causal=True, block_q=128, block_k=128)

    def test_unsupported_shape_raises(self):
        q = r(1, 100, 2, 64)
        with pytest.raises(ValueError):
            flash_attention(q, q, q, block_q=128, block_k=128)


class TestRMSNorm:
    def test_forward(self):
        x = r(64, 256)
        w = r(256)
        out = pallas_rms_norm(x, w)
        ref = xla_rms_norm(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_forward_3d(self):
        x = r(2, 32, 256)
        w = r(256)
        out = pallas_rms_norm(x, w)
        ref = xla_rms_norm(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_backward(self):
        x = r(32, 256)
        w = r(256)

        def lp(x, w):
            return jnp.sum(pallas_rms_norm(x, w) ** 2)

        def lx(x, w):
            return jnp.sum(xla_rms_norm(x, w) ** 2)

        gp = jax.grad(lp, argnums=(0, 1))(x, w)
        gx = jax.grad(lx, argnums=(0, 1))(x, w)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)


class TestFusedAddRMSNorm:
    """Residual-add + RMSNorm fused into one pass: the residual output
    must be BIT-identical to the unfused `x + y` (it feeds the next
    block), the norm to fp32 tolerance, and the backward must fuse the
    residual cotangent into dx == dy."""

    def test_forward(self):
        x, y, w = r(32, 256), r(32, 256), r(256)
        r1, o1 = pallas_add_rms_norm(x, y, w)
        r2, o2 = xla_fused_add_rms_norm(x, y, w)
        assert (np.asarray(r1) == np.asarray(r2)).all()
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-5, rtol=1e-5)

    def test_forward_3d(self):
        x, y, w = r(2, 16, 256), r(2, 16, 256), r(256)
        r1, o1 = pallas_add_rms_norm(x, y, w)
        r2, o2 = xla_fused_add_rms_norm(x, y, w)
        assert (np.asarray(r1) == np.asarray(r2)).all()
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-5, rtol=1e-5)

    def test_backward_both_outputs(self):
        # cotangents flow through BOTH outputs (the residual feeds the
        # next block, the norm feeds the MLP)
        x, y, w = r(32, 256), r(32, 256), r(256)

        def lp(x, y, w):
            res, out = pallas_add_rms_norm(x, y, w)
            return jnp.sum(out ** 2) + 0.3 * jnp.sum(res)

        def lx(x, y, w):
            res, out = xla_fused_add_rms_norm(x, y, w)
            return jnp.sum(out ** 2) + 0.3 * jnp.sum(res)

        gp = jax.grad(lp, argnums=(0, 1, 2))(x, y, w)
        gx = jax.grad(lx, argnums=(0, 1, 2))(x, y, w)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_shape_mismatch_rejected(self):
        with pytest.raises(ValueError):
            pallas_add_rms_norm(r(32, 256), r(16, 256), r(256))


class TestRope:
    """Fused rope application: one VMEM pass rotates q AND k; the VJP is
    the same kernel with sin negated (orthogonal rotation)."""

    def _qk(self, b=2, s=16, h=4, hk=2, d=8):
        return r(b, s, h, d), r(b, s, hk, d)

    def test_forward_matches_xla(self):
        q, k = self._qk()
        cos, sin = rope_cos_sin(16, 8)
        oq, ok = rope_apply(q, k, cos, sin)
        # the XLA reference path, explicitly (apply_rope would dispatch
        # to the kernel on TPU)
        from paddle_tpu.ops import _rotate_half
        c4, s4 = cos[None, :, None, :], sin[None, :, None, :]
        rq = q * c4 + _rotate_half(q) * s4
        rk = k * c4 + _rotate_half(k) * s4
        np.testing.assert_allclose(np.asarray(oq), np.asarray(rq),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(rk),
                                   atol=1e-5, rtol=1e-5)

    def test_forward_batched_positions(self):
        # [b, s, d] cos/sin — the per-slot position form decode uses
        q, k = self._qk()
        pos = jnp.asarray(_rng.randint(0, 16, (2, 1)).astype(np.int32)) \
            + jnp.arange(16, dtype=jnp.int32)[None]
        cos, sin = rope_cos_sin(16, 8, position_ids=pos)
        oq, ok = rope_apply(q, k, cos, sin)
        rq, rk = apply_rope(q, k, cos, sin)
        np.testing.assert_allclose(np.asarray(oq), np.asarray(rq),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(rk),
                                   atol=1e-5, rtol=1e-5)

    def test_backward_matches_xla(self):
        q, k = self._qk()
        cos, sin = rope_cos_sin(16, 8)

        def lp(q, k):
            oq, ok = rope_apply(q, k, cos, sin)
            return jnp.sum(oq ** 2) + jnp.sum(ok ** 3)

        def lx(q, k):
            from paddle_tpu.ops import _rotate_half
            c4, s4 = cos[None, :, None, :], sin[None, :, None, :]
            oq = q * c4 + _rotate_half(q) * s4
            ok = k * c4 + _rotate_half(k) * s4
            return jnp.sum(oq ** 2) + jnp.sum(ok ** 3)

        gp = jax.grad(lp, argnums=(0, 1))(q, k)
        gx = jax.grad(lx, argnums=(0, 1))(q, k)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_backward_asymmetric_sin_halves(self):
        # regression: the half-split adjoint swaps which sin half
        # multiplies which gradient half (dx1 = g1·c1 + g2·s2, dx2 =
        # g2·c2 − g1·s1) — plain neg_sin alone is only correct when the
        # cache duplicates sin across halves (the rope_cos_sin layout);
        # a user-supplied cache with DIFFERING halves must still get
        # true gradients through ops.apply_rope on TPU
        q, k = self._qk()
        cos = jnp.asarray(_rng.randn(16, 8).astype(np.float32))
        sin = jnp.asarray(_rng.randn(16, 8).astype(np.float32))

        def lp(q, k):
            oq, ok = rope_apply(q, k, cos, sin)
            return jnp.sum(oq ** 2) + jnp.sum(ok ** 3)

        def lx(q, k):
            from paddle_tpu.ops import _rotate_half
            c4, s4 = cos[None, :, None, :], sin[None, :, None, :]
            oq = q * c4 + _rotate_half(q) * s4
            ok = k * c4 + _rotate_half(k) * s4
            return jnp.sum(oq ** 2) + jnp.sum(ok ** 3)

        gp = jax.grad(lp, argnums=(0, 1))(q, k)
        gx = jax.grad(lx, argnums=(0, 1))(q, k)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_tiny_rows_rejected(self):
        # batch*seq below the sublane granule → ValueError so the ops
        # dispatch falls back to XLA (the decode path)
        q, k = self._qk(b=1, s=4)
        cos, sin = rope_cos_sin(4, 8)
        with pytest.raises(ValueError):
            rope_apply(q, k, cos, sin)

    def test_odd_head_dim_rejected(self):
        q, k = r(2, 16, 4, 7), r(2, 16, 2, 7)
        cos = sin = jnp.zeros((16, 7), jnp.float32)
        with pytest.raises(ValueError):
            rope_apply(q, k, cos, sin)


# One walk of the paged kernel against its twin.  ps 8 makes a block 16
# pages, so a table of 40 pages is walked in up to three blocks.
#   heads = (h, n_kv); pos: one depth a slot; table: "scattered" (every
#   slot its own pages), "dead0" (slot 0's row all null page) or
#   "shared" (slots 0 and 1 map the same pages); budget: the VMEM the
#   head blocking may take (None: the module's)
_PAGED_CASES = {
    # the three cases the kernel has always been held to
    "decode_gqa2": dict(C=1, heads=(4, 2), P_slot=3, pos=(0, 5, 13)),
    "chunk_gqa4": dict(C=4, heads=(8, 2), P_slot=3, pos=(0, 5, 13)),
    "chunk_mha": dict(C=4, heads=(2, 2), P_slot=3, pos=(0, 5, 13)),
    # ragged depths in one batch: depth 0; lanes ending exactly on a page
    # (and block) boundary; lanes straddling it; the last page of the
    # table; one page deep into the third block
    "ragged": dict(C=4, heads=(4, 2), P_slot=40,
                   pos=(0, 124, 126, 316, 257)),
    "dead_slot": dict(C=4, heads=(4, 2), P_slot=40, pos=(0, 150, 37),
                      table="dead0"),
    "chunk32_group1": dict(C=32, heads=(2, 2), P_slot=40,
                           pos=(0, 97, 288, 120)),
    "decode_group4": dict(C=1, heads=(8, 2), P_slot=40,
                          pos=(0, 127, 128, 319)),
    # 4 kv heads that a small budget cuts into blocks of 2 and of 1
    "head_blocks_of_2": dict(C=4, heads=(8, 4), P_slot=40,
                             pos=(3, 200, 129), budget=100_000, hb=2),
    "head_blocks_of_1": dict(C=1, heads=(4, 4), P_slot=40,
                             pos=(3, 200, 129), budget=1, hb=1),
    "shared_pages": dict(C=4, heads=(4, 2), P_slot=40,
                         pos=(140, 131, 20), table="shared"),
}


class TestPagedAttention:
    """Paged-attention kernel (its own copies of the live pages) vs the
    take-gather jnp twin (ops.xla_paged_attention)."""

    def _pool(self, P=10, ps=8, L=2, n_kv=2, d=16, quant=False):
        if quant:
            kp = jnp.asarray(_rng.randint(-127, 128,
                                          (P, L, n_kv, ps, d)), jnp.int8)
            vp = jnp.asarray(_rng.randint(-127, 128,
                                          (P, L, n_kv, ps, d)), jnp.int8)
            # every (page, layer, head) its own scale, a factor of six
            # apart: a scale read from the wrong page or head shows
            ks = jnp.asarray(_rng.rand(P, L, n_kv) * 0.05 + 0.01,
                             jnp.float32)
            vs = jnp.asarray(_rng.rand(P, L, n_kv) * 0.05 + 0.01,
                             jnp.float32)
            return kp, vp, ks, vs
        return r(P, L, n_kv, ps, d), r(P, L, n_kv, ps, d), None, None

    def _table(self, kind, B, P_slot):
        P = 1 + B * P_slot
        pt = _rng.permutation(P - 1)[:B * P_slot].reshape(B, P_slot) + 1
        if kind == "dead0":
            pt[0] = 0
        elif kind == "shared":
            pt[1] = pt[0]
        return P, jnp.asarray(pt, jnp.int32)

    def _check(self, case, quant):
        from paddle_tpu.ops.pallas import paged_attention as kernel
        from paddle_tpu.ops import xla_paged_attention
        ps, L, d = 8, 2, 16
        C, (h, n_kv), P_slot = case["C"], case["heads"], case["P_slot"]
        B = len(case["pos"])
        P, pt = self._table(case.get("table", "scattered"), B, P_slot)
        kp, vp, ks, vs = self._pool(P, ps, L, n_kv, d, quant=quant)
        q = r(B, C, h, d)
        pos = jnp.asarray(case["pos"], jnp.int32)
        extra = {}
        if "budget" in case:
            extra["vmem_budget"] = case["budget"]
            rows = -(-C * (h // n_kv) // 8) * 8
            assert kernel._blocking(
                n_kv, rows, ps, d, kp.dtype.itemsize, 4, P_slot,
                case["budget"])[0] == case["hb"]
        for li in range(L):
            out = kernel.paged_attention(q, kp, vp, pt, pos, li, ks, vs,
                                         interpret=True, **extra)
            ref = xla_paged_attention(q, kp, vp, pt, pos, li, ks, vs)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("name", list(_PAGED_CASES))
    def test_forward_vs_twin(self, name):
        self._check(_PAGED_CASES[name], quant=False)

    @pytest.mark.parametrize("name", ["chunk_gqa4", "ragged", "dead_slot",
                                      "decode_group4", "head_blocks_of_1",
                                      "shared_pages"])
    def test_int8_dequant_fused(self, name):
        self._check(_PAGED_CASES[name], quant=True)

    @pytest.mark.parametrize("C", [1, 4, 32])
    def test_pages_walked_are_the_live_pages(self, C):
        """The walk's bound: ceil((pos + C) / page_size) pages a slot,
        at least one (a walk of none would leave the next slot's first
        block unstarted) and never past its table — for a numpy vector
        as for a scalar."""
        from paddle_tpu.ops.pallas.paged_attention import pages_walked
        ps, P_slot = 16, 66
        pos = np.array([-40, 0, 1, 15, 16, 17, 100, 511, 1023, 1055])
        want = np.clip(-(-(pos + C) // ps), 1, P_slot)
        np.testing.assert_array_equal(pages_walked(pos, C, ps, P_slot),
                                      want)
        assert [int(pages_walked(int(p), C, ps, P_slot)) for p in pos] \
            == list(want)

    def test_int8_needs_scales(self):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        kp, vp, _, _ = self._pool(quant=True)
        q = r(2, 1, 4, 16)
        pt = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="scale"):
            paged_attention(q, kp, vp, pt, pos, 0, interpret=True)

    def test_gqa_heads_must_divide(self):
        from paddle_tpu.ops.pallas.paged_attention import paged_attention
        kp, vp, _, _ = self._pool(n_kv=2)
        q = r(2, 1, 3, 16)      # 3 heads over 2 kv heads
        pt = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="multiple"):
            paged_attention(q, kp, vp, pt, pos, 0, interpret=True)


class TestPagedKVUpdate:
    """Windowed page write (ops.paged_kv_update): row-exact vs a dense
    reference, untouched pages byte-identical, int8 requant coherent."""

    def test_rows_land_exactly(self):
        from paddle_tpu.ops import paged_kv_update
        B, C, P, ps, P_slot, L, n_kv, d = 2, 3, 12, 4, 5, 2, 2, 8
        kp = jnp.zeros((P, L, n_kv, ps, d), jnp.float32)
        vp = jnp.zeros((P, L, n_kv, ps, d), jnp.float32)
        pt = jnp.asarray(_rng.permutation(P - 1)[:B * P_slot]
                         .reshape(B, P_slot) + 1, jnp.int32)
        pos = jnp.asarray([2, 6], jnp.int32)
        kn, vn = r(B, C, n_kv, d), r(B, C, n_kv, d)
        kp2, vp2, _, _ = paged_kv_update(kp, vp, None, None, pt, pos,
                                         kn, vn, layer=1)
        # logical view must hold exactly the written rows
        lg = np.asarray(jnp.take(kp2[:, 1], pt, axis=0)
                        .transpose(0, 1, 3, 2, 4)
                        .reshape(B, P_slot * ps, n_kv, d))
        for b in range(B):
            p0 = int(pos[b])
            np.testing.assert_array_equal(lg[b, p0:p0 + C],
                                          np.asarray(kn[b]))
        # layer 0 untouched
        assert not np.asarray(kp2[:, 0]).any()

    def test_untouched_pages_keep_bytes(self):
        from paddle_tpu.ops import paged_kv_update
        B, C, P, ps, P_slot, L, n_kv, d = 1, 2, 8, 4, 4, 1, 2, 8
        kp = r(P, L, n_kv, ps, d)
        vp = r(P, L, n_kv, ps, d)
        pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        pos = jnp.asarray([5], jnp.int32)     # rows 5,6 → page 1 only
        kn, vn = r(B, C, n_kv, d), r(B, C, n_kv, d)
        kp2, _, _, _ = paged_kv_update(kp, vp, None, None, pt, pos,
                                       kn, vn, layer=0)
        # pages 3,4 (and every unmapped page) bit-identical
        for page in (3, 4, 5, 6, 7):
            np.testing.assert_array_equal(np.asarray(kp2[page]),
                                          np.asarray(kp[page]))

    def test_int8_requant_roundtrip(self):
        from paddle_tpu.ops import paged_kv_update, xla_paged_attention
        B, C, P, ps, P_slot, L, n_kv, d = 1, 4, 8, 4, 4, 1, 2, 8
        kp = jnp.zeros((P, L, n_kv, ps, d), jnp.int8)
        vp = jnp.zeros((P, L, n_kv, ps, d), jnp.int8)
        ks = jnp.ones((P, L, n_kv), jnp.float32)
        vs = jnp.ones((P, L, n_kv), jnp.float32)
        pt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        kn, vn = r(B, C, n_kv, d), r(B, C, n_kv, d)
        kp, vp, ks, vs = paged_kv_update(kp, vp, ks, vs, pt,
                                         jnp.asarray([0], jnp.int32),
                                         kn, vn, layer=0)
        lg = (np.asarray(jnp.take(kp[:, 0], pt, axis=0)
                         .astype(np.float32))
              * np.asarray(jnp.take(ks[:, 0], pt, axis=0)
                           )[:, :, :, None, None]) \
            .transpose(0, 1, 3, 2, 4).reshape(B, P_slot * ps, n_kv, d)
        np.testing.assert_allclose(lg[0, :C], np.asarray(kn[0]),
                                   atol=0.03, rtol=0.05)


def _layer_sliced_kv_update(k_pool, v_pool, k_scale, v_scale, page_table,
                             pos, k_new, v_new, layer):
    """ops.paged_kv_update as it indexed the pools up to PR 27: slice
    the layer out, scatter the window into the slice, write the slice
    back.  The same bytes, by a path that cost a pool-sized layout copy
    a layer on the chip (ISSUE 28) — kept HERE only, as what the
    whole-pool indexing is held bit-identical to."""
    P, L, n_kv, ps, hd = k_pool.shape
    B, C = k_new.shape[0], k_new.shape[1]
    P_slot = page_table.shape[1]
    n_t = -(-C // ps) + 1
    quant = k_pool.dtype == jnp.int8
    p0 = jnp.clip(pos // ps, 0, max(P_slot - n_t, 0))
    win = jnp.clip(p0[:, None] + jnp.arange(n_t, dtype=jnp.int32)[None],
                   0, P_slot - 1)
    ids = jnp.take_along_axis(page_table, win, axis=1)
    rel0 = pos - p0 * ps
    start = win * ps
    touched = (start < (pos + C)[:, None]) & ((start + ps) > pos[:, None])

    def upd(pool, scales, rows):
        layer_pool = pool[:, layer]
        raw = jnp.take(layer_pool, ids, axis=0)
        if quant:
            sc = jnp.take(scales[:, layer], ids, axis=0)
            w = (raw.astype(jnp.float32)
                 * sc[..., None, None]).astype(rows.dtype)
        else:
            w = raw
        w = w.transpose(0, 2, 1, 3, 4).reshape(B, n_kv, n_t * ps, hd)
        z = jnp.zeros((), jnp.int32)
        w = jax.vmap(lambda buf, r_, r0: jax.lax.dynamic_update_slice(
            buf, r_.astype(buf.dtype), (z, r0, z)))(
                w, rows.transpose(0, 2, 1, 3), rel0)
        w = w.reshape(B, n_kv, n_t, ps, hd).transpose(0, 2, 1, 3, 4)
        m = touched[:, :, None, None, None]
        if quant:
            amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=(3, 4))
            sc_new = jnp.maximum(amax, 1e-8) / 127.0
            q8 = jnp.clip(jnp.round(
                w.astype(jnp.float32) / sc_new[..., None, None]),
                -127, 127).astype(jnp.int8)
            pages_out = jnp.where(m, q8, raw)
            sc_out = jnp.where(touched[..., None], sc_new, sc)
            scales = scales.at[:, layer].set(
                scales[:, layer].at[ids].set(sc_out))
        else:
            pages_out = jnp.where(m, w.astype(pool.dtype), raw)
        return pool.at[:, layer].set(layer_pool.at[ids].set(pages_out)), \
            scales

    k_pool, k_scale = upd(k_pool, k_scale, k_new)
    v_pool, v_scale = upd(v_pool, v_scale, v_new)
    return k_pool, v_pool, k_scale, v_scale


class TestPagedKVUpdateWholePool:
    """ops.paged_kv_update gathers and scatters the window's pages on
    the WHOLE pool by (page, layer) (ISSUE 28: the carried pools keep
    the layout the paged kernel takes).  Same rows, same pages, same
    precision as the layer-sliced write it replaced: every pool and
    scale bit-identical on the same inputs, for the off-benchmark users
    too (GQA, int8, a C that straddles three pages, shared pages inside
    a window, free slots on the null page, a window clamped at the end
    of its table)."""
    PS, P_SLOT, L, HD, LAYER = 16, 6, 3, 8, 1
    # slots: 0 mid-page; 1 on a page boundary (its window's last page
    # untouched); 2, 3 free (null page, duplicate indices); 4 at the
    # end of its table (window start clamped)
    WIDTHS = {"decode": (1, [21, 32, 0, 0, 95]),
              "chunk": (32, [21, 32, 0, 0, 64]),
              "three_pages": (20, [14, 32, 0, 0, 76])}

    def _case(self, pool, n_kv, width):
        rng = np.random.RandomState(0)
        ps, P_slot, L, hd = self.PS, self.P_SLOT, self.L, self.HD
        C, pos = self.WIDTHS[width]
        B, P = len(pos), 1 + 3 * P_slot + 4
        shape = (P, L, n_kv, ps, hd)
        table = np.zeros((B, P_slot), np.int32)
        table[[0, 1, 4]] = (rng.permutation(P - 1)[:3 * P_slot] + 1) \
            .reshape(3, P_slot)
        if pool == "int8":
            kp, vp = (jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
                      for _ in range(2))
            ks, vs = (jnp.asarray(rng.uniform(0.01, 0.05, shape[:3]),
                                  jnp.float32) for _ in range(2))
        else:
            kp, vp = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                      for _ in range(2))
            ks = vs = None
        kn, vn = (jnp.asarray(rng.randn(B, C, n_kv, hd), jnp.bfloat16)
                  for _ in range(2))
        return (kp, vp, ks, vs, jnp.asarray(table),
                jnp.asarray(pos, jnp.int32), kn, vn)

    @staticmethod
    def _run(fn, args, layer):
        out = jax.jit(fn, static_argnums=(8,))(*args, layer)
        return [None if o is None else np.asarray(o) for o in out]

    def _both(self, pool, n_kv, width):
        from paddle_tpu.ops import paged_kv_update
        args = self._case(pool, n_kv, width)
        return (args, self._run(paged_kv_update, args, self.LAYER),
                self._run(_layer_sliced_kv_update, args, self.LAYER))

    @pytest.mark.parametrize("width", list(WIDTHS))
    @pytest.mark.parametrize("n_kv", [32, 8], ids=["mha", "gqa"])
    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    def test_pools_bit_identical_and_rows_land(self, pool, n_kv, width):
        args, got, want = self._both(pool, n_kv, width)
        for g, w in zip(got, want):
            if w is None:
                assert g is None
            else:
                assert g.dtype == w.dtype
                np.testing.assert_array_equal(g, w)
        # against the dense view: the occupied slots' logical rows
        # [pos, pos + C) of the written layer hold the step's rows
        kp, vp, ks, vs, table, pos, kn, vn = args
        C = kn.shape[1]
        for new, rows, scales in ((got[0], kn, got[2]), (got[1], vn, got[3])):
            lg = new[:, self.LAYER][np.asarray(table)].astype(np.float32)
            if scales is not None:
                lg = lg * scales[:, self.LAYER][np.asarray(table)][
                    ..., None, None]
            lg = lg.transpose(0, 1, 3, 2, 4).reshape(
                len(pos), -1, n_kv, self.HD)
            for b in (0, 1, 4):
                p = int(pos[b])
                want_rows = np.asarray(rows[b].astype(jnp.float32))
                if scales is None:
                    np.testing.assert_array_equal(lg[b, p:p + C], want_rows)
                else:
                    np.testing.assert_allclose(lg[b, p:p + C], want_rows,
                                               atol=0.05)
        # the other layers are never touched
        for new, old in ((got[0], kp), (got[1], vp)):
            for layer in (0, 2):
                np.testing.assert_array_equal(
                    new[:, layer], np.asarray(old)[:, layer])

    @pytest.mark.parametrize("width", list(WIDTHS))
    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    def test_read_only_page_in_window_keeps_bytes_and_scale(self, pool,
                                                            width):
        """Slot 1 writes from a page boundary, so the last page of its
        window (a shared prefix page could stand there) holds none of
        the step's rows: its bytes and its scale come back as they
        were — no re-encoding of a page nobody wrote."""
        from paddle_tpu.ops import paged_kv_update
        args = self._case(pool, 8, width)
        kp, vp, ks, vs, table, pos, kn, vn = args
        got = self._run(paged_kv_update, args, self.LAYER)
        C = kn.shape[1]
        first = int(pos[1]) // self.PS
        n_t = -(-C // self.PS) + 1
        untouched = int(table[1, first + n_t - 1])
        assert (first + n_t - 1) * self.PS >= int(pos[1]) + C
        for new, old in ((got[0], kp), (got[1], vp)):
            np.testing.assert_array_equal(new[untouched],
                                          np.asarray(old)[untouched])
        if pool == "int8":
            for new, old in ((got[2], ks), (got[3], vs)):
                np.testing.assert_array_equal(new[untouched],
                                              np.asarray(old)[untouched])

    @pytest.mark.parametrize("width", list(WIDTHS))
    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    def test_free_slots_meet_in_the_null_page_only(self, pool, width):
        """Every slot free: all B windows are the null page, n_t times
        each (duplicate scatter indices).  Whatever lands there, every
        other page and every other scale is untouched."""
        from paddle_tpu.ops import paged_kv_update
        kp, vp, ks, vs, table, pos, kn, vn = self._case(pool, 8, width)
        args = (kp, vp, ks, vs, jnp.zeros_like(table), jnp.zeros_like(pos),
                kn, vn)
        got = self._run(paged_kv_update, args, self.LAYER)
        for new, old in zip(got, (kp, vp, ks, vs)):
            if old is not None:
                np.testing.assert_array_equal(new[1:], np.asarray(old)[1:])

    @pytest.mark.parametrize("width", list(WIDTHS))
    @pytest.mark.parametrize("pool", ["bf16", "int8"])
    def test_window_clamped_at_the_table_end(self, pool, width):
        """Slot 4's rows end at its table's last row, so its window
        starts before its first row's page (p0 clamped): the rows land
        where the layer-sliced write put them and the pages of its
        table before the window keep their bytes."""
        args, got, want = self._both(pool, 8, width)
        kp, _, _, _, table, pos, kn, _ = args
        C = kn.shape[1]
        n_t = -(-C // self.PS) + 1
        assert int(pos[4]) // self.PS > self.P_SLOT - n_t   # clamped
        assert int(pos[4]) + C == self.P_SLOT * self.PS
        mine = np.asarray(table[4])
        np.testing.assert_array_equal(got[0][mine], want[0][mine])
        np.testing.assert_array_equal(got[1][mine], want[1][mine])
        before = mine[:self.P_SLOT - n_t]
        np.testing.assert_array_equal(got[0][before],
                                      np.asarray(kp)[before])


class TestDispatchDoesNotHideTheKernel:
    """paddle_tpu.ops picks kernel-or-twin from the backend and the shapes
    (each kernel module's `supports` predicate) and calls the kernel
    outside any try: what its lowering raises reaches the caller.

    Here the process is told that its backend is a TPU, so dispatch picks
    the kernel out of interpret mode, while the arrays live on the CPU —
    the Pallas lowering refuses with a ValueError, the very type the old
    dispatch caught to carry on with the XLA twin."""

    def test_attention_lowering_error_reaches_caller(self, monkeypatch):
        from paddle_tpu import ops
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q = r(1, 128, 2, 128)
        with pytest.raises(ValueError, match="interpret mode"):
            ops.attention(q, q, q, causal=True)

    def test_paged_attention_lowering_error_reaches_caller(self,
                                                           monkeypatch):
        from paddle_tpu import ops
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kp = r(8, 1, 2, 8, 128)
        pt = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        with pytest.raises(ValueError, match="interpret mode"):
            ops.paged_attention(r(2, 1, 2, 128), kp, kp, pt, pos, 0)

    def test_unsupported_shape_takes_the_twin_by_predicate(self,
                                                           monkeypatch):
        """head_dim 16 does not tile: the predicate says so and the twin
        answers — no kernel is tried, nothing is caught."""
        from paddle_tpu import ops
        from paddle_tpu.ops.pallas import paged_attention as k
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kp = r(8, 1, 2, 8, 16)
        q = r(2, 1, 2, 16)
        assert not k.supports(kp.shape)
        pt = jnp.zeros((2, 3), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        out = ops.paged_attention(q, kp, kp, pt, pos, 0)
        ref = ops.xla_paged_attention(q, kp, kp, pt, pos, 0)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


class TestKernelsPerShard:
    """On a mesh of more than one device a trainer enters
    ops.kernel_mesh_scope and dispatch runs each kernel under shard_map
    (GSPMD cannot partition a Mosaic kernel).  Four virtual devices,
    sharding 2 x mp 2 as chip_smoke's four-chip phase: values and
    gradients through ops' own entry points equal the XLA paths'.
    Inside the scope dispatch is told it may pick kernels; the kernels
    themselves still see a CPU backend and run in interpret mode."""

    @pytest.fixture()
    def scoped(self, monkeypatch):
        """scoped(fn) -> fn traced as a four-device trainer traces it."""
        from jax.sharding import Mesh
        from paddle_tpu import ops
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("sharding", "mp"))

        def wrap(fn):
            def inner(*a):
                with monkeypatch.context() as m, \
                        ops.kernel_mesh_scope(mesh):
                    m.setattr(ops, "_on_tpu", lambda: True)
                    return fn(*a)
            return inner
        return wrap

    @staticmethod
    def _check(per_shard, xla, args, argnums, tol):
        assert "shard_map" in str(jax.make_jaxpr(per_shard)(*args))
        assert "pallas_call" not in str(jax.make_jaxpr(xla)(*args))
        got = jax.jit(jax.value_and_grad(per_shard, argnums))(*args)
        want = jax.value_and_grad(xla, argnums)(*args)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=tol, rtol=tol)

    @pytest.mark.parametrize("kv_heads", [4, 2, 1],
                             ids=["mha", "gqa", "mqa_heads_stay_whole"])
    def test_attention(self, scoped, kv_heads):
        from paddle_tpu import ops
        q, k, v = (r(2, 256, 4, 128), r(2, 256, kv_heads, 128),
                   r(2, 256, kv_heads, 128))

        def loss(q, k, v):
            return jnp.sum(ops.attention(q, k, v, causal=True) ** 2)
        self._check(scoped(loss), loss, (q, k, v), (0, 1, 2), 1e-3)

    def test_rms_norm_and_add_rms_norm(self, scoped):
        from paddle_tpu import ops
        x, y, w = r(4, 16, 256), r(4, 16, 256), r(256)
        probe = jnp.arange(256, dtype=jnp.float32)

        def loss(x, y, w):
            resid, h = ops.fused_add_rms_norm(x, y, w)
            return jnp.sum(ops.rms_norm(h, w) * probe) + jnp.sum(resid ** 2)
        self._check(scoped(loss), loss, (x, y, w), (0, 1, 2), 2e-3)

    def test_rope(self, scoped):
        from paddle_tpu import ops
        q, k = r(2, 16, 4, 128), r(2, 16, 2, 128)
        cos, sin = rope_cos_sin(16, 128)

        def loss(q, k):
            a, b = ops.apply_rope(q, k, cos, sin)
            return jnp.sum(a ** 2 * 0.5) + jnp.sum(b ** 3)
        self._check(scoped(loss), loss, (q, k), (0, 1), 1e-4)

    def test_shapes_one_device_sees_decide(self, scoped):
        """Eight rows over two batch shards leave four per device: the
        rope kernel has no sublane-aligned block for that, `supports`
        says so about the LOCAL shape, and the XLA path answers."""
        from paddle_tpu import ops
        from paddle_tpu.ops.pallas import rope as k
        q = r(2, 4, 4, 128)
        cos, sin = rope_cos_sin(4, 128)
        assert k.supports(q.shape, q.shape, cos.shape)
        assert not k.supports((1, 4, 2, 128), (1, 4, 2, 128), cos.shape)
        f = scoped(lambda q: ops.apply_rope(q, q, cos, sin)[0])
        assert "pallas_call" not in str(jax.make_jaxpr(f)(q))

    def test_four_device_trainer_matches_xla_paths(self, monkeypatch):
        """The whole path: ShardedTrainStep (ZeRO-3, sharding 2 x mp 2,
        GQA llama) enters the scope by itself; with the kernels picked
        its first three losses equal the XLA paths' to fp32 rounding,
        and the lowered step holds one manual region per kernel call
        (2 layers x (rope, flash, add+rms, rms) + the final norm,
        forward and backward)."""
        import paddle_tpu as paddle
        from paddle_tpu import ops
        from paddle_tpu.distributed.topology import build_mesh
        from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                             shard_llama_tp)
        from paddle_tpu.parallel import ShardedTrainStep
        x = paddle.to_tensor(np.random.RandomState(0).randint(
            0, 128, (4, 128)).astype(np.int32))

        def run():
            cfg = LlamaConfig(
                vocab_size=128, hidden_size=256, intermediate_size=512,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=128,
                dtype="float32")
            paddle.seed(3)
            model = LlamaForCausalLM(cfg)
            mesh = build_mesh(sharding=2, mp=2, devices=jax.devices()[:4])
            shard_llama_tp(model, mesh)
            opt = paddle.optimizer.AdamW(
                1e-3, parameters=model.parameters(), weight_decay=0.1)
            step = ShardedTrainStep(model, opt, mesh, sharding_stage=3)
            manual = step.compiled_hlo(x, x, optimized=False).count(
                "manual_computation")
            return manual, [float(np.asarray(step(x, x).value))
                            for _ in range(3)]
        n_xla, want = run()
        monkeypatch.setattr(ops, "_on_tpu", lambda: True)
        n_kernels, got = run()
        assert (n_xla, n_kernels) == (0, 18)
        np.testing.assert_allclose(got, want, rtol=1e-5)
