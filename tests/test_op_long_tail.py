"""Long-tail op coverage: the full reference paddle.__all__ surface,
the extras module semantics vs numpy, and in-place write-back variants.

Reference: python/paddle/__init__.py __all__ (418 names);
tensor/manipulation.py, math.py; yaml `inplace:` annotations.
"""
import os
import re

import numpy as np
import pytest
import scipy.special as sps

import paddle_tpu as paddle


def test_reference_all_surface_complete():
    reference = "/root/reference/python/paddle/__init__.py"
    if not os.path.exists(reference):
        pytest.skip(f"the reference checkout is not mounted: {reference}")
    src = open(reference).read()
    m = re.search(r"__all__ = \[(.*?)\]", src, re.S)
    names = re.findall(r"'([^']+)'", m.group(1))
    missing = [n for n in names if not hasattr(paddle, n)]
    assert not missing, f"missing {len(missing)}: {missing[:20]}"


class TestExtras:
    def _t(self, a):
        return paddle.to_tensor(np.asarray(a))

    def test_stacks(self):
        a, b = np.ones((2, 3), np.float32), np.zeros((2, 3), np.float32)
        np.testing.assert_allclose(
            np.asarray(paddle.hstack([self._t(a), self._t(b)]).value),
            np.hstack([a, b]))
        np.testing.assert_allclose(
            np.asarray(paddle.vstack([self._t(a), self._t(b)]).value),
            np.vstack([a, b]))
        np.testing.assert_allclose(
            np.asarray(paddle.dstack([self._t(a), self._t(b)]).value),
            np.dstack([a, b]))

    def test_unbind_reverse_addn(self):
        x = np.arange(6, dtype=np.float32).reshape(2, 3)
        outs = paddle.unbind(self._t(x), axis=0)
        assert len(outs) == 2
        np.testing.assert_allclose(np.asarray(outs[1].value), x[1])
        np.testing.assert_allclose(
            np.asarray(paddle.reverse(self._t(x), axis=1).value),
            x[:, ::-1])
        np.testing.assert_allclose(
            np.asarray(paddle.add_n([self._t(x), self._t(x)]).value),
            2 * x)

    def test_histogram_bin_edges(self):
        x = np.array([0.0, 1.0, 2.0, 3.0], np.float32)
        got = np.asarray(paddle.histogram_bin_edges(self._t(x),
                                                    bins=4).value)
        np.testing.assert_allclose(got, np.histogram_bin_edges(x, 4),
                                   atol=1e-6)

    def test_special_functions(self):
        x = np.array([0.5, 1.5, 3.0], np.float32)
        np.testing.assert_allclose(
            np.asarray(paddle.gammaln(self._t(x)).value),
            sps.gammaln(x), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(paddle.gammainc(self._t(x), self._t(x)).value),
            sps.gammainc(x, x), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(paddle.multigammaln(self._t(x + 2), 2).value),
            sps.multigammaln(x + 2, 2), rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(paddle.sinc(self._t(x)).value), np.sinc(x),
            rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(paddle.polygamma(self._t(x), 1).value),
            sps.polygamma(1, x), rtol=1e-4)
        p = np.array([0.2, 0.8], np.float32)
        np.testing.assert_allclose(
            np.asarray(paddle.logit(self._t(p)).value),
            sps.logit(p), rtol=1e-5)

    def test_ldexp_renorm(self):
        x = np.array([1.0, 2.0], np.float32)
        e = np.array([2.0, 3.0], np.float32)
        np.testing.assert_allclose(
            np.asarray(paddle.ldexp(self._t(x), self._t(e)).value),
            np.ldexp(x, e.astype(np.int32)), rtol=1e-6)
        w = np.array([[3.0, 4.0], [0.3, 0.4]], np.float32)
        out = np.asarray(paddle.renorm(self._t(w), 2.0, 0, 1.0).value)
        norms = np.linalg.norm(out, axis=1)
        assert (norms <= 1.0 + 1e-5).all()
        np.testing.assert_allclose(out[1], w[1], rtol=1e-5)  # untouched

    def test_reduce_as_unfold_asstrided(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        tgt = np.zeros((1, 4), np.float32)
        np.testing.assert_allclose(
            np.asarray(paddle.reduce_as(self._t(x), self._t(tgt)).value),
            x.sum(axis=0, keepdims=True))
        u = np.asarray(paddle.unfold(self._t(x[0]), 0, 2, 1).value)
        np.testing.assert_allclose(u, np.stack([x[0][i:i + 2]
                                                for i in range(3)]))
        s = np.asarray(paddle.as_strided(self._t(x.ravel()), [2, 2],
                                         [4, 1]).value)
        np.testing.assert_allclose(
            s, np.lib.stride_tricks.as_strided(
                x.ravel(), (2, 2), (16, 4)).copy())

    def test_diagonal_scatter(self):
        x = np.zeros((3, 3), np.float32)
        y = np.array([1.0, 2.0, 3.0], np.float32)
        got = np.asarray(paddle.diagonal_scatter(self._t(x),
                                                 self._t(y)).value)
        np.testing.assert_allclose(got, np.diag(y))

    def test_random_families(self):
        paddle.seed(0)
        g = paddle.standard_gamma(self._t(np.full((2000,), 3.0,
                                                  np.float32)))
        assert abs(float(np.asarray(g.value).mean()) - 3.0) < 0.3
        ln = paddle.log_normal(mean=0.0, std=0.25, shape=[2000])
        assert abs(float(np.log(np.asarray(ln.value)).mean())) < 0.1
        t = self._t(np.zeros(2000, np.float32))
        paddle.geometric_(t, 0.5)
        assert abs(float(np.asarray(t.value).mean()) - 2.0) < 0.3
        t2 = self._t(np.zeros(100, np.float32))
        paddle.cauchy_(t2)
        assert np.asarray(t2.value).std() > 0


class TestInplace:
    def test_write_back_semantics(self):
        x = paddle.to_tensor(np.array([1.0, 4.0, 9.0], np.float32))
        out = paddle.sqrt_(x)
        assert out is x
        np.testing.assert_allclose(np.asarray(x.value), [1.0, 2.0, 3.0])

    def test_tensor_method_form(self):
        x = paddle.to_tensor(np.array([-1.0, 2.0], np.float32))
        x.abs_()
        np.testing.assert_allclose(np.asarray(x.value), [1.0, 2.0])

    def test_binary_inplace(self):
        x = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
        y = paddle.to_tensor(np.array([10.0, 20.0], np.float32))
        paddle.add_(x, y)
        np.testing.assert_allclose(np.asarray(x.value), [11.0, 22.0])
        np.testing.assert_allclose(np.asarray(y.value), [10.0, 20.0])

    def test_inplace_on_grad_leaf_rejected(self):
        x = paddle.to_tensor(np.ones(3, np.float32))
        x.stop_gradient = False
        with pytest.raises(RuntimeError, match="in-place"):
            paddle.exp_(x)

    def test_t_and_flatten(self):
        x = paddle.to_tensor(np.arange(6, dtype=np.float32).reshape(2, 3))
        paddle.t_(x)
        assert tuple(x.shape) == (3, 2)
        paddle.flatten_(x)
        assert tuple(x.shape) == (6,)


def test_box_coder_decode_center_size():
    """decode path vs direct formula (encode path is registry-tested)."""
    prior = np.array([[0., 0., 4., 4.], [2., 2., 8., 8.]], np.float32)
    deltas = np.random.RandomState(0).randn(3, 2, 4).astype(np.float32) * 0.3
    out = paddle.box_coder(paddle.to_tensor(prior),
                           paddle.to_tensor(deltas),
                           code_type="decode_center_size",
                           variance=[0.1, 0.1, 0.2, 0.2])
    got = np.asarray(out.value)
    assert got.shape == (3, 2, 4)
    pw = prior[:, 2] - prior[:, 0]
    ph = prior[:, 3] - prior[:, 1]
    pcx = prior[:, 0] + pw / 2
    pcy = prior[:, 1] + ph / 2
    cx = 0.1 * deltas[..., 0] * pw + pcx
    cy = 0.1 * deltas[..., 1] * ph + pcy
    w = np.exp(0.2 * deltas[..., 2]) * pw
    h = np.exp(0.2 * deltas[..., 3]) * ph
    want = np.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_yolo_box_iou_aware():
    """iou_aware layout: first an_num channels are IoU predictions."""
    rs = np.random.RandomState(3)
    x = rs.randn(1, 16, 3, 3).astype(np.float32)    # 2 anchors, 2 cls
    img = np.array([[96, 64]], np.float32)
    b, s = paddle.yolo_box(paddle.to_tensor(x), paddle.to_tensor(img),
                           anchors=[10, 13, 16, 30], class_num=2,
                           conf_thresh=0.0, downsample_ratio=32,
                           iou_aware=True, iou_aware_factor=0.4)
    b, s = np.asarray(b.value), np.asarray(s.value)
    assert b.shape == (1, 18, 4) and s.shape == (1, 18, 2)
    # spot-check one cell against the reference formulas
    sig = lambda v: 1.0 / (1.0 + np.exp(-v))
    an, cls, k, l, j = 2, 2, 1, 2, 1                # anchor 1, cell (1,2)
    e = lambda ent: x[0, an + j * (5 + cls) + ent, k, l]
    conf = sig(e(4)) ** 0.6 * sig(x[0, j, k, l]) ** 0.4
    cx = (l + sig(e(0))) * 64 / 3
    np.testing.assert_allclose(b[0, j * 9 + k * 3 + l, 0],
                               max(cx - np.exp(e(2)) * 16 * 64 /
                                   (32 * 3) / 2, 0), rtol=1e-4)
    np.testing.assert_allclose(s[0, j * 9 + k * 3 + l, 1],
                               conf * sig(e(6)), rtol=1e-4)
