"""Image node oracle tests: the conv/pool/rectifier nodes must agree with a
naive numpy im2col implementation of the reference algorithms
(parity with ConvolverSuite's scipy golden files, SURVEY §4)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.images.core import (
    CenterCornerPatcher,
    Convolver,
    Cropper,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    SymmetricRectifier,
    Windower,
    images_from_vectors,
    pack_filter_images,
    vectorize_images,
)
from keystone_tpu.nodes.learning.zca import ZCAWhitenerEstimator
from keystone_tpu.utils.stats import normalize_rows


def _patches_naive(img, S):
    """All S×S patches of (X, Y, C) img in the reference layout
    c + px*C + py*C*S (Convolver.makePatches)."""
    X, Y, C = img.shape
    rw, rh = X - S + 1, Y - S + 1
    out = np.zeros((rw * rh, S * S * C))
    for y in range(rh):
        for x in range(rw):
            row = x + y * rw
            for py in range(S):
                for px in range(S):
                    for c in range(C):
                        out[row, c + px * C + py * C * S] = img[
                            x + px, y + py, c
                        ]
    return out


def _norm_rows_np(mat, alpha):
    means = mat.mean(axis=1, keepdims=True)
    var = ((mat - means) ** 2).sum(axis=1, keepdims=True) / (mat.shape[1] - 1)
    return (mat - means) / np.sqrt(var + alpha)


def test_convolver_matches_naive_im2col():
    rng = np.random.default_rng(0)
    n, X, Y, C, S, K = 3, 8, 7, 2, 3, 5
    imgs = rng.standard_normal((n, X, Y, C)).astype(np.float32)
    filters = rng.standard_normal((K, S * S * C)).astype(np.float32)

    conv = Convolver(filters, X, Y, C, normalize_patches=False)
    out = np.asarray(conv.apply_batch(Dataset.of(imgs)).to_array())
    assert out.shape == (n, X - S + 1, Y - S + 1, K)

    for i in range(n):
        pm = _patches_naive(imgs[i], S)
        expected = pm @ filters.T  # (rw*rh, K)
        rw = X - S + 1
        for y in range(Y - S + 1):
            for x in range(rw):
                np.testing.assert_allclose(
                    out[i, x, y], expected[x + y * rw], rtol=1e-3, atol=1e-3
                )


def test_convolver_normalized_matches_naive():
    rng = np.random.default_rng(1)
    n, X, Y, C, S, K = 2, 6, 6, 3, 3, 4
    imgs = rng.standard_normal((n, X, Y, C)).astype(np.float32)
    filters = rng.standard_normal((K, S * S * C)).astype(np.float32)

    conv = Convolver(filters, X, Y, C, normalize_patches=True, var_constant=10.0)
    out = np.asarray(conv.apply_batch(Dataset.of(imgs)).to_array())

    for i in range(n):
        pm = _norm_rows_np(_patches_naive(imgs[i], S), 10.0)
        expected = pm @ filters.T
        rw = X - S + 1
        got = np.stack(
            [out[i, x, y] for y in range(X - S + 1) for x in range(rw)]
        )
        np.testing.assert_allclose(got, expected, rtol=1e-3, atol=1e-3)


def test_convolver_whitened_matches_naive():
    """Full reference path: normalize patches, subtract whitener means,
    multiply whitened filters."""
    rng = np.random.default_rng(2)
    n, X, Y, C, S, K = 2, 6, 6, 2, 3, 4
    imgs = rng.standard_normal((n, X, Y, C)).astype(np.float32)
    sample = rng.standard_normal((50, S * S * C)).astype(np.float32)
    whitener = ZCAWhitenerEstimator(0.1).fit_single(sample)
    filters = rng.standard_normal((K, S * S * C)).astype(np.float32)

    conv = Convolver(filters, X, Y, C, whitener=whitener, normalize_patches=True)
    out = np.asarray(conv.apply_batch(Dataset.of(imgs)).to_array())

    means = np.asarray(whitener.means)
    for i in range(n):
        pm = _norm_rows_np(_patches_naive(imgs[i], S), 10.0) - means
        expected = pm @ filters.T
        rw = X - S + 1
        got = np.stack(
            [out[i, x, y] for y in range(Y - S + 1) for x in range(rw)]
        )
        np.testing.assert_allclose(got, expected, rtol=1e-3, atol=1e-3)


def test_symmetric_rectifier():
    X = np.array([[[[1.0, -2.0]]]], dtype=np.float32)
    out = np.asarray(
        SymmetricRectifier(alpha=0.25).apply_batch(Dataset.of(X)).to_array()
    )
    np.testing.assert_allclose(out[0, 0, 0], [0.75, 0.0, 0.0, 1.75])


def test_pooler_matches_naive():
    """Sum pooling with clipped edge windows (Pooler.scala:21-84)."""
    rng = np.random.default_rng(3)
    n, X, Y, C = 2, 27, 27, 4
    imgs = rng.standard_normal((n, X, Y, C)).astype(np.float32)
    stride, ps = 13, 14
    out = np.asarray(
        Pooler(stride, ps, None, "sum").apply_batch(Dataset.of(imgs)).to_array()
    )
    start = ps // 2
    xs = list(range(start, X, stride))
    assert out.shape == (n, len(xs), len(xs), C)
    for i in range(n):
        for xi, x in enumerate(xs):
            for yi, y in enumerate(xs):
                x0, x1 = x - ps // 2, min(x + ps // 2, X)
                y0, y1 = y - ps // 2, min(y + ps // 2, Y)
                expected = imgs[i, x0:x1, y0:y1, :].sum(axis=(0, 1))
                np.testing.assert_allclose(
                    out[i, xi, yi], expected, rtol=1e-3, atol=1e-3
                )


def test_pooler_abs_pixel_fn():
    imgs = -np.ones((1, 4, 4, 1), dtype=np.float32)
    out = np.asarray(
        Pooler(2, 2, jnp.abs, "sum").apply_batch(Dataset.of(imgs)).to_array()
    )
    assert (out > 0).all()


def test_windower_matches_naive():
    rng = np.random.default_rng(4)
    n, X, Y, C, w, st = 2, 5, 5, 2, 3, 2
    imgs = rng.standard_normal((n, X, Y, C)).astype(np.float32)
    out = np.asarray(
        Windower(st, w).apply_batch(Dataset.of(imgs)).to_array()
    )
    xs = list(range(0, X - w + 1, st))
    assert out.shape == (n * len(xs) * len(xs), w, w, C)
    k = 0
    for i in range(n):
        for x in xs:
            for y in xs:
                np.testing.assert_allclose(
                    out[k], imgs[i, x : x + w, y : y + w, :]
                )
                k += 1


def test_vectorize_images_channel_major_layout():
    img = np.zeros((1, 2, 2, 2), dtype=np.float32)
    # value encodes (x, y, c) as x*100 + y*10 + c
    for x in range(2):
        for y in range(2):
            for c in range(2):
                img[0, x, y, c] = x * 100 + y * 10 + c
    v = np.asarray(vectorize_images(jnp.asarray(img)))[0]
    # layout index = c + x*C + y*X*C
    for x in range(2):
        for y in range(2):
            for c in range(2):
                assert v[c + x * 2 + y * 4] == x * 100 + y * 10 + c
    back = np.asarray(images_from_vectors(v[None], 2, 2, 2))
    np.testing.assert_allclose(back, img)


def test_zca_whitener_decorrelates():
    rng = np.random.default_rng(5)
    A = rng.standard_normal((500, 6)).astype(np.float32)
    A = A @ rng.standard_normal((6, 6)).astype(np.float32)  # correlate
    w = ZCAWhitenerEstimator(eps=1e-6).fit_single(A)
    out = np.asarray(w.transform(A))
    cov = out.T @ out / (A.shape[0] - 1)
    np.testing.assert_allclose(cov, np.eye(6), atol=0.05)


def test_normalize_rows_matches_numpy():
    rng = np.random.default_rng(6)
    A = rng.standard_normal((10, 8)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(normalize_rows(A, 10.0)),
        _norm_rows_np(A, 10.0),
        rtol=1e-4,
        atol=1e-4,
    )


def test_cropper_and_patcher_and_grayscale():
    rng = np.random.default_rng(7)
    imgs = rng.uniform(0, 255, (2, 8, 8, 3)).astype(np.float32)
    crop = np.asarray(
        Cropper(1, 2, 5, 6).apply_batch(Dataset.of(imgs)).to_array()
    )
    np.testing.assert_allclose(crop, imgs[:, 1:5, 2:6, :])
    cc = np.asarray(
        CenterCornerPatcher(4, 4).apply_batch(Dataset.of(imgs)).to_array()
    )
    assert cc.shape == (10, 4, 4, 3)
    # per-image grouping: cc[0] is img0's first crop, cc[5] img1's first
    np.testing.assert_allclose(cc[0], imgs[0, :4, :4, :])
    np.testing.assert_allclose(cc[5], imgs[1, :4, :4, :])
    # center crop is the 5th of each image's group
    np.testing.assert_allclose(cc[4], imgs[0, 2:6, 2:6, :])
    gray = np.asarray(GrayScaler().apply_batch(Dataset.of(imgs)).to_array())
    assert gray.shape == (2, 8, 8, 1)
    scaled = np.asarray(PixelScaler().apply_batch(Dataset.of(imgs)).to_array())
    assert scaled.max() <= 1.0


def test_convolver_sees_no_constant_in_filter_or_image():
    """A normalized patch has no mean: a constant added to a filter (what
    whitening at a small epsilon leaves in the bank, scaled by eps^-1/2) or
    to an image changes nothing — exactly, not up to a cancellation."""
    rng = np.random.default_rng(5)
    n, X, Y, C, S, K = 2, 8, 8, 3, 3, 6
    imgs = (128 + 40 * rng.standard_normal((n, X, Y, C))).astype(np.float32)
    filters = rng.standard_normal((K, S * S * C)).astype(np.float32)

    def out(f, x):
        conv = Convolver(f, X, Y, C, normalize_patches=True)
        return np.asarray(conv.apply_batch(Dataset.of(x)).to_array())

    base = out(filters, imgs)
    # 100 a filter entry against pixels of 128: conv − μ·Σf would subtract
    # two numbers of 3e5 to get one of 1e1, and keep two digits in float32
    np.testing.assert_allclose(out(filters + 100.0, imgs), base, atol=1e-3)
    np.testing.assert_allclose(out(filters, imgs + 1000.0), base, atol=1e-3)
    for i in range(n):
        pm = _norm_rows_np(_patches_naive(imgs[i], S), 10.0)
        got = np.stack(
            [base[i, x, y] for y in range(Y - S + 1) for x in range(X - S + 1)]
        )
        np.testing.assert_allclose(got, pm @ filters.T, rtol=1e-3, atol=1e-3)
