"""Dense multi-scale SIFT as a batched convolution stack.

Parity target: the reference's native path — utils/external/VLFeat.scala:18 →
src/main/cpp/VLFeat.cxx:40-210 (per-scale VlDsiftFilter with flat window,
windowSize=1.5, magnif=6, contrast threshold 0.005, ×512 short quantization)
wrapped by nodes/images/external/SIFTExtractor.scala:16.

The JNI/C++ pipeline becomes pure XLA: per scale —
Gaussian smooth (separable conv, σ = binSize/6) → central-difference
gradients → magnitude-weighted linear interpolation into 8 orientation maps →
4×4 spatial bins of side binSize pooled with a flat (box) window → sample the
keypoint grid (step) → L2 normalize, clamp 0.2, renormalize → zero
low-contrast descriptors → quantize (×512, clamp 255). Everything batched
over images on the MXU; no per-image native calls.

Descriptor layout matches vl_dsift: element (t, i, j) at t + 8·i + 32·j for
orientation t, x-bin i, y-bin j. Output per image: (128, N) float matrix, the
same shape external.SIFTExtractor emits.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.transformer import Transformer

_NBP = 4      # spatial bins per side
_NBO = 8      # orientation bins
_MAGNIF = 6.0
_CONTRAST_THRESHOLD = 0.005
_WINDOW_SIZE = 1.5


def _gaussian_kernel1d(sigma: float) -> np.ndarray:
    radius = max(1, int(math.ceil(4.0 * sigma)))
    x = np.arange(-radius, radius + 1)
    k = np.exp(-0.5 * (x / sigma) ** 2)
    return (k / k.sum()).astype(np.float32)


def _smooth(X, sigma: float):
    """Separable Gaussian blur of (n, X, Y) maps (σ=0 → identity);
    edge-replicated padding like vl_imsmooth. The taps are summed as
    shifted slices in float32, NOT a convolution: a TPU runs a float32
    convolution as one bf16 pass by default, which rounds the pixels by
    4e-3 of their value while the gradients below are differences of
    neighbouring smoothed pixels, a few hundredths of them — on the chip
    24% of the quantized descriptor elements then differ from a float32
    run's, by up to 3 (PERF.md §6, PR 33). At ``Precision.HIGHEST`` the
    one-channel convolution is exact and costs 1.5 ms an image of 500 ×
    375 on the MXU's six passes; the shifted sums are exact at 0.3."""
    if sigma <= 0:
        return X
    k = _gaussian_kernel1d(sigma)
    r = k.shape[0] // 2
    xd, yd = X.shape[1], X.shape[2]
    P = jnp.pad(X, [(0, 0), (r, r), (0, 0)], mode="edge")
    X = sum(float(w) * P[:, i : i + xd, :] for i, w in enumerate(k))
    P = jnp.pad(X, [(0, 0), (0, 0), (r, r)], mode="edge")
    return sum(float(w) * P[:, :, i : i + yd] for i, w in enumerate(k))


def _orientation_maps(X):
    """(n, X, Y) grayscale → (n, X, Y, 8) magnitude-weighted orientation
    histogram maps with linear interpolation between adjacent bins."""
    gx = (jnp.roll(X, -1, axis=1) - jnp.roll(X, 1, axis=1)) * 0.5
    gy = (jnp.roll(X, -1, axis=2) - jnp.roll(X, 1, axis=2)) * 0.5
    # replicate edges (roll wraps; fix borders with one-sided differences)
    gx = gx.at[:, 0, :].set(X[:, 1, :] - X[:, 0, :])
    gx = gx.at[:, -1, :].set(X[:, -1, :] - X[:, -2, :])
    gy = gy.at[:, :, 0].set(X[:, :, 1] - X[:, :, 0])
    gy = gy.at[:, :, -1].set(X[:, :, -1] - X[:, :, -2])

    mag = jnp.sqrt(gx * gx + gy * gy)
    theta = jnp.arctan2(gy, gx) % (2.0 * math.pi)
    t = theta / (2.0 * math.pi) * _NBO
    t0 = jnp.floor(t)
    frac = t - t0
    t0 = t0.astype(jnp.int32) % _NBO
    t1 = (t0 + 1) % _NBO
    w0 = mag * (1.0 - frac)
    w1 = mag * frac
    maps = (
        jax.nn.one_hot(t0, _NBO, dtype=X.dtype) * w0[..., None]
        + jax.nn.one_hot(t1, _NBO, dtype=X.dtype) * w1[..., None]
    )
    return maps


def _box_pool(maps, width: int):
    """Box-sum each orientation map over width×width windows ('flat window')
    → (n, X-w+1, Y-w+1, 8). Separable: two 1-D passes of ``width`` shifted
    slices added up. (``lax.reduce_window`` computes the same sums and took
    1.4 ms an image of 500 × 375 over the four scales on a TPU v5e where
    the shifted additions take 0.4: PERF.md §6, PR 33.)"""
    nx, ny = maps.shape[1] - width + 1, maps.shape[2] - width + 1
    maps = sum(maps[:, i : i + nx] for i in range(width))
    return sum(maps[:, :, i : i + ny] for i in range(width))


@partial(jax.jit, static_argnames=("bin_size", "step"))
def _sift_one_scale(gray, bin_size: int, step: int):
    """Descriptors for one scale over the keypoint grid.

    gray: (n, X, Y) already smoothed. Returns (n, nkx·nky, 128) float
    descriptors (un-normalized binning already weighted), plus norms.
    """
    n, xd, yd = gray.shape
    maps = _orientation_maps(gray)
    window = max(1, int(round(bin_size * _WINDOW_SIZE)))
    pooled = _box_pool(maps, window)  # value at p = sum over box anchored at p

    # Descriptor geometry: 4×4 bins of side bin_size; descriptor extent
    # 4·bin_size. Anchor descriptors at top-left corner positions.
    extent = _NBP * bin_size
    max_x = xd - extent
    max_y = yd - extent
    if max_x < 0 or max_y < 0:
        return jnp.zeros((n, 0, _NBP * _NBP * _NBO)), jnp.zeros((n, 0))
    kx = list(range(0, max_x + 1, step))
    ky = list(range(0, max_y + 1, step))

    # bin (i, j) of descriptor at (x, y) pools the box anchored at
    # (x + i·bin − (window−bin)//2, …) — centered flat window per bin.
    # NOTE: these advanced-index gathers were once rewritten as edge-pad
    # + stride-`step` slices (27% less HBM traffic by XLA's own count) —
    # and ran 1.5× SLOWER: stride-3 slices on the second-minor dim defeat
    # the TPU's vectorized loads worse than the gathers do. Measured,
    # reverted; don't repeat.
    off = (window - bin_size) // 2
    px_max = pooled.shape[1] - 1
    py_max = pooled.shape[2] - 1

    feats = []
    for j in range(_NBP):        # y bins slow
        for i in range(_NBP):    # x bins
            xs = np.clip(np.asarray(kx) + i * bin_size - off, 0, px_max)
            ys = np.clip(np.asarray(ky) + j * bin_size - off, 0, py_max)
            block = pooled[:, jnp.asarray(xs), :, :][:, :, jnp.asarray(ys), :]
            feats.append(block)  # (n, nkx, nky, 8)
    # layout: t + 8·i + 32·j  → stack bins in (j, i) order then interleave o
    desc = jnp.stack(feats, axis=3)  # (n, nkx, nky, 16, 8)
    desc = desc.reshape(n, len(kx) * len(ky), _NBP * _NBP * _NBO)

    norms = jnp.linalg.norm(desc, axis=-1)
    # vl_dsift norm semantics: norm before clamping used for the contrast test
    normed = desc / jnp.maximum(norms[..., None], 1e-12)
    normed = jnp.minimum(normed, 0.2)
    n2 = jnp.linalg.norm(normed, axis=-1, keepdims=True)
    normed = normed / jnp.maximum(n2, 1e-12)
    return normed, norms


class SIFTExtractor(Transformer):
    """Dense multi-scale SIFT over grayscale images (interface parity:
    SIFTExtractor.scala:10 / external/SIFTExtractor.scala:16).

    Input: (n, X, Y, 1) grayscale batch in [0, 1]. Output: list of (128, N)
    float matrices (N = Σ grid points over scales), scaled like the
    reference's short quantization (×512, clamp 255).
    """

    def __init__(self, step: int = 3, bin_size: int = 4,
                 num_scales: int = 4, scale_step: int = 0):
        self.step = step
        self.bin_size = bin_size
        self.num_scales = num_scales
        self.scale_step = scale_step

    def descriptors_batch(self, X) -> jnp.ndarray:
        """(n, X, Y, 1) → (n, N, 128) quantized descriptors."""
        gray = jnp.asarray(X)[..., 0].astype(jnp.float32)
        all_desc = []
        for scale in range(self.num_scales):
            bin_size = self.bin_size + 2 * scale  # VLFeat.cxx:71
            sigma = bin_size / _MAGNIF            # VLFeat.cxx:85
            smoothed = _smooth(gray, sigma)
            step = self.step + scale * self.scale_step
            desc, norms = _sift_one_scale(smoothed, bin_size, step)
            # zero low-contrast descriptors (VLFeat.cxx:62,146)
            desc = jnp.where(
                (norms > _CONTRAST_THRESHOLD)[..., None], desc, 0.0
            )
            # short quantization: ×512, clamp 255 (VLFeat.cxx:237-249)
            desc = jnp.minimum(jnp.floor(desc * 512.0), 255.0)
            all_desc.append(desc)
        return jnp.concatenate(all_desc, axis=1)

    def num_descriptors(self, xd: int, yd: int) -> int:
        """N: grid points over the scales of an ``xd`` × ``yd`` image."""
        total = 0
        for scale in range(self.num_scales):
            extent = _NBP * (self.bin_size + 2 * scale)
            step = self.step + scale * self.scale_step
            if xd >= extent and yd >= extent:
                total += ((xd - extent) // step + 1) * ((yd - extent) // step + 1)
        return total

    def row_scratch_bytes(self, shape: Tuple[int, ...]) -> int:
        """What an image holds besides the (128, N) output while its
        descriptors are made — segment dispatch prices a row by it
        (``compile/segment.py:_item_bytes``): the (N, 128) stack the scales
        are joined into, and a scale's eight orientation maps before and
        after the box sums. At 500 × 375 that is 37.6 + 12 MB."""
        _, xd, yd = shape[:3]
        stack = self.num_descriptors(xd, yd) * _NBP * _NBP * _NBO * 4
        return stack + 2 * xd * yd * _NBO * 4

    def trace_batch(self, X):
        # (n, N, 128) → (n, 128, N): the reference's column-major descriptor
        # matrix shape (external/SIFTExtractor.scala:27-33)
        with jax.named_scope("ks.featurize.sift"):
            return jnp.swapaxes(self.descriptors_batch(X), 1, 2)

    def apply(self, x):
        return self.trace_batch(jnp.asarray(x)[None])[0]
