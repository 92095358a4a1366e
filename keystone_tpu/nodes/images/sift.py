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

# What the two bodies of ``SIFTExtractor.sampled_batch`` pay a row they move,
# on a TPU v5e (``sampled_path`` has the readings; PERF.md §6, PR 36):
# "bins" gathers rows of 8 floats — its one gather over the rows gathered —,
# "grid" rows of 128 through the keypoint grid — what that body costs over
# "bins" without its gather, a row of the grid
_BIN_ROW_NS = 13.5
_GRID_ROW_NS = 9.5


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


def _bin_window(bin_size: int) -> Tuple[int, int]:
    """``(window, off)``: the side of the flat window a bin is pooled over,
    and how far its anchor lies before the bin's (a centered window)."""
    window = max(1, int(round(bin_size * _WINDOW_SIZE)))
    return window, (window - bin_size) // 2


def _grid(xd: int, yd: int, bin_size: int, step: int) -> Tuple[int, int]:
    """``(nkx, nky)``: the keypoints of one scale over an ``xd`` × ``yd``
    image — descriptors of 4×4 bins of side ``bin_size``, anchored at their
    top-left corners ``(ix·step, iy·step)`` — or (0, 0) where none fits."""
    extent = _NBP * bin_size
    if xd < extent or yd < extent:
        return 0, 0
    return (xd - extent) // step + 1, (yd - extent) // step + 1


def _pooled_maps(gray, bin_size: int):
    """(n, X, Y) smoothed → (n, X-w+1, Y-w+1, 8): the orientation maps
    box-summed over a bin's flat window; the value at p is the sum over the
    box anchored at p. Everything a scale computes whatever descriptors are
    then read from it."""
    return _box_pool(_orientation_maps(gray), _bin_window(bin_size)[0])


def _normalize(desc):
    """(…, 128) binned descriptors → (L2-normalized, clamped at 0.2 and
    normalized again; the norms before the clamp, which the contrast test
    reads: vl_dsift's semantics)."""
    norms = jnp.linalg.norm(desc, axis=-1)
    normed = desc / jnp.maximum(norms[..., None], 1e-12)
    normed = jnp.minimum(normed, 0.2)
    n2 = jnp.linalg.norm(normed, axis=-1, keepdims=True)
    return normed / jnp.maximum(n2, 1e-12), norms


def _quantize(desc, norms):
    """Zero the low-contrast descriptors (VLFeat.cxx:62,146), then the
    short quantization: ×512, clamp 255 (VLFeat.cxx:237-249)."""
    desc = jnp.where((norms > _CONTRAST_THRESHOLD)[..., None], desc, 0.0)
    return jnp.minimum(jnp.floor(desc * 512.0), 255.0)


def _binned(pooled, grid: Tuple[int, int], step: int, bin_size: int):
    """One scale's (n, px, py, 8) pooled maps → (n, nkx·nky, 128): the raw
    sums of the 4×4 bins of every keypoint of ``grid``, keypoints ``ix·nky +
    iy``, elements in vl_dsift's layout t + 8·i + 32·j; not normalized."""
    n = pooled.shape[0]
    nkx, nky = grid
    kx = np.arange(nkx) * step
    ky = np.arange(nky) * step

    # bin (i, j) of descriptor at (x, y) pools the box anchored at
    # (x + i·bin − (window−bin)//2, …) — centered flat window per bin.
    # NOTE: these advanced-index gathers were once rewritten as edge-pad
    # + stride-`step` slices (27% less HBM traffic by XLA's own count) —
    # and ran 1.5× SLOWER: stride-3 slices on the second-minor dim defeat
    # the TPU's vectorized loads worse than the gathers do. Measured,
    # reverted; don't repeat. Nor ONE gather an axis for all four bins and
    # a transpose in place of the 32 gathers and the stack: the sampled
    # grid body reads 0.568 ms an image for 0.531 (PERF.md §6, PR 36).
    _, off = _bin_window(bin_size)
    px_max = pooled.shape[1] - 1
    py_max = pooled.shape[2] - 1

    feats = []
    for j in range(_NBP):        # y bins slow
        for i in range(_NBP):    # x bins
            xs = np.clip(kx + i * bin_size - off, 0, px_max)
            ys = np.clip(ky + j * bin_size - off, 0, py_max)
            block = pooled[:, jnp.asarray(xs), :, :][:, :, jnp.asarray(ys), :]
            feats.append(block)  # (n, nkx, nky, 8)
    # layout: t + 8·i + 32·j  → stack bins in (j, i) order then interleave o
    desc = jnp.stack(feats, axis=3)  # (n, nkx, nky, 16, 8)
    return desc.reshape(n, nkx * nky, _NBP * _NBP * _NBO)


@partial(jax.jit, static_argnames=("bin_size", "step"))
def _sift_one_scale(gray, bin_size: int, step: int):
    """Descriptors for one scale over the keypoint grid.

    gray: (n, X, Y) already smoothed. Returns (n, nkx·nky, 128) float
    descriptors (un-normalized binning already weighted), plus norms.
    """
    n, xd, yd = gray.shape
    pooled = _pooled_maps(gray, bin_size)
    grid = _grid(xd, yd, bin_size, step)
    if not grid[0]:
        return jnp.zeros((n, 0, _NBP * _NBP * _NBO)), jnp.zeros((n, 0))
    return _normalize(_binned(pooled, grid, step, bin_size))


def _bin_addresses(local, grid, step: int, bin_size: int, pooled_shape):
    """Where the 16 bins of the keypoints ``local`` — (n, s) indices ``ix·nky
    + iy`` into one scale's ``grid`` — lie in that scale's pooled maps,
    flattened over the image: (n, s, 16) positions ``x·py + y``, bins in
    ``_sift_one_scale``'s (j, i) order, clipped as it clips them."""
    nky = grid[1]
    px, py = pooled_shape
    bins = np.arange(_NBP) * bin_size - _bin_window(bin_size)[1]
    x = (local // nky * step)[..., None] + np.tile(bins, _NBP)    # i fast
    y = (local % nky * step)[..., None] + np.repeat(bins, _NBP)   # j slow
    return jnp.clip(x, 0, px - 1) * py + jnp.clip(y, 0, py - 1)


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

    def _scales(self):
        """``(bin_size, sigma, step)`` of each scale."""
        for scale in range(self.num_scales):
            bin_size = self.bin_size + 2 * scale  # VLFeat.cxx:71
            yield (
                bin_size, bin_size / _MAGNIF,     # VLFeat.cxx:85
                self.step + scale * self.scale_step,
            )

    def descriptors_batch(self, X) -> jnp.ndarray:
        """(n, X, Y, 1) → (n, N, 128) quantized descriptors."""
        gray = jnp.asarray(X)[..., 0].astype(jnp.float32)
        all_desc = []
        for bin_size, sigma, step in self._scales():
            desc, norms = _sift_one_scale(_smooth(gray, sigma), bin_size, step)
            all_desc.append(_quantize(desc, norms))
        return jnp.concatenate(all_desc, axis=1)

    def sampled_batch(self, X, columns) -> jnp.ndarray:
        """(n, X, Y, 1) and (n, s) column indices in [0, N) → (n, 128, s):
        ``trace_batch(X)`` at those columns of each image, made without the
        rest. A column decodes to (scale, ix, iy) in the order
        ``descriptors_batch`` joins and reshapes — scales outermost, ``ix·nky
        + iy`` within one; every scale's pooled maps are made as the full
        body makes them, and a column's 16 bins × 8 orientations are read
        from its own scale's by one of two bodies (``sampled_path``).
        Normalization, the contrast test and the quantization read no other
        column and see the s sampled rows alone; the (128, N) transpose is
        never built."""
        gray = jnp.asarray(X)[..., 0].astype(jnp.float32)
        _, xd, yd = gray.shape
        path = self.sampled_path(xd, yd, columns.shape[1])
        body = self._sampled_grid if path == "grid" else self._sampled_bins
        with jax.named_scope("ks.featurize.sift_sampled"):
            desc = body(gray, columns)
            return jnp.swapaxes(_quantize(*_normalize(desc)), 1, 2)

    def sampled_path(self, xd: int, yd: int, samples: int) -> str:
        """Which body reads ``samples`` columns an image of ``xd`` × ``yd``
        off the pooled maps, from shapes alone: ``"bins"`` gathers 16 rows
        of 8 floats a column, ``"grid"`` reads all N rows of 128 floats
        through the keypoint grid to take ``samples`` of them — whichever
        moves its rows sooner by the two measured rates. One program each
        on a TPU v5e, ms an image, bins | grid: 256 × 256 at steps 3-4-5-6,
        1,220 of 13,436 columns (9.1%), slices of 256: 0.6745 | 0.5306
        (the gather alone 0.2794); 500 × 375 at step 3, 651 of 73,505
        (0.9%), slices of 64: 1.3562 | 1.8820 (the gather 0.1334). The
        bodies cross where a sample is 4.4% of the grid."""
        bins = _NBP * _NBP * samples * _BIN_ROW_NS
        grid = self.num_descriptors(xd, yd) * _GRID_ROW_NS
        return "grid" if grid < bins else "bins"

    def _pooled_scales(self, gray):
        """``(pooled maps, grid, step, bin_size)`` of each scale that fits
        the images."""
        _, xd, yd = gray.shape
        for bin_size, sigma, step in self._scales():
            grid = _grid(xd, yd, bin_size, step)
            if grid[0]:
                pooled = _pooled_maps(_smooth(gray, sigma), bin_size)
                yield pooled, grid, step, bin_size

    def _sampled_bins(self, gray, columns):
        """The sparse body, (n, s, 128) raw sums: ONE gather an image of all
        its columns' 16 × 8-float bins — the scales' pooled maps side by
        side, each column's bins addressed in its own scale's (a gather a
        scale for every column costs four times the lanes read: 0.13 ms an
        image each, PERF.md §6, PR 34). The (N, 128) stack and the join
        over scales are never built."""
        n = gray.shape[0]
        maps, at, first, base = [], None, 0, 0
        for pooled, grid, step, bin_size in self._pooled_scales(gray):
            # columns of other scales address a keypoint of this one
            # here, and are not kept
            local = jnp.clip(columns - first, 0, grid[0] * grid[1] - 1)
            here = base + _bin_addresses(
                local, grid, step, bin_size, pooled.shape[1:3]
            )
            at = here if at is None else jnp.where(
                (columns >= first)[..., None], here, at
            )
            maps.append(pooled.reshape(n, -1, _NBO))
            first += grid[0] * grid[1]
            base += maps[-1].shape[1]
        return jnp.take_along_axis(
            jnp.concatenate(maps, axis=1), at.reshape(n, -1, 1), axis=1
        ).reshape(n, -1, _NBP * _NBP * _NBO)

    def _sampled_grid(self, gray, columns):
        """The dense body, (n, s, 128) raw sums: every scale's bins at its
        keypoint grid as the full body reads them (``_binned``), joined to
        the (n, N, 128) stack of raw sums, and ONE take of the s sampled
        rows of 512 bytes (a take a scale with a ``where`` over the scales
        reads 0.571 ms an image where this reads 0.531, PERF.md §6,
        PR 36)."""
        stack = jnp.concatenate([
            _binned(pooled, grid, step, bin_size)
            for pooled, grid, step, bin_size in self._pooled_scales(gray)
        ], axis=1)
        return jnp.take_along_axis(stack, columns[..., None], axis=1)

    def num_descriptors(self, xd: int, yd: int) -> int:
        """N: grid points over the scales of an ``xd`` × ``yd`` image."""
        return sum(
            int(np.prod(_grid(xd, yd, bin_size, step)))
            for bin_size, _, step in self._scales()
        )

    def row_scratch_bytes(self, shape: Tuple[int, ...]) -> int:
        """What an image holds besides the (128, N) output while its
        descriptors are made — segment dispatch prices a row by it
        (``compile/segment.py:_item_bytes``): the (N, 128) stack the scales
        are joined into, and a scale's eight orientation maps before and
        after the box sums. At 500 × 375 that is 37.6 + 12 MB."""
        _, xd, yd = shape[:3]
        stack = self.num_descriptors(xd, yd) * _NBP * _NBP * _NBO * 4
        return stack + 2 * xd * yd * _NBO * 4

    def sampled_scratch_bytes(
        self, shape: Tuple[int, ...], samples: int
    ) -> int:
        """What an image holds besides its sample while ``sampled_batch``
        makes ``samples`` columns of it: a scale's eight orientation maps
        before and after the box sums, and what its body reads the columns
        from. ``"bins"``: every scale's pooled maps side by side for the one
        gather — at 500 × 375 that is 22.9 + 12 MB, not the 37.6 MB stack
        besides. ``"grid"``: the (N, 128) stack of raw bins and the widest
        scale's before it is joined — 6.9 + 3.4 + 4.2 MB at 256 × 256 and
        scale step 1."""
        _, xd, yd = shape[:3]
        maps = 2 * xd * yd * _NBO * 4
        fits = [
            (bin_size, int(np.prod(_grid(xd, yd, bin_size, step))))
            for bin_size, _, step in self._scales()
        ]
        if self.sampled_path(xd, yd, samples) == "grid":
            rows = sum(k for _, k in fits) + max(k for _, k in fits)
            return maps + rows * _NBP * _NBP * _NBO * 4
        windows = [_bin_window(bin_size)[0] for bin_size, k in fits if k]
        positions = sum((xd - w + 1) * (yd - w + 1) for w in windows)
        return maps + positions * _NBO * 4

    def trace_batch(self, X):
        # (n, N, 128) → (n, 128, N): the reference's column-major descriptor
        # matrix shape (external/SIFTExtractor.scala:27-33)
        with jax.named_scope("ks.featurize.sift"):
            return jnp.swapaxes(self.descriptors_batch(X), 1, 2)

    def apply(self, x):
        return self.trace_batch(jnp.asarray(x)[None])[0]
