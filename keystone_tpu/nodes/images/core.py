"""Image operator nodes, batched NXYC.

Parity targets: nodes/images/ — Convolver.scala:20,48, Pooler.scala:21,
SymmetricRectifier.scala:7, Windower.scala:13, GrayScaler.scala:9,
PixelScaler.scala:10, ImageVectorizer.scala:12, Cropper.scala:18,
RandomPatcher.scala:16, CenterCornerPatcher.scala:18.

Image representation: a batch is one ``(n, X, Y, C)`` float array in HBM —
the reference's five per-image storage layouts (utils/images/Image.scala)
collapse into this single canonical dense layout; loaders do the
transposition once at ingest. ``x``/``y`` follow the reference's
``Image.get(x, y, c)`` coordinates. The canonical *vectorized* layout is the
reference's channel-major order ``c + x*C + y*X*C``.

The Convolver is the showpiece mapping: the reference's im2col + GEMM over
patches (Convolver.scala:128-203) with per-patch mean/variance normalization
and ZCA whitening becomes ONE ``lax.conv_general_dilated`` (MXU) plus two
``reduce_window`` moment sums and elementwise algebra — the normalization
never materializes the patch matrix.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.transformer import Transformer
from ...utils.params import as_param

_DIMNUMS = ("NHWC", "HWIO", "NHWC")  # H≡x, W≡y throughout


def vectorize_images(X):
    """(n, X, Y, C) → (n, X*Y*C) in channel-major order c + x*C + y*X*C
    (parity: ImageVectorizer.scala:12 flattening ChannelMajor storage)."""
    n = X.shape[0]
    return jnp.transpose(X, (0, 2, 1, 3)).reshape(n, -1)


def images_from_vectors(V, x_dim: int, y_dim: int, channels: int):
    """Inverse of :func:`vectorize_images`."""
    n = V.shape[0]
    return jnp.transpose(
        V.reshape(n, y_dim, x_dim, channels), (0, 2, 1, 3)
    )


class ImageVectorizer(Transformer):
    def trace_batch(self, X):
        return vectorize_images(X)


class PixelScaler(Transformer):
    """byte pixels → [0,1] doubles (parity: PixelScaler.scala:10)."""

    def trace_batch(self, X):
        return X.astype(jnp.float32) / 255.0


class GrayScaler(Transformer):
    """Luminance per the reference's human-eye weights
    (parity: GrayScaler.scala:9 via ImageUtils.toGrayScale:73-113)."""

    def trace_batch(self, X):
        # uint8 ingestion: images ride to HBM as bytes (4x less transfer
        # than f32); entry ops cast on device
        X = X.astype(jnp.float32)
        # reference weights: 0.299 R + 0.587 G + 0.114 B
        w = jnp.array([0.299, 0.587, 0.114], dtype=X.dtype)
        if X.shape[-1] == 3:
            return (X * w).sum(axis=-1, keepdims=True)
        return X.mean(axis=-1, keepdims=True)


class SymmetricRectifier(Transformer):
    """Channel-doubling rectification [max(v, x−α); max(v, −x−α)]
    (parity: SymmetricRectifier.scala:7-32)."""

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def trace_batch(self, X):
        with jax.named_scope("ks.featurize.rectify"):
            return jnp.concatenate(
                [
                    jnp.maximum(self.max_val, X - self.alpha),
                    jnp.maximum(self.max_val, -X - self.alpha),
                ],
                axis=-1,
            )


def pack_filter_images(filters):
    """(k, S, S, C) filter images → (k, S·S·C) rows in the canonical patch
    layout c + px·C + py·C·S (parity: Convolver.packFilters:99-127)."""
    filters = jnp.asarray(filters)
    k = filters.shape[0]
    return jnp.transpose(filters, (0, 2, 1, 3)).reshape(k, -1)


class Convolver(Transformer):
    """Filter-bank convolution with optional per-patch normalization and ZCA
    whitening (parity: Convolver.scala:20-223).

    ``filters``: (k, S²·C) rows in patch layout c + px·C + py·C·S, already
    whitened by the caller when a whitener is used (the reference does the
    same: Convolver.scala:75-81 folds W·Wᵀ into the filters).

    out(x,y,k) = p̂(x,y)·f_k − means·f_k where p̂ is the
    mean/variance-normalized patch; computed as conv + window moments, with
    the patch mean taken out of the FILTER (f̃ = f − f̄, so p·f̃ = (p − μ)·f):

        p̂·f = conv(img − c, f̃) / sd_patch      (any constant c an image)
    """

    def __init__(
        self,
        filters,
        img_x: int,
        img_y: int,
        img_channels: int,
        whitener=None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
    ):
        self.filters = as_param(filters, dtype='float32')
        self.img_x = img_x
        self.img_y = img_y
        self.img_channels = img_channels
        self.whitener = whitener
        self.normalize_patches = normalize_patches
        self.var_constant = var_constant
        self.conv_size = int(
            math.isqrt(self.filters.shape[1] // img_channels)
        )
        if self.conv_size ** 2 * img_channels != self.filters.shape[1]:
            raise ValueError("filters must be square patches")

    def trace_batch(self, X):
        with jax.named_scope("ks.featurize.conv"):
            return self._convolve(X)

    def _operands(self, X):
        """``(X, filters)`` as the patch product takes them, float32."""
        X = X.astype(jnp.float32)
        filters = self.filters
        if self.normalize_patches:
            # A normalized patch has no mean, so only the mean-free part of
            # a filter meets it: (p − μ)·f = p·(f − f̄), and f − f̄ sees no
            # constant added to the image. A whitened filter's mean is
            # rounding scaled by ε^−½ (16 times the rest of the bank at
            # ε = 1e-5) and pixels are 128 ± 40: contracted as they come in
            # one bf16 pass, conv − μ·Σf cancels most of its digits.
            filters = filters - filters.mean(axis=1, keepdims=True)
            X = X - jnp.mean(X, axis=(1, 2, 3), keepdims=True)
        return X, filters

    def _patch_sd(self, X):
        """sqrt(var + var_constant) of every window of ``X`` (the product's
        operand), (n, resX, resY, 1): two window sums, no patch matrix."""
        S, C = self.conv_size, self.img_channels
        m = S * S * C
        ones_spec = (1, S, S, C)  # window over the whole patch
        p_sum = jax.lax.reduce_window(
            X, 0.0, jax.lax.add, ones_spec, (1, 1, 1, 1), "valid"
        ).sum(axis=-1, keepdims=True)
        p_sumsq = jax.lax.reduce_window(
            X * X, 0.0, jax.lax.add, ones_spec, (1, 1, 1, 1), "valid"
        ).sum(axis=-1, keepdims=True)
        var = (p_sumsq - p_sum * (p_sum / m)) / (m - 1)
        return jnp.sqrt(var + self.var_constant)

    def _bias(self):
        """What the whitener's mean leaves in every output, (K,) or None."""
        if self.whitener is None:
            return None
        return self.whitener.means @ self.filters.T

    def _convolve(self, X):
        S, C = self.conv_size, self.img_channels
        K = self.filters.shape[0]
        X, filters = self._operands(X)

        # kernel[pox, poy, c, k] from row layout c + pox*C + poy*C*S
        kernel = jnp.transpose(filters.reshape(K, S, S, C), (2, 1, 3, 0))
        conv = jax.lax.conv_general_dilated(
            X, kernel, window_strides=(1, 1), padding="VALID",
            dimension_numbers=_DIMNUMS,
        )  # (n, resX, resY, K)

        if self.normalize_patches:
            conv = conv / self._patch_sd(X)
        bias = self._bias()
        if bias is not None:
            conv = conv - bias
        return conv

    @staticmethod
    def build(
        filter_images,
        img_x: int,
        img_y: int,
        img_channels: int,
        whitener=None,
        normalize_patches: bool = True,
        var_constant: float = 10.0,
        flip_filters: bool = False,
    ) -> "Convolver":
        """From (k, S, S, C) filter images, whitening them if a whitener is
        given (parity: Convolver.apply:61-91)."""
        f = jnp.asarray(filter_images)
        if flip_filters:
            f = f[:, ::-1, ::-1, :]
        packed = pack_filter_images(f)
        if whitener is not None:
            packed = jnp.matmul(
                whitener.transform(packed), whitener.whitener.T,
                precision=jax.lax.Precision.HIGHEST,
            )
        return Convolver(
            packed, img_x, img_y, img_channels, whitener,
            normalize_patches, var_constant,
        )


class Pooler(Transformer):
    """Strided window pooling (parity: Pooler.scala:21-84). Pool centers
    start at poolSize/2 and step by ``stride``; windows clip at the image
    edge. ``pixel_fn`` maps pixels before pooling; ``pool_fn`` is 'sum',
    'max' or 'mean'."""

    def __init__(
        self,
        stride: int,
        pool_size: int,
        pixel_fn: Optional[Callable] = None,
        pool_fn: str = "sum",
    ):
        self.stride = stride
        self.pool_size = pool_size
        self.pixel_fn = pixel_fn
        if pool_fn not in ("sum", "max", "mean"):
            raise ValueError(f"unsupported pool_fn {pool_fn}")
        self.pool_fn = pool_fn

    def trace_batch(self, X):
        with jax.named_scope("ks.featurize.pool"):
            return self._pool(X)

    def _pool(self, X):
        ps, st = self.pool_size, self.stride
        start = ps // 2
        # The reference window is [x−ps/2, x+ps/2) with integer division —
        # 2·(ps//2) wide, NOT ps wide for odd ps (Pooler.scala:56-59).
        w = 2 * (ps // 2)
        n, xd, yd, c = X.shape
        if self.pixel_fn is not None:
            X = self.pixel_fn(X)
        npx = max(1, -(-(xd - start) // st))  # ceil
        npy = max(1, -(-(yd - start) // st))
        # pad so every (possibly clipped) window fits; identity element pad
        ext_x = (npx - 1) * st + w
        ext_y = (npy - 1) * st + w
        init = -jnp.inf if self.pool_fn == "max" else 0.0
        X = jnp.pad(
            X,
            ((0, 0), (0, max(0, ext_x - xd)), (0, max(0, ext_y - yd)), (0, 0)),
            constant_values=init if self.pool_fn == "max" else 0.0,
        )
        op = jax.lax.max if self.pool_fn == "max" else jax.lax.add
        out = jax.lax.reduce_window(
            X, init, op, (1, w, w, 1), (1, st, st, 1), "valid"
        )
        out = out[:, :npx, :npy, :]
        if self.pool_fn == "mean":
            out = out / (ps * ps)
        return out


class Windower(Transformer):
    """All windowSize×windowSize patches stepping by ``stride``; a batch of n
    images becomes a batch of n·numWindows patch images
    (parity: Windower.scala:13-55)."""

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def trace_batch(self, X):
        n, xd, yd, c = X.shape
        w, st = self.window_size, self.stride
        nx = len(range(0, xd - w + 1, st))
        ny = len(range(0, yd - w + 1, st))
        # w² shifted slices instead of nx·ny dynamic ones:
        # shifted[dx][dy][n, xi, yi, c] = X[n, xi·st+dx, yi·st+dy, c]
        rows = []
        for dx in range(w):
            cols = []
            for dy in range(w):
                cols.append(
                    X[:, dx : dx + (nx - 1) * st + 1 : st,
                      dy : dy + (ny - 1) * st + 1 : st, :]
                )
            rows.append(jnp.stack(cols, axis=-2))  # (n, nx, ny, w(dy), c)
        patches = jnp.stack(rows, axis=-3)  # (n, nx, ny, w(dx), w(dy), c)
        # reference emission order: per image, for x, for y
        return patches.reshape(n * nx * ny, w, w, c)


class Cropper(Transformer):
    """Fixed crop [startX,endX)×[startY,endY)
    (parity: Cropper.scala:18)."""

    def __init__(self, start_x: int, start_y: int, end_x: int, end_y: int):
        self.start_x, self.start_y = start_x, start_y
        self.end_x, self.end_y = end_x, end_y

    def trace_batch(self, X):
        return X[:, self.start_x : self.end_x, self.start_y : self.end_y, :]


class RandomPatcher(Transformer):
    """``num_patches`` random windows per image, fresh randomness per batch
    (parity: RandomPatcher.scala:16-47)."""

    def __init__(
        self, num_patches: int, patch_size_x: int, patch_size_y: int,
        seed: int = 0,
    ):
        self.num_patches = num_patches
        self.patch_size_x = patch_size_x
        self.patch_size_y = patch_size_y
        self._rng = np.random.default_rng(seed)

    def apply_batch(self, data: Dataset) -> Dataset:
        X = Dataset.of(data).to_array()
        n, xd, yd, c = X.shape
        px, py = self.patch_size_x, self.patch_size_y
        out = []
        for _ in range(self.num_patches):
            xs = self._rng.integers(0, xd - px + 1, size=n)
            ys = self._rng.integers(0, yd - py + 1, size=n)
            idx_x = xs[:, None] + np.arange(px)[None, :]
            idx_y = ys[:, None] + np.arange(py)[None, :]
            patch = X[
                jnp.arange(n)[:, None, None],
                jnp.asarray(idx_x)[:, :, None],
                jnp.asarray(idx_y)[:, None, :],
                :,
            ]
            out.append(patch)
        # per-image grouping (reference emits numPatches per image in turn:
        # RandomPatcher.scala:34)
        stacked = jnp.stack(out, axis=1)  # (n, num_patches, px, py, c)
        return Dataset(
            stacked.reshape(-1, px, py, X.shape[-1]), batched=True
        )


class CenterCornerPatcher(Transformer):
    """Center + four corner crops, optionally with horizontal flips
    (parity: CenterCornerPatcher.scala:18-60)."""

    def __init__(self, patch_size_x: int, patch_size_y: int,
                 horizontal_flips: bool = False):
        self.px = patch_size_x
        self.py = patch_size_y
        self.horizontal_flips = horizontal_flips

    def trace_batch(self, X):
        n, xd, yd, c = X.shape
        px, py = self.px, self.py
        starts = [
            (0, 0),
            (xd - px, 0),
            (0, yd - py),
            (xd - px, yd - py),
            ((xd - px) // 2, (yd - py) // 2),
        ]
        crops = [X[:, sx : sx + px, sy : sy + py, :] for sx, sy in starts]
        if self.horizontal_flips:
            # reference emits (crop, flipped-crop) pairs per image
            # (CenterCornerPatcher.scala:41-42)
            crops = [
                v for cr in crops for v in (cr, jnp.flip(cr, axis=2))
            ]
        # per-image grouping: img0's crops first, then img1's …
        stacked = jnp.stack(crops, axis=1)  # (n, ncrops, px, py, c)
        return stacked.reshape(-1, self.px, self.py, X.shape[-1])


class RandomImageTransformer(Transformer):
    """Random horizontal flip per image (parity:
    RandomImageTransformer.scala:16 — the reference's only stock transform is
    flip with probability 0.5)."""

    def __init__(self, flip_chance: float = 0.5, seed: int = 0):
        self.flip_chance = flip_chance
        self._rng = np.random.default_rng(seed)

    def apply_batch(self, data: Dataset) -> Dataset:
        X = Dataset.of(data).to_array()
        flips = self._rng.random(X.shape[0]) < self.flip_chance
        flipped = jnp.flip(X, axis=2)
        mask = jnp.asarray(flips)[:, None, None, None]
        return Dataset(jnp.where(mask, flipped, X), batched=True)


class LabelExtractor(Transformer):
    """(label, image) item → label (parity: LabeledImageExtractors.scala:9-18).
    Loaders here usually hand out LabeledData directly; these extractors keep
    the reference's RDD[LabeledImage] composition style available."""

    def apply(self, item):
        return item[0]


class ImageExtractor(Transformer):
    """(label, image) item → image (parity: LabeledImageExtractors.scala:20-24)."""

    def apply(self, item):
        return item[1]


class MultiLabelExtractor(Transformer):
    """(label_set, image) item → label set
    (parity: LabeledImageExtractors.scala:26-32)."""

    def apply(self, item):
        return item[0]
