"""The plain reference of ``imagenet_fv16``: ImageNetSiftLcsFV
(``pipelines/images/imagenet/ImageNetSiftLcsFV.scala``) written out in
``jax.numpy`` at float32 under ``highest`` and host numpy — two descriptor
branches over the same images (dense SIFT at four scales with the signed
square root on its descriptors; local colour statistics), each with its
sampled columns, its PCA by the covariance's eigenvectors, its k-means++
seeding and EM for a diagonal mixture, its Fisher vectors with L2, signed
square root, L2; the two concatenated; the class-weighted least squares of
``BlockWeightedLeastSquares.scala`` written out a class at a time; top-1 and
top-5 error — and the seeded synthetic images it is fed.

Imports nothing of the program. SIFT, the sampler's (seed, row) draw, the
PCA, k-means++ / EM, the Fisher vector and the normalisations are this
file's own copy of ``voc_fv256_reference.py``'s mathematics at this
configuration's settings (scale step 1, the signed root on SIFT's
descriptors ahead of the PCA); new are LCS, the concatenation and the
weighted solve. What this file solves inside itself:

* ``compare.fit_numbers`` hands ``apply`` blocks of 8,192 images, whose
  SIFT descriptors would be 56 GB: ``apply`` maps over slices of
  ``reference_slice`` images inside itself, and so does everything else;
* the two codebooks (PCA basis, mixture) are LEARNED from the training
  images at float32 ``highest`` whatever precision a control asks of the
  featurizer: they are the configuration's, as a data set is.
  ``precision["featurizer"]`` is the precision of the featurizer's products
  (the projection, the two posterior products, the two statistics);
  ``precision["solver"]`` that of the solve's products over all the rows
  (the population covariance, the two cross terms); what a full-size
  ``fit`` learned is kept (``_STATE``) for ``featurizer``;
* a sample of 10⁷ descriptors is 5.1 GB: the PCA's sample is never held —
  its mean and covariance are accumulated block of images by block, each
  block's sums taken about the first block's mean at ``highest`` and added
  up on the host in float64 —, and the mixture's sample is projected block
  by block (2.6 GB).

Departures from the Scala, each because the benchmark needs it:

* the sampled columns of image ``i`` are
  ``randint(fold_in(PRNGKey(seed), i), (per_image,), 0, N)`` — with
  replacement, keyed on the image's index alone. SIFT's PCA sample uses
  ``sample_seed``, its mixture's ``sample_seed + 1``; LCS's
  ``sample_seed + 17`` and ``+ 18`` (as ``build_predictor`` passes them);
* the mixture's sample is drawn ahead of the projection and projected after:
  the projection is per column, so the same columns come out;
* the PCA basis is the float64 eigendecomposition of the sample's
  covariance on the host; sign: the element of largest magnitude of each
  direction is positive. As in the Scala the projection subtracts no mean;
* the k-means++ draws use ``jax.random`` in a stated order from
  ``kmeans_seed``, followed by one Lloyd update;
* the Mahalanobis term of the posteriors is the expanded quadratic, at
  ``highest``; ``fv2`` scales ``(μ² − σ²)`` by ``s0`` a COLUMN (Sanchez et
  al., eq. 17);
* every image has one size (256 × 256): the port's loader resizes to one
  canonical size by policy, upstream reads the JPEGs at their own sizes;
* LCS's box means and deviations are taken from exact box SUMS of the
  8-bit pixels and of their squares (shifted additions; the window placed
  as ``ImageUtils.conv2D`` places it, zero padded);
* **the class systems.** ``jointXTX_c + λI`` is factored a class: directly
  (:func:`solve_direct`, a float64 LU a class) or — what :func:`fit` uses,
  because k direct factorisations of 4,096² take the chip machine's host
  minutes — by the part all classes share, ``B = (1−w)·popCov + λI``,
  Cholesky-factored ONCE in float64, and each class's remainder of rank
  n_c + 2 applied exactly (Woodbury, float64 throughout;
  :func:`solve_woodbury`). ``tests/benchmark/test_imagenet_fv16.py`` holds
  the two forms to 1e-9 of each other at a small size. The class part
  enters through the class's own rows (exact), the population covariance
  and the cross terms through products on the device at
  ``precision["solver"]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath

#: the learned codebooks by the rows they were learned from: a full-size
#: ``fit`` leaves them for ``featurizer``
_STATE: dict = {}

_HIGHEST = jax.lax.Precision.HIGHEST

#: class codes: an orientation and a spatial frequency (what SIFT reads)
#: and a hue (what LCS reads)
_N_THETA, _N_FREQ = 5, 5


# -- the images ------------------------------------------------------------


def class_grid(num_classes: int) -> tuple:
    """``(n_theta, n_freq, n_hue)``: class c has orientation ``c % 5``,
    frequency ``(c // 5) % 5`` and hue ``c // 25``."""
    return _N_THETA, _N_FREQ, -(-num_classes // (_N_THETA * _N_FREQ))


#: the pixel noise's strength a channel (a share of ``noise_sigma``), and
#: the widths in pixels, along x and along y, of the smooth noise a channel
_CHANNEL_NOISE = (1.0, 0.8, 1.2)
_SMOOTH_WIDTHS = ((1.5, 4.0), (2.5, 2.5), (4.0, 1.5))
_SMOOTH_RADIUS = 12


def _smooth_noise(field, width_x: float, width_y: float, size: int):
    """White noise (size + 2r)² blurred by a Gaussian of ``width_x`` by
    ``width_y`` pixels and scaled to unit variance: (size, size)."""
    r = _SMOOTH_RADIUS
    for axis, width in ((0, width_x), (1, width_y)):
        taps = np.exp(-0.5 * (np.arange(-r, r + 1) / width) ** 2)
        taps = taps / np.sqrt(np.sum(taps * taps))
        field = sum(
            float(w) * jax.lax.slice_in_dim(field, k, k + size, axis=axis)
            for k, w in enumerate(taps)
        )
    return field


@functools.partial(jax.jit, static_argnums=(2, 3))
def _images(seed_key, first, n: int, sizes: tuple):
    """Images ``first … first + n`` of the set drawn from ``seed_key``: each
    from its own key, so that a set is the same however it is cut."""
    (size, num_classes, amp, distract, sigma, smooth, tint, level,
     jitter) = sizes
    n_theta, n_freq, n_hue = class_grid(num_classes)
    d_theta, d_logf, d_hue = jnp.pi / n_theta, 0.25, 2 * jnp.pi / n_hue
    xx, yy = jnp.meshgrid(
        jnp.arange(size, dtype=jnp.float32),
        jnp.arange(size, dtype=jnp.float32), indexing="ij",
    )

    def latents(c, noise):
        """The class's orientation, frequency and hue, each off its grid
        point by ``jitter`` grid steps of Gaussian noise."""
        c = c.astype(jnp.int32)
        theta = d_theta * ((c % n_theta) + jitter * noise[0])
        logf = math.log(0.06) + d_logf * (
            ((c // n_theta) % n_freq) + jitter * noise[1]
        )
        hue = d_hue * ((c // (n_theta * n_freq)) + jitter * noise[2])
        return theta, jnp.exp(logf), hue

    def grating(theta, freq, phase):
        along = jnp.cos(theta) * xx + jnp.sin(theta) * yy
        return jnp.sin(2 * jnp.pi * freq * along + phase)

    def one(i):
        key = jax.random.fold_in(seed_key, i)
        kc, kl, kp, kr, kd, kn = jax.random.split(key, 6)
        classes = jax.random.randint(kc, (2,), 0, num_classes)
        noise = jax.random.normal(kl, (2, 3), jnp.float32)
        phases = 2 * jnp.pi * jax.random.uniform(kp, (2,), jnp.float32)
        theta, freq, hue = latents(classes[0], noise[0])
        other = latents(classes[1], noise[1])
        # the image's own grating everywhere; a distractor of a random
        # class in a half-size region at a uniform share of its amplitude
        kx, ky = jax.random.split(kr)
        x0 = jax.random.randint(kx, (), 0, size - size // 2 + 1)
        y0 = jax.random.randint(ky, (), 0, size - size // 2 + 1)
        inside = (
            (xx >= x0) & (xx < x0 + size // 2)
            & (yy >= y0) & (yy < y0 + size // 2)
        ).astype(jnp.float32)
        wave = amp * grating(theta, freq, phases[0]) + (
            distract * jax.random.uniform(kd, (), jnp.float32)
            * grating(other[0], other[1], phases[1]) * inside
        )
        # the hue: the three channels' levels 120 degrees apart
        shift = tint * jnp.cos(hue - 2 * jnp.pi * jnp.arange(3) / 3.0)
        img = level + wave[..., None] + shift
        # pixel noise, a channel at its own strength, and noise that is
        # smooth over some pixels — along x and along y to another width a
        # channel — as a photograph's is: with white noise alone the
        # statistics of LCS's non-overlapping windows are uncorrelated and
        # its covariance has whole subspaces of equal eigenvalues, in which
        # a PCA basis is decided by the last bit of a float32 sum
        k1, k2 = jax.random.split(kn)
        white = jax.random.normal(k1, img.shape, jnp.float32)
        img = img + sigma * jnp.asarray(_CHANNEL_NOISE) * white
        wide = jax.random.normal(
            k2, (size + 2 * _SMOOTH_RADIUS,) * 2 + (3,), jnp.float32
        )
        img = img + smooth * jnp.stack([
            _smooth_noise(wide[..., c], *_SMOOTH_WIDTHS[c], size)
            for c in range(3)
        ], axis=-1)
        return jnp.clip(img, 0.0, 255.0).astype(jnp.uint8), classes[0]

    return jax.lax.map(one, first + jnp.arange(n, dtype=jnp.uint32))


def make_rows(config: dict, seed: int, n: int):
    """``(X, y)``: ``n`` images (n, 256, 256, 3) uint8 of the
    configuration's task on the device, drawn from ``seed``, and one int32
    class label an image — after ``pipelines/imagenet_sift_lcs_fv.py``
    ``synthetic_gradient_imagenet`` / ``synthetic_imagenet_device`` (the
    class in local gradient structure: an oriented grating at a random
    phase) with the class ALSO in colour statistics (a hue: the channels'
    levels), so that both branches carry signal. Classes OVERLAP: an
    image's orientation, frequency and hue lie off the class's grid point
    by ``latent_jitter`` grid steps of Gaussian noise, the grating is
    weaker than the noise (white a pixel at ``noise_sigma``, a channel at
    its own strength, plus ``smooth_noise_sigma`` of noise smooth over a few
    pixels, to another width along x and y a channel), and every image
    carries a second grating of a random class in a half-size region. Made
    in pieces of 64 images."""
    a = config["assumed"]
    sizes = (
        config["image_x"], config["num_classes"], a["grating_amplitude"],
        a["distractor_amplitude"], a["noise_sigma"],
        a["smooth_noise_sigma"], a["tint_amplitude"], a["gray_level"],
        a["latent_jitter"],
    )
    if config["image_x"] != config["image_y"]:
        raise ValueError("imagenet_fv16's images are square")
    key = jax.random.PRNGKey(seed)
    Xs, ys = [], []
    for first in range(0, n, 64):
        X, y = _images(key, first, min(64, n - first), sizes)
        Xs.append(X)
        ys.append(y)
    return (
        jnp.concatenate(Xs, axis=0),
        jnp.concatenate(ys, axis=0).astype(jnp.int32),
    )


# -- dense SIFT --------------------------------------------------------------


def _gray(X):
    """uint8 (B, X, Y, 3) → luminance in [0, 1] (PixelScaler, GrayScaler)."""
    X = X.astype(jnp.float32) / 255.0
    return 0.299 * X[..., 0] + 0.587 * X[..., 1] + 0.114 * X[..., 2]


def _smooth(G, sigma: float):
    """Separable Gaussian blur of (B, X, Y), taps to 4σ, edges replicated
    (vl_imsmooth), as an explicit sum over the taps."""
    radius = max(1, int(math.ceil(4.0 * sigma)))
    taps = np.exp(-0.5 * (np.arange(-radius, radius + 1) / sigma) ** 2)
    taps = (taps / taps.sum()).astype(np.float32)
    xd, yd = G.shape[1], G.shape[2]
    P = jnp.pad(G, [(0, 0), (radius, radius), (0, 0)], mode="edge")
    G = sum(float(w) * P[:, k : k + xd, :] for k, w in enumerate(taps))
    P = jnp.pad(G, [(0, 0), (0, 0), (radius, radius)], mode="edge")
    return sum(float(w) * P[:, :, k : k + yd] for k, w in enumerate(taps))


def _orientation_maps(G):
    """(B, X, Y) → (B, X, Y, 8): the gradient's magnitude shared linearly
    between the two orientation bins its angle lies between. Central
    differences, one-sided at the border."""
    gx = jnp.concatenate([
        G[:, 1:2] - G[:, 0:1], 0.5 * (G[:, 2:] - G[:, :-2]),
        G[:, -1:] - G[:, -2:-1],
    ], axis=1)
    gy = jnp.concatenate([
        G[:, :, 1:2] - G[:, :, 0:1], 0.5 * (G[:, :, 2:] - G[:, :, :-2]),
        G[:, :, -1:] - G[:, :, -2:-1],
    ], axis=2)
    mag = jnp.sqrt(gx * gx + gy * gy)
    t = jnp.mod(jnp.arctan2(gy, gx), 2 * jnp.pi) / (2 * jnp.pi) * 8.0
    low = jnp.floor(t)
    frac = t - low
    low = jnp.mod(low.astype(jnp.int32), 8)
    bins = jnp.arange(8)
    return (
        (low[..., None] == bins) * (mag * (1.0 - frac))[..., None]
        + (jnp.mod(low + 1, 8)[..., None] == bins) * (mag * frac)[..., None]
    )


def _box_sums(M, width: int):
    """Sums over every ``width`` × ``width`` window of (B, X, Y, …), the
    window anchored at its corner: the shifted maps added up, one axis
    after the other."""
    nx, ny = M.shape[1] - width + 1, M.shape[2] - width + 1
    M = sum(M[:, k : k + nx] for k in range(width))
    return sum(M[:, :, k : k + ny] for k in range(width))


def grid(config: dict, scale: int):
    """``(xs, ys, bin_size)``: the corners of the descriptors of ``scale``
    along each axis (every ``step + scale · scale_step`` pixels while the
    4 × 4 bins fit)."""
    bin_size = config["bin_size"] + 2 * scale
    step = config["step"] + scale * config["scale_step"]
    extent = 4 * bin_size
    xs = np.arange(0, config["image_x"] - extent + 1, step)
    ys = np.arange(0, config["image_y"] - extent + 1, step)
    return xs, ys, bin_size


def sift_descriptors_per_image(config: dict) -> int:
    return sum(
        len(grid(config, s)[0]) * len(grid(config, s)[1])
        for s in range(config["num_scales"])
    )


def sift(config: dict, X):
    """uint8 images (B, X, Y, 3) → quantized descriptors (B, N, 128), the
    scales one after another, a scale's grid x-major."""
    G = _gray(X)
    out = []
    for scale in range(config["num_scales"]):
        xs, ys, bin_size = grid(config, scale)
        maps = _orientation_maps(_smooth(G, bin_size / 6.0))
        # the flat window: each spatial bin sums a box of 1.5 bins,
        # centred on the bin
        window = max(1, int(round(bin_size * 1.5)))
        sums = _box_sums(maps, window)
        off = (window - bin_size) // 2
        bins = []
        for j in range(4):  # element (t, i, j) at t + 8 i + 32 j
            for i in range(4):
                px = np.clip(xs + i * bin_size - off, 0, sums.shape[1] - 1)
                py = np.clip(ys + j * bin_size - off, 0, sums.shape[2] - 1)
                bins.append(sums[:, px][:, :, py])  # (B, nx, ny, 8)
        desc = jnp.stack(bins, axis=3).reshape(X.shape[0], -1, 128)
        norm = jnp.sqrt(jnp.sum(desc * desc, axis=-1, keepdims=True))
        unit = jnp.minimum(desc / jnp.maximum(norm, 1e-12), 0.2)
        again = jnp.sqrt(jnp.sum(unit * unit, axis=-1, keepdims=True))
        unit = unit / jnp.maximum(again, 1e-12)
        unit = jnp.where(norm > 0.005, unit, 0.0)  # the contrast threshold
        out.append(jnp.minimum(jnp.floor(unit * 512.0), 255.0))
    return jnp.concatenate(out, axis=1)


def signed_root(F):
    """sign(x)·√|x| (SignedHellingerMapper)."""
    return jnp.sign(F) * jnp.sqrt(jnp.abs(F))


# -- local colour statistics -----------------------------------------------------


def lcs_keypoints(config: dict) -> tuple:
    """The keypoints along each axis: every ``stride`` pixels inside the
    border."""
    g = config["lcs"]
    return (
        np.arange(g["border"], config["image_x"] - g["border"], g["stride"]),
        np.arange(g["border"], config["image_y"] - g["border"], g["stride"]),
    )


def lcs_descriptors_per_image(config: dict) -> int:
    kx, ky = lcs_keypoints(config)
    return len(kx) * len(ky)


def lcs_offsets(config: dict) -> list:
    """The 4 neighbourhood offsets an axis (−10, −4, 2, 8 at patch 6)."""
    p = config["lcs"]["patch"]
    return list(range(-2 * p + p // 2 - 1, p + p // 2, p))


def _box_sum_same(M, width: int):
    """Sums over the ``width`` × ``width`` window about every pixel of
    (B, X, Y), zero padded, ``(width − 1) // 2`` cells ahead of the pixel
    and the rest behind (where ``ImageUtils.conv2D`` puts the window)."""
    ahead = (width - 1) // 2
    pad = [(ahead, width - 1 - ahead)] * 2
    return _box_sums(jnp.pad(M, [(0, 0)] + pad), width)


def lcs(config: dict, X):
    """uint8 images (B, X, Y, 3) → (B, N, 96): per channel the mean and the
    standard deviation of the ``patch``² window at each of 4 × 4 offsets
    about each keypoint; element ``2·(4·(4·c + ix) + iy) + (0 mean, 1 std)``;
    keypoints x-major (LCSExtractor.scala:25-130)."""
    patch = config["lcs"]["patch"]
    cells = float(patch * patch)
    kx, ky = lcs_keypoints(config)
    offsets = lcs_offsets(config)
    X = X.astype(jnp.float32)
    maps = []
    for c in range(X.shape[3]):
        ch = X[..., c]
        mean = _box_sum_same(ch, patch) / cells
        second = _box_sum_same(ch * ch, patch) / cells
        std = jnp.sqrt(jnp.maximum(second - mean * mean, 0.0))
        maps.append(jnp.stack([mean, std], axis=1))
    maps = jnp.stack(maps, axis=1)  # (B, C, 2, X, Y)
    around = []
    for ox in offsets:
        px = np.clip(kx + ox, 0, X.shape[1] - 1)
        for oy in offsets:
            py = np.clip(ky + oy, 0, X.shape[2] - 1)
            around.append(maps[:, :, :, px][..., py])  # (B, C, 2, nx, ny)
    # (B, C, 16, 2, nx, ny): the element's index is its place in (C, 16, 2)
    values = jnp.stack(around, axis=2)
    return values.reshape(X.shape[0], -1, len(kx) * len(ky)).transpose(0, 2, 1)


#: a branch's descriptors (B, N, width) of uint8 images, as its PCA sees
#: them, and the offset of its seeds from ``sample_seed``
BRANCHES = {
    "sift": (lambda cfg, X: signed_root(sift(cfg, X)), 0),
    "lcs": (lcs, 17),
}


def descriptors_per_image(config: dict, branch: str) -> int:
    return {
        "sift": sift_descriptors_per_image, "lcs": lcs_descriptors_per_image
    }[branch](config)


# -- sampled columns ----------------------------------------------------------


def sampled_columns(seed: int, rows, per_image: int, total: int):
    """(len(rows), per_image) int32: the columns drawn of the images whose
    indices in their set are ``rows``."""
    key = jax.random.PRNGKey(seed)
    return jax.vmap(
        lambda r: jax.random.randint(
            jax.random.fold_in(key, r), (per_image,), 0, total
        )
    )(jnp.asarray(rows, jnp.uint32))


def per_image(config: dict, key: str) -> int:
    """Samples an image, as the Scala computes them."""
    return max(1, config[key] // config["n_train"])


def _in_slices(f, rows: int):
    """``f(X, first)`` over the leading axis in slices of ``rows`` inside
    one traced function — ``first`` the index of a slice's first row —, the
    rows padded with copies of the first to a whole number of slices,
    ``lax.map`` over them, the padding cut."""

    def g(X, first=0):
        n = X.shape[0]
        size = min(rows, n)
        slices = -(-n // size)
        pad = slices * size - n
        if pad:
            X = jnp.concatenate(
                [X, jnp.broadcast_to(X[:1], (pad,) + X.shape[1:])], axis=0
            )
        starts = first + size * jnp.arange(slices)
        out = jax.lax.map(
            lambda a: f(a[0], a[1]),
            (X.reshape((slices, size) + X.shape[1:]), starts),
        )
        return jax.tree_util.tree_map(
            lambda o: o.reshape((slices * size,) + o.shape[2:])[:n], out
        )

    return g


def _sample_blocks(config: dict, branch: str, X, seed: int, count: int,
                   then=None):
    """The sampled descriptors of the training images ``X``, block of
    ``reference_rows`` images by block: yields a function a block that
    draws ``count`` columns an image from ``seed`` and hands the block's
    sample (rows · count, width), with its arguments, to ``then`` (the
    sample itself where none is given)."""
    describe, _ = BRANCHES[branch]
    total = descriptors_per_image(config, branch)

    def one_slice(Xs, first):
        D = describe(config, Xs)
        rows = first + jnp.arange(Xs.shape[0])
        cols = sampled_columns(seed, rows, count, total)
        return jnp.take_along_axis(D, cols[:, :, None], axis=1)

    @jax.jit
    def block(Xb, at, *args):
        S = _in_slices(one_slice, config["reference_slice"])(Xb, at)
        S = S.reshape(-1, S.shape[-1])
        return S if then is None else then(S, *args)

    at = 0
    for Xb in refmath.row_blocks(X, config["reference_rows"]):
        yield functools.partial(block, Xb, at)
        at += Xb.shape[0]


# -- the codebook: PCA, k-means++, EM ----------------------------------------


def pca_basis(config: dict, branch: str, X) -> np.ndarray:
    """(width, desc_dim) float64: the leading eigenvectors of the covariance
    of the branch's PCA sample over the training images ``X``, each with its
    largest element positive. The sample is never held: a block's sum and
    second moments about the FIRST block's mean are taken on the device at
    ``highest`` and added up on the host in float64."""
    seed = config["sample_seed"] + BRANCHES[branch][1]
    count = per_image(config, "num_pca_samples")

    def moments(S, pilot):
        C = S - pilot
        return jnp.sum(C, axis=0), jnp.matmul(C.T, C, precision=_HIGHEST)

    # the pilot: the mean of the first slice's sample
    (first,) = _sample_blocks(
        config, branch, X[: config["reference_slice"]], seed, count
    )
    pilot = jnp.mean(first(), axis=0)
    width = pilot.shape[0]
    total, second = np.zeros(width), np.zeros((width, width))
    for block in _sample_blocks(config, branch, X, seed, count, moments):
        s, G = block(pilot)
        total += np.asarray(s, np.float64)
        second += np.asarray(G, np.float64)
    samples = count * X.shape[0]
    mean = total / samples  # about the pilot: the covariance does not move
    cov = second / samples - np.outer(mean, mean)
    _, V = np.linalg.eigh(cov)
    V = V[:, ::-1][:, : config["desc_dim"]]
    largest = V[np.argmax(np.abs(V), axis=0), np.arange(V.shape[1])]
    return V * np.where(largest >= 0, 1.0, -1.0)


def _half_sq_dists(X, centres):
    """½‖x‖² − x·c + ½‖c‖² a point and centre (the Scala's distance)."""
    return (
        0.5 * jnp.sum(X * X, axis=1, keepdims=True)
        - jnp.matmul(X, centres.T, precision=_HIGHEST)
        + 0.5 * jnp.sum(centres * centres, axis=1)
    )


@functools.partial(jax.jit, static_argnums=(2,))
def kmeans_seeds(X, key, k: int):
    """k-means++ seeding. The draws, in order: ``k0, key = split(key)``,
    the first centre ``X[randint(k0, (), 0, n)]``; then for each further
    centre ``key, kw, ku = split(key, 3)`` and the point
    ``categorical(kw, log D²)`` with D² the (half) squared distance to the
    nearest centre so far, clamped at 0 (a uniform draw from ``ku`` where
    every point is covered)."""
    n = X.shape[0]
    half_sq = 0.5 * jnp.sum(X * X, axis=1)
    k0, key = jax.random.split(key)
    first = X[jax.random.randint(k0, (), 0, n)]

    def step(carry, _):
        nearest, last, key = carry
        to_last = (
            half_sq - jnp.matmul(X, last, precision=_HIGHEST)
            + 0.5 * jnp.dot(last, last)
        )
        nearest = jnp.minimum(nearest, to_last)
        weight = jnp.maximum(nearest, 0.0)
        key, kw, ku = jax.random.split(key, 3)
        drawn = jax.random.categorical(kw, jnp.log(weight))
        anywhere = jax.random.randint(ku, (), 0, n)
        chosen = X[jnp.where(jnp.sum(weight) > 0, drawn, anywhere)]
        return (nearest, chosen, key), chosen

    start = (jnp.full((n,), jnp.inf, X.dtype), first, key)
    _, rest = jax.lax.scan(step, start, None, length=k - 1)
    return jnp.concatenate([first[None], rest], axis=0)


@jax.jit
def _lloyd_update(X, centres):
    """One Lloyd update; an empty cluster stays where it was."""
    k = centres.shape[0]
    nearest = jnp.argmin(_half_sq_dists(X, centres), axis=1)
    member = jax.nn.one_hot(nearest, k, dtype=X.dtype)
    counts = jnp.sum(member, axis=0)
    means = jnp.matmul(member.T, X, precision=_HIGHEST) / jnp.maximum(
        counts, 1.0
    )[:, None]
    return jnp.where((counts > 0)[:, None], means, centres)


@jax.jit
def _initial_mixture(X, centres):
    """Weights, means and variances (k, d) of the clusters nearest each
    centre, and the variance floor a dimension."""
    k = centres.shape[0]
    nearest = jnp.argmin(_half_sq_dists(X, centres), axis=1)
    member = jax.nn.one_hot(nearest, k, dtype=X.dtype)
    mass = jnp.sum(member, axis=0)
    means = jnp.matmul(member.T, X, precision=_HIGHEST) / mass[:, None]
    second = jnp.matmul(member.T, X * X, precision=_HIGHEST) / mass[:, None]
    mean_all = jnp.mean(X, axis=0)
    var_all = jnp.mean(X * X, axis=0) - mean_all * mean_all
    floor = jnp.maximum(1e-2 * var_all, 1e-9)
    return (
        mass / X.shape[0], means, jnp.maximum(second - means * means, floor),
        floor,
    )


def _log_likelihoods(X, means, variances, weights, precision: str):
    """(m, k): log wₖ N(x; μₖ, σₖ²), the quadratic expanded."""
    d = X.shape[1]
    quad = (
        refmath.mm(X * X, (0.5 / variances).T, precision)
        - refmath.mm(X, (means / variances).T, precision)
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    prior = (
        -0.5 * d * math.log(2 * math.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1) + jnp.log(weights)
    )
    return prior - quad


def _posteriors(llh, threshold: float):
    """Posteriors from log likelihoods: normalised, those at or under
    ``threshold`` zeroed, normalised again (appendix B)."""
    q = jnp.exp(llh - jnp.max(llh, axis=1, keepdims=True))
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > threshold, q, 0.0)
    return q / jnp.sum(q, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(5,))
def _em_step(X, means, variances, weights, floor, threshold: float):
    llh = _log_likelihoods(X, means, variances, weights, "highest")
    cost = jnp.mean(jax.scipy.special.logsumexp(llh, axis=1))
    q = _posteriors(llh, threshold)
    mass = jnp.sum(q, axis=0)
    new_means = jnp.matmul(q.T, X, precision=_HIGHEST) / mass[:, None]
    second = jnp.matmul(q.T, X * X, precision=_HIGHEST) / mass[:, None]
    new_vars = jnp.maximum(second - new_means * new_means, floor)
    return cost, mass, mass / X.shape[0], new_means, new_vars


def fit_mixture(config: dict, X) -> dict:
    """``{"means", "variances"}`` (k, d) and ``"weights"`` (k,) of the
    diagonal mixture fitted to the rows ``X``: k-means++ seeds, one Lloyd
    update, the clusters' moments, then EM until the mean log likelihood
    gains less than ``stop_tolerance`` of itself, a component's mass falls
    under ``min_cluster_size`` (either way keeping the parameters it had),
    or ``max_iterations``."""
    g = config["gmm"]
    centres = kmeans_seeds(
        X, jax.random.PRNGKey(config["kmeans_seed"]), config["vocab_size"]
    )
    centres = _lloyd_update(X, centres)
    weights, means, variances, floor = _initial_mixture(X, centres)
    before = None
    for _ in range(g["max_iterations"]):
        cost, mass, w, m, v = _em_step(
            X, means, variances, weights, floor, g["weight_threshold"]
        )
        cost = float(cost)
        if before is not None and not (
            cost - before >= g["stop_tolerance"] * abs(before)
        ):
            break
        before = cost
        if float(jnp.min(mass)) < g["min_cluster_size"]:
            break
        weights, means, variances = w, m, v
    return {"means": means, "variances": variances, "weights": weights}


def learn_codebook(config: dict, branch: str, X) -> dict:
    """The PCA basis (width, desc_dim) and the mixture of ``branch``, from
    the training images ``X``, at float32 ``highest``."""
    basis = jnp.asarray(pca_basis(config, branch, X), jnp.float32)
    seed = config["sample_seed"] + BRANCHES[branch][1] + 1
    count = per_image(config, "num_gmm_samples")
    project = lambda S, basis: jnp.matmul(  # noqa: E731
        S, basis, precision=_HIGHEST
    )
    projected = jnp.concatenate([
        block(basis)
        for block in _sample_blocks(config, branch, X, seed, count, project)
    ], axis=0)
    return dict(fit_mixture(config, projected), basis=basis)


def learn_codebooks(config: dict, X) -> dict:
    return {b: learn_codebook(config, b, X) for b in BRANCHES}


# -- the featurizer ------------------------------------------------------------


def fisher_vectors(config: dict, codebook: dict, D, precision: str):
    """Descriptors (B, N, width) → normalised Fisher vectors (B, 2·d·k):
    project, posteriors, the two statistics, vectorize, L2, signed square
    root, L2."""
    means, variances, weights = (
        codebook["means"], codebook["variances"], codebook["weights"]
    )
    threshold = config["gmm"]["weight_threshold"]

    def one(Di):
        P = refmath.mm(Di, codebook["basis"], precision)  # (N, d)
        q = _posteriors(
            _log_likelihoods(P, means, variances, weights, precision),
            threshold,
        )
        n = P.shape[0]
        s0 = jnp.mean(q, axis=0)  # (k,)
        s1 = refmath.mm(q.T, P, precision) / n  # (k, d)
        s2 = refmath.mm(q.T, P * P, precision) / n
        fv1 = (s1 - means * s0[:, None]) / (
            jnp.sqrt(variances) * jnp.sqrt(weights)[:, None]
        )
        fv2 = (
            s2 - 2.0 * means * s1 + (means * means - variances) * s0[:, None]
        ) / (variances * jnp.sqrt(2.0 * weights)[:, None])
        # the (d, 2k) matrix [fv1 | fv2], column-major: component after
        # component, first orders then second orders, d numbers each
        return jnp.concatenate([fv1, fv2], axis=0).reshape(-1)

    F = jax.vmap(one)(D)

    def unit(F):
        norm = jnp.sqrt(jnp.sum(F * F, axis=1, keepdims=True))
        return F / jnp.where(norm == 0, 1.0, norm)

    return unit(signed_root(unit(F)))


def combined_features(config: dict, codebooks: dict, Xs, precision: str):
    """uint8 images (B, X, Y, 3) → (B, d): the SIFT branch's normalised
    Fisher vector, then the LCS branch's (gather, VectorCombiner)."""
    return jnp.concatenate([
        fisher_vectors(
            config, codebooks[b], BRANCHES[b][0](config, Xs), precision
        )
        for b in BRANCHES
    ], axis=1)


def _apply(config: dict, precision: str):
    """``f(codebooks, X)``: uint8 images → (n, d) features, in slices of
    ``reference_slice`` images inside one traced function."""

    def f(codebooks, X):
        return _in_slices(
            lambda Xs, _: combined_features(config, codebooks, Xs, precision),
            config["reference_slice"],
        )(X)

    return f


def _codebooks(config: dict, X=None) -> dict:
    """The codebooks of the configuration's training images: what a
    full-size ``fit`` learned, or learned here from the images made again
    from ``train_seed``."""
    key = config["n_train"]
    if key not in _STATE:
        if X is None:
            X, _ = make_rows(config, config["train_seed"], config["n_train"])
        _STATE[key] = learn_codebooks(config, X)
    return _STATE[key]


def featurizer(config: dict, precision: str):
    """``(apply, params)``: images (n, 256, 256, 3) uint8 → (n, 4096)
    features; ``apply(params, images)`` maps over slices of
    ``reference_slice`` images inside itself."""
    return _apply(config, precision), _codebooks(config)


def features(config: dict, codebooks: dict, X, precision: str):
    """The features of the images ``X`` (n, d), block of
    ``reference_rows`` images by block."""
    block = jax.jit(_apply(config, precision))
    return jnp.concatenate([
        block(codebooks, Xb)
        for Xb in refmath.row_blocks(X, config["reference_rows"])
    ], axis=0)


# -- the class-weighted solve ------------------------------------------------------


def class_statistics(F, y, *, num_classes: int, w: float, precision: str):
    """What ``BlockWeightedLeastSquares.scala:86-321`` takes from all the
    rows of ONE block from zero weights, as float64 host arrays: counts,
    the population mean and covariance, the class means, the joint means
    and label means, and ``jointXTR`` (d, k). The products over all the
    rows (the centred Gram, ``FᵀR`` and ``Fᵀ(1_c ∘ R)``) run on the device
    at ``precision``."""
    n = F.shape[0]
    onehot = jax.nn.one_hot(y, num_classes, dtype=jnp.float32)
    counts = np.asarray(jnp.sum(onehot, axis=0), np.float64)
    # jointLabelMean_c = 2w + 2(1−w)·n_c/n − 1 (the ±1 indicators' mean
    # under the mixture; ref :148-155)
    label_mean = 2 * w + 2 * (1 - w) * counts / n - 1.0
    R = refmath.one_hot_pm(y, num_classes) - jnp.asarray(
        label_mean, jnp.float32
    )

    @jax.jit
    def products(F, R, onehot):
        mean = jnp.mean(F, axis=0)
        C = F - mean
        return (
            mean, refmath.mm(C.T, C, precision), refmath.mm(F.T, R, precision),
            refmath.mm(F.T, onehot * R, precision),
            refmath.mm(onehot.T, F, "highest"), jnp.mean(R, axis=0),
            jnp.sum(onehot * R, axis=0),
        )

    mean, G, xtr, class_xtr, class_sums, r_mean, class_r_sum = (
        np.asarray(a, np.float64) for a in products(F, R, onehot)
    )
    safe = np.maximum(counts, 1.0)
    class_means = class_sums / safe[:, None]
    joint_means = w * class_means + (1 - w) * mean
    mixture = (1 - w) * r_mean + w * class_r_sum / safe
    joint_xtr = (
        (1 - w) * xtr / n + w * class_xtr / safe
        - joint_means.T * mixture
    )
    return {
        "counts": counts, "mean": mean, "cov": G / n,
        "class_means": class_means, "joint_means": joint_means,
        "label_mean": label_mean, "joint_xtr": joint_xtr,
    }


def solve_direct(F, y, stats: dict, *, w: float, lam: float) -> np.ndarray:
    """W (d, k): ``(jointXTX_c + λI) W_c = jointXTR_c`` a class, each
    ``jointXTX_c = (1−w)·popCov + w·classCov_c + w(1−w)·(μ_c − μ)(μ_c − μ)ᵀ``
    built and factored (LU) on the host in float64."""
    F64, y = np.asarray(F, np.float64), np.asarray(y)
    d, k = F64.shape[1], len(stats["counts"])
    W = np.zeros((d, k))
    for c in range(k):
        rows = F64[y == c]
        mu_c = stats["class_means"][c]
        class_cov = rows.T @ rows / max(len(rows), 1) - np.outer(mu_c, mu_c)
        diff = mu_c - stats["mean"]
        joint = (
            (1 - w) * stats["cov"] + w * class_cov
            + w * (1 - w) * np.outer(diff, diff)
        )
        W[:, c] = np.linalg.solve(
            joint + lam * np.eye(d), stats["joint_xtr"][:, c]
        )
    return W


def solve_woodbury(F, y, stats: dict, *, w: float, lam: float) -> np.ndarray:
    """The same W: ``B = (1−w)·popCov + λI`` Cholesky-factored once, and a
    class's remainder ``U S Uᵀ`` — ``U = [rows_cᵀ, μ_c, μ_c − μ]``,
    ``S = diag(w/n_c …, −w, w(1−w))``, rank n_c + 2 — applied exactly:
    ``(B + USUᵀ)⁻¹r = B⁻¹r − B⁻¹U (S⁻¹ + UᵀB⁻¹U)⁻¹ UᵀB⁻¹r``. Float64 on the
    host throughout."""
    import scipy.linalg

    F64, y = np.asarray(F, np.float64), np.asarray(y)
    d, k = F64.shape[1], len(stats["counts"])
    factor = scipy.linalg.cho_factor(
        (1 - w) * stats["cov"] + lam * np.eye(d), lower=True
    )
    Z = scipy.linalg.cho_solve(factor, F64.T)  # B⁻¹ of every row
    Zr = scipy.linalg.cho_solve(factor, stats["joint_xtr"])  # (d, k)
    # B⁻¹ of the means AS THE STATISTICS HOLD THEM (float32 sums): the mean
    # of a class's columns of Z is B⁻¹ of another rounding of the same mean
    Zm = scipy.linalg.cho_solve(factor, stats["class_means"].T)  # (d, k)
    z_mean = scipy.linalg.cho_solve(factor, stats["mean"])
    W = np.zeros((d, k))
    for c in range(k):
        at = np.flatnonzero(y == c)
        # a class with no row has mean 0 and covariance 0, as the direct
        # form's: its first n_c columns are none and its μ_c column is 0
        mu_c = stats["class_means"][c]
        U = np.concatenate(
            [F64[at].T, mu_c[:, None], (mu_c - stats["mean"])[:, None]],
            axis=1,
        )
        BU = np.concatenate(
            [Z[:, at], Zm[:, c : c + 1], (Zm[:, c] - z_mean)[:, None]], axis=1
        )
        s_inv = np.concatenate([
            np.full(len(at), len(at) / w), [-1.0 / w, 1.0 / (w * (1 - w))]
        ])
        inner = np.diag(s_inv) + U.T @ BU
        W[:, c] = Zr[:, c] - BU @ np.linalg.solve(inner, U.T @ Zr[:, c])
    return W


def weighted_model(F, y, config: dict, precision: str, *,
                   solve=solve_woodbury) -> dict:
    """``{"W", "b", "mean"}`` of the class-weighted fit on the features
    ``F``: one block, one pass, from zero; the intercept
    ``jointLabelMean − Σ jointMeans · W`` (ref :310-315). ``mean`` is zero:
    the means a class subtracts are in its intercept."""
    if F.shape[1] > config["block_size"] or config["epochs"] != 1:
        raise ValueError("the reference solves one block in one pass")
    w, lam = config["mixture_weight"], config["lam"]
    stats = class_statistics(
        F, y, num_classes=config["num_classes"], w=w, precision=precision
    )
    W = solve(F, y, stats, w=w, lam=lam)
    b = stats["label_mean"] - np.einsum("cd,dc->c", stats["joint_means"], W)
    return {
        "W": jnp.asarray(W, jnp.float32), "b": jnp.asarray(b, jnp.float32),
        "mean": jnp.zeros((F.shape[1],), jnp.float32),
    }


def fit(config: dict, X, y, *, precision: dict):
    """The model ``{"W", "b", "mean"}`` the configuration defines. The
    codebooks are learned from ``X`` where ``X`` is the whole training set,
    as the program learns them from the images it is handed, and from the
    whole set made again where ``X`` is a part of it (the half-rows fault
    leaves rows out of the solve, not out of the codebooks)."""
    whole = int(X.shape[0]) == config["n_train"]
    codebooks = _codebooks(config, X if whole else None)
    F = features(config, codebooks, X, precision["featurizer"])
    return weighted_model(F, y, config, precision["solver"])


def top_k_error(scores, labels, k: int) -> float:
    """The share of rows whose label is not among the ``k`` largest scores
    (Stats.getErrPercent over TopKClassifier(k), as a share)."""
    scores, labels = np.asarray(scores), np.asarray(labels).reshape(-1)
    best = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return float(1.0 - (best == labels[:, None]).any(axis=1).mean())


def expected_d(config: dict) -> int:
    """d from the widths: two branches, first and second orders of
    desc_dim numbers a centre."""
    return 2 * 2 * config["desc_dim"] * config["vocab_size"]
