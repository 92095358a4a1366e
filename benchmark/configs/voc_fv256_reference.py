"""The plain reference of ``voc_fv256``: VOCSIFTFisher
(``pipelines/images/voc/VOCSIFTFisher.scala``) written out in ``jax.numpy``
at float32 under ``highest`` and host numpy — gray conversion, dense SIFT at
four scales (Gaussian smoothing with edge replication, central-difference
gradients, eight linearly interpolated orientation maps, flat-window box
sums, the 4 × 4 × 8 layout ``t + 8i + 32j``, normalise / clamp 0.2 /
renormalise / contrast threshold / ×512-floor-clamp-255), sampled columns,
PCA by the covariance's eigenvectors, k-means++ seeding and EM for a
diagonal mixture (Sanchez et al., IJCV'13, appendix B), Fisher vectors,
vectorize, L2, signed square root, L2, one pass of block ridge, the 11-point
average precision — and the seeded synthetic images it is fed.

Imports nothing of the program. What this file solves inside itself:

* ``compare.fit_numbers`` hands ``apply`` blocks of 8,192 images, whose
  descriptors would be 308 GB: ``apply`` maps over slices of
  ``reference_slice`` images inside itself, and so does everything else;
* the codebook (PCA basis, mixture) is LEARNED from the training images at
  float32 ``highest`` whatever precision a control asks of the featurizer:
  it is the configuration's, as a data set is. ``precision["featurizer"]``
  is the precision of the featurizer's products (the projection, the two
  posterior products, the two statistics); what a full-size ``fit`` learned
  is kept (``_STATE``) for ``featurizer``;
* the labels cross the harness as one int32 bitmask an image (bit c: class
  c), because ``drivers/common.host_labels`` hands the program an int32
  array; :func:`label_sets` and :func:`indicators` decode it.

Departures from the Scala, each because the benchmark needs it:

* the sampled columns of image ``i`` are
  ``randint(fold_in(PRNGKey(seed), i), (per_image,), 0, N)`` — with
  replacement, keyed on the image's index alone, so that any program can
  draw them again (the Scala's ``ColumnSampler`` draws from each
  partition's own generator); the PCA's sample uses ``sample_seed``, the
  mixture's ``sample_seed + 1``;
* the mixture's sample is drawn ahead of the projection and projected after
  (one pass over the training images instead of two): the projection is
  per column, so the same columns come out;
* the PCA basis is the float64 eigendecomposition of the sample's 128 × 128
  covariance on the host, where the Scala calls a float32 ``sgesvd`` on the
  centred sample: the same directions. Sign: the element of largest
  magnitude of each direction is positive (the Scala's
  ``enforceMatlabPCASignConvention``). As in the Scala the projection
  subtracts no mean;
* the k-means++ draws use ``jax.random`` in a stated order (below) from
  ``kmeans_seed``, where the Scala draws from Breeze's generator; the
  seeding is followed by one Lloyd update, as
  ``KMeansPlusPlusEstimator(k, 1)`` gives it;
* the Mahalanobis term of the posteriors is the expanded quadratic
  (``x²/2σ² − xμ/σ² + μ²/2σ²`` as two products), at ``highest``: the direct
  form over 10⁶ × 256 × 80 differences is 82 TB;
* ``fv2`` scales ``(μ² − σ²)`` by ``s0`` a COLUMN (Sanchez et al., eq. 17);
  the Scala's line carries a transpose that type-checks only when
  ``descDim == vocabSize``;
* every image has one size (500 × 375, VOC2007's commonest): the port's
  loader resizes to one size by policy;
* the solve is ONE pass in the stated block order from zero weights — the
  configuration's estimator (``BlockLeastSquaresEstimator(4096, 1, λ)``) —
  in residual form with a float64 Cholesky a block on the host
  (``cifar_patch10k_reference.one_pass_block_ridge``'s mathematics, in this
  file's own copy).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath

#: the learned codebook by the rows it was learned from: a full-size ``fit``
#: leaves it for ``featurizer``
_STATE: dict = {}

_HIGHEST = jax.lax.Precision.HIGHEST


# -- the images ------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3))
def _images(seed_key, first, n: int, sizes: tuple):
    """Images ``first … first + n`` of the set drawn from ``seed_key``: each
    from its own key, so that a set is the same however it is cut."""
    xd, yd, num_classes, amp, distract, sigma, level = sizes
    xx, yy = jnp.meshgrid(
        jnp.arange(xd, dtype=jnp.float32), jnp.arange(yd, dtype=jnp.float32),
        indexing="ij",
    )

    def grating(c, phase):
        # synthetic_voc's class code: a spatial frequency and an
        # orientation a class
        c = c.astype(jnp.float32)
        freq = 0.12 + 0.035 * jnp.mod(c, 10.0)
        theta = jnp.pi * c / num_classes
        along = jnp.cos(theta) * xx + jnp.sin(theta) * yy
        return jnp.sin(2 * jnp.pi * freq * along + phase)

    def region(key):
        # a half-size rectangle at a random place
        kx, ky = jax.random.split(key)
        x0 = jax.random.randint(kx, (), 0, xd - xd // 2 + 1)
        y0 = jax.random.randint(ky, (), 0, yd - yd // 2 + 1)
        inside = (
            (xx >= x0) & (xx < x0 + xd // 2) & (yy >= y0) & (yy < y0 + yd // 2)
        )
        return inside.astype(jnp.float32)

    def one(i):
        key = jax.random.fold_in(seed_key, i)
        kc, kk, kp, kr, kd, kn = jax.random.split(key, 6)
        classes = jax.random.permutation(kc, num_classes)[:4]
        count = jax.random.randint(kk, (), 1, 4)  # 1 to 3 labelled classes
        phases = 2 * jnp.pi * jax.random.uniform(kp, (4,), jnp.float32)
        regions = jax.vmap(region)(jax.random.split(kr, 4))
        waves = jax.vmap(grating)(classes, phases) * regions
        # the first ``count`` classes are the image's labels; the fourth is
        # a distractor, present in every image and in no label set
        shown = (jnp.arange(4) < count).astype(jnp.float32) * amp
        shown = shown.at[3].set(
            distract * jax.random.uniform(kd, (), jnp.float32)
        )
        img = level + jnp.tensordot(shown, waves, axes=1)
        img = img + sigma * jax.random.normal(kn, img.shape, jnp.float32)
        labelled = jnp.arange(4) < jnp.minimum(count, 3)
        mask = jnp.sum(jnp.where(labelled, 1 << classes, 0)).astype(jnp.int32)
        gray = jnp.clip(img, 0.0, 255.0).astype(jnp.uint8)
        return jnp.repeat(gray[..., None], 3, axis=-1), mask

    return jax.lax.map(one, first + jnp.arange(n, dtype=jnp.uint32))


def make_rows(config: dict, seed: int, n: int):
    """``(X, y)``: ``n`` images (n, 500, 375, 3) uint8 of the configuration's
    task on the device, drawn from ``seed``, and one int32 bitmask of labels
    an image — after ``pipelines/voc_sift_fisher.py`` ``synthetic_voc``
    (that one is host numpy at 64 × 64): 1 to 3 class-specific oriented
    gratings in random half-size regions over noise, so that the class lives
    in local gradient structure. Classes OVERLAP: the gratings are no
    stronger than the noise, neighbouring classes differ by 9° and a
    twelfth of the frequency, and every image carries a fourth, unlabelled
    grating of a random class at a uniform share of
    ``distractor_amplitude``. Made in pieces of 64 images (an image is 2.25
    MB as float32 while it is made)."""
    a = config["assumed"]
    sizes = (
        config["image_x"], config["image_y"], config["num_classes"],
        a["grating_amplitude"], a["distractor_amplitude"], a["noise_sigma"],
        a["gray_level"],
    )
    key = jax.random.PRNGKey(seed)
    Xs, ys = [], []
    for first in range(0, n, 64):
        X, y = _images(key, first, min(64, n - first), sizes)
        Xs.append(X)
        ys.append(y)
    return jnp.concatenate(Xs, axis=0), jnp.concatenate(ys, axis=0)


def label_sets(masks, num_classes: int) -> list:
    """The label set of each image from its bitmask (host)."""
    masks = np.asarray(masks).astype(np.int64)
    return [
        np.flatnonzero((m >> np.arange(num_classes)) & 1) for m in masks
    ]


def indicators(masks, num_classes: int):
    """±1 multi-hot indicators (n, num_classes) from the bitmasks
    (ClassLabelIndicatorsFromIntArrayLabels)."""
    bits = (jnp.asarray(masks)[:, None] >> jnp.arange(num_classes)) & 1
    return 2.0 * bits.astype(jnp.float32) - 1.0


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
    """Sums over every ``width`` × ``width`` window of (B, X, Y, 8), the
    window anchored at its corner: the shifted maps added up, one axis
    after the other."""
    nx, ny = M.shape[1] - width + 1, M.shape[2] - width + 1
    M = sum(M[:, k : k + nx] for k in range(width))
    return sum(M[:, :, k : k + ny] for k in range(width))


def grid(config: dict, scale: int):
    """``(xs, ys, bin_size)``: the corners of the descriptors of ``scale``
    along each axis (every ``step`` pixels while the 4 × 4 bins fit)."""
    bin_size = config["bin_size"] + 2 * scale
    step = config["step"] + scale * config["scale_step"]
    extent = 4 * bin_size
    xs = np.arange(0, config["image_x"] - extent + 1, step)
    ys = np.arange(0, config["image_y"] - extent + 1, step)
    return xs, ys, bin_size


def num_descriptors(config: dict) -> int:
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


def sample_descriptors(config: dict, X):
    """``(pca_sample, gmm_sample)``: the sampled descriptors (rows, 128) of
    the training images ``X``, image after image, both drawn in one pass."""
    total = num_descriptors(config)
    s_pca = per_image(config, "num_pca_samples")
    s_gmm = per_image(config, "num_gmm_samples")
    seed = config["sample_seed"]

    def one_slice(Xs, first):
        D = sift(config, Xs)
        rows = first + jnp.arange(Xs.shape[0])
        take = lambda cols: jnp.take_along_axis(  # noqa: E731
            D, cols[:, :, None], axis=1
        )
        return (
            take(sampled_columns(seed, rows, s_pca, total)),
            take(sampled_columns(seed + 1, rows, s_gmm, total)),
        )

    block = jax.jit(_in_slices(one_slice, config["reference_slice"]))
    parts, at = [], 0
    for Xb in refmath.row_blocks(X, config["reference_rows"]):
        parts.append(block(Xb, at))
        at += Xb.shape[0]
    return tuple(
        jnp.concatenate([p[i] for p in parts], axis=0).reshape(-1, 128)
        for i in (0, 1)
    )


# -- the codebook: PCA, k-means++, EM ----------------------------------------


def pca_basis(config: dict, sample) -> np.ndarray:
    """(128, desc_dim) float64: the leading eigenvectors of the sample's
    covariance, each with its largest element positive."""
    mean = jnp.mean(sample, axis=0)
    centred = sample - mean
    cov = jnp.matmul(centred.T, centred, precision=_HIGHEST)
    lam, V = np.linalg.eigh(np.asarray(cov, np.float64))
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


def learn_codebook(config: dict, X) -> dict:
    """The PCA basis (128, desc_dim) and the mixture, from the training
    images ``X``, at float32 ``highest``."""
    pca_sample, gmm_sample = sample_descriptors(config, X)
    basis = jnp.asarray(pca_basis(config, pca_sample), jnp.float32)
    projected = jnp.matmul(gmm_sample, basis, precision=_HIGHEST)
    return dict(fit_mixture(config, projected), basis=basis)


# -- the featurizer ------------------------------------------------------------


def fisher_vectors(config: dict, codebook: dict, D, precision: str):
    """Descriptors (B, N, 128) → normalised Fisher vectors (B, 2·d·k):
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

    F = unit(F)
    return unit(jnp.sign(F) * jnp.sqrt(jnp.abs(F)))


def _apply(config: dict, precision: str):
    """``f(codebook, X)``: uint8 images → (n, d) features, in slices of
    ``reference_slice`` images inside one traced function."""

    def f(codebook, X):
        return _in_slices(
            lambda Xs, _: fisher_vectors(
                config, codebook, sift(config, Xs), precision
            ),
            config["reference_slice"],
        )(X)

    return f


def _codebook(config: dict, X=None) -> dict:
    """The codebook of the configuration's training images: what a
    full-size ``fit`` learned, or learned here from the images made again
    from ``train_seed``."""
    key = config["n_train"]
    if key not in _STATE:
        if X is None:
            X, _ = make_rows(config, config["train_seed"], config["n_train"])
        _STATE[key] = learn_codebook(config, X)
    return _STATE[key]


def featurizer(config: dict, precision: str):
    """``(apply, params)``: images (n, 500, 375, 3) uint8 → (n, 40960)
    normalised Fisher vectors; ``apply(params, images)`` maps over slices
    of ``reference_slice`` images inside itself."""
    return _apply(config, precision), _codebook(config)


# -- the solve and the score -----------------------------------------------------


def one_pass_block_ridge(A, R, *, block_size: int, lam: float,
                         precision: str):
    """ONE pass of block coordinate descent on
    ``min ‖A W − R‖² + λ Σ‖W_j‖²`` from zero, in residual form, blocks in
    column order: ``W_j = (Ã_jᵀÃ_j + λI)⁻¹ Ã_jᵀ r`` with ``Ã_j`` the block
    less its column means, then ``r −= Ã_j W_j``. Products on the device at
    ``precision``; each block's Cholesky factor on the host in float64.
    Returns ``(W, means)``."""
    import scipy.linalg

    d = A.shape[1]

    @functools.partial(jax.jit, static_argnums=(2,))
    def normal(A, r, width, start):
        Aj = jax.lax.dynamic_slice_in_dim(A, start, width, axis=1)
        mj = jnp.mean(Aj, axis=0)
        Aj = Aj - mj
        return (
            refmath.mm(Aj.T, Aj, precision), refmath.mm(Aj.T, r, precision), mj
        )

    @functools.partial(jax.jit, static_argnums=(2,))
    def residual(A, r, width, start, mj, Wj):
        Aj = jax.lax.dynamic_slice_in_dim(A, start, width, axis=1) - mj
        return r - refmath.mm(Aj, Wj, precision)

    r, Ws, means = R, [], []
    for start in range(0, d, block_size):
        width = min(block_size, d - start)
        G, c, mj = normal(A, r, width, start)
        factor = scipy.linalg.cho_factor(
            np.asarray(G, np.float64) + lam * np.eye(width), lower=True
        )
        Wj = jnp.asarray(
            scipy.linalg.cho_solve(factor, np.asarray(c, np.float64)),
            jnp.float32,
        )
        r = residual(A, r, width, start, mj, Wj)
        Ws.append(Wj)
        means.append(mj)
    return jnp.concatenate(Ws, axis=0), jnp.concatenate(means, axis=0)


def features(config: dict, codebook: dict, X, precision: str):
    """The features of the images ``X`` (n, d), block of
    ``reference_rows`` images by block."""
    block = jax.jit(_apply(config, precision))
    return jnp.concatenate([
        block(codebook, Xb)
        for Xb in refmath.row_blocks(X, config["reference_rows"])
    ], axis=0)


def fit(config: dict, X, y, *, precision: dict):
    """The model ``{"W", "b", "mean"}`` the configuration defines: ±1
    multi-hot indicators (``y``: one bitmask an image) less their mean
    regressed on the Fisher vectors by one pass of block coordinate
    descent. The codebook is learned from ``X`` where ``X`` is the whole
    training set, as the program learns it from the images it is handed,
    and from the whole set made again where ``X`` is a part of it (the
    half-rows fault leaves rows out of the solve, not out of the
    codebook)."""
    whole = int(X.shape[0]) == config["n_train"]
    codebook = _codebook(config, X if whole else None)
    F = features(config, codebook, X, precision["featurizer"])
    Y = indicators(y, config["num_classes"])
    y_mean = jnp.mean(Y, axis=0)
    W, mean = one_pass_block_ridge(
        F, Y - y_mean, block_size=config["block_size"], lam=config["lam"],
        precision=precision["solver"],
    )
    return {"W": W, "b": y_mean, "mean": mean}


def average_precisions(scores, masks, num_classes: int) -> np.ndarray:
    """The 11-point interpolated average precision a class
    (MeanAveragePrecisionEvaluator.scala:84-96): images ranked by score,
    precision and recall after each, and the mean over recall levels
    0, 0.1 … 1 of the best precision at that recall or beyond."""
    scores = np.asarray(scores, np.float64)
    truth = np.zeros((scores.shape[0], num_classes))
    for i, labels in enumerate(label_sets(masks, num_classes)):
        truth[i, labels] = 1.0
    out = np.zeros(num_classes)
    for c in range(num_classes):
        hit = truth[np.argsort(-scores[:, c], kind="stable"), c]
        if hit.sum() == 0:
            continue
        tp = np.cumsum(hit)
        recall, precision = tp / hit.sum(), tp / np.arange(1, len(hit) + 1)
        out[c] = np.mean([
            precision[recall >= level / 10.0].max(initial=0.0)
            for level in range(11)
        ])
    return out


def expected_d(config: dict) -> int:
    """d from the widths: first and second orders of desc_dim numbers a
    centre."""
    return 2 * config["desc_dim"] * config["vocab_size"]
