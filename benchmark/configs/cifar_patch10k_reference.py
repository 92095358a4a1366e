"""The plain reference of ``cifar_patch10k``: RandomPatchCifar
(``pipelines/images/cifar/RandomPatchCifar.scala``) written out in
``jax.numpy`` and host numpy — sample patches of the training images,
normalise their rows, ZCA-whiten, choose and scale a filter bank, convolve
every image with it as an explicit patch product with per-patch mean and
variance normalisation, rectify both ways, sum-pool 2×2, standardise the
columns, one pass of block coordinate descent, argmax — and the seeded
synthetic images it is fed.

Imports nothing of the program. What differs from ``timit_cos4``'s
reference, and is solved inside this file:

* the featurizer's state (filter bank, whitener, column scaler) is LEARNED
  from training images. ``fit`` learns it from the images it is handed;
  ``featurizer`` makes the configuration's training images again from
  ``train_seed``. What a full-size ``fit`` learned is kept (``_STATE``), so
  that the comparison featurizes the training set once;
* ``compare.fit_numbers`` hands ``apply`` blocks of 8,192 images, whose
  convolution output would be 239 GB: ``apply`` maps over slices of
  ``reference_slice`` images inside itself;
* ``refmath.bcd_ridge`` wants the whole d×d Gram (25.6 GB at d = 80,000)
  and a d the block divides, so the one pass runs here in residual form on
  the kept features: ``c = A_jᵀr``, ``G = A_jᵀA_j``,
  ``W_j = (G + λI)⁻¹c`` by a float64 Cholesky on the host, ``r −= A_j W_j``.

Departures from the Scala, each because the benchmark needs it:

* patches are sampled by ``numpy.random.default_rng(seed).choice`` over all
  windows in emission order (per image, for x, for y), sorted — the Scala
  takes ``takeSample`` of an RDD, whose draw no other program can repeat;
  the filters are chosen by a generator of the same seed made anew (the
  Scala's ``MatrixUtils.sampleRows``);
* the whitener comes from the float64 eigendecomposition of the sample's
  108×108 covariance on the host, where the Scala calls a float32
  ``sgesvd`` on the centred 100,000×108 sample: the same matrix,
  ``V diag((σ²/(n−1) + ε)^−½) Vᵀ``;
* the column scaler's variance has n−1 below it, as the Scala's summarizer
  has; a column whose deviation is 0 or not finite is left unscaled;
* the solve is ONE pass in the stated block order from zero weights — the
  configuration's estimator (``BlockLeastSquaresEstimator(4096, 1, λ)``) —
  and not the least-squares optimum; block means are subtracted again
  inside each block step, as the Scala's solver does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import refmath

#: learned featurizer state by (rows it was learned from, precision): a
#: full-size ``fit`` leaves it for ``featurizer``
_STATE: dict = {}


# -- the images ----------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2))
def _images(row_key, n: int, sizes: tuple):
    side, channels, num_classes, amp, distract, sigma = sizes
    ky, kz, ku, kp, kq, kn = jax.random.split(row_key, 6)
    y = jax.random.randint(ky, (n,), 0, num_classes)
    z = jax.random.randint(kz, (n,), 0, num_classes)  # a distractor class
    share = jax.random.uniform(ku, (n,), jnp.float32)
    xx, yy = jnp.meshgrid(
        jnp.arange(side, dtype=jnp.float32),
        jnp.arange(side, dtype=jnp.float32), indexing="ij",
    )

    def texture(k, phase):
        # synthetic_cifar's class code: a spatial frequency and an
        # orientation a class; the phase is the row's
        k = k.astype(jnp.float32)
        freq = 0.25 + 0.3 * jnp.mod(k, 5.0)
        theta = jnp.pi * k / num_classes
        along = jnp.cos(theta)[:, None, None] * xx + (
            jnp.sin(theta)[:, None, None] * yy
        )
        return jnp.sin(
            2 * jnp.pi * freq[:, None, None] * along + phase[:, None, None]
        )

    two_pi = 2 * jnp.pi
    own = texture(y, two_pi * jax.random.uniform(kp, (n,), jnp.float32))
    other = texture(z, two_pi * jax.random.uniform(kq, (n,), jnp.float32))
    wave = amp * own + distract * share[:, None, None] * other
    tint = jnp.cos(1.1 * jnp.arange(channels, dtype=jnp.float32))
    X = 128.0 + wave[..., None] * tint
    X = X + sigma * jax.random.normal(kn, X.shape, jnp.float32)
    return jnp.clip(X, 0.0, 255.0), y


def make_rows(config: dict, seed: int, n: int):
    """``(X, y)``: ``n`` images (n, 32, 32, 3) of the configuration's task
    on the device, drawn from ``seed``, after ``loaders/cifar.py``
    ``synthetic_cifar`` (that one is host numpy): the class is a local
    texture — a spatial frequency and an orientation — at a random phase,
    under pixel noise. Classes OVERLAP: every image also carries the
    texture of a random other class at a uniform share of
    ``distractor_amplitude``, which passes the class's own amplitude in one
    image of five, so the held-out error is far from 0 and from chance and
    near-ties exist for a lower precision to flip. ``synthetic_cifar``'s
    position-fixed level pattern a class is left out: the 2×2 pool keeps
    coarse position, and with it the classes separate again (1.8% against
    28% held-out error at 256 filters, CPU)."""
    a = config["assumed"]
    sizes = (
        config["image_side"], config["image_channels"],
        config["num_classes"], a["texture_amplitude"],
        a["distractor_amplitude"], a["noise_sigma"],
    )
    return _images(jax.random.PRNGKey(seed), n, sizes)


# -- the featurizer's learned state --------------------------------------


def _window_grid(config: dict):
    side, size, step = (
        config["image_side"], config["patch_size"], config["patch_steps"]
    )
    return len(range(0, side - size + 1, step))


def _patches(X, size: int):
    """Every ``size``×``size`` window of ``X`` (B, S, S, C) at stride 1 as
    (B, R, R, size·size·C) in the layout ``c + px·C + py·C·size``."""
    r = X.shape[1] - size + 1
    shifted = [
        X[:, px : px + r, py : py + r, :]
        for py in range(size) for px in range(size)
    ]
    stacked = jnp.stack(shifted, axis=3)  # (B, R, R, size², C), py-major
    return stacked.reshape(X.shape[0], r, r, -1)


def sample_patches(config: dict, X) -> np.ndarray:
    """``whitener_size`` vectorised patches of ``X`` on the host: drawn
    without replacement from all windows in emission order (per image, for
    x, for y) by ``default_rng(filter_seed)``, sorted."""
    n, per_side = int(X.shape[0]), _window_grid(config)
    size, step = config["patch_size"], config["patch_steps"]
    total = n * per_side * per_side
    idx = np.sort(np.random.default_rng(config["filter_seed"]).choice(
        total, size=min(config["whitener_size"], total), replace=False
    ))
    img, window = np.divmod(idx, per_side * per_side)
    xi, yi = np.divmod(window, per_side)
    Xh = np.asarray(X)
    px, py, c = np.meshgrid(
        np.arange(size), np.arange(size), np.arange(Xh.shape[-1]),
        indexing="ij",
    )
    # out[i, c + px·C + py·C·size] = X[img, x + px, y + py, c]
    order = np.argsort((c + px * Xh.shape[-1] + py * Xh.shape[-1] * size).ravel())
    px, py, c = px.ravel()[order], py.ravel()[order], c.ravel()[order]
    return Xh[
        img[:, None], (xi * step)[:, None] + px, (yi * step)[:, None] + py, c
    ].astype(np.float64)


def learn_filters(config: dict, X) -> dict:
    """The whitened filter bank from the training images ``X``, in float64
    on the host (RandomPatchCifar.scala:41-58): ``{"filters" (K, 108),
    "whitener" (108, 108), "means" (108,)}``."""
    base = sample_patches(config, X)
    m = base.shape[1]
    mu = base.mean(axis=1, keepdims=True)
    var = ((base - mu) ** 2).sum(axis=1, keepdims=True) / (m - 1.0)
    base = (base - mu) / np.sqrt(var + config["var_constant"])
    means = base.mean(axis=0)
    centred = base - means
    cov = centred.T @ centred / (base.shape[0] - 1.0)
    lam, V = np.linalg.eigh(cov)
    whitener = (V * (np.maximum(lam, 0.0) + config["whitening_epsilon"]) ** -0.5) @ V.T
    pick = np.sort(np.random.default_rng(config["filter_seed"]).choice(
        base.shape[0], size=min(config["num_filters"], base.shape[0]),
        replace=False,
    ))
    unnorm = (base[pick] - means) @ whitener
    norms = np.sqrt((unnorm * unnorm).sum(axis=1))
    filters = (unnorm / (norms + 1e-10)[:, None]) @ whitener.T
    return {"filters": filters, "whitener": whitener, "means": means}


def _pool_windows(config: dict, side: int):
    """The reference Pooler's windows along one axis: centres from
    ``pool_size // 2`` every ``pool_stride``, each ``[c − h, c + h)`` with
    ``h = pool_size // 2`` — ``2·(pool_size // 2)`` wide — clipped at the
    edge."""
    half = config["pool_size"] // 2
    return [
        (c - half, min(c + half, side))
        for c in range(half, side, config["pool_stride"])
    ]


def _unscaled(config: dict, precision: str):
    """``f(params, X)``: images (B, 32, 32, 3) → (B, d) features before the
    column scaler, B small enough that (B, 27, 27, 2K) fits."""
    size, alpha = config["patch_size"], config["alpha"]
    var_constant = config["var_constant"]

    def f(params, X):
        P = _patches(X.astype(jnp.float32), size)
        B, r, _, m = P.shape
        mu = jnp.mean(P, axis=-1, keepdims=True)
        var = jnp.sum((P - mu) ** 2, axis=-1, keepdims=True) / (m - 1.0)
        normal = (P - mu) / jnp.sqrt(var + var_constant)
        # the whitener's mean folded in: (p̂ − means)·f = p̂·f − means·f
        conv = refmath.mm(
            normal.reshape(-1, m), params["filters_t"], precision
        ).reshape(B, r, r, -1) - params["bias"]
        rectified = jnp.concatenate(
            [jnp.maximum(0.0, conv - alpha), jnp.maximum(0.0, -conv - alpha)],
            axis=-1,
        )
        windows = _pool_windows(config, r)
        pooled = jnp.stack([
            jnp.stack([
                jnp.sum(rectified[:, x0:x1, y0:y1, :], axis=(1, 2))
                for y0, y1 in windows
            ], axis=1)
            for x0, x1 in windows
        ], axis=1)  # (B, px, py, 2K)
        # ImageVectorizer: index c + x·C + y·X·C
        return jnp.transpose(pooled, (0, 2, 1, 3)).reshape(B, -1)

    return f


def _in_slices(f, rows: int):
    """``f`` over the leading axis in slices of ``rows`` inside one traced
    function: the rows padded with copies of the first to a whole number of
    slices, ``lax.map`` over them, the padding cut."""

    def g(params, X):
        n = X.shape[0]
        size = min(rows, n)
        slices = -(-n // size)
        pad = slices * size - n
        if pad:
            X = jnp.concatenate(
                [X, jnp.broadcast_to(X[:1], (pad,) + X.shape[1:])], axis=0
            )
        out = jax.lax.map(
            lambda Xs: f(params, Xs),
            X.reshape((slices, size) + X.shape[1:]),
        )
        return out.reshape((slices * size,) + out.shape[2:])[:n]

    return g


def _filter_params(learned: dict) -> dict:
    filters = jnp.asarray(learned["filters"], jnp.float32)
    return {
        "filters_t": filters.T,
        "bias": jnp.asarray(
            learned["means"] @ learned["filters"].T, jnp.float32
        ),
    }


def _put():
    """``F[start : start + len(part)] = part``, in place on an accelerator
    (the CPU backend's donation is not to be trusted: ``linalg/bcd.py`` of
    the program says why)."""
    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(
        lambda F, part, start: jax.lax.dynamic_update_slice_in_dim(
            F, part, start, axis=0
        ),
        donate_argnums=donate,
    )


def _scale():
    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(
        lambda F, mean, std: (F - mean) / std, donate_argnums=donate
    )


def _learn(config: dict, X, precision: str):
    """``(params, Fs)``: the featurizer's state learned from the training
    images ``X`` — filter bank, then the column scaler from the features of
    every image — and the scaled features (n, d), kept for the solve."""
    n = int(X.shape[0])
    params = _filter_params(learn_filters(config, X))
    block = jax.jit(
        _in_slices(_unscaled(config, precision), config["reference_slice"])
    )
    put, F = _put(), None
    at = 0
    for Xb in refmath.row_blocks(X, config["reference_rows"]):
        part = block(params, Xb)
        if F is None:
            F = jnp.zeros((n, part.shape[1]), jnp.float32)
        F = put(F, part, at)
        at += part.shape[0]
    mean = jnp.mean(F, axis=0)
    std = jnp.sqrt(jnp.var(F, axis=0, ddof=1))
    std = jnp.where(jnp.isfinite(std) & (std >= 1e-12), std, 1.0)
    params = dict(params, mean=mean, std=std)
    return params, _scale()(F, mean, std)


def featurizer(config: dict, precision: str):
    """``(apply, params)``: images (n, 32, 32, 3) → (n, 80000) scaled
    features; ``apply(params, images)`` maps over slices of
    ``reference_slice`` images inside itself. The state is what a full-size
    ``fit`` at this precision learned, or is learned here from the
    configuration's training images made again from ``train_seed``."""
    key = (config["n_train"], precision)
    if key not in _STATE:
        X, _ = make_rows(config, config["train_seed"], config["n_train"])
        _STATE[key], _ = _learn(config, X, precision)
    unscaled = _in_slices(
        _unscaled(config, precision), config["reference_slice"]
    )

    def apply(params, X):
        return (unscaled(params, X) - params["mean"]) / params["std"]

    return apply, _STATE[key]


# -- the solve -------------------------------------------------------------


def one_pass_block_ridge(A, R, *, block_size: int, lam: float,
                         precision: str):
    """ONE pass of block coordinate descent on
    ``min ‖A W − R‖² + λ Σ‖W_j‖²`` from zero, in residual form, blocks in
    column order, the last narrower where ``block_size`` does not divide d:
    ``W_j = (Ã_jᵀÃ_j + λI)⁻¹ Ã_jᵀ r`` with ``Ã_j`` the block less its
    column means, then ``r −= Ã_j W_j``. Products on the device at
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


def fit(config: dict, X, y, *, precision: dict):
    """The model ``{"W", "b", "mean"}`` the configuration defines, on the
    SCALED features: ±1 indicators less their mean regressed by one pass of
    block coordinate descent. The featurizer's state is learned from ``X``,
    as the program learns it from the images it is handed."""
    params, Fs = _learn(config, X, precision["featurizer"])
    if int(X.shape[0]) == config["n_train"]:
        _STATE[(config["n_train"], precision["featurizer"])] = params
    Y = refmath.one_hot_pm(y, config["num_classes"])
    y_mean = jnp.mean(Y, axis=0)
    W, mean = one_pass_block_ridge(
        Fs, Y - y_mean, block_size=config["block_size"], lam=config["lam"],
        precision=precision["solver"],
    )
    return {"W": W, "b": y_mean, "mean": mean}


def expected_d(config: dict) -> int:
    """d from the widths: filters × 2 (the rectifier) × pooled windows."""
    per_side = len(_pool_windows(config, _window_grid(config)))
    return config["num_filters"] * 2 * per_side * per_side

