"""The plain reference of ``cifar_krr``: RandomPatchCifarKernel
(``pipelines/images/cifar/RandomPatchCifarKernel.scala``) written out in
``jax.numpy`` and host numpy — the featurizer of ``cifar_patch10k``'s
reference at this configuration's widths (sample patches, whiten, choose
and scale a filter bank, convolve with per-patch normalisation, rectify
both ways, sum-pool 2×2, standardise the columns), then ONE epoch of block
Gauss–Seidel kernel ridge regression on the Gaussian kernel in the block
order the seed permutes, argmax.

Imports nothing of the program. The images, the filter learning and the
featurizer are ``cifar_patch10k_reference.py``'s, given this
configuration's values. What is solved inside this file:

* the model is DUAL: one row of coefficients a training image. So that
  ``compare.fit_numbers`` and ``refmath.scores`` score it as they score a
  linear model, ``featurizer`` maps an image to its KERNEL ROW against the
  reference's own scaled training features (``params["train"]``,
  n_train × d), and a model is ``{"W": A (n_train × k), "mean": 0,
  "b": 0}``. A fit handed fewer rows (the half-batch fault) gives the rows
  it never saw a coefficient of 0;
* a kernel block is ``exp(−γ·max(‖x‖² − 2x·y + ‖y‖², 0))`` with the cross
  product at the stated precision (the kernel rows of ``featurizer`` at
  ``highest``); the residual ``K_Bᵀ W − K_BBᵀ W_B`` at the solver's;
* each block's system ``(K_BB + λI) W_B = Y_B − residual`` is factored by
  Cholesky on the host in float64 and solved there. One step of precision
  lower (a control) the factor is taken on the device instead, in float32
  by a right-looking blocked Cholesky whose trailing updates — nearly all
  of its work — run at that precision (``cholesky_at``), and solved in
  float64 on the host.

Departures from the Scala, each because the benchmark needs it: those of
``cifar_patch10k_reference.py`` for the featurizer; the block order is
``numpy.random.default_rng(seed).shuffle`` of the block indices, drawn anew
each epoch — the Scala's ``blockPermuter`` shuffles with a
``scala.util.Random`` of the seed, whose draw no other program can repeat.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness, refmath

_PATCH = harness.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "cifar_patch10k_reference.py"
))

#: the learned featurizer state and the scaled training features by (rows
#: they were learned from, precision): a full-size ``fit`` leaves them for
#: ``featurizer``
_STATE: dict = {}

#: the panel of ``cholesky_at``: the width of XLA's own blocked Cholesky
PANEL = 128


def make_rows(config: dict, seed: int, n: int):
    """``(X, y)``: ``n`` images (n, 32, 32, 3) of ``cifar_patch10k``'s
    synthetic task on the device, drawn from ``seed``."""
    return _PATCH.make_rows(config, seed, n)


def expected_d(config: dict) -> int:
    return _PATCH.expected_d(config)


def block_order(config: dict, n: int) -> list:
    """The blocks of every epoch in the order they are solved: the block
    indices shuffled in place by ONE generator of the seed that also draws
    the patches and the filters (``filter_seed``, the port's one
    ``conf.seed``), once an epoch."""
    blocks = -(-n // config["block_size"])
    rng = np.random.default_rng(config["filter_seed"])
    out = []
    for _ in range(config["epochs"]):
        order = list(range(blocks))
        rng.shuffle(order)
        out.extend(order)
    return out


def kernel(A, B, gamma, precision: str):
    """The Gaussian kernel block ``exp(−γ‖a − b‖²)`` of the rows of ``A``
    against the rows of ``B``, (len(A), len(B))."""
    an = jnp.sum(A * A, axis=1, keepdims=True)
    bn = jnp.sum(B * B, axis=1)
    sq = an - 2.0 * refmath.mm(A, B.T, precision) + bn
    return jnp.exp(-gamma * jnp.maximum(sq, 0.0))


@functools.partial(jax.jit, static_argnums=(2,))
def cholesky_at(A, lam, precision: str):
    """The lower Cholesky factor of ``A + λI`` in float32 on the device, by
    panels of ``PANEL`` columns: each panel's diagonal block factored and
    the panel solved against it at full float32, then the trailing matrix
    less the panel's outer product at ``precision``. Shapes stay fixed
    (the matrix padded with the identity to a whole number of panels, rows
    outside the panel masked), so ONE program serves every block."""
    n = A.shape[0]
    m = -(-n // PANEL) * PANEL
    pad = jnp.concatenate([jnp.zeros(n), jnp.ones(m - n)]).astype(A.dtype)
    A = jnp.pad(A, ((0, m - n), (0, m - n))) + jnp.diag(pad)
    A = A + lam * jnp.diag(1.0 - pad)
    rows = jnp.arange(m)[:, None]

    def panel(k, carry):
        A, L = carry
        s = k * PANEL
        D = jax.lax.dynamic_slice(A, (s, s), (PANEL, PANEL))
        Ld = jnp.linalg.cholesky(D)
        col = jax.lax.dynamic_slice(A, (0, s), (m, PANEL))
        Lc = jax.scipy.linalg.solve_triangular(Ld, col.T, lower=True).T
        Lc = jnp.where(rows >= s, Lc, 0.0)
        below = jnp.where(rows >= s + PANEL, Lc, 0.0)
        A = A - refmath.mm(below, below.T, precision)
        return A, jax.lax.dynamic_update_slice(L, Lc, (0, s))

    _, L = jax.lax.fori_loop(0, m // PANEL, panel, (A, jnp.zeros_like(A)))
    return L[:n, :n]


def solve_block(Kbb, rhs, lam: float, precision: str) -> np.ndarray:
    """``(K_BB + λI)⁻¹ rhs`` in float64 on the host, the factor taken there
    at ``highest`` and by ``cholesky_at`` on the device below it."""
    import scipy.linalg

    if precision == "highest":
        factor = scipy.linalg.cho_factor(
            np.asarray(Kbb, np.float64) + lam * np.eye(Kbb.shape[0]),
            lower=True,
        )
    else:
        L = cholesky_at(Kbb, jnp.float32(lam), precision)
        factor = (np.asarray(L, np.float64), True)
    return scipy.linalg.cho_solve(factor, np.asarray(rhs, np.float64))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _block(F, start, gamma, size: int, precision: str):
    Fb = jax.lax.dynamic_slice_in_dim(F, start, size, axis=0)
    return kernel(F, Fb, gamma, precision)


@functools.partial(jax.jit, static_argnums=(4,))
def _rhs(Kb, Y, W, start, precision: str):
    size = Kb.shape[1]
    Kbb = jax.lax.dynamic_slice_in_dim(Kb, start, size, axis=0)
    Wb = jax.lax.dynamic_slice_in_dim(W, start, size, axis=0)
    Yb = jax.lax.dynamic_slice_in_dim(Y, start, size, axis=0)
    residual = refmath.mm(Kb.T, W, precision) - refmath.mm(Kbb.T, Wb, precision)
    return Kbb, Yb - residual


def gauss_seidel(F, Y, config: dict, *, kernel_precision: str,
                 solver_precision: str):
    """``epochs`` sweeps of block Gauss–Seidel on ``(K + λI) W = Y`` from
    zero, blocks of ``block_size`` rows in ``block_order``: for each block
    ``W_B ← (K_BB + λI)⁻¹ (Y_B − (K_Bᵀ W − K_BBᵀ W_B))``. Each block's
    kernel columns are made when the block is solved."""
    n = F.shape[0]
    bs, gamma = config["block_size"], jnp.float32(config["gamma"])
    W = jnp.zeros(Y.shape, jnp.float32)
    for b in block_order(config, n):
        start = b * bs
        size = min(bs, n - start)
        Kb = _block(F, start, gamma, size, kernel_precision)
        Kbb, rhs = _rhs(Kb, Y, W, start, solver_precision)
        del Kb
        Wb = solve_block(Kbb, rhs, config["lam"], solver_precision)
        W = jax.lax.dynamic_update_slice_in_dim(
            W, jnp.asarray(Wb, jnp.float32), start, axis=0
        )
    return W


def fit(config: dict, X, y, *, precision: dict):
    """The dual model the configuration defines: the featurizer's state
    learned from ``X`` as the program learns it, ±1 class indicators, one
    epoch of block Gauss–Seidel on the Gaussian kernel of the scaled
    features. ``W`` has ``n_train`` rows whatever the rows handed in."""
    params, F = _PATCH._learn(config, X, precision["featurizer"])
    n = int(X.shape[0])
    if n == config["n_train"]:
        _STATE[(n, precision["featurizer"])] = dict(params, train=F)
    Y = refmath.one_hot_pm(y, config["num_classes"])
    W = gauss_seidel(
        # compare.HIGHEST names no kernel part: it is highest too
        F, Y, config, kernel_precision=precision.get("kernel", "highest"),
        solver_precision=precision["solver"],
    )
    k = config["num_classes"]
    if n < config["n_train"]:
        W = jnp.concatenate(
            [W, jnp.zeros((config["n_train"] - n, k), jnp.float32)]
        )
    return {
        "W": W, "mean": jnp.zeros((config["n_train"],), jnp.float32),
        "b": jnp.zeros((k,), jnp.float32),
    }


def featurizer(config: dict, precision: str):
    """``(apply, params)``: images (n, 32, 32, 3) → (n, n_train) kernel rows
    against the reference's scaled training features, the convolution at
    ``precision`` and the kernel at ``highest``. The state is what a
    full-size ``fit`` at this precision learned, or is learned here from
    the configuration's training images made again from ``train_seed``."""
    key = (config["n_train"], precision)
    if key not in _STATE:
        X, _ = make_rows(config, config["train_seed"], config["n_train"])
        params, F = _PATCH._learn(config, X, precision)
        _STATE[key] = dict(params, train=F)
    unscaled = _PATCH._in_slices(
        _PATCH._unscaled(config, precision), config["reference_slice"]
    )
    gamma = config["gamma"]

    def apply(params, X):
        f = (unscaled(params, X) - params["mean"]) / params["std"]
        return kernel(f, params["train"], gamma, "highest")

    return apply, _STATE[key]
