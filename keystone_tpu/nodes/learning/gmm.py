"""Diagonal-covariance Gaussian mixture models.

Parity: nodes/learning/GaussianMixtureModel.scala:19 (posterior-assignment
transformer) and GaussianMixtureModelEstimator.scala:25 (EM following the
Sanchez et al. IJCV'13 Appendix B recipe: k-means++ init, incremental
log-sum-exp likelihood, aggressive posterior thresholding, variance floors).

The whole E and M steps are batched matrix algebra — one jit program each —
with the convergence test host-side, mirroring the reference's driver loop.
The native enceval EM path (utils/external/EncEval.scala computeGMM via JNI)
is subsumed: this on-device implementation IS the fast path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.transformer import Estimator, Transformer
from ...utils.params import as_param
from .kmeans import KMeansPlusPlusEstimator

KMEANS_PLUS_PLUS_INITIALIZATION = "kmeans++"
RANDOM_INITIALIZATION = "random"


# The Mahalanobis term is an expanded quadratic (‖x‖²/σ² − 2xμ/σ² + ‖μ‖²/σ²
# as GEMMs — the TPU-right shape), which cancels catastrophically: at
# single-pass-bf16 matmul precision the residual error (~4e-3 of the large
# terms) lands in the exponent of the posterior softmax and flips
# assignments depending on how XLA fused the surrounding program (observed:
# the SAME FisherVector inputs gave posteriors differing by O(1) inside vs
# outside a whole-chain jit). precision=high keeps the cancellation at f32
# noise, making the encoding fusion-invariant.
_PREC = "high"

# The EM's own products at float32 on any backend. The loop stops when the
# mean log likelihood gains less than 1e-4 of itself: at three passes the
# likelihood carries 1e-5 of noise, so a run on a TPU can stop a round
# before or after a float32 run does, and a round moves the weights by
# percents. Ten or twenty rounds over the sample, once a fit: 0.3 s more
# at 10⁶ × 80 × 256 on a v5e (PERF.md §6, PR 33) beside a featurizer of
# seconds. The posteriors of the ENCODING (``_posteriors``, the Fisher
# vector's path over every descriptor of every image) stay at ``_PREC``.
_EM_PREC = jax.lax.Precision.HIGHEST


@jax.jit
def _posteriors(X, means, variances, weights, weight_threshold):
    """Thresholded posterior assignments q (n, k)
    (parity: GaussianMixtureModel.apply:47-82). means/variances here are
    (k, d) row-major."""
    Xsq = X * X
    half_inv_var = 0.5 / variances
    sq_mahal = (
        jnp.matmul(Xsq, half_inv_var.T, precision=_PREC)
        - jnp.matmul(X, (means / variances).T, precision=_PREC)
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    d = X.shape[1]
    log_prior = (
        -0.5 * d * math.log(2 * math.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
    )
    llh = log_prior - sq_mahal
    llh = llh - jnp.max(llh, axis=1, keepdims=True)
    q = jnp.exp(llh)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    return q / jnp.sum(q, axis=1, keepdims=True)


@jax.jit
def _e_step(X, means, variances, weights, weight_threshold):
    """One fused E-step: (mean log-sum-exp likelihood, thresholded
    posteriors) from a single Mahalanobis computation — the reference reuses
    llh for both too (GaussianMixtureModelEstimator.scala:118-165)."""
    Xsq = X * X
    sq_mahal = (
        jnp.matmul(Xsq, (0.5 / variances).T, precision=_EM_PREC)
        - jnp.matmul(X, (means / variances).T, precision=_EM_PREC)
        + 0.5 * jnp.sum(means * means / variances, axis=1)
    )
    d = X.shape[1]
    log_prior = (
        -0.5 * d * math.log(2 * math.pi)
        - 0.5 * jnp.sum(jnp.log(variances), axis=1)
        + jnp.log(weights)
    )
    llh = log_prior - sq_mahal
    cost = jnp.mean(jax.scipy.special.logsumexp(llh, axis=1))
    shifted = llh - jnp.max(llh, axis=1, keepdims=True)
    q = jnp.exp(shifted)
    q = q / jnp.sum(q, axis=1, keepdims=True)
    q = jnp.where(q > weight_threshold, q, 0.0)
    return cost, q / jnp.sum(q, axis=1, keepdims=True)


@jax.jit
def _m_step(X, q, var_floor):
    q_sum = jnp.sum(q, axis=0)
    weights = q_sum / X.shape[0]
    means = jnp.matmul(q.T, X, precision=_EM_PREC) / q_sum[:, None]
    variances = (
        jnp.matmul(q.T, X * X, precision=_EM_PREC) / q_sum[:, None]
        - means * means
    )
    variances = jnp.maximum(variances, var_floor)
    return weights, means, variances, q_sum


@functools.partial(
    jax.jit,
    static_argnames=("max_iterations", "weight_threshold",
                     "stop_tolerance", "min_cluster_size"),
)
def _em_loop(X, means, variances, weights, var_floor, *,
             max_iterations: int, weight_threshold: float,
             stop_tolerance: float, min_cluster_size: int):
    """The whole EM iteration as ONE device program (lax.while_loop).

    The eager loop paid two blocking host round-trips per iteration (the
    f32 cost scalar for the convergence test, the q_sum min-cluster
    check), each of which drains the device queue. Break semantics match
    the reference loop exactly (GaussianMixtureModelEstimator.scala:
    118-165): stop on non-improving cost or an unbalanced cluster, in both
    cases KEEPING the previous iteration's parameters. Returns the
    parameters and the iterations run."""

    def cond(carry):
        i, done, *_ = carry
        return (i < max_iterations) & ~done

    def body(carry):
        i, done, prev_cost, has_prev, m, v, w = carry
        cost, q = _e_step(X, m, v, w, weight_threshold)
        stop_conv = has_prev & ~(
            cost - prev_cost >= stop_tolerance * jnp.abs(prev_cost)
        )
        new_w, new_m, new_v, q_sum = _m_step(X, q, var_floor)
        unbalanced = jnp.any(q_sum < min_cluster_size)
        advance = ~stop_conv & ~unbalanced
        m2 = jnp.where(advance, new_m, m)
        v2 = jnp.where(advance, new_v, v)
        w2 = jnp.where(advance, new_w, w)
        return (i + 1, stop_conv | unbalanced, cost, True, m2, v2, w2)

    init = (
        jnp.int32(0),
        jnp.bool_(False),
        jnp.float32(0.0),
        jnp.bool_(False),
        means,
        variances,
        weights,
    )
    i, _, _, _, m, v, w = jax.lax.while_loop(cond, body, init)
    return m, v, w, i


class GaussianMixtureModel(Transformer):
    """Posterior-assignment transformer. Stored column-major like the
    reference: ``means``/``variances`` are (d, k), ``weights`` (k,)
    (parity: GaussianMixtureModel.scala:19-85)."""

    def __init__(self, means, variances, weights,
                 weight_threshold: float = 1e-4):
        self.means = as_param(means)
        self.variances = as_param(variances)
        self.weights = as_param(weights)
        self.weight_threshold = weight_threshold
        self.k = self.means.shape[1]
        self.dim = self.means.shape[0]

    def trace_batch(self, X):
        return _posteriors(
            X, self.means.T, self.variances.T, self.weights,
            self.weight_threshold,
        )

    @staticmethod
    def load(mean_file: str, vars_file: str, weights_file: str
             ) -> "GaussianMixtureModel":
        """CSV checkpoint load (parity: GaussianMixtureModel.load:97-105)."""
        means = np.loadtxt(mean_file, delimiter=",", ndmin=2)
        variances = np.loadtxt(vars_file, delimiter=",", ndmin=2)
        weights = np.loadtxt(weights_file, delimiter=",").ravel()
        return GaussianMixtureModel(means, variances, weights)


class GaussianMixtureModelEstimator(Estimator):
    """EM for diagonal GMMs (parity:
    GaussianMixtureModelEstimator.scala:25-193)."""

    def __init__(
        self,
        k: int,
        max_iterations: int = 100,
        min_cluster_size: int = 40,
        stop_tolerance: float = 1e-4,
        weight_threshold: float = 1e-4,
        small_variance_threshold: float = 1e-2,
        absolute_variance_threshold: float = 1e-9,
        initialization_method: str = KMEANS_PLUS_PLUS_INITIALIZATION,
        seed: int = 0,
    ):
        if k <= 0 or max_iterations <= 0 or min_cluster_size <= 0:
            raise ValueError("k, max_iterations, min_cluster_size must be > 0")
        self.k = k
        self.max_iterations = max_iterations
        self.min_cluster_size = min_cluster_size
        self.stop_tolerance = stop_tolerance
        self.weight_threshold = weight_threshold
        self.small_variance_threshold = small_variance_threshold
        self.absolute_variance_threshold = absolute_variance_threshold
        self.initialization_method = initialization_method
        self.seed = seed

    def fit(self, data: Dataset) -> GaussianMixtureModel:
        return self.fit_matrix(Dataset.of(data).to_array())

    def fit_matrix(self, X) -> GaussianMixtureModel:
        X = jnp.asarray(X, dtype=jnp.float32)
        n, d = X.shape
        k = self.k

        mean_g = jnp.mean(X, axis=0)
        var_g = jnp.mean(X * X, axis=0) - mean_g * mean_g

        if self.initialization_method == KMEANS_PLUS_PLUS_INITIALIZATION:
            km = KMeansPlusPlusEstimator(k, 1, seed=self.seed).fit_matrix(X)
            assign = km.trace_batch(X)
            mass = jnp.sum(assign, axis=0)
            weights = mass / n
            # the clusters' moments at float32 on any backend: a mean of
            # descriptors rounded to bfloat16 starts the EM from another
            # point (kmeans.py says what that costs)
            means = jnp.matmul(assign.T, X, precision=_EM_PREC) / mass[:, None]
            variances = (
                jnp.matmul(assign.T, X * X, precision=_EM_PREC) / mass[:, None]
                - means * means
            )
        else:
            rng = np.random.default_rng(self.seed)
            col_min = jnp.min(X, axis=0)
            col_range = jnp.max(X, axis=0) - col_min
            means = (
                jnp.asarray(rng.random((k, d)), dtype=X.dtype) * col_range
                + col_min
            )
            variances = 0.1 * jnp.ones((k, d), X.dtype) * col_range * col_range
            weights = jnp.full((k,), 1.0 / k, X.dtype)

        var_floor = jnp.maximum(
            self.small_variance_threshold * var_g,
            self.absolute_variance_threshold,
        )
        variances = jnp.maximum(variances, var_floor)

        means, variances, weights, self.iterations_run = _em_loop(
            X, means, variances, weights, var_floor,
            max_iterations=self.max_iterations,
            weight_threshold=self.weight_threshold,
            stop_tolerance=self.stop_tolerance,
            min_cluster_size=self.min_cluster_size,
        )

        return GaussianMixtureModel(
            means.T, variances.T, weights, self.weight_threshold
        )
