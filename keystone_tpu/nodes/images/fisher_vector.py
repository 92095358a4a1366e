"""Fisher Vector encoding (parity: nodes/images/FisherVector.scala:21-94 and
the native enceval path external/FisherVector.scala:17 — the formula from
Sanchez et al. IJCV'13; the JNI fast path is subsumed by running the same
matrix algebra on the MXU).

Input items are (d, n_desc) descriptor matrices; output (d, 2k) — first- and
second-order statistics per mixture component.

Note: the reference's fv2 line (FisherVector.scala:47) carries a stray ``.t``
on the ``(μ²−σ²)·diag(s0)`` term that only type-checks when d == k; the
published Sanchez et al. formula (and the enceval native implementation the
reference validates against) scale per column by s0 — implemented as intended
here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...data.dataset import Dataset
from ...obs.tracer import span
from ...workflow.transformer import Estimator, Transformer
from ..learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
    _posteriors,
)


@jax.jit
def _fisher_vector(X, means, variances, weights, weight_threshold):
    """X: (n, d, m) batch of descriptor matrices; means/variances (d, k);
    weights (k,). Returns (n, d, 2k)."""
    n_desc = X.shape[-1]
    # posteriors per descriptor: (n, m, k)
    Xt = jnp.swapaxes(X, 1, 2)  # (n, m, d)
    q = jax.vmap(
        lambda xt: _posteriors(
            xt, means.T, variances.T, weights, weight_threshold
        )
    )(Xt)
    s0 = jnp.mean(q, axis=1)                       # (n, k)
    # precision=high like the GMM contractions (see gmm.py _PREC): the fv2
    # term subtracts products of these statistics, so bf16 GEMM noise there
    # is visible after the ±cancellation
    s1 = jnp.einsum("ndm,nmk->ndk", X, q, precision="high") / n_desc
    s2 = jnp.einsum("ndm,nmk->ndk", X * X, q, precision="high") / n_desc

    fv1 = (s1 - means * s0[:, None, :]) / (
        jnp.sqrt(variances) * jnp.sqrt(weights)
    )
    fv2 = (
        s2
        - 2.0 * means * s1
        + (means * means - variances) * s0[:, None, :]
    ) / (variances * jnp.sqrt(2.0 * weights))
    return jnp.concatenate([fv1, fv2], axis=-1)


class FisherVector(Transformer):
    """FV encoding transformer (parity: FisherVector, FisherVector.scala:21-55)."""

    def __init__(self, gmm: GaussianMixtureModel):
        self.gmm = gmm

    def row_scratch_bytes(self, shape) -> int:
        """What an image holds besides the (d, 2k) output while it is
        encoded — segment dispatch prices a row by it
        (``compile/segment.py:_item_bytes``): the posteriors ``q`` (m, k),
        and ``X*X`` with the transposed descriptors (m, d). At 73,505
        descriptors, 80 dimensions and 256 centres: 75 + 2 × 23.5 MB. (With
        the members' outputs a row of the descriptor → Fisher-vector chain
        is then priced at 258 MB; the TPU compiler's own figure is 175–179 MB
        of temporaries, ``memory_analysis()`` of the program for a described
        v5e at slices of 4 and 8.)"""
        _, d, m = shape
        return 4 * m * (self.gmm.k + 2 * d)

    def trace_batch(self, X):
        with jax.named_scope("ks.featurize.fisher"):
            return _fisher_vector(
                X.astype(jnp.float32),
                self.gmm.means.astype(jnp.float32),
                self.gmm.variances.astype(jnp.float32),
                self.gmm.weights.astype(jnp.float32),
                self.gmm.weight_threshold,
            )

    def apply(self, x):
        return self.trace_batch(jnp.asarray(x)[None])[0]


class GMMFisherVectorEstimator(Estimator):
    """Fit a GMM on descriptor columns, emit the FV transformer (parity:
    ScalaGMMFisherVectorEstimator / GMMFisherVectorEstimator,
    FisherVector.scala:66-94; the k≥32 native-vs-scala choice point vanishes —
    there is one on-device implementation)."""

    def __init__(self, k: int, **gmm_kwargs):
        self.k = k
        self.gmm_kwargs = gmm_kwargs

    def fit(self, data: Dataset) -> FisherVector:
        data = Dataset.of(data)
        if data.is_batched:
            X = jnp.asarray(data.to_array())
            cols = jnp.transpose(X, (0, 2, 1)).reshape(-1, X.shape[1])
        else:
            import numpy as np

            cols = jnp.asarray(
                np.concatenate([np.asarray(i).T for i in data], axis=0)
            )
        with span(
            "gmm_fv.em_fit", samples=int(cols.shape[0]), centres=self.k
        ) as sp:
            est = GaussianMixtureModelEstimator(self.k, **self.gmm_kwargs)
            gmm = est.fit_matrix(cols)
            # the parameters are host arrays by now: the loop has ended
            sp.attrs["iterations"] = int(est.iterations_run)
        return FisherVector(gmm)
