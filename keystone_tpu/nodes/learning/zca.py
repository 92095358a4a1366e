"""ZCA whitening (parity: nodes/learning/ZCAWhitener.scala:12,30).

The reference centers the sample matrix, takes a float32 SVD via a direct
LAPACK ``sgesvd`` call, and builds W = Vᵀ diag((σ²/(n−1) + ε)^−½) V. Here the
same algebra runs on-device through ``jnp.linalg.svd`` — f32 end to end, like
the reference's deliberate float path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...data.dataset import Dataset
from ...workflow.transformer import Estimator, Transformer
from ...utils.params import as_param


class ZCAWhitener(Transformer):
    """x → (x − means) · W (parity: ZCAWhitener.scala:12-18)."""

    def __init__(self, whitener, means):
        self.whitener = as_param(whitener)
        self.means = as_param(means)

    def trace_batch(self, X):
        # float32 all the way (six bf16 passes on a TPU): the whitener
        # scales its smallest directions by ε^−½ — 316 at the published
        # 1e-5 — and one bf16 pass is wrong in the third digit before that
        return jnp.matmul(X - self.means, self.whitener, precision=_EXACT)

    # alias used by Convolver.build and host-side callers
    def transform(self, X):
        return self.trace_batch(jnp.asarray(X))


_EXACT = jax.lax.Precision.HIGHEST


@jax.jit
def _fit_zca(X, eps):
    means = jnp.mean(X, axis=0)
    Xc = (X - means).astype(jnp.float32)
    n = X.shape[0]
    # the reference's "deliberate float path" is float32, not a TPU's
    # default of one bf16 pass a product: on the chip the filter bank this
    # whitener makes was 10% off the float64 one (PERF.md §6, PR 29)
    with jax.default_matmul_precision("highest"):
        _, s, vt = jnp.linalg.svd(Xc, full_matrices=False)
        scale = (s * s / (n - 1.0) + eps) ** -0.5
        W = vt.T @ (scale[:, None] * vt)
    return W, means


class ZCAWhitenerEstimator(Estimator):
    """Fit the whitening rotation from a sample matrix
    (parity: ZCAWhitener.scala:30-73)."""

    def __init__(self, eps: float = 0.1):
        self.eps = eps

    def fit(self, data: Dataset) -> ZCAWhitener:
        return self.fit_single(Dataset.of(data).to_array())

    def fit_single(self, X) -> ZCAWhitener:
        W, means = _fit_zca(
            jnp.asarray(X, dtype=jnp.float32), jnp.float32(self.eps)
        )
        return ZCAWhitener(W, means)
