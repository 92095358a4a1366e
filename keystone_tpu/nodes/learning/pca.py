"""PCA family: local SVD, distributed TSQR, randomized sketch, and the
cost-model chooser.

Parity: nodes/learning/PCA.scala:19,38,118-160,163-226 (PCATransformer,
BatchPCATransformer, ColumnPCAEstimator, PCAEstimator),
DistributedPCA.scala:20 (TSQR-based), ApproximatePCA.scala:22,58
(Halko/Martinsson/Tropp randomized range finder).

"Column" estimators treat each item — a (d, n_desc) descriptor matrix — as
n_desc separate d-vectors, matching the reference's matrixToColArray
flattening.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...linalg.tsqr import tsqr_r
from ...obs.tracer import span
from ...parallel.mesh import default_mesh
from ...workflow.node_optimization import Optimizable
from ...workflow.transformer import Estimator, Transformer
from ...utils.params import as_param
from .cost import (
    CostModel,
    DEFAULT_CPU_WEIGHT,
    DEFAULT_MEM_WEIGHT,
    DEFAULT_NETWORK_WEIGHT,
)


def enforce_matlab_sign_convention(pca):
    """Largest-|coefficient| element of each column gets a positive sign
    (parity: PCAEstimator.enforceMatlabPCASignConvention, PCA.scala:228-247)."""
    col_max = jnp.max(pca, axis=0)
    abs_col_max = jnp.max(jnp.abs(pca), axis=0)
    signs = jnp.where(col_max == abs_col_max, 1.0, -1.0)
    return pca * signs


class PCATransformer(Transformer):
    """x → pcaMatᵀ x for d-vectors (parity: PCATransformer, PCA.scala:19-30).
    ``pca_mat`` is (d, dims)."""

    def __init__(self, pca_mat):
        self.pca_mat = as_param(pca_mat)

    def trace_batch(self, X):
        return X @ self.pca_mat


class BatchPCATransformer(Transformer):
    """Per-item descriptor matrices (d, n_desc) → (dims, n_desc)
    (parity: BatchPCATransformer, PCA.scala:38-44)."""

    #: a column's projection reads that column alone
    column_wise = True

    def __init__(self, pca_mat):
        self.pca_mat = as_param(pca_mat)

    def trace_batch(self, X):
        # X: (n, d, n_desc) → (n, dims, n_desc). precision=high, as the
        # Fisher vector's products downstream: the projected descriptors go
        # into an expanded Mahalanobis form that cancels, and one bf16 pass
        # over descriptors of 0..255 lands in the posteriors' exponent
        with jax.named_scope("ks.featurize.pca"):
            return jnp.einsum(
                "dk,ndm->nkm", self.pca_mat, X, precision="high"
            )

    def apply(self, x):
        return self.pca_mat.T @ jnp.asarray(x)


@jax.jit
def _pca_svd(X):
    means = jnp.mean(X, axis=0)
    _, _, vt = jnp.linalg.svd(X - means, full_matrices=False)
    return enforce_matlab_sign_convention(vt.T)


@jax.jit
def _pca_gram_eigh(X):
    """PCA directions via the d×d covariance eigendecomposition.

    XLA has no native tall-skinny SVD — jnp.linalg.svd of a 200k×128
    sample matrix measures ~12 s on a v5e, dominating the whole ImageNet
    PCA phase. For n ≫ d the right singular vectors are the eigenvectors
    of XᵀX: one MXU GEMM (precision=high, so the squared-condition worry
    stays below f32 noise for featurizer-scale conditioning) plus an eigh
    of a d×d matrix — milliseconds. The reference's own local path is f32
    sgesvd (PCA.scala:192-206); agreement is pinned by the PCA oracle
    tests."""
    means = jnp.mean(X, axis=0)
    Xc = X - means
    # float32 on any backend: the directions of close eigenvalues turn
    # with an error in G, and everything fitted after the projection (a
    # codebook, a model) is fitted in these coordinates. One n × d × d
    # product a fit.
    G = jnp.matmul(Xc.T, Xc, precision=jax.lax.Precision.HIGHEST)
    _, vecs = jnp.linalg.eigh(G)  # ascending eigenvalues
    v = vecs[:, ::-1]  # descending, like svd's vt ordering
    return enforce_matlab_sign_convention(v)


@jax.jit
def _pca_gram_eigh_items(X):
    """:func:`_pca_gram_eigh` of the columns of the items ``X`` (n, d, m)
    — the same centred covariance at float32, the same eigenvectors —
    accumulated a few items at a time, so that neither the (n·m, d) column
    matrix nor its centred copy is built beside the sample: at the ImageNet
    pipeline's 10⁷ sampled descriptors of 128 each is 5.1 GB."""
    n, d, m = X.shape
    means = jnp.mean(X, axis=(0, 2))
    # items a step: the most that divide n and stay under 64 MB
    step = max(
        c for c in range(1, n + 1)
        if n % c == 0 and (c == 1 or c * d * m * 4 <= (1 << 26))
    )

    def add(G, Xs):
        Xc = Xs - means[:, None]
        return G + jnp.einsum(
            "cdm,cem->de", Xc, Xc, precision=jax.lax.Precision.HIGHEST
        ), None

    G, _ = jax.lax.scan(
        add, jnp.zeros((d, d), X.dtype), X.reshape(n // step, step, d, m)
    )
    _, vecs = jnp.linalg.eigh(G)
    return enforce_matlab_sign_convention(vecs[:, ::-1])


def _pca_directions(X):
    """svd for small samples, Gram-eigh for tall ones (n ≥ 8·d)."""
    n, d = X.shape
    if n >= 8 * d:
        return _pca_gram_eigh(X)
    return _pca_svd(X)


class PCAEstimator(Estimator, CostModel):
    """Local SVD PCA over collected samples (parity: PCAEstimator,
    PCA.scala:163-226; the direct sgesvd call becomes jnp.linalg.svd in f32)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, data: Dataset) -> PCATransformer:
        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        return PCATransformer(self.compute_pca(X))

    def compute_pca(self, X):
        return _pca_directions(X)[:, : self.dims]

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        flops = n * d * d
        # the sample is collected to one machine: nothing to collect where
        # there is one (a single chip then takes the local path, whose
        # covariance and eigenvectors are float32 on any backend)
        collect = network_weight * n * d if num_machines > 1 else 0.0
        return max(cpu_weight * flops, mem_weight * n * d) + collect


class DistributedPCAEstimator(Estimator, CostModel):
    """TSQR-based PCA: R factor over the mesh, then a d×d SVD of R
    (parity: DistributedPCAEstimator, DistributedPCA.scala:20-74; the
    per-partition QR + tree reduction becomes linalg.tsqr_r over ICI)."""

    def __init__(self, dims: int):
        self.dims = dims

    def fit(self, data: Dataset) -> PCATransformer:
        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        means = jnp.mean(X, axis=0)
        R = tsqr_r(X - means, mesh=default_mesh())
        _, _, vt = jnp.linalg.svd(R, full_matrices=False)
        pca = enforce_matlab_sign_convention(vt.T)
        return PCATransformer(pca[:, : self.dims])

    def cost(self, n, d, k, sparsity, num_machines,
             cpu_weight, mem_weight, network_weight):
        import math

        log2m = math.log2(max(num_machines, 2))
        flops = n * d * d / num_machines + d * d * d * log2m
        return max(cpu_weight * flops, mem_weight * n * d) \
            + network_weight * d * d * log2m


class ApproximatePCAEstimator(Estimator):
    """Randomized sketch PCA, HMT 2011 algorithms 4.4 + 5.1
    (parity: ApproximatePCAEstimator, ApproximatePCA.scala:22-105)."""

    def __init__(self, dims: int, q: int = 10, p: int = 5, seed: int = 0):
        self.dims = dims
        self.q = q
        self.p = p
        self.seed = seed

    def fit(self, data: Dataset) -> PCATransformer:
        X = jnp.asarray(Dataset.of(data).to_array(), dtype=jnp.float32)
        return PCATransformer(self._approximate_pca(X))

    def _approximate_pca(self, A):
        k, p, q = self.dims, self.p, self.q
        n, d = A.shape
        key = jax.random.PRNGKey(self.seed)
        omega = jax.random.normal(key, (d, k + p), dtype=A.dtype)
        means = jnp.mean(A, axis=0)
        A = A - means
        Q, _ = jnp.linalg.qr(A @ omega)
        for _ in range(q):
            Qh, _ = jnp.linalg.qr(A.T @ Q)
            Q, _ = jnp.linalg.qr(A @ Qh)
        B = Q.T @ A
        _, _, vt = jnp.linalg.svd(B, full_matrices=False)
        pca = enforce_matlab_sign_convention(vt.T)
        return pca[:, :k]


class _ColumnFit:
    """Mixin: flatten per-item (d, n_desc) matrices into sample rows."""

    @staticmethod
    def _collect_columns(data: Dataset):
        data = Dataset.of(data)
        if data.is_batched:
            X = jnp.asarray(data.to_array())
            # (n, d, m) → (n·m, d)
            return jnp.transpose(X, (0, 2, 1)).reshape(-1, X.shape[1])
        cols = [np.asarray(item).T for item in data]
        return jnp.asarray(np.concatenate(cols, axis=0), dtype=jnp.float32)

    def _fit_columns(
        self, data: Dataset, directions, items_directions=None
    ) -> "BatchPCATransformer":
        """The transformer of ``directions(rows)`` over the columns of
        ``data``, under a ``pca.fit`` span. ``items_directions``, where an
        estimator has one, takes a tall batched sample as the (n, d, m)
        items it is — no (n·m, d) copy of it is made."""
        data = Dataset.of(data)
        X = jnp.asarray(data.to_array()) if data.is_batched else None
        if (
            items_directions is not None and X is not None and X.ndim == 3
            and X.shape[0] * X.shape[2] >= 8 * X.shape[1]
        ):
            samples = int(X.shape[0] * X.shape[2])
            compute = lambda: items_directions(X.astype(jnp.float32))  # noqa: E731
        else:
            rows = self._collect_columns(data)
            samples = int(rows.shape[0])
            compute = lambda: directions(rows)  # noqa: E731
        with span("pca.fit", samples=samples, dims=self.dims) as sp:
            pca_mat = compute()
            sp.sync_on(pca_mat)
        return BatchPCATransformer(pca_mat)


class LocalColumnPCAEstimator(Estimator, CostModel, _ColumnFit):
    """(parity: LocalColumnPCAEstimator, PCA.scala:52-73)."""

    def __init__(self, dims: int):
        self.dims = dims
        self._est = PCAEstimator(dims)

    def fit(self, data: Dataset) -> BatchPCATransformer:
        return self._fit_columns(
            data, self._est.compute_pca,
            lambda X: _pca_gram_eigh_items(X)[:, : self.dims],
        )

    def cost(self, *a):
        return self._est.cost(*a)


class DistributedColumnPCAEstimator(Estimator, CostModel, _ColumnFit):
    """(parity: DistributedColumnPCAEstimator, PCA.scala:81-103)."""

    def __init__(self, dims: int):
        self.dims = dims
        self._est = DistributedPCAEstimator(dims)

    def fit(self, data: Dataset) -> BatchPCATransformer:
        return self._fit_columns(
            data, lambda rows: self._est.fit(Dataset.of(rows)).pca_mat
        )

    def cost(self, *a):
        return self._est.cost(*a)


class ColumnPCAEstimator(Estimator, _ColumnFit, Optimizable):
    """Cost-model chooser between local and distributed column PCA
    (parity: ColumnPCAEstimator, PCA.scala:105-160). Falls back to the local
    estimator when no sample statistics are available. Participates in
    graph-level NodeOptimizationRule via ``sample_optimize``
    (parity: OptimizableNodes.scala:12-25)."""

    def __init__(
        self,
        dims: int,
        num_machines: Optional[int] = None,
        cpu_weight: float = DEFAULT_CPU_WEIGHT,
        mem_weight: float = DEFAULT_MEM_WEIGHT,
        network_weight: float = DEFAULT_NETWORK_WEIGHT,
    ):
        self.dims = dims
        self.num_machines = num_machines
        self.cpu_weight = cpu_weight
        self.mem_weight = mem_weight
        self.network_weight = network_weight
        self.local = LocalColumnPCAEstimator(dims)
        self.distributed = DistributedColumnPCAEstimator(dims)

    def sample_optimize(self, samples, num_items: int) -> Estimator:
        return self.optimize(samples[0], total_items=num_items)

    def optimize(self, sample: Dataset,
                 total_items: Optional[int] = None) -> Estimator:
        sample = Dataset.of(sample)
        # shapes only — no device→host materialization of the descriptors
        if sample.is_batched:
            shape = jax.tree_util.tree_leaves(sample.payload)[0].shape
            d, n = shape[1], shape[0] * shape[2]
            n_sample_items = shape[0]
        else:
            items = sample.payload
            d = items[0].shape[0]
            n = sum(item.shape[1] for item in items)
            n_sample_items = len(items)
        if total_items is not None and n_sample_items:
            # scale descriptor-column count from the sample to the full set
            n = int(n * total_items / n_sample_items)
        machines = self.num_machines or default_mesh().size
        args = (n, d, self.dims, 1.0, machines,
                self.cpu_weight, self.mem_weight, self.network_weight)
        if self.local.cost(*args) <= self.distributed.cost(*args):
            return self.local
        return self.distributed

    def fit(self, data: Dataset) -> BatchPCATransformer:
        return self.optimize(data).fit(data)
