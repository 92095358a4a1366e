"""Oracle tests for PCA family, k-means++, GMM EM and Fisher Vectors —
cross-implementation (numpy/scipy) and distributed-vs-local agreement
(parity: PCASuite.scala:85, GMMSuite, FisherVectorSuite patterns)."""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.images.fisher_vector import (
    FisherVector,
    GMMFisherVectorEstimator,
)
from keystone_tpu.nodes.learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
)
from keystone_tpu.nodes.learning.kmeans import (
    KMeansModel,
    KMeansPlusPlusEstimator,
)
from keystone_tpu.nodes.learning.pca import (
    ApproximatePCAEstimator,
    BatchPCATransformer,
    ColumnPCAEstimator,
    DistributedPCAEstimator,
    LocalColumnPCAEstimator,
    PCAEstimator,
)


def _low_rank_data(rng, n=300, d=10, rank=3, noise=0.01):
    U = rng.standard_normal((n, rank))
    V = rng.standard_normal((rank, d))
    return (U @ V + noise * rng.standard_normal((n, d))).astype(np.float32)


def _subspace_agrees(P1, P2, atol=0.05):
    """Two orthonormal bases span the same subspace iff P1 P1ᵀ == P2 P2ᵀ."""
    return np.allclose(P1 @ P1.T, P2 @ P2.T, atol=atol)


def test_local_pca_matches_numpy_svd():
    rng = np.random.default_rng(0)
    X = _low_rank_data(rng)
    pca = PCAEstimator(3).fit(Dataset.of(X))
    P = np.asarray(pca.pca_mat)
    Xc = X - X.mean(axis=0)
    _, _, vt = np.linalg.svd(Xc, full_matrices=False)
    assert _subspace_agrees(P, vt[:3].T)
    # sign convention: each column's max-|coeff| entry is positive
    for j in range(3):
        assert P[np.abs(P[:, j]).argmax(), j] > 0


def test_distributed_pca_agrees_with_local():
    rng = np.random.default_rng(1)
    X = _low_rank_data(rng, n=512)
    local = np.asarray(PCAEstimator(3).fit(Dataset.of(X)).pca_mat)
    dist = np.asarray(DistributedPCAEstimator(3).fit(Dataset.of(X)).pca_mat)
    assert _subspace_agrees(local, dist)


def test_approximate_pca_agrees_with_local():
    rng = np.random.default_rng(2)
    X = _low_rank_data(rng, n=400, d=12, rank=4)
    local = np.asarray(PCAEstimator(4).fit(Dataset.of(X)).pca_mat)
    approx = np.asarray(
        ApproximatePCAEstimator(4, q=5).fit(Dataset.of(X)).pca_mat
    )
    assert _subspace_agrees(local, approx, atol=0.1)


def test_column_pca_on_descriptor_matrices():
    rng = np.random.default_rng(3)
    # 6 items of (d=8, m=50) descriptors
    items = rng.standard_normal((6, 8, 50)).astype(np.float32)
    t = LocalColumnPCAEstimator(4).fit(Dataset.of(items))
    assert isinstance(t, BatchPCATransformer)
    out = np.asarray(t.apply_batch(Dataset.of(items)).to_array())
    assert out.shape == (6, 4, 50)
    # chooser returns one of the two implementations and fit works
    chooser = ColumnPCAEstimator(4)
    t2 = chooser.fit(Dataset.of(items))
    assert np.asarray(t2.pca_mat).shape == (8, 4)


def test_kmeans_recovers_separated_clusters():
    rng = np.random.default_rng(4)
    centers = np.array([[0, 0], [10, 10], [-10, 10]], dtype=np.float32)
    X = np.concatenate(
        [c + 0.5 * rng.standard_normal((100, 2)) for c in centers]
    ).astype(np.float32)
    model = KMeansPlusPlusEstimator(3, 20, seed=0).fit(Dataset.of(X))
    means = np.asarray(model.means)
    # every true center has a learned center nearby
    for c in centers:
        assert np.min(np.linalg.norm(means - c, axis=1)) < 1.0
    assign = np.asarray(model.trace_batch(jnp.asarray(X)))
    assert assign.shape == (300, 3)
    np.testing.assert_allclose(assign.sum(axis=1), 1.0)
    # points in one true cluster share an assignment column
    assert (assign[:100].argmax(axis=1) == assign[0].argmax()).all()


def test_gmm_em_recovers_mixture():
    rng = np.random.default_rng(5)
    means_true = np.array([[0.0, 0.0], [6.0, 6.0]])
    X = np.concatenate(
        [
            means_true[0] + rng.standard_normal((200, 2)),
            means_true[1] + 0.5 * rng.standard_normal((200, 2)),
        ]
    ).astype(np.float32)
    gmm = GaussianMixtureModelEstimator(
        2, max_iterations=50, seed=0
    ).fit_matrix(X)
    means = np.asarray(gmm.means).T  # (k, d)
    for c in means_true:
        assert np.min(np.linalg.norm(means - c, axis=1)) < 0.5
    w = np.asarray(gmm.weights)
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-5)
    np.testing.assert_allclose(w, [0.5, 0.5], atol=0.1)
    # posteriors: rows sum to 1, cluster structure respected
    q = np.asarray(gmm.trace_batch(jnp.asarray(X)))
    np.testing.assert_allclose(q.sum(axis=1), 1.0, rtol=1e-5)
    assert (q[:200].argmax(axis=1) == q[0].argmax()).all()


def test_fisher_vector_matches_naive_numpy():
    rng = np.random.default_rng(6)
    d, k, m = 4, 3, 30
    means = rng.standard_normal((d, k))
    variances = rng.uniform(0.5, 2.0, (d, k))
    weights = np.array([0.5, 0.3, 0.2])
    gmm = GaussianMixtureModel(means, variances, weights)
    X = rng.standard_normal((2, d, m)).astype(np.float32)

    fv = np.asarray(FisherVector(gmm).apply_batch(Dataset.of(X)).to_array())
    assert fv.shape == (2, d, 2 * k)

    for i in range(2):
        x = X[i].astype(np.float64)  # (d, m)
        q = np.asarray(gmm.trace_batch(jnp.asarray(x.T, dtype=jnp.float32)))
        s0 = q.mean(axis=0)
        s1 = x @ q / m
        s2 = (x * x) @ q / m
        fv1 = (s1 - means * s0) / (np.sqrt(variances) * np.sqrt(weights))
        fv2 = (s2 - 2 * means * s1 + (means ** 2 - variances) * s0) / (
            variances * np.sqrt(2 * weights)
        )
        expected = np.concatenate([fv1, fv2], axis=1)
        np.testing.assert_allclose(fv[i], expected, rtol=1e-2, atol=1e-2)


def test_gmm_fisher_vector_estimator_end_to_end():
    rng = np.random.default_rng(7)
    items = rng.standard_normal((4, 6, 40)).astype(np.float32)
    est = GMMFisherVectorEstimator(2, max_iterations=5, min_cluster_size=1)
    fv = est.fit(Dataset.of(items))
    out = np.asarray(fv.apply_batch(Dataset.of(items)).to_array())
    assert out.shape == (4, 6, 4)
    assert np.isfinite(out).all()


def test_gmm_csv_load_roundtrip(tmp_path):
    rng = np.random.default_rng(8)
    means = rng.standard_normal((4, 2))
    variances = rng.uniform(0.5, 1.5, (4, 2))
    weights = np.array([0.4, 0.6])
    np.savetxt(tmp_path / "m.csv", means, delimiter=",")
    np.savetxt(tmp_path / "v.csv", variances, delimiter=",")
    np.savetxt(tmp_path / "w.csv", weights, delimiter=",")
    gmm = GaussianMixtureModel.load(
        str(tmp_path / "m.csv"), str(tmp_path / "v.csv"), str(tmp_path / "w.csv")
    )
    np.testing.assert_allclose(np.asarray(gmm.means), means)
    assert gmm.k == 2 and gmm.dim == 4


def test_posteriors_eager_and_nested_in_jit_match_float64_oracle():
    """`_posteriors` is a jitted helper that the FisherVector program calls
    from inside its own jit: called at top level, or nested in an outer
    trace, it must agree with itself and with a float64 oracle (a nested
    call once miscompiled on an experimental backend; PR 21 re-ran this on
    a TPU v5e with plain jax.jit and it held)."""
    import jax

    from keystone_tpu.nodes.learning.gmm import _posteriors

    rng = np.random.default_rng(0)
    X = (rng.standard_normal((512, 8)) * 5).astype(np.float32)
    # one descriptor with a large-magnitude coordinate, like real PCA'd SIFT
    X[0, 0] = -36.6
    means = rng.standard_normal((2, 8)).astype(np.float32)
    var = (2.0 * (1 + rng.random((2, 8)))).astype(np.float32)
    w = np.array([0.7, 0.3], dtype=np.float32)
    thr = 1e-4

    x64, m64, v64, w64 = (a.astype(np.float64) for a in (X, means, var, w))
    ll = np.stack(
        [
            -0.5 * np.sum((x64 - m64[j]) ** 2 / v64[j], axis=1)
            - 0.5 * np.sum(np.log(2 * np.pi * v64[j]))
            + np.log(w64[j])
            for j in range(len(w64))
        ],
        axis=1,
    )
    ll -= ll.max(axis=1, keepdims=True)
    q = np.exp(ll)
    q /= q.sum(axis=1, keepdims=True)
    q = np.where(q > thr, q, 0.0)
    q /= q.sum(axis=1, keepdims=True)

    q_eager = np.asarray(_posteriors(X, means, var, w, thr))
    q_nested = np.asarray(
        jax.jit(lambda x: _posteriors(x, means, var, w, thr))(X)
    )
    np.testing.assert_allclose(q_nested, q_eager, atol=1e-4)
    np.testing.assert_allclose(q_eager, q, atol=1e-3)
    np.testing.assert_allclose(q_nested, q, atol=1e-3)
