"""Solver tests: weighted BCD vs per-class oracle, LBFGS vs exact, kernel
ridge exact interpolation, NB/logistic/LDA sanity, auto-solver selection —
mirroring the reference suites (BlockWeightedLeastSquaresSuite:115,
KernelModelSuite, LeastSquaresEstimatorSuite)."""

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning.classifiers import (
    LeastSquaresEstimator,
    LinearDiscriminantAnalysis,
    LogisticRegressionEstimator,
    NaiveBayesEstimator,
)
from keystone_tpu.nodes.learning.kernel import (
    KernelBlockLinearMapper,
    KernelRidgeRegression,
)
from keystone_tpu.nodes.learning.lbfgs import (
    DenseLBFGSwithL2,
    LocalLeastSquaresEstimator,
    SparseLBFGSwithL2,
)
from keystone_tpu.nodes.learning.linear import (
    BlockLeastSquaresEstimator,
    LinearMapEstimator,
)
from keystone_tpu.nodes.learning.weighted import (
    BlockWeightedLeastSquaresEstimator,
    PerClassWeightedLeastSquaresEstimator,
    ReWeightedLeastSquaresEstimator,
)


def _class_data(rng, n=120, d=10, k=3):
    y = rng.integers(0, k, n)
    W = rng.standard_normal((d, k))
    X = rng.standard_normal((n, d)).astype(np.float32) + 0.5 * W.T[y]
    Y = -np.ones((n, k), dtype=np.float32)
    Y[np.arange(n), y] = 1.0
    return X.astype(np.float32), Y, y


def test_block_weighted_agrees_with_per_class():
    """parity: BlockWeightedLeastSquaresSuite.scala:115."""
    rng = np.random.default_rng(0)
    X, Y, _ = _class_data(rng)
    block = BlockWeightedLeastSquaresEstimator(
        4, 20, lam=0.5, mixture_weight=0.3
    ).fit(Dataset.of(X), Dataset.of(Y))
    per_class = PerClassWeightedLeastSquaresEstimator(
        4, 1, lam=0.5, mixture_weight=0.3
    ).fit(Dataset.of(X), Dataset.of(Y))
    pb = np.asarray(block.apply_batch(Dataset.of(X)).to_array())
    pc = np.asarray(per_class.apply_batch(Dataset.of(X)).to_array())
    np.testing.assert_allclose(pb, pc, rtol=5e-2, atol=5e-2)


def test_weighted_family_three_way_agreement_mixed_balance():
    """block ≈ exact per-class ≈ iterative reweighted BCD at heavily mixed
    class balance (VERDICT r3 #8; parity: the reference validates its block
    solver against the per-class path, whose inner solver is
    internal/ReWeightedLeastSquares.scala:18 — here all three are compared
    pairwise on one problem)."""
    rng = np.random.default_rng(7)
    n, d, k = 160, 12, 4
    # mixed balance: class sizes roughly 8 / 24 / 48 / 80
    y = np.repeat(np.arange(k), [8, 24, 48, 80])
    rng.shuffle(y)
    W = rng.standard_normal((d, k))
    X = (rng.standard_normal((n, d)) + 0.5 * W.T[y]).astype(np.float32)
    Y = -np.ones((n, k), dtype=np.float32)
    Y[np.arange(n), y] = 1.0

    args = dict(lam=0.5, mixture_weight=0.3)
    block = BlockWeightedLeastSquaresEstimator(4, 25, **args).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    exact = PerClassWeightedLeastSquaresEstimator(4, 1, **args).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    reweighted = ReWeightedLeastSquaresEstimator(4, 25, **args).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    pb = np.asarray(block.apply_batch(Dataset.of(X)).to_array())
    pe = np.asarray(exact.apply_batch(Dataset.of(X)).to_array())
    pr = np.asarray(reweighted.apply_batch(Dataset.of(X)).to_array())
    # the iterative BCD converges to the exact per-class solution
    np.testing.assert_allclose(pr, pe, rtol=2e-2, atol=2e-2)
    # and the block solver agrees with both (its iteration path differs)
    np.testing.assert_allclose(pb, pe, rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(pb, pr, rtol=5e-2, atol=5e-2)


def test_block_weighted_dual_path_agrees_with_per_class():
    """n + 3 < d engages the Woodbury/dual sample-space solve (the
    reference's 1000-class ImageNet regime: few samples per class, wide
    features). With a single block and one iteration the block update IS
    the exact per-class system, so the dual result must match the
    independent dense per-class implementation tightly — at both a
    benign λ and the ImageNet-scale tiny λ that stresses the Woodbury
    cancellation."""
    rng = np.random.default_rng(11)
    n, d, k = 48, 64, 6
    y = np.repeat(np.arange(k), n // k)
    rng.shuffle(y)
    W = rng.standard_normal((d, k))
    X = (rng.standard_normal((n, d)) + 0.5 * W.T[y]).astype(np.float32)
    Y = -np.ones((n, k), dtype=np.float32)
    Y[np.arange(n), y] = 1.0

    # HELD-OUT rows are the load-bearing check: training rows lie in
    # span(Q) and annihilate any weight-error component orthogonal to
    # the data span — the exact error mode a 1/λ-amplified ⊥ term
    # produces (invisible on train, near-random held-out).
    X_test = rng.standard_normal((32, d)).astype(np.float32)
    for lam in (0.5, 1e-4):
        args = dict(lam=lam, mixture_weight=0.25)
        dual = BlockWeightedLeastSquaresEstimator(d, 1, **args).fit(
            Dataset.of(X), Dataset.of(Y)
        )
        exact = PerClassWeightedLeastSquaresEstimator(d, 1, **args).fit(
            Dataset.of(X), Dataset.of(Y)
        )
        for batch in (X, X_test):
            pd_ = np.asarray(dual.apply_batch(Dataset.of(batch)).to_array())
            pe = np.asarray(exact.apply_batch(Dataset.of(batch)).to_array())
            scale = np.abs(pe).max()
            np.testing.assert_allclose(pd_, pe, rtol=2e-2, atol=2e-2 * scale)


def test_reweighted_solver_single_block_is_exact():
    """With one block and one iteration the reweighted update IS the closed
    form (Gram cache + rhs reduce to the normal equations), pinning the
    weighted algebra itself."""
    from keystone_tpu.nodes.learning.weighted import solve_reweighted_l2

    rng = np.random.default_rng(3)
    n, d, k = 64, 6, 2
    A = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal((n, k)).astype(np.float32)
    b = rng.random(n).astype(np.float32) + 0.1
    reg = 0.3
    Ws = solve_reweighted_l2([A], y, b, reg=reg, num_iter=1)
    A64, y64, b64 = (
        A.astype(np.float64), y.astype(np.float64), b.astype(np.float64)
    )
    want = np.linalg.solve(
        A64.T @ (A64 * b64[:, None]) + reg * np.eye(d),
        A64.T @ (y64 * b64[:, None]),
    )
    np.testing.assert_allclose(np.asarray(Ws[0]), want, rtol=1e-3, atol=1e-3)


def test_block_weighted_learns_class_structure():
    """w=0.5, single block sanity: classifies far above chance."""
    rng = np.random.default_rng(1)
    X, Y, y = _class_data(rng)
    model = BlockWeightedLeastSquaresEstimator(
        10, 10, lam=0.1, mixture_weight=0.5
    ).fit(Dataset.of(X), Dataset.of(Y))
    pred = np.asarray(model.apply_batch(Dataset.of(X)).to_array())
    assert (pred.argmax(axis=1) == y).mean() > 0.6  # chance = 1/3


def test_dense_lbfgs_matches_exact_ols():
    rng = np.random.default_rng(2)
    n, d, k = 200, 12, 3
    X = rng.standard_normal((n, d)).astype(np.float32)
    W = rng.standard_normal((d, k)).astype(np.float32)
    Y = X @ W
    model = DenseLBFGSwithL2(reg_param=0.0, num_iterations=100).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=1e-2, atol=1e-2)


def test_sparse_lbfgs_accepts_scipy_items():
    rng = np.random.default_rng(3)
    n, d = 80, 20
    dense = (rng.random((n, d)) < 0.2) * rng.standard_normal((n, d))
    items = [sp.csr_matrix(dense[i : i + 1]) for i in range(n)]
    W = rng.standard_normal((d, 2)).astype(np.float32)
    Y = dense.astype(np.float32) @ W
    model = SparseLBFGSwithL2(reg_param=0.0, num_iterations=100).fit(
        Dataset.from_items(items), Dataset.of(Y)
    )
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=5e-2, atol=5e-2)


def test_local_least_squares_dual_matches_primal():
    """d >> n regime (parity: LocalLeastSquaresEstimator d>>n dual form)."""
    rng = np.random.default_rng(4)
    n, d, k = 30, 100, 2
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = rng.standard_normal((n, k)).astype(np.float32)
    lam = 1.0
    model = LocalLeastSquaresEstimator(lam).fit(Dataset.of(X), Dataset.of(Y))
    # primal ridge on centered data
    Xc = X - X.mean(axis=0)
    Yc = Y - Y.mean(axis=0)
    W = np.linalg.solve(Xc.T @ Xc + lam * np.eye(d), Xc.T @ Yc)
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=1e-2, atol=1e-2)


def test_kernel_ridge_multiblock_matches_closed_form():
    """Multi-block Gauss-Seidel converges to (K+λI)⁻¹Y
    (parity: KernelModelSuite agreement checks)."""
    rng = np.random.default_rng(5)
    n, d, k = 64, 4, 2
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = rng.standard_normal((n, k)).astype(np.float32)
    gamma, lam = 0.5, 1.0
    model = KernelRidgeRegression(
        gamma=gamma, lam=lam, block_size=16, num_epochs=25
    ).fit(Dataset.of(X), Dataset.of(Y))
    diff = X[:, None, :] - X[None, :, :]
    K = np.exp(-gamma * (diff ** 2).sum(-1))
    W = np.linalg.solve(K + lam * np.eye(n), Y)
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=0.02, atol=0.02)


def test_kernel_ridge_one_block_matches_closed_form():
    rng = np.random.default_rng(6)
    n, d, k = 40, 3, 2
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = rng.standard_normal((n, k)).astype(np.float32)
    gamma, lam = 0.3, 0.5
    model = KernelRidgeRegression(
        gamma=gamma, lam=lam, block_size=n, num_epochs=1
    ).fit(Dataset.of(X), Dataset.of(Y))
    # closed form: W = (K + λI)⁻¹ Y
    diff = X[:, None, :] - X[None, :, :]
    K = np.exp(-gamma * (diff ** 2).sum(-1))
    W = np.linalg.solve(K + lam * np.eye(n), Y)
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=1e-3, atol=1e-3)


def test_naive_bayes_classifies_counts():
    rng = np.random.default_rng(7)
    # two classes with disjoint dominant features
    n = 100
    X0 = rng.poisson(5, (n, 4)) * np.array([1, 1, 0, 0])
    X1 = rng.poisson(5, (n, 4)) * np.array([0, 0, 1, 1])
    X = np.concatenate([X0, X1]).astype(np.float32)
    y = np.concatenate([np.zeros(n), np.ones(n)]).astype(np.int32)
    model = NaiveBayesEstimator(2).fit(Dataset.of(X), Dataset.of(y))
    scores = np.asarray(model.apply_batch(Dataset.of(X)).to_array())
    preds = scores.argmax(axis=1)
    assert (preds == y).mean() > 0.95


def test_logistic_regression_separable():
    rng = np.random.default_rng(8)
    n = 100
    X = np.concatenate(
        [rng.standard_normal((n, 2)) + 3, rng.standard_normal((n, 2)) - 3]
    ).astype(np.float32)
    y = np.concatenate([np.zeros(n), np.ones(n)]).astype(np.int32)
    model = LogisticRegressionEstimator(2, reg_param=0.01, num_iters=50).fit(
        Dataset.of(X), Dataset.of(y)
    )
    preds = np.asarray(model.apply_batch(Dataset.of(X)).to_array())
    assert (preds == y).mean() > 0.97


def test_lda_projects_classes_apart():
    rng = np.random.default_rng(9)
    n = 60
    X = np.concatenate(
        [
            rng.standard_normal((n, 5)) + np.array([4, 0, 0, 0, 0]),
            rng.standard_normal((n, 5)),
            rng.standard_normal((n, 5)) - np.array([4, 0, 0, 0, 0]),
        ]
    ).astype(np.float32)
    y = np.repeat([0, 1, 2], n).astype(np.int32)
    mapper = LinearDiscriminantAnalysis(2).fit(Dataset.of(X), Dataset.of(y))
    Z = np.asarray(mapper.apply_batch(Dataset.of(X)).to_array())
    assert Z.shape == (3 * n, 2)
    # class means well separated along the first discriminant
    m = [Z[y == c, 0].mean() for c in range(3)]
    s = [Z[y == c, 0].std() for c in range(3)]
    gaps = sorted(m)
    assert (gaps[1] - gaps[0]) > 2 * max(s) and (gaps[2] - gaps[1]) > 2 * max(s)


def test_least_squares_auto_selection_regimes():
    """Cost model picks the expected solver per regime
    (parity: LeastSquaresEstimatorSuite)."""
    est = LeastSquaresEstimator(lam=0.1, num_machines=16)
    rng = np.random.default_rng(10)

    # dense small-d: exact/normal-equations family should win over 20-iter
    # LBFGS at huge n, small d
    dense_sample = Dataset.of(rng.standard_normal((100, 8)).astype(np.float32))
    labels = Dataset.of(rng.standard_normal((100, 2)).astype(np.float32))
    chosen = est.optimize(dense_sample, labels)
    assert chosen is not None

    # very sparse data → sparse LBFGS wins
    items = [sp.csr_matrix(np.eye(1, 10000, k=i % 100)) for i in range(50)]
    sparse_sample = Dataset.from_items(items)
    chosen_sparse = est.optimize(
        sparse_sample, Dataset.of(rng.standard_normal((50, 2)))
    )
    from keystone_tpu.nodes.learning.lbfgs import SparseLBFGSwithL2 as S

    assert isinstance(chosen_sparse, S)


def test_lbfgs_with_l2_matches_closed_form_ridge():
    rng = np.random.default_rng(11)
    n, d, k = 150, 10, 2
    X = rng.standard_normal((n, d)).astype(np.float32)
    Y = rng.standard_normal((n, k)).astype(np.float32)
    lam = 0.5
    model = DenseLBFGSwithL2(reg_param=lam, num_iterations=200).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    # loss = ||XW−Y||²/(2n) + λ/2‖W‖² → (XᵀX/n + λI) W = XᵀY/n
    W = np.linalg.solve(X.T @ X / n + lam * np.eye(d), X.T @ Y / n)
    np.testing.assert_allclose(np.asarray(model.W), W, rtol=2e-2, atol=2e-2)


def test_sparse_lbfgs_strategies_agree():
    """The two sparse-LBFGS execution strategies — precomputed-Gram
    quadratic and the gather/scatter path — must fit the same model
    (gram_budget_bytes picks the strategy)."""
    import scipy.sparse as sp

    from keystone_tpu.data.sparse import SparseRows
    from keystone_tpu.nodes.learning.lbfgs import SparseLBFGSwithL2

    rng = np.random.default_rng(21)
    n, d, k = 256, 96, 2
    dense = (rng.random((n, d)) < 0.1) * rng.standard_normal((n, d))
    X = SparseRows.from_scipy(sp.csr_matrix(dense.astype(np.float32)))
    Y = np.sign(rng.standard_normal((n, k))).astype(np.float32)

    def fit(budget):
        # tight tolerance: both strategies must reach the same optimum,
        # not just wander near it on different trajectories
        est = SparseLBFGSwithL2(
            reg_param=1e-3, num_iterations=200, convergence_tol=1e-9,
            gram_budget_bytes=budget,
        )
        m = est.fit(Dataset(X, batched=True), Dataset.of(Y))
        return np.asarray(m.W)

    w_gram = fit(1e9)   # d x d Gram fits easily
    w_gather = fit(0)   # Gram disabled -> gather/scatter path
    np.testing.assert_allclose(w_gather, w_gram, rtol=2e-2, atol=2e-3)


def test_minimize_lbfgs_quadratic_exact():
    """On a strictly convex quadratic the compiled L-BFGS must reach the
    analytic optimum (pins the two-loop recursion + line search)."""
    from keystone_tpu.nodes.learning.lbfgs import minimize_lbfgs

    rng = np.random.default_rng(5)
    d = 24
    M = rng.standard_normal((d, d)).astype(np.float32)
    H = M @ M.T + 0.5 * np.eye(d, dtype=np.float32)
    b = rng.standard_normal(d).astype(np.float32)

    def vag(w, H, b):
        Hw = H @ w
        return 0.5 * jnp.vdot(w, Hw) - jnp.vdot(b, w), Hw - b

    w = minimize_lbfgs(
        vag, np.zeros(d, np.float32), max_iterations=100,
        convergence_tol=1e-12, vag_args=(jnp.asarray(H), jnp.asarray(b)),
    )
    want = np.linalg.solve(H.astype(np.float64), b.astype(np.float64))
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-3, atol=1e-3)


def test_minimize_lbfgs_ill_scaled_and_badly_started():
    """Poor scaling exercises the memory/γ machinery; a far-off start
    exercises backtracking (step 1 overshoots badly at first)."""
    from keystone_tpu.nodes.learning.lbfgs import minimize_lbfgs

    # condition number 1e2: curvature-aware enough to stress the memory
    # while staying above the f32 |Δf| convergence floor
    scales = jnp.asarray(
        np.logspace(0, 2, 16).astype(np.float32)
    )

    def vag(w, scales):
        return 0.5 * jnp.sum(scales * w * w), scales * w

    w0 = np.full(16, 50.0, np.float32)
    w = minimize_lbfgs(
        vag, w0, max_iterations=200, convergence_tol=1e-12,
        vag_args=(scales,),
    )
    assert float(jnp.max(jnp.abs(w))) < 5e-2


def test_minimize_lbfgs_handles_flat_objective():
    """A constant objective (zero gradient everywhere) must terminate
    and return the start point, not NaN or loop forever."""
    from keystone_tpu.nodes.learning.lbfgs import minimize_lbfgs

    def vag(w):
        return jnp.float32(1.0), jnp.zeros_like(w)

    w0 = np.ones(4, np.float32)
    w = minimize_lbfgs(vag, w0, max_iterations=30)
    np.testing.assert_allclose(np.asarray(w), w0)
    assert np.all(np.isfinite(np.asarray(w)))


def _wls_block_spans(fit):
    """The ``wls.block`` spans of ``fit()`` and what it returned."""
    from keystone_tpu.obs import tracer as tracer_mod

    tracer = tracer_mod.start()
    try:
        out = fit()
    finally:
        tracer_mod.stop()
    return [sp for sp in tracer.spans() if sp.name == "wls.block"], out


@pytest.mark.parametrize("rows_less_d", [-4, -3, -2], ids=["dual", "meet", "primal"])
def test_block_weighted_paths_agree_where_they_meet(rows_less_d):
    """``use_dual = lam > 0 and n + 3 < d``: at n + 3 = d − 1 the dual
    path runs, at d and d + 1 the primal. With one block and one pass both
    ARE the exact per-class system, so on either side of the line the
    held-out predictions are the independent per-class oracle's — float32
    ``highest``, within 2% of the scores' scale — and the span's ``path``
    names the one that ran."""
    rng = np.random.default_rng(13)
    d, k = 64, 4
    n = d + rows_less_d
    y = np.arange(n) % k
    rng.shuffle(y)
    W = rng.standard_normal((d, k))
    X = (rng.standard_normal((n, d)) + 0.5 * W.T[y]).astype(np.float32)
    Y = -np.ones((n, k), dtype=np.float32)
    Y[np.arange(n), y] = 1.0
    X_test = rng.standard_normal((32, d)).astype(np.float32)
    args = dict(lam=1e-3, mixture_weight=0.25)
    spans, block = _wls_block_spans(
        lambda: BlockWeightedLeastSquaresEstimator(d, 1, **args).fit(
            Dataset.of(X), Dataset.of(Y)
        )
    )
    exact = PerClassWeightedLeastSquaresEstimator(d, 1, **args).fit(
        Dataset.of(X), Dataset.of(Y)
    )
    dual = n + 3 < d
    (span,) = spans
    assert span.attrs["path"] == ("dual" if dual else "primal")
    assert span.attrs["class_systems"] == (0 if dual else k)
    assert span.attrs["gram_products"] == (0 if dual else k)
    assert (span.attrs["rows"], span.attrs["dims"]) == (n, d)
    pb = np.asarray(block.apply_batch(Dataset.of(X_test)).to_array())
    pe = np.asarray(exact.apply_batch(Dataset.of(X_test)).to_array())
    np.testing.assert_allclose(pb, pe, atol=2e-2 * np.abs(pe).max())


def test_block_weighted_rows_of_no_class_enter_the_population_alone():
    """A row whose indicators are all −1 belongs to none of the k classes:
    it enters the population statistics and no class's — so a solve over a
    share of the classes (``Y`` cut to its columns, every row kept) gives
    those classes' columns and intercepts of the uncut solve."""
    rng = np.random.default_rng(5)
    X, Y, _ = _class_data(rng, n=90, d=12, k=6)

    def solve(Y):
        m = BlockWeightedLeastSquaresEstimator(
            12, 1, lam=1e-2, mixture_weight=0.25
        ).fit(Dataset.of(X), Dataset.of(Y))
        return np.asarray(m.xs[0]), np.asarray(m.b)

    W, b = solve(Y)
    for half in ([0, 1, 2], [3, 4, 5]):
        W_half, b_half = solve(Y[:, half])
        np.testing.assert_allclose(W_half, W[:, half], atol=1e-4)
        np.testing.assert_allclose(b_half, b[half], atol=1e-4)
