"""The per-block-dispatch solver on ONE matrix with a ragged last block:
each block is read out of the matrix by the program that uses it, and the
answers are those of the solver on column slices."""

import numpy as np
import pytest

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.linalg import solve_blockwise_l2, solve_blockwise_l2_columns
from keystone_tpu.nodes.learning.linear import BlockLeastSquaresEstimator
from keystone_tpu.obs import tracer as tracer_mod


def _problem(n=160, d=44, k=3, seed=0):
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((n, d)).astype(np.float32)
    y = rng.standard_normal((n, k)).astype(np.float32)
    return A, y


@pytest.mark.parametrize("num_iter", [1, 3])
def test_columns_equal_slices(num_iter):
    A, y = _problem()
    means = A.mean(axis=0)
    blocks = [A[:, i : i + 16] for i in range(0, 44, 16)]
    want = solve_blockwise_l2(
        blocks, y, reg=0.5, num_iter=num_iter,
        means=[means[i : i + 16] for i in range(0, 44, 16)],
    )
    got = solve_blockwise_l2_columns(
        A, y, reg=0.5, block_size=16, num_iter=num_iter, means=means
    )
    assert [w.shape for w in got] == [(16, 3), (16, 3), (12, 3)]
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-5)


def test_a_warm_start_is_consistent():
    A, y = _problem(seed=1)
    means = A.mean(axis=0)
    cold = solve_blockwise_l2_columns(
        A, y, reg=0.5, block_size=16, num_iter=2, means=means
    )
    first = solve_blockwise_l2_columns(
        A, y, reg=0.5, block_size=16, num_iter=1, means=means
    )
    warm = solve_blockwise_l2_columns(
        A, y, reg=0.5, block_size=16, num_iter=1, means=means, init=first
    )
    for c, w in zip(cold, warm):
        np.testing.assert_allclose(np.asarray(c), np.asarray(w), atol=1e-5)
    with pytest.raises(ValueError):
        solve_blockwise_l2_columns(
            A, y, reg=0.5, block_size=16, means=means, init=first[:2]
        )


def test_the_ragged_fit_says_what_it_dispatched():
    A, y = _problem(seed=2)
    tracer = tracer_mod.start()
    try:
        ragged = BlockLeastSquaresEstimator(16, 1, lam=0.5).fit(
            Dataset.of(A), Dataset.of(y)
        )
        even = BlockLeastSquaresEstimator(11, 1, lam=0.5).fit(
            Dataset.of(A), Dataset.of(y)
        )
    finally:
        tracer_mod.stop()
    solves = [sp for sp in tracer.spans() if sp.name == "block_ls.solve"]
    assert solves[0].attrs["blocks"] == 3
    assert solves[0].attrs["ragged_cols"] == 12
    assert "gram_products" in solves[1].attrs  # the scan path's attrs
    updates = [sp for sp in tracer.spans() if sp.name == "bcd.block_update"]
    assert len(updates) == 3
    assert [x.shape for x in ragged.xs] == [(16, 3), (16, 3), (12, 3)]
    assert len(even.xs) == 4
    # both are one pass of the same descent, in other blocks: near, not equal
    Ac = A - A.mean(0)
    for model in (ragged, even):
        W = np.concatenate([np.asarray(x) for x in model.xs])
        assert np.linalg.norm(Ac @ W - (y - y.mean(0))) < np.linalg.norm(y - y.mean(0))
