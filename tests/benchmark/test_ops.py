"""The operation counts against hand-worked numbers."""

import json
import os

import pytest

from benchmark.ops import (
    apply_row,
    featurizer_gemm_row,
    fit_job,
    shapes,
    solver_gemms,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_timit_solver_gemms():
    # the Gram once a block, the three k-wide products every epoch:
    # 2*65536*16384*4096 + 5 * 6*65536*16384*147
    want = 2 * 65536 * 16384 * 4096 + 5 * 6 * 65536 * 16384 * 147
    got = solver_gemms.count(_config("timit_cos4"), {})
    assert got["flops"] == pytest.approx(want)
    assert got["flops"] == pytest.approx(1.3531e13, rel=1e-4)
    # one read of the features for the Gram, three an epoch
    assert got["bytes"] == pytest.approx((1 + 3 * 5) * 4 * 65536 * 16384)
    assert got["bytes"] == pytest.approx(6.872e10, rel=1e-4)
    # the bandwidth roof binds (benchmark/peaks.json: 197 TFLOP/s, 819 GB/s)
    assert got["bytes"] / 819e9 == pytest.approx(0.0839, rel=1e-3)
    assert got["flops"] / 197e12 == pytest.approx(0.0687, rel=1e-3)


def _old_solve(config, n):
    """The count before PR 28: everything ``epochs`` times."""
    d, bs, k = config["d"], config["block_size"], config["num_classes"]
    epochs = config["epochs"]
    return {
        "gemm_flops": epochs * (2.0 * n * d * bs + 6.0 * n * d * k),
        "other_flops": epochs * (d // bs) * bs**3 / 3.0,
    }


@pytest.mark.parametrize("name", ["mnist_fft", "timit_cos4"])
def test_one_epoch_counts_the_products_as_before(name):
    cfg = dict(_config(name), epochs=1)
    n, bs, k = cfg["n_train"], cfg["block_size"], cfg["num_classes"]
    old, new = _old_solve(cfg, n), shapes.solve(cfg, n)
    assert new["gemm_flops"] == old["gemm_flops"]
    # the triangular solves are counted now: 2*bs*bs*k a block
    solves = (cfg["d"] // bs) * 2.0 * bs**2 * k
    assert new["other_flops"] == old["other_flops"] + solves
    # four reads of the features: the Gram, and each k-wide product
    assert new["bytes"] == 4 * 4 * n * cfg["d"]


@pytest.mark.parametrize("key", ["d", "block_size", "num_classes", "epochs"])
def test_the_solve_depends_on_each_size(key):
    cfg = _config("timit_cos4")
    n = cfg["n_train"]
    assert shapes.solve(dict(cfg, **{key: cfg[key] * 2}), n) != shapes.solve(cfg, n)


def test_the_solve_depends_on_its_sizes_alone():
    cfg = _config("timit_cos4")
    n = cfg["n_train"]
    base = shapes.solve(cfg, n)
    assert shapes.solve(cfg, 2 * n) != base
    sizes = {k: cfg[k] for k in ("d", "block_size", "num_classes", "epochs")}
    assert shapes.solve(sizes, n) == base
    other = dict(cfg, n_train=1, n_test=3, gamma=9.0, lam=5.0, num_cosines=1,
                 cosine_features=7, input_dim=2, pipeline="another.program")
    assert shapes.solve(other, n) == base


def test_timit_row_scored():
    cfg = _config("timit_cos4")
    assert featurizer_gemm_row.count(cfg, {})["flops"] == 2 * 440 * 16384
    assert apply_row.count(cfg, {})["flops"] == (
        2 * 440 * 16384 + 2 * 16384 + 2 * 16384 * 147
    )


def test_mnist_fit_job():
    cfg = _config("mnist_fft")
    feat = 4 * (784 + 2.5 * 1024 * 10 + 512)  # signs, FFT, rectifier
    assert shapes.featurize_row(cfg)["other_flops"] == pytest.approx(feat)
    solve = (2 * 60000 * 2048 * 2048 + 6 * 60000 * 2048 * 10 + 2048**3 / 3
             + 2 * 2048**2 * 10)  # Gram, k-wide, Cholesky, triangular solves
    # run() featurizes the training rows twice (fit, then its train error)
    # and the test rows once, and scores train and test
    want = (2 * 60000 + 10000) * feat + solve + 70000 * 2 * 2048 * 10
    assert fit_job.count(cfg, {})["flops"] == pytest.approx(want)
    assert want == pytest.approx(5.30e11, rel=0.01)


def test_timit_fit_job_is_mostly_the_solver():
    cfg = _config("timit_cos4")
    job = fit_job.count(cfg, {})
    gemms = solver_gemms.count(cfg, {})["flops"]
    # 1.353e13 of 1.499e13: the featurizer's 440-wide product over 81,920
    # rows (1.18e12) and the Cholesky and triangular solves are the rest
    assert job["flops"] == pytest.approx(1.499e13, rel=2e-3)
    assert 0.89 < gemms / job["flops"] < 0.92
