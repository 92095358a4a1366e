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
    # 5 epochs x (2*65536*16384*4096 + 6*65536*16384*147)
    want = 5 * (2 * 65536 * 16384 * 4096 + 6 * 65536 * 16384 * 147)
    got = solver_gemms.count(_config("timit_cos4"), {})
    assert got["flops"] == pytest.approx(want)
    assert got["flops"] == pytest.approx(4.8716e13, rel=1e-4)
    assert got["bytes"] == pytest.approx(5 * 3 * 4 * 65536 * 16384)


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
    solve = 2 * 60000 * 2048 * 2048 + 6 * 60000 * 2048 * 10 + 2048**3 / 3
    # run() featurizes the training rows twice (fit, then its train error)
    # and the test rows once, and scores train and test
    want = (2 * 60000 + 10000) * feat + solve + 70000 * 2 * 2048 * 10
    assert fit_job.count(cfg, {})["flops"] == pytest.approx(want)
    assert want == pytest.approx(5.30e11, rel=0.01)


def test_timit_fit_job_is_mostly_the_solver():
    cfg = _config("timit_cos4")
    job = fit_job.count(cfg, {})["flops"]
    gemms = solver_gemms.count(cfg, {})["flops"]
    assert 0.95 < gemms / job < 1.0
