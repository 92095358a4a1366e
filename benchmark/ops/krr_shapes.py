"""The arithmetic of the RandomPatchCifarKernel counts: the fit's kernel
ridge solve counted as ONE piece of work whatever implements it, and the
whole job. A configuration is one of these where it states ``gamma`` and
``num_filters`` (a Gaussian kernel over convolutional features); every
count here answers None for any other."""

from __future__ import annotations

from benchmark.ops import cifar_shapes

F32 = 4


def applies(config: dict) -> bool:
    return "gamma" in config and cifar_shapes.applies(config)


def block_widths(config: dict) -> list:
    n, bs = config["n_train"], config["block_size"]
    return [min(bs, n - start) for start in range(0, n, bs)]


def solve(config: dict) -> dict:
    """The least work of ``epochs`` sweeps of block Gauss–Seidel with the
    kernel kept: the n² entries made ONCE (a d-wide product and an exp an
    entry), every epoch the residual of each block (2·n·w·k, 2·n²·k in
    all), each block's Cholesky (w³/3) once and a pair of triangular
    solves (2·w²·k) an epoch; the kernel written once and read once an
    epoch, the features read once."""
    n, d, k = config["n_train"], config["d"], config["num_classes"]
    epochs, widths = config["epochs"], block_widths(config)
    return {
        "gemm_flops": 2.0 * n * n * d + epochs * 2.0 * n * n * k,
        "other_flops": 1.0 * n * n + sum(
            w**3 / 3.0 + epochs * 2.0 * w * w * k for w in widths
        ),
        "bytes": F32 * (n * n * (1.0 + epochs) + n * d),
    }


def job(config: dict) -> dict:
    """One whole job as ``pipelines.random_patch_cifar_kernel.run`` defines
    it, counted as the least work it needs: every training and held-out
    image featurized ONCE, the scaler, the solve, the held-out images'
    kernel rows, and both scorings (the kernel rows times the
    coefficients). The job as written makes the training kernel a second
    time for the training error; that is not work the algorithm needs."""
    n, n_test = config["n_train"], config["n_test"]
    d, k = config["d"], config["num_classes"]
    feat, sol = cifar_shapes.featurize_image(config), solve(config)
    whole = lambda part: part["gemm_flops"] + part["other_flops"]  # noqa: E731
    return {
        "flops": (n + n_test) * whole(feat) + 2.0 * n * d + whole(sol)
        + n_test * n * (2.0 * d + 1.0) + 2.0 * (n + n_test) * n * k,
        "bytes": (n + n_test) * feat["bytes"] + sol["bytes"]
        + F32 * n_test * n,
    }
