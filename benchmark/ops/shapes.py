"""The arithmetic the counts share."""

from __future__ import annotations

import math

F32 = 4


def featurize_row(config: dict) -> dict:
    """One row through the featurizer: its matrix product (if it has one)
    apart from the rest."""
    if "num_cosines" in config:
        d, dim = config["d"], config["input_dim"]
        return {"gemm_flops": 2.0 * dim * d, "other_flops": 2.0 * d,
                "bytes": F32 * (dim + d)}
    # random signs, a real FFT of fft_size (2.5 N log2 N), the rectifier
    size, padded = config["image_size"], config["fft_size"]
    branch = size + 2.5 * padded * math.log2(padded) + padded // 2
    return {"gemm_flops": 0.0, "other_flops": config["num_ffts"] * branch,
            "bytes": F32 * (size + config["d"])}


def solve(config: dict, n: int) -> dict:
    """Block coordinate descent over n rows: per epoch and block the Gram
    (2·n·bs²), the residual, cross and prediction products (6·n·bs·k) and
    a Cholesky (bs³/3) — bench.py's ``block_shape`` arithmetic."""
    d, bs, k = config["d"], config["block_size"], config["num_classes"]
    nb, epochs = d // bs, config["epochs"]
    gemm = epochs * (2.0 * n * d * bs + 6.0 * n * d * k)
    return {
        "gemm_flops": gemm,
        "other_flops": epochs * nb * bs**3 / 3.0,
        # each block of columns is read for the Gram, the cross product
        # and the prediction update
        "bytes": epochs * 3.0 * F32 * n * d,
    }


def apply_row(config: dict) -> dict:
    d, k = config["d"], config["num_classes"]
    return {"gemm_flops": 2.0 * d * k, "other_flops": 0.0,
            "bytes": F32 * (d + k)}
