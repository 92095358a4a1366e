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
    """The least work of block coordinate descent over n rows, whatever
    implements it — not a description of ``_bcd_scan_impl``. A block's Gram
    does not depend on the epoch, so it and its Cholesky (bs³/3) are needed
    once a block: 2·n·d·bs in all. Every epoch and block needs the three
    k-wide products (the residual, the cross product, the prediction
    update: 6·n·bs·k) and a pair of triangular solves (2·bs²·k). The bytes
    are one read of the features for the Gram and one for each k-wide
    product of each epoch. With one epoch the products are bench.py's
    ``block_shape`` arithmetic."""
    d, bs, k = config["d"], config["block_size"], config["num_classes"]
    nb, epochs = d // bs, config["epochs"]
    return {
        "gemm_flops": 2.0 * n * d * bs + epochs * 6.0 * n * d * k,
        "other_flops": nb * bs**3 / 3.0 + epochs * nb * 2.0 * bs**2 * k,
        "bytes": F32 * n * d * (1.0 + 3.0 * epochs),
    }


def apply_row(config: dict) -> dict:
    d, k = config["d"], config["num_classes"]
    return {"gemm_flops": 2.0 * d * k, "other_flops": 0.0,
            "bytes": F32 * (d + k)}
