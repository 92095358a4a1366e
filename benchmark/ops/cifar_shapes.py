"""The arithmetic of the RandomPatchCifar counts: one image through the
convolution chain, and the one-pass block solve with a ragged last block.
``ops/shapes.py`` describes the cosine and FFT configurations and divides
d by the block size; a configuration is one of these where it states
``num_filters``, and every count here answers None for any other."""

from __future__ import annotations

F32 = 4


def applies(config: dict) -> bool:
    return "num_filters" in config


def featurize_image(config: dict) -> dict:
    """One image through conv → rectify → pool, counted as one piece of
    work whatever implements it: the patch product over every window
    (2 · windows · patch · filters), the per-patch normalisation, the two
    rectified halves (a subtraction and a maximum each), the sums of the
    pool. The bytes are the least the chain can move: the image in, its d
    float32 features out."""
    side, size = config["image_side"], config["patch_size"]
    channels, filters = config["image_channels"], config["num_filters"]
    windows = len(range(0, side - size + 1, config["patch_steps"])) ** 2
    patch = size * size * channels
    half = config["pool_size"] // 2
    pooled = config["d"] // (2 * filters)  # windows of the pool an image
    return {
        "gemm_flops": 2.0 * windows * patch * filters,
        "other_flops": (
            4.0 * windows * patch  # patch mean and variance
            + 2.0 * windows * filters  # (conv − μ Σf) / sd
            + 4.0 * windows * filters  # two halves: subtract, maximum
            + pooled * (2 * half) ** 2 * 2.0 * filters  # the pool's sums
        ),
        "bytes": F32 * (side * side * channels + config["d"]),
    }


def filter_bank_bytes(config: dict) -> int:
    patch = config["patch_size"] ** 2 * config["image_channels"]
    return F32 * patch * config["num_filters"]


def images_featurized(config: dict) -> int:
    """``run`` featurizes the training images for the fit and again for
    the training error, the held-out images once."""
    return 2 * config["n_train"] + config["n_test"]


def block_widths(config: dict) -> list:
    d, bs = config["d"], config["block_size"]
    return [min(bs, d - start) for start in range(0, d, bs)]


def solve(config: dict, n: int) -> dict:
    """``shapes.solve``'s rule for blocks of unequal width: the least work
    of ``epochs`` passes of block coordinate descent — each block's Gram
    (2·n·w²) and its Cholesky (w³/3) once, every epoch the three k-wide
    products (6·n·w·k) and a pair of triangular solves (2·w²·k); one read
    of the features for the Gram and one for each k-wide product."""
    k, epochs = config["num_classes"], config["epochs"]
    widths = block_widths(config)
    return {
        "gemm_flops": sum(
            2.0 * n * w * w + epochs * 6.0 * n * w * k for w in widths
        ),
        "other_flops": sum(
            w**3 / 3.0 + epochs * 2.0 * w * w * k for w in widths
        ),
        "bytes": F32 * n * config["d"] * (1.0 + 3.0 * epochs),
    }


def apply_row(config: dict) -> dict:
    d, k = config["d"], config["num_classes"]
    return {"gemm_flops": 2.0 * d * k, "other_flops": 2.0 * d,
            "bytes": F32 * (d + k)}
