"""The arithmetic of the VOCSIFTFisher counts: the descriptors of an image,
one image through gray → SIFT → PCA → Fisher vector → normalisations, the
codebook's fit (PCA, k-means++, EM) and the one-pass block solve — the
least the algorithm needs, whatever implements it, as ``ops/shapes.py:
solve`` counts. A configuration is one of these where it states
``vocab_size``, and every count here answers None for any other."""

from __future__ import annotations

import math

F32 = 4


def applies(config: dict) -> bool:
    return "vocab_size" in config


def scales(config: dict) -> list:
    """``(bin_size, step, nx, ny)`` of each scale: descriptors every
    ``step`` pixels while the 4 × 4 bins of ``bin_size`` fit the image."""
    out = []
    for s in range(config["num_scales"]):
        bin_size = config["bin_size"] + 2 * s
        step = config["step"] + s * config["scale_step"]
        extent = 4 * bin_size
        nx = (config["image_x"] - extent) // step + 1
        ny = (config["image_y"] - extent) // step + 1
        out.append((bin_size, step, max(nx, 0), max(ny, 0)))
    return out


def descriptors(config: dict) -> int:
    """N: 162·120 + 159·118 + 157·115 + 154·112 = 73,505 at 500 × 375."""
    return sum(nx * ny for _, _, nx, ny in scales(config))


def sift_flops(config: dict) -> float:
    """Dense SIFT of one image, none of it a matrix product: a scale's
    separable Gaussian (taps to 4σ, σ = bin / 6: a multiply-add a tap, two
    axes), gradients, magnitude, angle and the two interpolated orientation
    weights (about 30 operations a pixel), the flat window's separable box
    sums over eight maps (1.5 bins wide: 2 · width additions), and the two
    normalisations, the clamp, the threshold and the quantization of each
    of the descriptor's 128 numbers (about 8)."""
    pixels = config["image_x"] * config["image_y"]
    total = 0.0
    for bin_size, _, nx, ny in scales(config):
        taps = 2 * max(1, math.ceil(4.0 * bin_size / 6.0)) + 1
        window = max(1, round(bin_size * 1.5))
        total += pixels * (2 * 2.0 * taps + 30.0 + 2.0 * window * 8)
        total += nx * ny * config["descriptor_width"] * 8.0
    return total


def featurize_image(config: dict) -> dict:
    """One image through the chain, counted as one piece of work: the
    projection (2·N·128·d), the posteriors' two products and the two
    statistics (2·N·d·k each), SIFT, the posteriors' exponent, two
    normalisations and threshold (about 10 operations a descriptor and
    centre) and the Fisher vector's own arithmetic. The bytes are the least
    the chain can move: the uint8 image in, its 2·d·k float32 out."""
    n, d, k = descriptors(config), config["desc_dim"], config["vocab_size"]
    return {
        "gemm_flops": 2.0 * n * config["descriptor_width"] * d
        + 4 * 2.0 * n * d * k,
        "other_flops": sift_flops(config) + 10.0 * n * k + 12.0 * 2 * d * k,
        "bytes": config["image_x"] * config["image_y"]
        * config["image_channels"] + F32 * config["d"],
    }


def images_featurized(config: dict) -> int:
    """The least a job has to featurize: every training image and every
    held-out image once. (``run`` featurizes the training images three
    times — the PCA's sample, the codebook's, the fit — which
    ``featurizer.descriptor_passes_per_fit`` reads as 2.0.)"""
    return config["n_train"] + config["n_test"]


def codebook(config: dict) -> dict:
    """The PCA's covariance (2·S·128²), the seeding's distances (2·S·d a
    centre) with one Lloyd update and the clusters' moments (2·S·d·k each
    for the distances, the means and the second moments), and
    ``max_iterations`` rounds of EM: two products for the posteriors, two
    for the moments (2·S·d·k each) and the posteriors' chain."""
    d, k = config["desc_dim"], config["vocab_size"]
    s_pca = max(1, config["num_pca_samples"] // config["n_train"]) * config["n_train"]
    s_gmm = max(1, config["num_gmm_samples"] // config["n_train"]) * config["n_train"]
    rounds = config["gmm"]["max_iterations"]
    width = config["descriptor_width"]
    return {
        "gemm_flops": 2.0 * s_pca * width * width
        + 2.0 * s_gmm * d * (k - 1)
        + 5 * 2.0 * s_gmm * d * k
        + rounds * 4 * 2.0 * s_gmm * d * k,
        "other_flops": rounds * 10.0 * s_gmm * k,
        "bytes": F32 * (s_pca * width + (1 + rounds) * s_gmm * d),
    }


def solve(config: dict, n: int) -> dict:
    """``shapes.solve``'s count: each block's Gram and Cholesky once, every
    epoch the three k-wide products and a pair of triangular solves."""
    d, bs, c = config["d"], config["block_size"], config["num_classes"]
    nb, epochs = d // bs, config["epochs"]
    return {
        "gemm_flops": 2.0 * n * d * bs + epochs * 6.0 * n * d * c,
        "other_flops": nb * bs**3 / 3.0 + epochs * nb * 2.0 * bs**2 * c,
        "bytes": F32 * n * d * (1.0 + 3.0 * epochs),
    }


def apply_row(config: dict) -> dict:
    d, c = config["d"], config["num_classes"]
    return {"gemm_flops": 2.0 * d * c, "other_flops": 0.0,
            "bytes": F32 * (d + c)}
