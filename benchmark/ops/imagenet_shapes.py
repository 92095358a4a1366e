"""The arithmetic of the ImageNetSiftLcsFV counts: the descriptors of an
image in both branches, one image through both chains (gray → SIFT → signed
root → PCA → Fisher vector → normalisations; LCS → PCA → Fisher vector →
normalisations) and their join, the two codebooks' fits (PCA, k-means++, EM)
and the class-weighted solve — the least the algorithm needs, whatever
implements it. A configuration is one of these where it states
``mixture_weight``, and every count here answers None for any other."""

from __future__ import annotations

import math

F32 = 4


def applies(config: dict) -> bool:
    return "mixture_weight" in config


def sift_scales(config: dict) -> list:
    """``(bin_size, step, nx, ny)`` of each scale: descriptors every
    ``step + scale · scale_step`` pixels while the 4 × 4 bins of
    ``bin_size`` fit the image."""
    out = []
    for s in range(config["num_scales"]):
        bin_size = config["bin_size"] + 2 * s
        step = config["step"] + s * config["scale_step"]
        extent = 4 * bin_size
        nx = (config["image_x"] - extent) // step + 1
        ny = (config["image_y"] - extent) // step + 1
        out.append((bin_size, step, max(nx, 0), max(ny, 0)))
    return out


def sift_descriptors(config: dict) -> int:
    """81² + 59² + 45² + 37² = 13,436 at 256 × 256."""
    return sum(nx * ny for _, _, nx, ny in sift_scales(config))


def lcs_descriptors(config: dict) -> int:
    """Keypoints every ``stride`` pixels inside the border: 56² = 3,136 at
    256 × 256."""
    g = config["lcs"]
    along = lambda size: len(  # noqa: E731
        range(g["border"], size - g["border"], g["stride"])
    )
    return along(config["image_x"]) * along(config["image_y"])


def branches(config: dict) -> list:
    """``(descriptors an image, descriptor width)`` of each branch."""
    return [
        (sift_descriptors(config), config["descriptor_width"]),
        (lcs_descriptors(config), config["lcs_descriptor_width"]),
    ]


def sift_flops(config: dict) -> float:
    """Dense SIFT of one image with the signed root of its descriptors,
    none of it a matrix product (``voc_shapes.sift_flops``' count: a
    scale's separable Gaussian, gradients and orientation weights, the flat
    window's box sums over eight maps, the normalisations and the
    quantization of each descriptor's 128 numbers) and one root an
    element."""
    pixels = config["image_x"] * config["image_y"]
    total = 0.0
    for bin_size, _, nx, ny in sift_scales(config):
        taps = 2 * max(1, math.ceil(4.0 * bin_size / 6.0)) + 1
        window = max(1, round(bin_size * 1.5))
        total += pixels * (2 * 2.0 * taps + 30.0 + 2.0 * window * 8)
        total += nx * ny * config["descriptor_width"] * (8.0 + 1.0)
    return total


def lcs_flops(config: dict) -> float:
    """LCS of one image: a channel's squares, the separable box sums of
    the pixels and of the squares (2 · patch additions each), the mean, the
    variance and its root (about 6 a pixel); the 96 values a keypoint are
    moved, not computed."""
    pixels = config["image_x"] * config["image_y"] * config["image_channels"]
    return pixels * (1.0 + 2 * 2.0 * config["lcs"]["patch"] + 6.0)


def featurize_image(config: dict) -> dict:
    """One image through both chains, counted as one piece of work: a
    branch's projection (2·N·width·d), the posteriors' two products and the
    two statistics (2·N·d·k each), the descriptors, the posteriors' chain
    (about 10 operations a descriptor and centre) and the Fisher vector's
    own arithmetic. The bytes are the least the chains can move: the uint8
    image in, its ``d`` float32 out."""
    d, k = config["desc_dim"], config["vocab_size"]
    gemm = other = 0.0
    for n, width in branches(config):
        gemm += 2.0 * n * width * d + 4 * 2.0 * n * d * k
        other += 10.0 * n * k + 12.0 * 2 * d * k
    return {
        "gemm_flops": gemm,
        "other_flops": other + sift_flops(config) + lcs_flops(config),
        "bytes": config["image_x"] * config["image_y"]
        * config["image_channels"] + F32 * config["d"],
    }


def images_featurized(config: dict) -> int:
    """The least a job has to featurize: every training image and every
    held-out image once. (``run`` featurizes the training images five
    times: each branch's two samples, and the fit.)"""
    return config["n_train"] + config["n_test"]


def codebooks(config: dict) -> dict:
    """Both branches' fits: the PCA's covariance (2·S·width²), the
    seeding's distances (2·S·d a centre) with one Lloyd update and the
    clusters' moments (2·S·d·k each for the distances, the means and the
    second moments), and ``max_iterations`` rounds of EM: two products for
    the posteriors, two for the moments (2·S·d·k each) and the posteriors'
    chain."""
    d, k = config["desc_dim"], config["vocab_size"]
    n = config["n_train"]
    s_pca = max(1, config["num_pca_samples"] // n) * n
    s_gmm = max(1, config["num_gmm_samples"] // n) * n
    rounds = config["gmm"]["max_iterations"]
    gemm = other = nbytes = 0.0
    for _, width in branches(config):
        gemm += (
            2.0 * s_pca * width * width + 2.0 * s_gmm * d * (k - 1)
            + 5 * 2.0 * s_gmm * d * k + rounds * 4 * 2.0 * s_gmm * d * k
        )
        other += rounds * 10.0 * s_gmm * k
        nbytes += F32 * (s_pca * width + (1 + rounds) * s_gmm * d)
    return {"gemm_flops": gemm, "other_flops": other, "bytes": nbytes}


def solve(config: dict, n: int) -> dict:
    """The class-weighted solve of one block in one pass, the least the
    algorithm needs whatever implements it: the population Gram (2·n·d²);
    the class Grams from class-sorted rows — a row enters its own class's
    Gram alone, 2·n·d² in ALL, not a class; the population cross term and
    the residual update (2·n·d·k each) and the class cross term from sorted
    rows (2·n·d); k factorisations of d³/3 with a pair of substitutions
    (2·d²). The bytes: the features read once for each of the four passes
    over them, and every class system written and read once."""
    d, k = config["d"], config["num_classes"]
    return {
        "gemm_flops": 2 * 2.0 * n * d * d + 2 * 2.0 * n * d * k + 2.0 * n * d,
        "other_flops": k * (d**3 / 3.0 + 2.0 * d * d),
        "bytes": F32 * (4.0 * n * d + 2.0 * k * d * d),
    }


def apply_row(config: dict) -> dict:
    d, k = config["d"], config["num_classes"]
    return {"gemm_flops": 2.0 * d * k, "other_flops": 0.0,
            "bytes": F32 * (d + k)}
