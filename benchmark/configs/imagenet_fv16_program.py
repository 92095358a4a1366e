"""How the benchmark drives the program for ``imagenet_fv16``: the fit goes
through ``pipelines.imagenet_sift_lcs_fv.run`` exactly as a user's job would
— both descriptor branches, both PCAs and both codebooks fitted from sampled
descriptors of the training images (no checkpoint files), the gathered
Fisher vectors, the class-weighted block solve, then the held-out images
scored and the top-5 and top-1 errors — and the fitted model is read back
from the pipeline it returns."""

from __future__ import annotations

import sys

import numpy as np

from benchmark.program import FitHandle


def _require_lazy_sampling() -> None:
    """A program that cannot run this configuration fails at once, as the
    harness asks. The parent's ``compute_pca_fisher_branch`` pulled the
    descriptors of the WHOLE training set before it sampled them — 6.9 MB
    an image for SIFT at 256 × 256, 56 GB over 8,192 images — and its
    ``run`` had no sampling pass of its own; this program's draws each
    sample in one lazily composed, row-sliced pull
    (``_sample_descriptors``) and reports both errors (``TopKErrors``)."""
    from keystone_tpu.pipelines import imagenet_sift_lcs_fv as pipeline

    if not (
        hasattr(pipeline, "_sample_descriptors")
        and hasattr(pipeline, "TopKErrors")
    ):
        print(
            "benchmark: this program cannot run imagenet_fv16: its "
            "ImageNetSiftLcsFV pulls the descriptors of the whole training "
            "set before it samples them (6.9 MB an image of 256 x 256, 56 "
            "GB at 8,192 images)",
            file=sys.stderr,
        )
        raise SystemExit(2)


def conf_of(config: dict):
    """The job's configuration. The SIFT grid, the block size, the passes
    and the mixture's settings are not arguments of ``run`` — it builds
    ``SIFTExtractor`` with its defaults, ``BlockWeightedLeastSquaresEstimator
    (4096, 1, …)`` and a mixture of 20 iterations and a least cluster of 1 —
    so the configuration's keys are held to those here."""
    from keystone_tpu.nodes.images import SIFTExtractor
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
    )

    sift = SIFTExtractor(scale_step=config["scale_step"])
    built = (sift.step, sift.bin_size, sift.num_scales, 4096, 1, 20, 1, 5)
    asked = (
        config["step"], config["bin_size"], config["num_scales"],
        config["block_size"], config["epochs"],
        config["gmm"]["max_iterations"], config["gmm"]["min_cluster_size"],
        config["top_k"],
    )
    if built != asked:
        print(
            f"benchmark: imagenet_fv16 asks for step, bin size, scales, "
            f"block, passes, EM iterations, least cluster and top-k {asked}; "
            f"the program builds {built}", file=sys.stderr,
        )
        raise SystemExit(2)
    lcs = config["lcs"]
    return ImageNetSiftLcsFVConfig(
        lam=config["lam"], mixture_weight=config["mixture_weight"],
        desc_dim=config["desc_dim"], vocab_size=config["vocab_size"],
        sift_scale_step=config["scale_step"], lcs_stride=lcs["stride"],
        lcs_border=lcs["border"], lcs_patch=lcs["patch"],
        num_pca_samples=config["num_pca_samples"],
        num_gmm_samples=config["num_gmm_samples"],
        num_classes=config["num_classes"], seed=config["sample_seed"],
    )


def fit(config: dict, X_train, y_train, X_test, y_test):
    """One whole job on fresh estimators. Ends synchronised (the errors are
    host numbers). ``test_error`` is the job's TOP-1 error as a share, which
    is what ``compare.fit_numbers`` computes of the reference's scores; the
    top-5 error the job reports stands beside it on the handle."""
    _require_lazy_sampling()
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import run
    from keystone_tpu.workflow.env import PipelineEnv

    conf = conf_of(config)
    PipelineEnv.get_or_create().reset()  # a job starts with no fit state
    pipeline, errors, _ = run(X_train, y_train, X_test, y_test, conf)
    handle = FitHandle(pipeline=pipeline, test_error=errors.top1 / 100.0)
    handle.top5_error = errors.top5 / 100.0
    return handle


def fitted(handle: FitHandle):
    """``run`` hands back the estimator-free pipeline itself."""
    return handle.pipeline


def model(handle: FitHandle) -> dict:
    """``{"W", "b", "mean"}`` of the fitted weighted model. The weighted
    solver's mapper carries no feature means (each class's joint mean is in
    its intercept), which ``benchmark.program.linear_model`` takes for
    granted: the mean handed on is zero."""
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper

    graph = handle.pipeline.graph
    for node in graph.nodes:
        op = graph.get_operator(node)
        if isinstance(op, BlockLinearMapper):
            W = np.concatenate([np.asarray(x) for x in op.xs], axis=0)
            means = op.feature_means
            return {
                "W": W, "b": np.asarray(op.b),
                "mean": np.zeros(W.shape[0], np.float32) if means is None
                else np.concatenate([np.asarray(m) for m in means], axis=0),
            }
    raise LookupError("no BlockLinearMapper in the fitted graph")
