"""How the benchmark drives the program for ``voc_fv256``: the fit goes
through ``pipelines.voc_sift_fisher.run`` exactly as a user's job would —
dense SIFT, the PCA and the codebook fitted from sampled descriptors of the
training images (no checkpoint files), Fisher vectors, the one-pass block
solve, then the held-out images scored and the average precisions — and the
fitted model is read back from the pipeline it returns."""

from __future__ import annotations

import sys

import numpy as np

from benchmark.program import FitHandle, linear_model


def _require_declined_caches() -> None:
    """A program that cannot run this configuration fails at once, as the
    harness asks. ``run`` puts a ``Cacher`` after the PCA projection, whose
    value is 23.5 MB an image — 48 GB over 2,048 images; a program whose
    executor keeps every cache it is asked for (no
    ``compile.segment.unheld_caches``) exhausts the device there, and so
    does one whose ``run`` draws its samples from the descriptors of the
    whole training set at once (37.6 MB an image), as the parent's did."""
    from keystone_tpu.compile import segment

    if not hasattr(segment, "unheld_caches"):
        print(
            "benchmark: this program cannot run voc_fv256: its executor "
            "keeps every cache it is asked for, and the Cacher after the "
            "PCA projection asks for 23.5 MB an image at 73,505 "
            "descriptors an image",
            file=sys.stderr,
        )
        raise SystemExit(2)


def conf_of(config: dict):
    """The job's configuration. The SIFT grid and the mixture's settings
    are not arguments of ``run`` — it builds ``SIFTExtractor`` with its
    defaults and passes the mixture 20 iterations and a least cluster of 1
    — so the configuration's keys are held to those here."""
    from keystone_tpu.nodes.images import SIFTExtractor
    from keystone_tpu.pipelines.voc_sift_fisher import (
        NUM_CLASSES,
        SIFTFisherConfig,
    )

    sift = SIFTExtractor(scale_step=config["scale_step"])
    built = (sift.step, sift.bin_size, sift.num_scales, NUM_CLASSES, 20, 1)
    asked = (
        config["step"], config["bin_size"], config["num_scales"],
        config["num_classes"], config["gmm"]["max_iterations"],
        config["gmm"]["min_cluster_size"],
    )
    if built != asked:
        print(
            f"benchmark: voc_fv256 asks for step, bin size, scales, classes, "
            f"EM iterations and least cluster {asked}; the program builds "
            f"{built}", file=sys.stderr,
        )
        raise SystemExit(2)
    return SIFTFisherConfig(
        num_pca_samples=config["num_pca_samples"],
        num_gmm_samples=config["num_gmm_samples"],
        vocab_size=config["vocab_size"], desc_dim=config["desc_dim"],
        lam=config["lam"], scale_step=config["scale_step"],
        seed=config["sample_seed"],
    )


def label_sets(masks, num_classes: int) -> list:
    """The loaders hand the program a label set an image; the harness one
    int32 bitmask an image (bit c: class c)."""
    masks = np.asarray(masks).astype(np.int64)
    return [
        np.flatnonzero((m >> np.arange(num_classes)) & 1) for m in masks
    ]


def fit(config: dict, X_train, y_train, X_test, y_test):
    """One whole job on fresh estimators. Ends synchronised (the average
    precisions are host numbers). ``test_error`` is 1 − MAP."""
    from keystone_tpu.pipelines.voc_sift_fisher import run
    from keystone_tpu.workflow.env import PipelineEnv

    _require_declined_caches()
    conf = conf_of(config)
    PipelineEnv.get_or_create().reset()  # a job starts with no fit state
    k = config["num_classes"]
    pipeline, aps, _ = run(
        X_train, label_sets(y_train, k), X_test, label_sets(y_test, k), conf
    )
    return FitHandle(pipeline=pipeline, test_error=1.0 - float(aps.mean()))


def fitted(handle: FitHandle):
    """``run`` hands back the estimator-free pipeline itself."""
    return handle.pipeline


def model(handle: FitHandle) -> dict:
    return linear_model(handle.pipeline)
