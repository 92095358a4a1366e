"""How the benchmark drives the program for ``cifar_patch10k``: the fit goes
through ``pipelines.random_patch_cifar.run`` exactly as a user's job would
— filters learned from the training images, featurize, scale, the one-pass
block solve, then the training and the test error — and the fitted model
is read back from the pipeline it returns."""

from __future__ import annotations

import sys

from benchmark.program import FitHandle, fitted, model  # noqa: F401


def _require_row_slices() -> None:
    """A program that cannot run this configuration fails at once, as the
    harness asks. No segment of it fits the device whole — the convolution's
    output over 16,384 images is 478 GB — so a program whose segment dispatch
    cannot cut a segment's rows (``SegmentBinding.row_plan``, PR 29) exhausts
    the device, falls back to node-by-node dispatch and exhausts it again,
    for minutes: the parent of PR 29 had not ended after 600 s on the chip."""
    from keystone_tpu.compile.segment import SegmentBinding

    if not hasattr(SegmentBinding, "row_plan"):
        print(
            "benchmark: this program cannot run cifar_patch10k: its segment "
            "dispatch has no row slices, and a segment over 16,384 images "
            "at 10,000 filters makes 1.4 TB of intermediates",
            file=sys.stderr,
        )
        raise SystemExit(2)


def conf_of(config: dict):
    from keystone_tpu.pipelines.random_patch_cifar import RandomCifarConfig

    return RandomCifarConfig(
        num_filters=config["num_filters"],
        whitening_epsilon=config["whitening_epsilon"],
        patch_size=config["patch_size"], patch_steps=config["patch_steps"],
        pool_size=config["pool_size"], pool_stride=config["pool_stride"],
        alpha=config["alpha"], lam=config["lam"],
        whitener_size=config["whitener_size"], seed=config["filter_seed"],
    )


def fit(config: dict, X_train, y_train, X_test, y_test):
    """One whole job on fresh estimators. Ends synchronised (both errors
    are host numbers)."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.pipelines.random_patch_cifar import run
    from keystone_tpu.workflow.env import PipelineEnv

    _require_row_slices()
    PipelineEnv.get_or_create().reset()  # a job starts with no fit state
    pipeline, _, test_error, _ = run(
        LabeledData(y_train, X_train), LabeledData(y_test, X_test),
        conf_of(config),
    )
    return FitHandle(pipeline=pipeline, test_error=float(test_error))
