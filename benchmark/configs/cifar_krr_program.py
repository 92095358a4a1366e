"""How the benchmark drives the program for ``cifar_krr``: the fit goes
through ``pipelines.random_patch_cifar_kernel.run`` exactly as a user's job
would — filters learned from the training images, featurize, scale, one
epoch of block Gauss–Seidel kernel ridge regression on the cached kernel,
then the training and the test error — and the fitted DUAL model is read
back from the pipeline it returns: one row of coefficients a training
image, in the order the images were given, scored by the reference through
its kernel rows (``cifar_krr_reference.featurizer``).

The first job of a process runs :data:`SETTLE_JOBS` more whole jobs before
it returns, on the TPU alone: they are set-up and count in ``setup_s``. On
the chip a process's first 30–40 s hold stalls of 80–140 ms in the
convolution's row-slice loops (one job in 15 there; none of 80 ms or more
in some 400 jobs after, TPU v5 lite), and set-up's single warm-up job ends
about 10 s after start, so the timed window would sit inside them. A
process that refits job after job, as this cell's users do, meets them
once."""

from __future__ import annotations

import gc
import sys

from benchmark.program import FitHandle, fitted  # noqa: F401

#: whole jobs the first job of a process runs ahead of its own on the TPU:
#: 40 of 0.66 s end set-up some 36 s after the process starts
SETTLE_JOBS = 40

_settled = False


def _require_kernel_run() -> None:
    """A program that cannot run this configuration fails at once, as the
    harness asks: one without ``pipelines.random_patch_cifar_kernel.run``
    has no job this adapter can drive, and its older entry scores the
    kernel model at one bf16 pass."""
    try:
        from keystone_tpu.pipelines import random_patch_cifar_kernel
    except ImportError:
        random_patch_cifar_kernel = None
    if not hasattr(random_patch_cifar_kernel, "run"):
        print(
            "benchmark: this program cannot run cifar_krr: it has no "
            "pipelines.random_patch_cifar_kernel.run",
            file=sys.stderr,
        )
        raise SystemExit(2)


def conf_of(config: dict):
    from keystone_tpu.pipelines.random_patch_cifar_kernel import (
        KernelCifarConfig,
    )

    return KernelCifarConfig(
        num_filters=config["num_filters"],
        whitening_epsilon=config["whitening_epsilon"],
        patch_size=config["patch_size"], patch_steps=config["patch_steps"],
        pool_size=config["pool_size"], pool_stride=config["pool_stride"],
        alpha=config["alpha"], lam=config["lam"],
        whitener_size=config["whitener_size"], seed=config["filter_seed"],
        gamma=config["gamma"], block_size=config["block_size"],
        num_epochs=config["epochs"], cache_kernel=config["cache_kernel"],
    )


def settle_jobs() -> int:
    """The jobs a process's first job runs ahead of its own: the start-up
    stalls are the chip runtime's, so none elsewhere."""
    import jax

    return SETTLE_JOBS if jax.default_backend() == "tpu" else 0


def _job(config: dict, X_train, y_train, X_test, y_test) -> FitHandle:
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.pipelines.random_patch_cifar_kernel import run
    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.get_or_create().reset()  # a job starts with no fit state
    pipeline, _, test_error, _ = run(
        LabeledData(y_train, X_train), LabeledData(y_test, X_test),
        conf_of(config),
    )
    return FitHandle(pipeline=pipeline, test_error=float(test_error))


def fit(config: dict, X_train, y_train, X_test, y_test):
    """One whole job on fresh estimators, after :func:`settle_jobs` more
    where it is the first of its process. Ends synchronised (both errors
    are host numbers)."""
    global _settled
    _require_kernel_run()
    if not _settled:
        _settled = True
        for _ in range(settle_jobs()):
            _job(config, X_train, y_train, X_test, y_test)
            gc.collect(1)  # as fit_loop starts a job: no earlier garbage
    return _job(config, X_train, y_train, X_test, y_test)


def model(handle: FitHandle) -> dict:
    """``{"W": A, "mean": 0, "b": 0}``: the fitted kernel mapper's dual
    coefficients (n_train, k) as host numpy."""
    import numpy as np

    from keystone_tpu.nodes.learning.kernel import KernelBlockLinearMapper

    graph = fitted(handle).graph
    for node in graph.nodes:
        op = graph.get_operator(node)
        if isinstance(op, KernelBlockLinearMapper):
            W = np.asarray(op.W)
            return {
                "W": W, "mean": np.zeros(W.shape[0], np.float32),
                "b": np.zeros(W.shape[1], np.float32),
            }
    raise LookupError("no KernelBlockLinearMapper in the fitted graph")
