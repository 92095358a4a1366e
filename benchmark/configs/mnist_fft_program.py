"""How the benchmark drives the program for ``mnist_fft``: the fit goes
through ``pipelines.mnist_random_fft.run`` exactly as a user's job would,
and the fitted model is read back from the pipeline it returns."""

from __future__ import annotations

from benchmark.program import FitHandle, fitted, model  # noqa: F401


def fit(config: dict, X_train, y_train, X_test, y_test):
    """One whole job on fresh estimators: featurize, solve, evaluate the
    train and the test rows. Ends synchronised (the errors are host
    floats)."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.pipelines.mnist_random_fft import (
        MnistRandomFFTConfig,
        run,
    )
    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.get_or_create().reset()  # a job starts with no fit state
    conf = MnistRandomFFTConfig(
        num_ffts=config["num_ffts"], block_size=config["block_size"],
        lam=config["lam"], seed=config["feature_seed"],
    )
    pipeline, _, test_error, _ = run(
        LabeledData(y_train, X_train), LabeledData(y_test, X_test), conf
    )
    return FitHandle(pipeline=pipeline, test_error=float(test_error))
