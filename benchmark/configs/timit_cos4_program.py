"""How the benchmark drives the program for ``timit_cos4``: the fit goes
through ``pipelines.timit.run`` exactly as a user's job would, and the
fitted model is read back from the pipeline it returns."""

from __future__ import annotations

from benchmark.program import FitHandle, fitted, model  # noqa: F401


def fit(config: dict, X_train, y_train, X_test, y_test):
    """One whole job on fresh estimators: featurize, five epochs of the
    block solve, evaluate the test rows. Ends synchronised (the evaluation
    is host numbers)."""
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.pipelines.timit import TimitConfig, run
    from keystone_tpu.workflow.env import PipelineEnv

    PipelineEnv.get_or_create().reset()  # a job starts with no fit state
    conf = TimitConfig(
        num_cosines=config["num_cosines"], gamma=config["gamma"],
        lam=config["lam"], num_epochs=config["epochs"],
        num_classes=config["num_classes"], input_dim=config["input_dim"],
        cosine_features=config["cosine_features"], seed=config["feature_seed"],
    )
    predictor, evaluation, _ = run(
        LabeledData(y_train, X_train), LabeledData(y_test, X_test), conf
    )
    return FitHandle(
        pipeline=predictor, test_error=float(evaluation.total_error)
    )
