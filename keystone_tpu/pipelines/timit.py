"""TimitPipeline — phone classification on pre-featurized TIMIT frames with
cosine random features and a multi-epoch block solver.

Parity: pipelines/speech/TimitPipeline.scala:21-140. Pipeline:
gather(numCosines × CosineRandomFeatures(440 → 4096, γ, Gaussian|Cauchy)) →
VectorCombiner → BlockLeastSquaresEstimator(4096, numEpochs, λ) →
MaxClassifier, evaluated with MulticlassClassifierEvaluator over 147 classes.

Every stage is GEMM/elementwise, so like MnistRandomFFT the fitted chain
compiles to one XLA program; the gathered cosine branches fuse into a single
(n, 440) × (440, numCosines·4096) MXU matmul.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.csv_loader import LabeledData
from ..loaders.text import TIMIT_DIMENSION, TIMIT_NUM_CLASSES, load_timit_features
from ..nodes.learning.linear import BlockLeastSquaresEstimator
from ..nodes.stats import CosineRandomFeatures
from ..nodes.util import ClassLabelIndicators, MaxClassifier, VectorCombiner
from ..obs.tracer import span
from ..utils.params import to_device
from ..workflow.pipeline import Pipeline

NUM_COSINE_FEATURES = 4096  # TimitPipeline.scala:51


@dataclass
class TimitConfig:
    """Parity: TimitConfig (TimitPipeline.scala:25-36)."""

    train_data: str = ""
    train_labels: str = ""
    test_data: str = ""
    test_labels: str = ""
    num_cosines: int = 50
    gamma: float = 0.05555
    rf_type: str = "gaussian"  # or "cauchy"
    lam: float = 0.0
    num_epochs: int = 5
    num_classes: int = TIMIT_NUM_CLASSES
    input_dim: int = TIMIT_DIMENSION
    cosine_features: int = NUM_COSINE_FEATURES
    seed: int = 123


def _cosine_branch(conf: TimitConfig, i: int) -> CosineRandomFeatures:
    if conf.rf_type == "cauchy":
        # Cauchy draws give the Laplacian-kernel features
        # (TimitPipeline.scala:73-80)
        key = jax.random.PRNGKey(conf.seed + i)
        kw, kb = jax.random.split(key)
        W = conf.gamma * jax.random.cauchy(
            kw, (conf.cosine_features, conf.input_dim)
        )
        b = jax.random.uniform(
            kb, (conf.cosine_features,), maxval=2 * np.pi
        )
        return CosineRandomFeatures(W, b)
    return CosineRandomFeatures.create(
        conf.input_dim, conf.cosine_features, conf.gamma, seed=conf.seed + i
    )


def build_featurizer(conf: TimitConfig) -> Pipeline:
    branches = [
        _cosine_branch(conf, i).to_pipeline()
        for i in range(conf.num_cosines)
    ]
    return Pipeline.gather(branches).and_then(VectorCombiner())


def run(train: LabeledData, test: LabeledData, conf: TimitConfig):
    """Returns (predictor, test evaluation, seconds)."""
    start = time.perf_counter()
    with span("job", pipeline="Timit"):
        labels = ClassLabelIndicators(conf.num_classes).apply_batch(
            to_device(train.labels)
        )
        with span("plan.build"):
            predictor = (
                build_featurizer(conf)
                .and_then(
                    BlockLeastSquaresEstimator(
                        conf.cosine_features, conf.num_epochs, conf.lam
                    ),
                    train.data,
                    labels,
                )
                .and_then(MaxClassifier())
            )
        evaluation = MulticlassClassifierEvaluator(conf.num_classes).evaluate(
            predictor(test.data).get().to_array(), test.labels
        )
    return predictor, evaluation, time.perf_counter() - start


def synthetic_timit(n: int, num_classes: int, dim: int = TIMIT_DIMENSION,
                    seed: int = 0) -> LabeledData:
    """Gaussian class prototypes in the 440-dim MFCC-feature space.

    The prototypes come from a constant RNG so that differently-seeded draws
    (train vs test) share the same class structure; only the sample noise
    varies with ``seed``.
    """
    protos = (
        np.random.default_rng(1234)
        .standard_normal((num_classes, dim))
        .astype(np.float32)
    )
    rng = np.random.default_rng(seed)
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    X = protos[y] + 1.5 * rng.standard_normal((n, dim)).astype(np.float32)
    return LabeledData(y, X)


def main(argv=None) -> int:
    p = argparse.ArgumentParser("Timit")
    p.add_argument("--trainDataLocation", default=None)
    p.add_argument("--trainLabelsLocation", default=None)
    p.add_argument("--testDataLocation", default=None)
    p.add_argument("--testLabelsLocation", default=None)
    p.add_argument("--numCosines", type=int, default=50)
    p.add_argument("--gamma", type=float, default=0.05555)
    p.add_argument("--rfType", default="gaussian",
                   choices=["gaussian", "cauchy"])
    p.add_argument("--lambda", dest="lam", type=float, default=0.0)
    p.add_argument("--numEpochs", type=int, default=5)
    p.add_argument("--numClasses", type=int, default=TIMIT_NUM_CLASSES)
    p.add_argument("--nTrain", type=int, default=2048)
    p.add_argument("--nTest", type=int, default=512)
    args = p.parse_args(argv)
    conf = TimitConfig(
        train_data=args.trainDataLocation or "",
        num_cosines=args.numCosines,
        gamma=args.gamma,
        rf_type=args.rfType,
        lam=args.lam,
        num_epochs=args.numEpochs,
        num_classes=args.numClasses,
    )
    if args.trainDataLocation:
        data = load_timit_features(
            args.trainDataLocation, args.trainLabelsLocation,
            args.testDataLocation, args.testLabelsLocation,
        )
        train, test = data.train, data.test
    else:
        train = synthetic_timit(args.nTrain, conf.num_classes, seed=1)
        test = synthetic_timit(args.nTest, conf.num_classes, seed=2)
    _, evaluation, seconds = run(train, test, conf)
    print(f"TEST Error is {100 * evaluation.total_error}%")
    print(f"Pipeline took {seconds} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
