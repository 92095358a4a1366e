"""RandomPatchCifarKernel — CIFAR-10 with random-patch convolutional
features and Gaussian-kernel ridge regression.

Parity: pipelines/images/cifar/RandomPatchCifarKernel.scala:20-117. Stages:
RandomPatchCifar's filter learning (sample patches → normalize + ZCA-whiten
→ random filter bank) → Convolver (whitened, patch-normalized) →
SymmetricRectifier → sum-Pooler → vectorize → StandardScaler →
KernelRidgeRegression (Gaussian kernel, block Gauss-Seidel in a seeded
block order, the kernel's column blocks cached) → MaxClassifier.

The convolution's patch product is stated at :data:`FEATURIZER_PRECISION`:
at the published λ = 0 the kernel's smallest eigenvalues are 1e-2 of its
diagonal and the dual coefficients reach 100, so features from one bf16
pass move the held-out scores by 4.4e-2 of their norm where three passes
move them by 5e-4, under what float32 itself leaves (CPU emulation at
5,000 training images; the chip's readings are in
``benchmark/configs/cifar_krr.json``). At that precision the fused
convolution kernel, one bf16 pass, stands aside for the three XLA bodies.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional

from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.cifar import NCHAN, NROW
from ..loaders.csv_loader import LabeledData
from ..nodes.images.core import (
    Convolver,
    ImageVectorizer,
    Pooler,
    SymmetricRectifier,
)
from ..nodes.learning.kernel import KernelRidgeRegression
from ..nodes.stats import StandardScaler
from ..nodes.util import ClassLabelIndicators, MaxClassifier
from ..obs.tracer import span
from .random_patch_cifar import RandomCifarConfig, learn_filters

NUM_CLASSES = 10

#: the patch product of the featurizer: three bf16 passes
FEATURIZER_PRECISION = "high"


@dataclass
class KernelCifarConfig(RandomCifarConfig):
    """Parity: RandomCifarConfig (RandomPatchCifarKernel.scala:101-117).
    ``seed`` draws the patches, the filters and the block order."""

    gamma: float = 2e-4
    cache_kernel: bool = True
    block_size: int = 5000
    num_epochs: int = 1
    checkpoint_dir: Optional[str] = None


def build_pipeline(train: LabeledData, conf: KernelCifarConfig):
    labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
    filters, whitener = learn_filters(train.data, conf)
    featurizer = (
        Convolver(
            filters, NROW, NROW, NCHAN, whitener=whitener,
            normalize_patches=True, precision=FEATURIZER_PRECISION,
        )
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size, None, "sum"))
        .and_then(ImageVectorizer())
    )
    return featurizer.and_then(
        StandardScaler(), train.data
    ).and_then(
        KernelRidgeRegression(
            conf.gamma,
            conf.lam or 0.0,
            conf.block_size,
            conf.num_epochs,
            block_permuter=conf.seed,
            cache_kernel=conf.cache_kernel,
            checkpoint_dir=conf.checkpoint_dir,
        ),
        train.data,
        labels,
    ).and_then(MaxClassifier())


def run(train: LabeledData, test: LabeledData, conf: KernelCifarConfig):
    """One job: fit, then the training and the test error. Returns
    ``(pipeline, train_error, test_error, seconds)``."""
    start = time.perf_counter()
    with span("job", pipeline="RandomPatchCifarKernel"):
        with span("plan.build"):
            pipeline = build_pipeline(train, conf)
        fitted = pipeline.fit()
        # through the executor, as the fit's own featurization went: the
        # featurizer in row slices, the kernel mapper block by block
        ev = MulticlassClassifierEvaluator(NUM_CLASSES)
        train_eval = ev.evaluate(
            fitted.apply(train.data).to_array(), train.labels
        )
        test_eval = ev.evaluate(
            fitted.apply(test.data).to_array(), test.labels
        )
    return pipeline, train_eval.total_error, test_eval.total_error, \
        time.perf_counter() - start
