"""The remaining CIFAR applications: LinearPixels, RandomCifar,
RandomPatchCifarAugmented, and RandomPatchCifarKernel.

Parity: pipelines/images/cifar/LinearPixels.scala:17-80,
RandomCifar.scala:18-95, RandomPatchCifarAugmented.scala:25-135,
RandomPatchCifarKernel.scala:17-120. All share the loaders and node stack of
RandomPatchCifar; what differs is the featurization/solver tail:

  * LinearPixels: GrayScaler → vectorize → exact linear map.
  * RandomCifar: random Gaussian filters (no whitening) → conv stack →
    exact linear map.
  * RandomPatchCifarAugmented: whitened patch filters at 24×24, training on
    random crops + flips, testing with center/corner(+flip) crops merged by
    AugmentedExamplesEvaluator.
  * RandomPatchCifarKernel: whitened patch features → StandardScaler →
    Gauss-Seidel kernel ridge regression with streaming kernel blocks
    (cache_blocks configurable) and periodic solver-state checkpointing —
    its own module, ``pipelines/random_patch_cifar_kernel.py``, whose
    ``run`` the CLI below calls.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..data.dataset import Dataset
from ..evaluation.augmented import AugmentedExamplesEvaluator
from ..evaluation.multiclass import MulticlassClassifierEvaluator
from ..loaders.cifar import NCHAN, NROW, load_cifar, synthetic_cifar
from ..loaders.csv_loader import LabeledData
from ..nodes.images.core import (
    CenterCornerPatcher,
    Convolver,
    GrayScaler,
    ImageVectorizer,
    Pooler,
    RandomImageTransformer,
    RandomPatcher,
    SymmetricRectifier,
)
from ..nodes.learning.linear import LinearMapEstimator
from ..nodes.stats import StandardScaler
from ..nodes.util import ClassLabelIndicators, MaxClassifier
from .random_patch_cifar import RandomCifarConfig, learn_filters
from .random_patch_cifar_kernel import KernelCifarConfig
from .random_patch_cifar_kernel import run as run_random_patch_cifar_kernel

NUM_CLASSES = 10


# ---- LinearPixels --------------------------------------------------------

def run_linear_pixels(train: LabeledData, test: LabeledData,
                      lam: Optional[float] = None):
    """(parity: LinearPixels.scala:21-55). Returns
    (pipeline, train_err, test_err, seconds)."""
    start = time.perf_counter()
    labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
    pipeline = (
        GrayScaler()
        .and_then(ImageVectorizer())
        .and_then(LinearMapEstimator(lam), train.data, labels)
        .and_then(MaxClassifier())
    )
    ev = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_err = ev.evaluate(
        pipeline(train.data).get().to_array(), train.labels
    ).total_error
    test_err = ev.evaluate(
        pipeline(test.data).get().to_array(), test.labels
    ).total_error
    return pipeline, train_err, test_err, time.perf_counter() - start


# ---- RandomCifar ---------------------------------------------------------

def run_random_cifar(train: LabeledData, test: LabeledData,
                     conf: RandomCifarConfig):
    """Random Gaussian filter bank, no whitening
    (parity: RandomCifar.scala:40-66)."""
    start = time.perf_counter()
    labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(train.labels)
    rng = np.random.default_rng(conf.seed)
    filters = jnp.asarray(
        rng.standard_normal(
            (conf.num_filters, conf.patch_size * conf.patch_size * NCHAN)
        ),
        dtype=jnp.float32,
    )
    pipeline = (
        Convolver(filters, NROW, NROW, NCHAN, whitener=None,
                  normalize_patches=True)
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size, None, "sum"))
        .and_then(ImageVectorizer())
        .and_then(StandardScaler(), train.data)
        .and_then(LinearMapEstimator(conf.lam), train.data, labels)
        .and_then(MaxClassifier())
    )
    ev = MulticlassClassifierEvaluator(NUM_CLASSES)
    train_err = ev.evaluate(
        pipeline(train.data).get().to_array(), train.labels
    ).total_error
    test_err = ev.evaluate(
        pipeline(test.data).get().to_array(), test.labels
    ).total_error
    return pipeline, train_err, test_err, time.perf_counter() - start


# ---- RandomPatchCifarAugmented ------------------------------------------

@dataclass
class AugmentedCifarConfig(RandomCifarConfig):
    """Parity: RandomCifarFeaturizerConfig
    (RandomPatchCifarAugmented.scala:100-115)."""

    num_random_images_augment: int = 4
    augment_img_size: int = 24
    flip_chance: float = 0.5


def run_random_patch_cifar_augmented(
    train: LabeledData, test: LabeledData, conf: AugmentedCifarConfig
):
    """Train on random crops+flips, test on center/corner+flip crops with
    per-source vote merging (parity: RandomPatchCifarAugmented.scala:33-98).
    """
    start = time.perf_counter()
    filters, whitener = learn_filters(train.data, conf)

    # augment training images: numRandomImagesAugment random crops, each
    # randomly flipped; labels replicate per crop (LabelAugmenter)
    patcher = RandomPatcher(
        conf.num_random_images_augment,
        conf.augment_img_size, conf.augment_img_size, seed=conf.seed,
    )
    flipper = RandomImageTransformer(conf.flip_chance, seed=conf.seed + 1)
    train_aug = flipper.apply_batch(
        patcher.apply_batch(Dataset.of(train.data.to_array()))
    )
    train_labels_aug = np.repeat(
        np.asarray(train.labels.to_array()), conf.num_random_images_augment
    )
    labels = ClassLabelIndicators(NUM_CLASSES).apply_batch(
        Dataset.of(train_labels_aug)
    )

    sz = conf.augment_img_size
    featurizer = (
        Convolver(filters, sz, sz, NCHAN, whitener=whitener,
                  normalize_patches=True)
        .and_then(SymmetricRectifier(alpha=conf.alpha))
        .and_then(Pooler(conf.pool_stride, conf.pool_size, None, "sum"))
        .and_then(ImageVectorizer())
    )
    from ..nodes.learning.linear import BlockLeastSquaresEstimator

    scorer = featurizer.and_then(
        StandardScaler(), train_aug
    ).and_then(
        BlockLeastSquaresEstimator(4096, 1, conf.lam or 0.0),
        train_aug,
        labels,
    )

    # test: 5 crops (+ flips) per image, vote-merged per source image
    test_patcher = CenterCornerPatcher(sz, sz, horizontal_flips=True)
    test_aug = test_patcher.apply_batch(Dataset.of(test.data.to_array()))
    n_aug = 10  # 4 corners + center, and flips of each
    names = np.repeat(np.arange(len(test)), n_aug)
    scores = np.asarray(scorer(test_aug).get().to_array())
    evaluation = AugmentedExamplesEvaluator(
        names.tolist(), NUM_CLASSES, "average"
    ).evaluate(scores, np.repeat(np.asarray(test.labels.to_array()), n_aug))
    return scorer, evaluation, time.perf_counter() - start


# ---- CLI -----------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser("CifarExtras")
    p.add_argument("app", choices=[
        "LinearPixels", "RandomCifar", "RandomPatchCifarAugmented",
        "RandomPatchCifarKernel",
    ])
    p.add_argument("--trainLocation", default=None)
    p.add_argument("--testLocation", default=None)
    p.add_argument("--numFilters", type=int, default=100)
    p.add_argument("--whiteningEpsilon", type=float, default=0.1)
    p.add_argument("--patchSize", type=int, default=6)
    p.add_argument("--poolSize", type=int, default=14)
    p.add_argument("--poolStride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--gamma", type=float, default=2e-4)
    p.add_argument("--lambda", dest="lam", type=float, default=None)
    p.add_argument("--blockSize", type=int, default=5000)
    p.add_argument("--numEpochs", type=int, default=1)
    p.add_argument("--cacheKernel", type=lambda s: s.lower() == "true",
                   default=True)
    p.add_argument("--checkpointDir", default=None)
    p.add_argument("--nTrain", type=int, default=1024)
    p.add_argument("--nTest", type=int, default=256)
    args = p.parse_args(argv)
    if args.trainLocation:
        train = load_cifar(args.trainLocation)
        test = load_cifar(args.testLocation)
    else:
        train = synthetic_cifar(args.nTrain, seed=1)
        test = synthetic_cifar(args.nTest, seed=2)

    if args.app == "LinearPixels":
        _, tr, te, secs = run_linear_pixels(train, test, args.lam)
        print(f"Training error is: {tr}\nTest error is: {te}")
    elif args.app == "RandomCifar":
        conf = RandomCifarConfig(
            num_filters=args.numFilters, patch_size=args.patchSize,
            pool_size=args.poolSize, pool_stride=args.poolStride,
            alpha=args.alpha, lam=args.lam,
        )
        _, tr, te, secs = run_random_cifar(train, test, conf)
        print(f"Training error is: {tr}\nTest error is: {te}")
    elif args.app == "RandomPatchCifarAugmented":
        conf = AugmentedCifarConfig(
            num_filters=args.numFilters,
            whitening_epsilon=args.whiteningEpsilon,
            patch_size=args.patchSize, pool_size=args.poolSize,
            pool_stride=args.poolStride, alpha=args.alpha, lam=args.lam,
        )
        _, evaluation, secs = run_random_patch_cifar_augmented(
            train, test, conf
        )
        print(f"Test error is: {evaluation.total_error}")
    else:
        conf = KernelCifarConfig(
            num_filters=args.numFilters,
            whitening_epsilon=args.whiteningEpsilon,
            patch_size=args.patchSize, pool_size=args.poolSize,
            pool_stride=args.poolStride, alpha=args.alpha,
            gamma=args.gamma, lam=args.lam, block_size=args.blockSize,
            num_epochs=args.numEpochs, cache_kernel=args.cacheKernel,
            checkpoint_dir=args.checkpointDir,
        )
        _, tr, te, secs = run_random_patch_cifar_kernel(train, test, conf)
        print(f"Training error is: {tr}\nTest error is: {te}")
    print(f"Pipeline took {secs} s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
